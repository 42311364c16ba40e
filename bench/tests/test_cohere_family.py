"""The Cohere family (``families/cohere.py``, Command R+): its configuration
resolves through the seam; a tiny Cohere cell of the ``chat.tp4`` traffic
runs correct on one chip and on four virtual CPU devices, and not correct
on four with the exchange between the chips left out; each Cohere part
that shows in served tokens, left out or replaced in the program's place,
comes out not correct; and
``collective_share`` reads its share from a trace made by hand.
"""
import copy
import dataclasses
import json
import os
import subprocess
import sys
import time

import jax
import pytest

import cells
import harness
import program
import tracefile
import weights

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CONFIG = "command_r_plus_104b"

# published keys at a tiny size: GQA 8 over 4, head_dim 16
TINY = {"model_type": "cohere", "hidden_act": "silu", "hidden_size": 128,
        "intermediate_size": 256, "num_attention_heads": 8,
        "num_key_value_heads": 4, "num_hidden_layers": 2,
        "layer_norm_eps": 1e-5, "logit_scale": 0.8333333333333334,
        "rope_theta": 75000000.0, "use_qk_norm": True,
        "attention_bias": False, "vocab_size": 256}
HIGH_BER = 1e-3     # enough upsets at 128 wide that every token is hit
SHARD_BERS = [1e-3, 2e-3, 3e-3, 4e-3]
OPS = ("q", "k", "v", "qkt", "sv", "o", "gate", "up", "down")


# Tiny limits from readings on the CPU (one chip, seeds 1-7 and 2**31 + 5,
# one call and 1.5 s windows): the sound program reads gap <= 0.092 and
# mean_gap <= 0.0028; the weakest departure, QK norm weights shared across
# heads, reads mean_gap >= 0.0043 on one call of every seed (gap 0.106-0.242)
LIMITS = {"gap": 0.2, "mean_gap": 0.0035, "faulted_top1": 0.5}


def tiny_cell(chips: int = 1) -> dict:
    """The cell's own traffic file, cut to 8 x 16 + 8 tokens, at BERs high
    enough that the faulted calls part from the reference."""
    with open(os.path.join(BENCH, "traffic", "chat.tp4.json")) as f:
        traffic = json.load(f)
    traffic.update(batch=8, prompt_tokens=16, new_tokens=8,
                   check_requests=8, check_faulted_requests=8)
    traffic["device"]["ber"] = {
        op: (HIGH_BER if chips == 1 else SHARD_BERS) for op in OPS}
    return {"workload": {"name": "tiny.cohere.chat.tp4", "chips": chips},
            "config_name": "cohere", "config": copy.deepcopy(TINY),
            "traffic": traffic,
            "limits": dict(LIMITS),
            "end_to_end": [{"name": "tok_s", "unit": "tokens/s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}


class HighBers:
    """One chip's aged device: every operator at HIGH_BER."""
    age_years = 9.0

    def op_bers(self):
        return {op: HIGH_BER for op in OPS}

    def total_power(self):
        return 1.0


def run(cell, tmp_path, seconds=1.5, seed=2 ** 31 + 5):
    return harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                            str(tmp_path), log=lambda s: None)


def test_configuration_resolves_through_the_seam():
    bench = cells.load_benchmark()
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    with open(os.path.join(cells.ROOT, entry["file"])) as f:
        conf = json.load(f)
    fam = cells.family(conf)
    assert os.path.basename(fam.__file__) == "cohere.py"
    dims = fam.read_dims(CONFIG, conf)
    assert (dims.d_model, dims.n_heads, dims.n_kv_heads, dims.head_dim,
            dims.d_ff, dims.vocab) == (12288, 96, 8, 128, 33792, 256000)
    assert dims.n_layers == 8 and conf["published"]["num_hidden_layers"] == 64
    assert entry["reduced"] == ["num_hidden_layers"]
    cfg = fam.model_config(dims)
    assert (cfg.parallel_block, cfg.qk_norm, cfg.rope_interleaved,
            cfg.tie_embeddings, cfg.norm) == (True, "per_head", True, True,
                                              "ln_nobias")
    assert cfg.logit_scale == dims.logit_scale == pytest.approx(0.83333333)
    cell = cells.load_cell(CONFIG + ".chat.tp4")
    assert cell["workload"]["chips"] == 4
    assert "collective_share" in {m["name"] for m in cell["per_layer"]}


def test_tree_is_the_programs():
    """The family's tree has the shapes and names the program's own
    initialiser gives this configuration."""
    from repro.models import transformer as tf
    fam = cells.family(TINY)
    dims = fam.read_dims("cohere", TINY)
    cfg = fam.model_config(dims)
    shapes = lambda tree: jax.tree.map(lambda a: (a.shape, a.dtype), tree)
    assert shapes(weights.build_params(fam, dims, 3)) == \
        shapes(tf.init_params(cfg, weights.base_key(3)))


@pytest.fixture
def one_chip(monkeypatch):
    monkeypatch.setattr(program, "aged_runtime", lambda dev: HighBers())
    return tiny_cell(1)


def test_one_chip_correct(one_chip, tmp_path):
    r = run(one_chip, tmp_path)
    assert r["correct"], r["checks"]
    assert r["checks"]["faulted_top1"]["value"] < 0.2


def _sequential(params):
    blk = params["groups"]["b0_attn"]
    blk["norm2"] = blk["norm1"]
    return params


def _shared_qk_norm(params):
    a = params["groups"]["b0_attn"]["attn"]
    a["q_norm"], a["k_norm"] = a["q_norm"][:, 0], a["k_norm"][:, 0]
    return params


# each: (the program's configuration changed, its weights changed).  A
# dropped logit scale is not among them: the comparison reads the served
# tokens, and a greedy argmax is blind to a positive scale of the logits
# (the program's logits are held to it in tests/test_cohere.py)
DEPARTURES = {
    "sequential_block": ({"parallel_block": False}, _sequential),
    "half_split_rope": ({"rope_interleaved": False}, None),
    "qk_norm_dropped": ({"qk_norm": None}, None),
    "qk_norm_shared_weights": ({"qk_norm": "shared"}, _shared_qk_norm),
}


@pytest.mark.parametrize("name", sorted(DEPARTURES))
def test_departure_in_the_programs_place_is_not_correct(
        name, one_chip, monkeypatch, tmp_path):
    change, rebuild = DEPARTURES[name]
    fam = cells.family(TINY)
    real_config, real_build = fam.model_config, weights.build_params
    monkeypatch.setattr(fam, "model_config", lambda dims: dataclasses.replace(
        real_config(dims), **change))
    if rebuild is not None:
        monkeypatch.setattr(weights, "build_params",
                            lambda *a, **kw: rebuild(real_build(*a, **kw)))
    # one call, served at BER 0: the sample, and each reading, is the seed's
    r = run(one_chip, tmp_path, seconds=1e-3)
    assert not r["correct"], (name, r["checks"])
    assert any(r["checks"][k]["value"] > r["checks"][k]["limit"]
               for k in ("gap", "mean_gap")), (name, r["checks"])


CHILD = r"""
import json, sys, time
sys.path[:0] = [BENCH, HERE]
import jax, numpy as np
import harness, program
from test_cohere_family import OPS, SHARD_BERS, tiny_cell


class ShardBers:
    # the aged device's fleet, split in four, each shard at its own BER
    operators = OPS
    n_devices, n_shards = 1, 4
    ages_years = np.asarray([[2.25, 4.5, 6.75, 9.0]])

    def __init__(self):
        self._tab = jax.numpy.asarray(self.op_ber_shard_array())

    def op_ber_shard_array(self):
        return np.repeat(np.asarray(SHARD_BERS, np.float32)[None, :, None],
                         len(self.operators), axis=2)

    def op_ber_shard_jax(self):
        return self._tab

    def fleet_power(self):
        return np.ones(1)


from jax.sharding import PartitionSpec as P
from repro.kernels import ops

SHARD_MAPPED = []       # the matmuls traced through the shard_map route
sound = ops._fused_aged_matmul_sharded


def counted(*args):
    SHARD_MAPPED.append(1)
    return sound(*args)


def no_gather(xq, wq, bers, seed, mesh, shard_axis, interpret):
    # the exchange between chips left out: each chip runs the fused kernel
    # on its own column block and hands that block back, in the first
    # columns, as the whole (replicated) output.  (Tiled over every block
    # instead, each chip's own columns would hold its own block, and the
    # partitioner, slicing them out for the column-split scales of the
    # epilogue and gathering after it, would serve the sound tokens.)
    SHARD_MAPPED.append(1)
    S = int(mesh.shape[shard_axis])

    def body(xq, wq_blk, bers, seed):
        s = jax.lax.axis_index(shard_axis)
        acc = ops.fused_aged_matmul(xq, wq_blk, ber=bers[s],
                                    seed=ops.fold_seed(seed, s),
                                    interpret=interpret)
        return jax.numpy.pad(acc, ((0, 0), (0, (S - 1) * acc.shape[1])))

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(), P(None, shard_axis), P(), P()),
                         out_specs=P(), check_vma=False)(
        xq, wq, jax.numpy.asarray(bers, jax.numpy.float32),
        jax.numpy.asarray(seed, jax.numpy.int32))


ops._fused_aged_matmul_sharded = no_gather if PLANT else counted
program.aged_fleet = lambda device, chips: ShardBers()
r = harness.run_cell(tiny_cell(4), 2 ** 31 + 5, 1.5, False,
                     time.perf_counter(), WORK, log=lambda s: None)
print("RESULT " + json.dumps({"correct": r["correct"],
                              "checks": r["checks"],
                              "count": r["device"]["count"],
                              "shard_mapped": len(SHARD_MAPPED)}))
"""


def four_chips(tmp_path, plant: bool) -> dict:
    code = (f"BENCH, HERE, WORK = {BENCH!r}, {HERE!r}, {str(tmp_path)!r}\n"
            f"PLANT = {plant!r}\n" + CHILD)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=1200, cwd=str(tmp_path))
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    assert p.returncode == 0 and lines, p.stderr[-4000:]
    out = json.loads(lines[-1][len("RESULT "):])
    assert out["count"] == 4
    # the weight matmuls take the shard_map route, sound or planted
    assert out["shard_mapped"] > 0
    return out


def test_four_chips_correct(tmp_path):
    out = four_chips(tmp_path, plant=False)
    assert out["correct"], out["checks"]
    assert out["checks"]["window_compiles"]["value"] == 0
    assert out["checks"]["faulted_top1"]["value"] < 0.2


def test_four_chips_without_the_gather_is_not_correct(tmp_path):
    """A shard_map that returns each chip's own column block as the whole
    output, with no exchange between the chips, comes out not correct."""
    out = four_chips(tmp_path, plant=True)
    assert not out["correct"], out["checks"]
    assert any(out["checks"][k]["value"] > out["checks"][k]["limit"]
               for k in ("gap", "mean_gap")), out["checks"]


def test_collective_share_by_hand():
    """Two generate programs and one other; collectives inside the
    generate programs count, one between them does not."""
    E = tracefile.Event
    modules = [E("jit_sharded_gen", 0, 100), E("jit_other", 110, 130),
               E("jit_sharded_gen", 200, 300)]
    ops = [E("fusion.1", 0, 40), E("all-gather.1", 40, 50),
           E("fusion.2", 50, 90),
           E("all-gather.2", 115, 125),                      # other program
           E("all-reduce.3", 200, 210),
           E("async-collective-start.4", 210, 215),
           E("collective-permute-done.5", 215, 220),
           E("fusion.6", 220, 295)]
    tr = tracefile.make_trace(ops, modules, [], (0, 400))
    ctx = harness.Context(tr, None, {"device": {"route": "fused_kernel"}},
                          None, chips=4)
    read = cells.metric_reader("collective_share")
    # busy 90 + 95; collectives 10 + 10 + 5 + 5
    assert read(ctx) == pytest.approx(100.0 * 30 / 185)
    ctx.trace = tracefile.make_trace(ops[:3], modules[:1], [], (0, 400))
    assert read(ctx) == pytest.approx(100.0 * 10 / 90)
    ctx.trace = tracefile.make_trace([], [], [], (0, 400))
    assert read(ctx) is None
