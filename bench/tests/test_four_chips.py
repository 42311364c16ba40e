"""A cell at ``chips: 4`` on four virtual CPU devices: the weights made in
the program's serve layout, the mesh engine with a shard of the aged
device on each chip, the reference spread over the chips, and the
per-shard BER check.

The tiny ``llama`` cell on ``chat.aged9y`` traffic with four shard ages
runs in a child process (``XLA_FLAGS`` has to give the CPU four devices
before JAX starts), which prints what the tests below compare.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)

# per-shard BERs high enough that every faulted token is hit at 64 wide
SHARD_BERS = [1e-3, 2e-3, 3e-3, 4e-3]
AGES = [2.25, 4.5, 6.75, 9.0]

CHILD = r"""
import json, sys, time
sys.path[:0] = [BENCH, HERE]
import jax, numpy as np
import cells, harness, program, reference, weights
from conftest import tiny_cell

SEED = 2 ** 31 + 11


class ShardBers:
    # the aged device's fleet, split in four, each shard at its own BER
    operators = ("q", "k", "v", "qkt", "sv", "o", "gate", "up", "down")
    n_devices, n_shards = 1, 4
    ages_years = np.asarray([AGES])

    def __init__(self):
        self._tab = jax.numpy.asarray(self.op_ber_shard_array())

    def op_ber_shard_array(self):
        return np.repeat(np.asarray(SHARD_BERS, np.float32)[None, :, None],
                         len(self.operators), axis=2)

    def op_ber_shard_jax(self):
        return self._tab

    def fleet_power(self):
        return np.ones(1)


class HighBers:
    # one chip: every operator at the highest shard's BER
    def op_bers(self):
        return {op: max(SHARD_BERS) for op in ShardBers.operators}
    age_years = 9.0

    def total_power(self):
        return 1.0


def cell(chips, bers):
    c = tiny_cell("llama", "chat.aged9y")
    c["workload"]["chips"] = chips
    c["traffic"]["device"]["shard_ages_years"] = AGES
    c["traffic"]["device"]["ber"] = {op: bers for op in ShardBers.operators}
    return c


def run(c):
    # run_cell, and the calls of its window
    real = harness.window
    calls = []

    def keep(*a, **kw):
        out = real(*a, **kw)
        calls.extend(out[0])
        return out
    harness.window = keep
    try:
        r = harness.run_cell(c, SEED, 1.5, False, time.perf_counter(),
                             WORK, log=lambda s: None)
    finally:
        harness.window = real
    return r, calls


out = {}
base = cell(4, SHARD_BERS)
fam = cells.family(base["config"])
dims = fam.read_dims("llama", base["config"])

# the real fleet takes the four ages, one a chip
fleet = program.aged_fleet(base["traffic"]["device"], 4)
out["fleet_ages"] = np.asarray(fleet.ages_years).ravel().tolist()

# weights: made in the serve layout, the same bits as on one chip
mesh = program.serve_mesh(jax.devices()[:4])
place = lambda shapes: program.param_shardings(
    fam.model_config(dims), mesh, shapes)
four = weights.build_params(fam, dims, SEED, place)
one = weights.build_params(fam, dims, SEED)
out["tree_equal"] = all(jax.tree.leaves(jax.tree.map(
    lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))),
    four, one)))
out["split_leaves"] = sum(len(a.sharding.device_set) == 4
                          and not a.sharding.is_fully_replicated
                          for a in jax.tree.leaves(four))
out["largest_share"] = max(
    max(s.data.nbytes for s in a.addressable_shards) / a.nbytes
    for a in jax.tree.leaves(four) if not a.sharding.is_fully_replicated)
del four, one

program.aged_fleet = lambda device, chips: ShardBers()
program.aged_runtime = lambda device: HighBers()
r4, calls4 = run(base)
out["four"] = {"correct": r4["correct"], "checks": r4["checks"],
               "count": r4["device"]["count"]}
r1, _ = run(cell(1, max(SHARD_BERS)))
out["one"] = {"correct": r1["correct"], "checks": r1["checks"]}

# the BER-0 calls against one chip's engine on the same arithmetic: the
# mesh's aged matmuls round as the route without the kernel does
from repro.serve.engine import ServeEngine
free = [c for c in calls4 if c.fault_free]
t = base["traffic"]
one_chip = ServeEngine(fam.model_config(dims),
                       weights.build_params(fam, dims, SEED),
                       runtime=program.FaultFree(HighBers()),
                       max_len=t["prompt_tokens"] + t["new_tokens"] + 1,
                       use_systolic_kernel=False)
out["free_calls"] = len(free)
out["free_tokens_equal"] = all(
    np.array_equal(c.tokens, one_chip.generate(c.prompts,
                                               t["new_tokens"]).tokens)
    for c in free)

# clean serving: the serve layout's tokens are one chip's, call for call
clean = {}
for chips in (1, 4):
    c = tiny_cell("llama", "chat.clean")
    c["workload"]["chips"] = chips
    r, calls = run(c)
    clean[chips] = (r, [c.tokens for c in calls])
n = min(len(clean[1][1]), len(clean[4][1]))
out["clean"] = {"correct": clean[4][0]["correct"],
                "checks": clean[4][0]["checks"], "calls": n,
                "tokens_equal": all(np.array_equal(a, b) for a, b in
                                    zip(clean[1][1][:n], clean[4][1][:n]))}

wrong = list(SHARD_BERS)
wrong[2] *= 10
rw, _ = run(cell(4, wrong))
out["wrong"] = {"correct": rw["correct"], "checks": rw["checks"]}

rng = np.random.default_rng(3)
prompts = rng.integers(0, dims.vocab, (4, 16)).astype(np.int32)
served = rng.integers(0, dims.vocab, (4, 4)).astype(np.int32)
ctrl = [{"linear": 4, "attn": 4, "head": 4}]
ref = {c: reference.readings(fam, dims, SEED, prompts, served,
                             controls=ctrl, chips=c) for c in (1, 4)}
out["reference"] = {k: [ref[1][k].tolist(), ref[4][k].tolist()]
                    for k in ("best", "at_served")}
out["reference"]["control"] = [ref[1]["at_control"][0].tolist(),
                               ref[4]["at_control"][0].tolist()]
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def child(tmp_path_factory):
    work = tmp_path_factory.mktemp("four_chips")
    code = (f"BENCH, HERE, WORK = {BENCH!r}, {HERE!r}, {str(work)!r}\n"
            f"SHARD_BERS, AGES = {SHARD_BERS!r}, {AGES!r}\n") + CHILD
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=1200, cwd=str(work))
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    assert p.returncode == 0 and lines, p.stderr[-4000:]
    return json.loads(lines[-1][len("RESULT "):])


def test_fleet_takes_one_age_a_chip(child):
    assert child["fleet_ages"] == pytest.approx(AGES)


def test_weights_split_yet_the_same_bits(child):
    assert child["tree_equal"]
    assert child["split_leaves"] >= 8
    assert child["largest_share"] == pytest.approx(0.25)


def test_four_chips_correct(child):
    four = child["four"]
    assert four["count"] == 4
    assert four["correct"], four["checks"]
    assert four["checks"]["window_compiles"]["value"] == 0
    assert four["checks"]["faulted_top1"]["value"] < 0.2
    assert child["one"]["correct"], child["one"]["checks"]


def test_fault_free_tokens_match_one_chip(child):
    """The serve layout is exact: at BER 0 the mesh serves the tokens of
    one chip's engine on the route without the kernel, whose rounding the
    mesh's sharded kernel shares (one chip's fused kernel rounds its
    dequantisation otherwise, and parts a near tie now and then)."""
    assert child["free_calls"] >= 2 and child["free_tokens_equal"]


def test_clean_four_chips_serve_one_chips_tokens(child):
    clean = child["clean"]
    assert clean["correct"], clean["checks"]
    assert clean["calls"] >= 2 and clean["tokens_equal"]


def test_wrong_shard_ber_fails(child):
    wrong = child["wrong"]
    assert not wrong["correct"]
    assert wrong["checks"]["ber_decades"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("key", ["best", "at_served", "control"])
def test_reference_over_four_chips(child, key):
    """The same readings as on one chip, up to float32 reduction order."""
    one, four = child["reference"][key]
    np.testing.assert_allclose(four, one, rtol=1e-5, atol=1e-6)
