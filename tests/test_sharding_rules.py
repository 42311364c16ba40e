"""Sharding-rule unit tests (spec shapes, divisibility fallbacks) plus
multi-device integration via a subprocess (8 faked host devices — kept out
of this process so other tests see the real single CPU device).

Property tests sweep the WHOLE zoo x tp x layout grid (hypothesis when
installed, the deterministic fallback shim otherwise); the explicit tests
below them pin each documented serve-layout fallback to the config that
fires it."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.distributed.sharding import batch_spec, param_specs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeMesh:
    """Duck-typed mesh: shape mapping + axis_names (no devices needed)."""
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)
        self.size = int(np.prod(list(shape.values())))


def _specs(arch, fsdp=False, mesh=None):
    cfg = get_config(arch)
    mesh = mesh or FakeMesh({"data": 16, "model": 16})
    sds = jax.eval_shape(
        lambda k: __import__("repro.models.transformer",
                             fromlist=["init_params"])
        .init_params(cfg, k, jnp.bfloat16), jax.random.PRNGKey(0))
    return cfg, param_specs(sds, cfg, mesh, fsdp=fsdp), sds


def _leaf(specs, *path):
    node = specs
    for p in path:
        node = node[p]
    return node


def test_dense_tp_rules():
    cfg, specs, sds = _specs("deepseek_7b")          # H=32, KV=32 both %16==0
    g = specs["groups"]["b0_attn"]
    assert _leaf(g, "attn", "wq") == P(None, None, "model", None)
    assert _leaf(g, "attn", "wk") == P(None, None, "model", None)
    assert _leaf(g, "attn", "wo") == P(None, "model", None, None)
    assert _leaf(g, "ffn", "w_up") == P(None, None, "model")
    assert _leaf(g, "ffn", "w_down") == P(None, "model", None)
    assert specs["embed"] == P(None, "model")
    assert specs["lm_head"] == P(None, "model")       # vocab % 16 == 0
    assert _leaf(g, "norm1", "scale") == P()


def test_awkward_heads_fall_back_to_contraction_sharding():
    cfg, specs, _ = _specs("starcoder2_7b")           # H=36, KV=4: not %16
    g = specs["groups"]["b0_attn"]
    assert _leaf(g, "attn", "wq") == P(None, "model", None, None)
    assert _leaf(g, "attn", "wo") == P(None, None, None, "model")
    assert _leaf(g, "attn", "wk") == P(None, "model", None, None)


def test_moe_expert_parallel():
    cfg, specs, _ = _specs("qwen3_moe_235b")          # 128 experts % 16
    g = specs["groups"]["b0_attn"]
    assert _leaf(g, "ffn", "w_up") == P(None, "model", None, None)
    assert _leaf(g, "ffn", "w_down") == P(None, "model", None, None)
    assert _leaf(g, "ffn", "w_router") == P()


def test_fsdp_adds_data_axis():
    cfg, specs, sds = _specs("deepseek_7b", fsdp=True)
    g = specs["groups"]["b0_attn"]
    wq = _leaf(g, "attn", "wq")
    assert "model" in wq and any(
        ax == "data" or (isinstance(ax, tuple) and "data" in ax)
        for ax in wq if ax)
    # every >=2D leaf gets data-sharded somewhere when divisible
    flat_specs = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
    flat_sds = jax.tree.leaves(sds)
    n_fsdp = sum(1 for s, l in zip(flat_specs, flat_sds)
                 if len(l.shape) >= 3 and any(
                     ax == "data" or (isinstance(ax, tuple) and "data" in ax)
                     for ax in s if ax))
    assert n_fsdp > 0


def test_divisibility_never_violated():
    mesh = FakeMesh({"data": 16, "model": 16})
    for arch in ("arctic_480b", "whisper_large_v3", "paligemma_3b",
                 "rwkv6_3b", "recurrentgemma_2b"):
        cfg = get_config(arch)
        from repro.models import encdec, transformer as tf
        init = encdec.init_params if cfg.n_encoder_layers else tf.init_params
        sds = jax.eval_shape(lambda k: init(cfg, k, jnp.bfloat16),
                             jax.random.PRNGKey(0))
        specs = param_specs(sds, cfg, mesh, fsdp=True)
        for leaf, spec in zip(
                jax.tree.leaves(sds),
                jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))):
            for dim, ax in zip(leaf.shape, tuple(spec)):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                n = int(np.prod([mesh.shape[a] for a in axes]))
                assert dim % n == 0, (arch, leaf.shape, spec)


def test_batch_spec_divisibility():
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
    assert batch_spec(256, mesh) == ("pod", "data")
    assert batch_spec(2, mesh) == ("pod",)
    assert batch_spec(1, mesh) is None
    mesh1 = FakeMesh({"data": 16, "model": 16})
    assert batch_spec(32, mesh1) == ("data",)


# --------------------------------------------------------------------------- #
# property tests: the whole zoo x tp x layout grid
# --------------------------------------------------------------------------- #
_SDS_CACHE = {}


def _abstract_params(arch):
    """Abstract param tree for one zoo config (cached: eval_shape only)."""
    if arch not in _SDS_CACHE:
        cfg = get_config(arch)
        from repro.models import encdec, transformer as tf
        init = encdec.init_params if cfg.n_encoder_layers else tf.init_params
        _SDS_CACHE[arch] = (cfg, jax.eval_shape(
            lambda k: init(cfg, k, jnp.bfloat16), jax.random.PRNGKey(0)))
    return _SDS_CACHE[arch]


_STACKS = ("groups", "enc_layers", "dec_layers")


def _flat_with_names(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda s: isinstance(s, P))
    out = []
    for path, leaf in flat:
        names = [getattr(p, "key", getattr(p, "name", None)) for p in path]
        out.append(([n for n in names if n is not None], leaf))
    return out


@settings(max_examples=40, deadline=None)
@given(arch=st.sampled_from(ARCH_IDS),
       tp=st.sampled_from([1, 2, 4, 8, 16]),
       serve=st.booleans())
def test_every_param_gets_a_valid_spec(arch, tp, serve):
    """For every zoo config x tp x layout: every leaf has a spec, specs are
    full-rank (right-aligned: leading stack axes replicated), and a sharded
    dim is always divisible by the axis size."""
    cfg, sds = _abstract_params(arch)
    mesh = FakeMesh({"data": 2, "model": tp})
    layout = "serve" if serve else "train"
    specs = param_specs(sds, cfg, mesh, layout=layout)

    leaves = _flat_with_names(sds)
    spec_leaves = _flat_with_names(specs)
    assert len(leaves) == len(spec_leaves) and len(leaves) > 0
    for (names, leaf), (snames, spec) in zip(leaves, spec_leaves):
        assert names == snames
        assert isinstance(spec, P)
        if tp == 1:
            assert spec == P(), (arch, names)
            continue
        if spec == P():            # fully replicated leaves compress to P()
            continue
        assert len(spec) == len(leaf.shape), (arch, names, spec)
        # leading stacked-layer axes are never sharded
        n_stack = sum(1 for n in names if n in _STACKS)
        assert all(ax is None for ax in tuple(spec)[:n_stack]), \
            (arch, names, spec)
        for dim, ax in zip(leaf.shape, tuple(spec)):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = int(np.prod([mesh.shape[a] for a in axes]))
            assert dim % n == 0, (arch, names, leaf.shape, spec)


@settings(max_examples=20, deadline=None)
@given(arch=st.sampled_from(ARCH_IDS), tp=st.sampled_from([2, 4, 8, 16]))
def test_serve_layout_never_shards_contraction_dims(arch, tp):
    """The exact-TP contract: serve specs shard OUTPUT dims only — for 2-D
    weights (in, out) the contraction (first) dim must stay replicated, so
    no float reduction ever spans shards."""
    cfg, sds = _abstract_params(arch)
    specs = param_specs(sds, cfg, FakeMesh({"data": 1, "model": tp}),
                        layout="serve")
    for names, spec in _flat_with_names(specs):
        if names[-1] in ("embed",) or spec == P():
            continue                       # row gather / fully replicated
        base = tuple(spec)[sum(1 for n in names if n in _STACKS):]
        if len(base) == 2:
            assert base[0] is None, (arch, names, spec)


# --------------------------------------------------------------------------- #
# the documented serve-layout fallbacks, each pinned to a firing config
# --------------------------------------------------------------------------- #
def _serve_wq(arch, tp=16):
    cfg, sds = _abstract_params(arch)
    specs = param_specs(sds, cfg, FakeMesh({"data": 1, "model": tp}),
                        layout="serve")
    return [(n, s) for n, s in _flat_with_names(specs) if n[-1] == "wq"]


@pytest.mark.parametrize("arch,heads", [
    ("arctic_480b", 56), ("starcoder2_7b", 36), ("whisper_large_v3", 20),
    ("paligemma_3b", 8), ("recurrentgemma_2b", 10)])
def test_serve_head_fallback_replicates(arch, heads):
    """Head counts not divisible by tp=16 REPLICATE wq under the serve
    layout (the train layout would contraction-shard instead — exactness
    over memory)."""
    cfg = get_config(arch)
    assert cfg.n_heads == heads and heads % 16 != 0
    wqs = _serve_wq(arch)
    assert wqs, arch
    for names, spec in wqs:
        assert all(ax is None for ax in tuple(spec)), (arch, names, spec)


def test_serve_head_rule_fires_when_divisible():
    for names, spec in _serve_wq("deepseek_7b"):     # H=32 % 16 == 0
        assert "model" in tuple(spec), (names, spec)


def test_serve_gqa_kv_fallback():
    """GQA with fewer KV heads than tp: wk/wv replicate, wq still shards."""
    cfg, sds = _abstract_params("llama3_8b")         # H=32, KV=8
    specs = param_specs(sds, cfg, FakeMesh({"data": 1, "model": 16}),
                        layout="serve")
    for names, spec in _flat_with_names(specs):
        if names[-1] in ("wk", "wv"):
            assert all(ax is None for ax in tuple(spec)), (names, spec)
        if names[-1] == "wq":
            assert "model" in tuple(spec), (names, spec)


def test_serve_vocab_fallback_whisper():
    """vocab=51866 is not divisible by 16: the lm_head replicates."""
    cfg, sds = _abstract_params("whisper_large_v3")
    assert cfg.vocab % 16 != 0
    specs = param_specs(sds, cfg, FakeMesh({"data": 1, "model": 16}),
                        layout="serve")
    assert specs["lm_head"] == P()         # replicated (compressed spec)


def test_serve_tied_vocab_shards_embed():
    """command_r ties embeddings with vocab % tp == 0: the embed row-shards
    over the vocab (gather adds exact zeros; the tied unembed becomes
    column-parallel)."""
    cfg, sds = _abstract_params("command_r_plus_104b")
    assert cfg.tie_embeddings and cfg.vocab % 16 == 0
    specs = param_specs(sds, cfg, FakeMesh({"data": 1, "model": 16}),
                        layout="serve")
    assert specs["embed"] == P("model", None)


def test_serve_vs_train_output_dim_contrast():
    """w_down: train contraction-shards (f, d) -> ("model", None); serve
    output-shards -> (None, "model").  The disagreement IS the layout."""
    cfg, sds = _abstract_params("deepseek_7b")
    mesh = FakeMesh({"data": 1, "model": 16})
    train = param_specs(sds, cfg, mesh, layout="train")
    serve = param_specs(sds, cfg, mesh, layout="serve")
    g_t = train["groups"]["b0_attn"]["ffn"]["w_down"]
    g_s = serve["groups"]["b0_attn"]["ffn"]["w_down"]
    assert g_t == P(None, "model", None)
    assert g_s == P(None, None, "model")


def test_serve_moe_expert_parallel():
    cfg, sds = _abstract_params("qwen3_moe_235b")    # 128 experts % 16
    specs = param_specs(sds, cfg, FakeMesh({"data": 1, "model": 16}),
                        layout="serve")
    g = specs["groups"]["b0_attn"]["ffn"]
    assert g["w_up"] == P(None, "model", None, None)
    assert g["w_down"] == P(None, "model", None, None)


MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.data import SyntheticLM
    from repro.distributed.sharding import (make_mesh, param_specs,
                                            state_specs)
    from repro.distributed.elastic import (make_mesh_from_plan, plan_remesh,
                                           reshard_state)
    from repro.optim import AdamWConfig
    from repro.train.steps import (TrainState, dp_residuals_init,
                                   init_train_state, make_dp_train_step,
                                   make_train_step)

    out = {}
    cfg = get_config("deepseek_7b").reduced()
    mesh = make_mesh((4, 2), ("data", "model"))
    data = SyntheticLM(vocab=cfg.vocab, seq_len=32, global_batch=8)

    # --- pjit TP+DP step executes and matches single-device math ----------
    opt_cfg = AdamWConfig(total_steps=10, warmup_steps=0)
    step = make_train_step(cfg, opt_cfg)
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    sds = jax.eval_shape(lambda: state)
    specs = state_specs(sds, cfg, mesh, fsdp=True)
    ns = lambda s: NamedSharding(mesh, s)
    shardings = jax.tree.map(ns, specs, is_leaf=lambda s: isinstance(s, P))
    state_sh = jax.tree.map(lambda x, s: jax.device_put(x, s), state,
                            shardings)
    tb = data.batch_at(0)
    batch = {"tokens": jnp.asarray(tb.tokens), "labels": jnp.asarray(tb.labels)}
    jstep = jax.jit(step, in_shardings=(shardings, None),
                    out_shardings=(shardings, None))
    st2, m2 = jstep(state_sh, batch)
    st1, m1 = jax.jit(step)(state, batch)
    out["pjit_loss_delta"] = abs(float(m1["loss"]) - float(m2["loss"]))

    # --- compressed-DP shard_map step approximates exact DP ---------------
    mesh_dp = make_mesh((8,), ("data",))
    st = init_train_state(cfg, jax.random.PRNGKey(0))
    res = dp_residuals_init(st.params, mesh_dp)
    st_c = TrainState(st.params, st.opt, res)
    step_c = make_dp_train_step(cfg, opt_cfg, mesh_dp, compress=True)
    step_u = make_dp_train_step(cfg, opt_cfg, mesh_dp, compress=False)
    st2 = init_train_state(cfg, jax.random.PRNGKey(0))  # independent buffers
    st_u = TrainState(st2.params, st2.opt, None)
    lc, lu = [], []
    for i in range(6):
        tb = data.batch_at(i)
        b = {"tokens": jnp.asarray(tb.tokens),
             "labels": jnp.asarray(tb.labels)}
        st_c, mc = step_c(st_c, b)
        st_u, mu = step_u(st_u, b)
        lc.append(float(mc["loss"])); lu.append(float(mu["loss"]))
    out["dp_loss_compressed"] = lc
    out["dp_loss_uncompressed"] = lu

    # --- elastic re-mesh: 8 -> 4 devices preserves state ------------------
    plan = plan_remesh(mesh, 4, global_batch=8)
    new_mesh = make_mesh_from_plan(plan)
    st_new = reshard_state(st_u.params, cfg, new_mesh)
    d = jax.tree.map(lambda a, b: float(np.max(np.abs(
        np.asarray(a, np.float32) - np.asarray(b, np.float32)))),
        st_u.params, st_new)
    out["remesh_max_delta"] = max(jax.tree.leaves(d))
    out["remesh_shape"] = list(plan.new_shape)
    print("RESULT " + json.dumps(out))
""")


@pytest.mark.slow
def test_multidevice_integration():
    """TP+DP pjit step, compressed-DP shard_map step, elastic re-mesh — on
    8 faked devices in a subprocess."""
    proc = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT],
                          capture_output=True, text=True, timeout=900,
                          cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    out = json.loads(line[len("RESULT "):])
    # distributed step == single-device step
    assert out["pjit_loss_delta"] < 1e-4
    # compressed DP tracks exact DP within quantisation noise
    lc, lu = out["dp_loss_compressed"], out["dp_loss_uncompressed"]
    assert abs(lc[0] - lu[0]) < 1e-5          # first step: same loss
    assert all(abs(a - b) < 0.05 for a, b in zip(lc, lu))
    # elastic re-mesh is value-preserving
    assert out["remesh_max_delta"] == 0.0
    assert out["remesh_shape"] == [2, 2]
