"""Faulted weights quantised once per generate call.

``quantize_faulted_weights`` prepares each attention projection and dense
FFN weight as a ``QuantizedWeight`` ahead of the prefill; ``aged_linear``
then skips the weight's in-place quantise.  The prepared int8 values and
scales are the in-place ones bit for bit, every matmul route reads the
two forms alike, and the scanned generation (prepared) keeps token parity
with the eager per-token loop (in place).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.fleet import FleetRuntime
from repro.distributed.sharding import make_mesh
from repro.kernels import ops as kops
from repro.models import transformer as tf
from repro.serve import steps
from repro.serve.engine import ServeEngine, _generate_fn
from repro.train.steps import init_train_state

# leaf -> number of leading (per-layer) dims its matmul contracts
CONTRACT = {"wq": 1, "wk": 1, "wv": 1, "wo": 2,
            "w_gate": 1, "w_up": 1, "w_down": 1}


def _cfg(name):
    cfg = get_config(name).reduced()
    if name == "starcoder2_7b":      # GQA at the published 4 KV heads
        cfg = dataclasses.replace(cfg, n_heads=8, n_kv_heads=4)
    if name == "recurrentgemma_2b":  # one (rec, rec, attn) group + a tail
        cfg = dataclasses.replace(cfg, n_layers=4)
    return cfg


def _bits(a):
    """Floats as their raw bits, so that equality is bit for bit."""
    a = np.asarray(a)
    if not jnp.issubdtype(a.dtype, jnp.floating):
        return a
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def _prepared_leaves(raw, prep, stacked):
    """(name, float leaf, prepared leaf, stacked) for every prepared
    weight of one block, recursing through its sub-dicts."""
    for name, leaf in raw.items():
        got = prep[name]
        if isinstance(leaf, dict):
            yield from _prepared_leaves(leaf, got, stacked)
        elif isinstance(got, kops.QuantizedWeight):
            yield name, leaf, got, stacked


@pytest.mark.parametrize("arch", ["deepseek_7b", "starcoder2_7b",
                                  "recurrentgemma_2b"])
def test_quantize_faulted_weights_bit_exact(arch):
    """Every prepared leaf is exactly ``quantize_weight(w.reshape(K, -1))``
    of its layer, compiled as the in-place quantise always is,
    the stacked group axis included; every other leaf is untouched."""
    cfg = _cfg(arch)
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    prep = jax.jit(tf.quantize_faulted_weights)(params)
    blocks = [(params["groups"][k], prep["groups"][k], True)
              for k in params["groups"]]
    blocks += [(blk[k], pblk[k], False)
               for blk, pblk in zip(params.get("tail", []),
                                    prep.get("tail", [])) for k in blk]
    seen = set()
    for raw, got, stacked in blocks:
        for name, w, qw, st in _prepared_leaves(raw, got, stacked):
            seen.add(name)
            layers = list(w) if st else [w]
            for g, wl in enumerate(layers):
                nc = CONTRACT[name]
                k = int(np.prod(wl.shape[:nc]))
                q, s = jax.jit(lambda a: kops.quantize_weight(
                    a.reshape(k, -1)))(wl)
                gq = qw.q[g] if st else qw.q
                gs = qw.scale[g] if st else qw.scale
                assert gq.dtype == jnp.int8 and gq.shape == q.shape
                np.testing.assert_array_equal(np.asarray(gq), np.asarray(q))
                assert gs.dtype == s.dtype and gs.shape == s.shape
                np.testing.assert_array_equal(_bits(gs), _bits(s))
                assert qw.out_dims == tuple(wl.shape[nc:])
    want = {"w_up", "w_down"} | ({"w_gate"} if cfg.mlp == "gated" else set())
    want |= {"wq", "wk", "wv", "wo"}
    assert seen == want
    if arch == "starcoder2_7b":
        assert params["groups"]["b0_attn"]["attn"]["wk"].shape[2] == 4
    if arch == "recurrentgemma_2b":   # RG-LRU stays float, its FFN does not
        rec = prep["groups"]["b0_rec"]
        assert not any(isinstance(v, kops.QuantizedWeight)
                       for v in jax.tree.leaves(
                           rec["rglru"], is_leaf=lambda v: isinstance(
                               v, kops.QuantizedWeight)))
        assert isinstance(prep["tail"][0]["b0_rec"]["ffn"]["w_up"],
                          kops.QuantizedWeight)
    is_qw = lambda v: isinstance(v, kops.QuantizedWeight)
    kept = jax.tree.leaves(jax.tree.map(
        lambda got, raw: None if is_qw(got) else (got, raw),
        prep, params, is_leaf=is_qw))
    assert kept
    for got, raw in zip(kept[::2], kept[1::2]):
        np.testing.assert_array_equal(_bits(got), _bits(raw))


ROUTES = {
    "fused": dict(use_kernel=True, fused=True),
    "three_pass": dict(use_kernel=True, fused=False),
    "kernel_free": dict(use_kernel=False, fused=False),
    "per_shard": dict(ber=jnp.float32([0.02, 0.05])),
    "shard_map": dict(ber=jnp.float32([0.02]), shard_axis="model",
                      interpret=True),
}


@pytest.mark.parametrize("route", list(ROUTES))
def test_aged_linear_reads_prepared_weight_alike(route):
    """Each matmul route gives the same bits from a prepared weight as
    from the float weight it quantises in place."""
    kw = dict(ROUTES[route])
    kw.setdefault("ber", jnp.float32(0.02))
    if route == "shard_map":
        kw["mesh"] = make_mesh((1, 1), ("data", "model"))
    if kw.get("fused", True) and kw.get("use_kernel", True):
        kw["seed"] = jnp.int32(9)
    else:
        kw["key"] = jax.random.PRNGKey(9)
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 64), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 96), jnp.bfloat16)
    f = jax.jit(lambda x, w: kops.aged_linear(x, w, **kw))
    qw = kops.QuantizedWeight(*jax.jit(kops.quantize_weight)(w), (96,))
    inline, prepared = f(x, w), f(x, qw)
    np.testing.assert_array_equal(_bits(prepared), _bits(inline))
    clean = jax.jit(lambda x, w: kops.aged_linear(
        x, w, **{**kw, "ber": jnp.zeros_like(kw["ber"])}))(x, qw)
    assert (_bits(prepared) != _bits(clean)).any()     # upsets were live


def _aged_runtime():
    rt = FleetRuntime(n_devices=1)
    rt.set_age(years=9.0)
    return rt


def _generate_counts(cfg, params, fi):
    kops.AGED_WEIGHTS.clear()
    gen = jax.jit(steps.make_generate_fn(cfg, 24, 3))
    gen.lower(params, jnp.zeros((2, 8), jnp.int32), fi,
              jax.random.PRNGKey(1), jnp.float32(0))
    return dict(kops.AGED_WEIGHTS)


@pytest.mark.parametrize("arch", ["deepseek_7b", "qwen3_moe_235b",
                                  "rwkv6_3b"])
def test_generate_trace_counts_prepared_sites(arch):
    """Tracing generate on an aged runtime: every faulted weight matmul of
    a dense decoder reads a prepared weight, none quantises in place; a
    family left unprepared (MoE router, RWKV) still does."""
    cfg = get_config(arch).reduced()
    params = init_train_state(cfg, jax.random.PRNGKey(0)).params
    fi = ServeEngine(cfg, params, runtime=_aged_runtime(), max_len=24,
                     use_systolic_kernel=True)._fault_config()
    counts = _generate_counts(cfg, params, fi)
    if arch == "deepseek_7b":
        # one traced group body in the prefill and one in the decode scan,
        # 7 weight matmuls each (q, k, v, o, gate, up, down)
        assert counts == {"prequantized": 2 * 7}
    else:
        assert counts.get("inline", 0) > 0
    clean = _generate_counts(cfg, params, None)
    assert clean == {}                        # clean serving never faults


def test_scanned_matches_eager_per_shard_bers():
    """Per-shard ``(S,)`` BER vectors through the prepared bf16 weights:
    the scanned generation matches the eager loop that quantises in
    place, token for token, and the upsets change the tokens."""
    cfg = get_config("deepseek_7b").reduced()
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    prompts = jnp.asarray(np.arange(4 * 8).reshape(4, 8) * 7 % cfg.vocab,
                          jnp.int32)
    ops = _aged_runtime().op_bers()

    class PerShard:
        def __init__(self, scale):
            self.scale = scale

        def op_bers(self):
            return {op: np.float32([1e-4, 3e-3, 0.0, 1e-3]) * self.scale
                    for op in ops}

    def tokens(scale, scan):
        eng = ServeEngine(cfg, params, runtime=PerShard(scale), max_len=24,
                          seed=3, use_systolic_kernel=True)
        fi, key, temp = (eng._fault_config(), jax.random.PRNGKey(5),
                         jnp.float32(0))
        if not scan:
            return eng._generate_eager(prompts, 5, fi, key, temp, None, ())
        return np.asarray(_generate_fn(cfg, 24, 5, None)(
            params, prompts, fi, key, temp)[0])

    a = tokens(1.0, scan=True)
    np.testing.assert_array_equal(a, tokens(1.0, scan=False))
    assert not np.array_equal(a, tokens(0.0, scan=True))
