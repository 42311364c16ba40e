"""Command R+ (Cohere) block: parallel residual under one bias-free
LayerNorm, per-head QK LayerNorm, interleaved RoPE and a tied head scaled
by ``logit_scale``.

The serving path (prefill, then decode through the cache) is compared in
logits with a plain float32 forward written here from the published layer
equations, on seeded random weights with norm scales drawn near 1; each
Cohere part left out or replaced must fail that comparison.  Also: the
prefill head at the last position equals the last row of the full head,
and the mesh engine at tp=4 serves one device's tokens.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as tf
from repro.serve import steps

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HI = jax.lax.Precision.HIGHEST
B, S, N = 2, 12, 6           # batch, prompt, decoded positions compared
# float32 on both sides; the program and the reference differ only in the
# order of float32 sums (cache, grouped attention, fused einsums), some
# 1e-6 of logits whose spread is about 1, while every departure below moves
# them by more than 0.05
TOL = 1e-3


def cohere_cfg():
    return get_config("command_r_plus_104b").reduced()


def cohere_params(cfg, seed=0):
    """The program's tree in float32, norm scales 1 + 0.1 N(0, 1) and the
    tied table at std d**-0.5, so that the head's logits spread about 1."""
    params = tf.init_params(cfg, jax.random.PRNGKey(seed), jnp.float32)
    params["embed"] = params["embed"] / (0.02 * cfg.d_model ** 0.5)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 8))

    def near_one(a):
        return 1.0 + 0.1 * jax.random.normal(next(keys), a.shape)
    blk = params["groups"]["b0_attn"]
    blk["norm1"]["scale"] = near_one(blk["norm1"]["scale"])
    blk["attn"]["q_norm"] = near_one(blk["attn"]["q_norm"])
    blk["attn"]["k_norm"] = near_one(blk["attn"]["k_norm"])
    params["final_norm"]["scale"] = near_one(params["final_norm"]["scale"])
    return params


# --------------------------------------------------------------------------- #
# the plain reference: transformers' CohereForCausalLM in float32
# --------------------------------------------------------------------------- #
def _ln(x, w, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w


def _rope_interleaved(x, theta):
    """x (n, T, heads, hd): pair (2i, 2i+1) rotates by t * theta**(-2i/hd)."""
    T, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    cos = jnp.repeat(jnp.cos(ang), 2, -1)[None, :, None]
    sin = jnp.repeat(jnp.sin(ang), 2, -1)[None, :, None]
    rot = jnp.stack([-x[..., 1::2], x[..., 0::2]], -1).reshape(x.shape)
    return x * cos + rot * sin


def reference_logits(params, cfg, tokens):
    """x' = x + Wo attn(RoPE(LNq(Wq h)), RoPE(LNk(Wk h)), Wv h)
    + Wd(silu(Wg h) * Wu h), h = LN(x); logits = scale * LN_f(x) E^T."""
    mm = lambda a, b: jnp.matmul(a, b, precision=HI)
    E = params["embed"]
    x = E[tokens]
    n, T, d = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    causal = jnp.tril(jnp.ones((T, T), bool))
    for l in range(cfg.n_layers):
        w = jax.tree.map(lambda a: a[l], params["groups"]["b0_attn"])
        a, f = w["attn"], w["ffn"]
        h = _ln(x, w["norm1"]["scale"])
        q = mm(h, a["wq"].reshape(d, H * hd)).reshape(n, T, H, hd)
        k = mm(h, a["wk"].reshape(d, KV * hd)).reshape(n, T, KV, hd)
        v = mm(h, a["wv"].reshape(d, KV * hd)).reshape(n, T, KV, hd)
        q = _rope_interleaved(_ln(q, a["q_norm"]), cfg.rope_theta)
        k = _rope_interleaved(_ln(k, a["k_norm"]), cfg.rope_theta)
        k, v = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
        s = jnp.einsum("nthd,nshd->nhts", q, k, precision=HI) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        o = jnp.einsum("nhts,nshd->nthd", p, v, precision=HI)
        attn = mm(o.reshape(n, T, H * hd), a["wo"].reshape(H * hd, d))
        mlp = mm(jax.nn.silu(mm(h, f["w_gate"])) * mm(h, f["w_up"]),
                 f["w_down"])
        x = x + attn + mlp
    return cfg.logit_scale * mm(_ln(x, params["final_norm"]["scale"]), E.T)


def served_logits(params, cfg, tokens):
    """Prefill over the first S tokens, then decode the rest through the
    cache: logits (n, N, V) at positions S-1 .. S+N-2."""
    prefill = jax.jit(steps.make_prefill_fn(cfg, S + N))
    decode = jax.jit(steps.make_decode_fn(cfg))
    logits, cache = prefill(params, tokens[:, :S], None)
    out = [logits]
    for t in range(S, S + N - 1):
        logits, cache = decode(params, tokens[:, t:t + 1], cache,
                               jnp.int32(t + 1), None)
        out.append(logits)
    return jnp.stack(out, 1)


def _error(params, cfg, served=None, served_cfg=None):
    """Widest logit error of the program (``served`` weights and config,
    by default the reference's) against the reference, and the spread of
    the reference's logits."""
    tokens = jax.random.randint(jax.random.PRNGKey(5), (B, S + N), 0,
                                cfg.vocab)
    ref = reference_logits(params, cfg, tokens)[:, S - 1:S + N - 1]
    got = served_logits(params if served is None else served,
                        served_cfg or cfg, tokens)
    return float(jnp.max(jnp.abs(got - ref))), float(jnp.std(ref))


def test_cohere_config_is_the_published_block():
    cfg = get_config("command_r_plus_104b")
    assert (cfg.parallel_block, cfg.qk_norm, cfg.rope_interleaved,
            cfg.norm, cfg.tie_embeddings) == (True, "per_head", True,
                                              "ln_nobias", True)
    assert cfg.rope_theta == 75e6
    assert cfg.logit_scale == pytest.approx(0.8333333333333334)


def test_cohere_tree_has_one_norm_and_per_head_qk_norms():
    cfg = cohere_cfg()
    blk = cohere_params(cfg)["groups"]["b0_attn"]
    assert "norm2" not in blk and set(blk["norm1"]) == {"scale"}
    assert blk["attn"]["q_norm"].shape == (cfg.n_layers, cfg.n_heads, cfg.hd)
    assert blk["attn"]["k_norm"].shape == (cfg.n_layers, cfg.n_kv_heads,
                                           cfg.hd)


def test_cohere_prefill_then_decode_matches_reference():
    cfg = cohere_cfg()
    err, spread = _error(cohere_params(cfg), cfg)
    assert spread > 0.3                 # logits that a departure can move
    assert err < TOL, err


def _sequential(cfg, params):
    blk = params["groups"]["b0_attn"]
    blk["norm2"] = {"scale": blk["norm1"]["scale"]}
    return dataclasses.replace(cfg, parallel_block=False), params


def _shared_qk_norm(cfg, params):
    a = params["groups"]["b0_attn"]["attn"]
    a["q_norm"], a["k_norm"] = a["q_norm"][:, 0], a["k_norm"][:, 0]
    return dataclasses.replace(cfg, qk_norm="shared"), params


DEPARTURES = {
    "sequential_block": _sequential,
    "half_split_rope": lambda c, p: (
        dataclasses.replace(c, rope_interleaved=False), p),
    "qk_norm_dropped": lambda c, p: (dataclasses.replace(c, qk_norm=None), p),
    "qk_norm_shared_weights": _shared_qk_norm,
    "logit_scale_dropped": lambda c, p: (
        dataclasses.replace(c, logit_scale=1.0), p),
}


@pytest.mark.parametrize("name", sorted(DEPARTURES))
def test_cohere_departure_fails_the_comparison(name):
    cfg = cohere_cfg()
    wrong_cfg, wrong = DEPARTURES[name](cfg, cohere_params(cfg))
    err, _ = _error(cohere_params(cfg), cfg, wrong, wrong_cfg)
    assert err > 50 * TOL, (name, err)


@pytest.mark.parametrize("arch", ["deepseek_7b", "starcoder2_7b",
                                  "command_r_plus_104b"])
def test_prefill_last_position_head_is_the_full_heads_last_row(arch):
    cfg = get_config(arch).reduced()
    params = tf.init_params(cfg, jax.random.PRNGKey(2), jnp.bfloat16)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab)
    full, _, _ = jax.jit(lambda p, t: tf.forward_logits(p, cfg, t))(
        params, tokens)
    last, _, _ = jax.jit(lambda p, t: tf.forward_logits(
        p, cfg, t, last_only=True))(params, tokens)
    assert last.shape == (B, 1, cfg.vocab)
    np.testing.assert_allclose(np.asarray(last[:, 0]),
                               np.asarray(full[:, -1]), rtol=0, atol=1e-6)


MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys; sys.path.insert(0, "src")
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.core.fleet import FleetRuntime
    from repro.models import transformer as tf
    from repro.serve.engine import ServeEngine
    from repro.serve.sharded import MeshServeEngine


    class ZeroBers:
        # a shard-granular fleet of 4 with every admitted BER at 0
        def __init__(self):
            self._fleet = FleetRuntime(n_devices=1, n_shards=4)
            self._tab = jnp.zeros_like(self._fleet.op_ber_shard_jax())

        def __getattr__(self, name):
            return getattr(self._fleet, name)

        def op_ber_shard_jax(self):
            return self._tab

        def op_ber_shard_array(self):
            return np.zeros_like(self._fleet.op_ber_shard_array())


    cfg = get_config("command_r_plus_104b").reduced()
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 10),
                                            0, cfg.vocab), np.int32)
    kw = dict(max_len=16, seed=3)
    one = ServeEngine(cfg, params, runtime=None, **kw).generate(prompts, 5)
    mesh = MeshServeEngine(cfg, params, tp=4, **kw)
    aged = MeshServeEngine(cfg, params, tp=4, fleet=ZeroBers(), **kw)
    class ZeroRuntime:
        # one device with every admitted BER at 0
        def __init__(self):
            self._dev = FleetRuntime(n_devices=1).device(0)

        def __getattr__(self, name):
            return getattr(self._dev, name)

        def op_bers(self):
            return {op: 0.0 for op in self._dev.op_bers()}


    free = ServeEngine(cfg, params, runtime=ZeroRuntime(),
                       use_systolic_kernel=False, **kw)
    out = {"tp": mesh.tp,
           "one": one.tokens.tolist(),
           "mesh": mesh.generate(prompts, 5).tokens.tolist(),
           "aged_mesh": aged.generate(prompts, 5).tokens.tolist(),
           "aged_one": free.generate(prompts, 5).tokens.tolist()}
    print("RESULT " + json.dumps(out))
""")


def test_mesh_tp4_serves_one_devices_tokens():
    """Four host devices, the serve layout: clean serving gives one
    device's tokens, and so does the fused-kernel route at BER 0 against
    one device's route without the kernel (the mesh's sharded kernel
    rounds as that route does)."""
    proc = subprocess.run([sys.executable, "-c", MESH_SCRIPT],
                          capture_output=True, text=True, timeout=900,
                          cwd=REPO, env=dict(os.environ,
                                             JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out["tp"] == 4
    assert out["mesh"] == out["one"]
    assert out["aged_mesh"] == out["aged_one"]
