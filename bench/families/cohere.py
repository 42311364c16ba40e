"""model_type "cohere" (Command R, Command R+): a parallel block under one
bias-free LayerNorm, per-head QK LayerNorm, interleaved (GPT-J) RoPE, a
SwiGLU MLP, and a head tied to the embedding and scaled by
``logit_scale``, as ``transformers``' ``CohereForCausalLM`` computes it:

    h  = LN(x)
    x' = x + Wo attn(RoPEi(LNq(Wq h)), RoPEi(LNk(Wk h)), Wv h)
           + Wd (silu(Wg h) * Wu h)
    logits = logit_scale * LN_f(x) E^T

Every LayerNorm subtracts the mean and has a weight and no bias; ``LNq``
and ``LNk`` normalise each head over head_dim with a weight per head.  The
family module's interface is ``decoder.py``'s; the sizes reuse its
``Dims`` (``linear_shapes`` is the same gated block), and the norm and the
rotary embedding are this file's own.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

import weights
from families import decoder
from reference import model

__all__ = ["Dims", "read_dims", "layer_weights", "outer_weights", "embed",
           "block", "head_logits", "model_config", "OUT_AXES"]

# norms stay whole; the tied table is spread over its vocabulary
OUT_AXES = {"wq": 1, "wk": 1, "wv": 1, "wo": 2, "w_gate": 1, "w_up": 1,
            "w_down": 1, "embed": 0}


@dataclasses.dataclass(frozen=True)
class Dims(decoder.Dims):
    logit_scale: float


def read_dims(name: str, conf: dict) -> Dims:
    for key, want in (("hidden_act", "silu"), ("use_qk_norm", True),
                      ("attention_bias", False)):
        if conf.get(key) != want:
            raise ValueError(f"{name}: {key} {conf.get(key)!r} has no "
                             f"reader (the family reads {want!r})")
    h = int(conf["num_attention_heads"])
    return Dims(
        name=name,
        n_layers=int(conf["num_hidden_layers"]),
        d_model=int(conf["hidden_size"]),
        n_heads=h,
        n_kv_heads=int(conf["num_key_value_heads"]),
        head_dim=int(conf["hidden_size"]) // h,
        d_ff=int(conf["intermediate_size"]),
        vocab=int(conf["vocab_size"]),
        mlp="gated",
        norm="ln",
        norm_eps=float(conf["layer_norm_eps"]),
        rope_theta=float(conf["rope_theta"]),
        window=None,
        logit_scale=float(conf["logit_scale"]),
    )


# --- weights: layer l draws from fold_in(fold_in(key, 1), l) alone; every
# norm weight is drawn near 1, so a program that drops one is caught

def _near_one(key, shape, dtype):
    return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


def layer_weights(key: jax.Array, layer, dims: Dims,
                  dtype=weights.DTYPE) -> dict:
    """One block, as the program's ``b0_attn`` entry holds it: one norm,
    per-head q/k norms inside ``attn``."""
    d, H, KV, hd, f = (dims.d_model, dims.n_heads, dims.n_kv_heads,
                       dims.head_dim, dims.d_ff)
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, 1),
                                             layer), 10)
    normal = lambda k, shape, s: jax.random.normal(k, shape, dtype) \
        * jnp.asarray(s, dtype)
    return {
        "norm1": {"scale": _near_one(ks[7], (d,), dtype)},
        "attn": {"wq": normal(ks[0], (d, H, hd), d ** -0.5),
                 "wk": normal(ks[1], (d, KV, hd), d ** -0.5),
                 "wv": normal(ks[2], (d, KV, hd), d ** -0.5),
                 "wo": normal(ks[3], (H, hd, d), (H * hd) ** -0.5),
                 "q_norm": _near_one(ks[8], (H, hd), dtype),
                 "k_norm": _near_one(ks[9], (KV, hd), dtype)},
        "ffn": {"w_gate": normal(ks[4], (d, f), d ** -0.5),
                "w_up": normal(ks[5], (d, f), d ** -0.5),
                "w_down": normal(ks[6], (f, d), f ** -0.5)},
    }


def outer_weights(key: jax.Array, dims: Dims, dtype=weights.DTYPE) -> dict:
    """The tied table, at std d**-0.5 so that the head's logits spread as
    an untied head's do, and the final norm; no lm_head."""
    ke, kn = jax.random.split(jax.random.fold_in(key, 2))
    d = dims.d_model
    return {"embed": jax.random.normal(ke, (dims.vocab, d), dtype)
            * jnp.asarray(d ** -0.5, dtype),
            "final_norm": {"scale": _near_one(kn, (d,), dtype)}}


# --- the reference, in float32

def layer_norm(x, scale, eps: float):
    """Mean-subtracting LayerNorm over the last axis, a weight, no bias."""
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale.astype(jnp.float32)


def rope_interleaved(x, theta: float):
    """Rotary embedding at positions 0..T-1, each pair (2i, 2i+1) of a head
    rotated by the angle t * theta**(-2i/hd).  x: (n, T, heads, hd)."""
    T, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.repeat(jnp.cos(ang), 2, -1)[None, :, None, :]
    sin = jnp.repeat(jnp.sin(ang), 2, -1)[None, :, None, :]
    rot = jnp.stack([-x[..., 1::2], x[..., 0::2]], -1).reshape(x.shape)
    return x * cos + rot * sin


def block(x, w, dims: Dims, prec):
    """One layer on hidden states x (n, T, d)."""
    n, T, d = x.shape
    H, KV, hd, eps = dims.n_heads, dims.n_kv_heads, dims.head_dim, \
        dims.norm_eps
    bits = prec["linear"]
    a, f = w["attn"], w["ffn"]
    h = layer_norm(x, w["norm1"]["scale"], eps)
    q = model.matmul(h, a["wq"].reshape(d, H * hd), bits).reshape(
        n, T, H, hd)
    k = model.matmul(h, a["wk"].reshape(d, KV * hd), bits).reshape(
        n, T, KV, hd)
    v = model.matmul(h, a["wv"].reshape(d, KV * hd), bits).reshape(
        n, T, KV, hd)
    q = rope_interleaved(layer_norm(q, a["q_norm"], eps), dims.rope_theta)
    k = rope_interleaved(layer_norm(k, a["k_norm"], eps), dims.rope_theta)
    o = model.attention(q, k, v, None, prec["attn"])
    attn = model.matmul(o.reshape(n, T, H * hd), a["wo"].reshape(H * hd, d),
                        bits)
    u = jax.nn.silu(model.matmul(h, f["w_gate"], bits)) \
        * model.matmul(h, f["w_up"], bits)
    return x + attn + model.matmul(u, f["w_down"], bits)


def embed(outer, tokens):
    return outer["embed"][tokens].astype(jnp.float32)


def head_logits(x, outer, dims: Dims, prec):
    """Final norm and the scaled tied head: x (n, P, d) -> logits (n, P, V)."""
    h = layer_norm(x, outer["final_norm"]["scale"], dims.norm_eps)
    return dims.logit_scale * model.matmul(h, outer["embed"].T, prec["head"])


def model_config(dims: Dims):
    """The serving program's configuration of this model."""
    import program
    return program.ModelConfig(
        name=dims.name, family="dense", n_layers=dims.n_layers,
        d_model=dims.d_model, n_heads=dims.n_heads,
        n_kv_heads=dims.n_kv_heads, d_ff=dims.d_ff, vocab=dims.vocab,
        head_dim=dims.head_dim, mlp="gated", norm="ln_nobias", pos="rope",
        rope_theta=dims.rope_theta, rope_interleaved=True,
        qk_norm="per_head", parallel_block=True, tie_embeddings=True,
        logit_scale=dims.logit_scale)
