"""Seeded random weights, in the layout the serving program takes.

The benchmark makes the weights, not the program: the same function
builds the whole tree on the device for the program (one jitted call,
bfloat16) and each layer alone for the reference, so the reference sees
the very values the program serves without taking them from it.

Layer ``l`` draws from ``fold_in(key, l)`` and nothing else, so a layer
can be made on its own.  Norm scales are drawn near 1 and LayerNorm biases
near 0, so a program that drops either is caught by the comparison.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from dims import Dims

DTYPE = jnp.bfloat16


def base_key(seed: int) -> jax.Array:
    """A key from any whole seed: the low and high 32 bits both count."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _norm(key, dims: Dims, dtype):
    ks, kb = jax.random.split(key)
    p = {"scale": (1.0 + 0.1 * jax.random.normal(ks, (dims.d_model,),
                                                  jnp.float32)).astype(dtype)}
    if dims.norm == "ln":
        p["bias"] = (0.1 * jax.random.normal(kb, (dims.d_model,),
                                             jnp.float32)).astype(dtype)
    return p


def layer_weights(key: jax.Array, layer, dims: Dims, dtype=DTYPE) -> dict:
    """One transformer block, as the program's ``b0_attn`` entry holds it."""
    d, H, KV, hd, f = (dims.d_model, dims.n_heads, dims.n_kv_heads,
                       dims.head_dim, dims.d_ff)
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, 1),
                                             layer), 9)
    normal = lambda k, shape, s: jax.random.normal(k, shape, dtype) \
        * jnp.asarray(s, dtype)
    ffn = {"w_up": normal(ks[5], (d, f), d ** -0.5),
           "w_down": normal(ks[6], (f, d), f ** -0.5)}
    if dims.mlp == "gated":
        ffn["w_gate"] = normal(ks[4], (d, f), d ** -0.5)
    return {
        "norm1": _norm(ks[7], dims, dtype),
        "norm2": _norm(ks[8], dims, dtype),
        "attn": {"wq": normal(ks[0], (d, H, hd), d ** -0.5),
                 "wk": normal(ks[1], (d, KV, hd), d ** -0.5),
                 "wv": normal(ks[2], (d, KV, hd), d ** -0.5),
                 "wo": normal(ks[3], (H, hd, d), (H * hd) ** -0.5)},
        "ffn": ffn,
    }


def outer_weights(key: jax.Array, dims: Dims, dtype=DTYPE) -> dict:
    """Embedding, final norm and lm_head."""
    ke, kn, kh = jax.random.split(jax.random.fold_in(key, 2), 3)
    d, V = dims.d_model, dims.vocab
    return {
        "embed": jax.random.normal(ke, (V, d), dtype),
        "final_norm": _norm(kn, dims, dtype),
        "lm_head": jax.random.normal(kh, (d, V), dtype)
        * jnp.asarray(d ** -0.5, dtype),
    }


def build_params(dims: Dims, seed: int) -> dict:
    """The program's whole parameter tree, made on the device in one call."""
    def build(key):
        layers = [layer_weights(key, l, dims) for l in range(dims.n_layers)]
        params = outer_weights(key, dims)
        params["groups"] = {"b0_attn": jax.tree.map(
            lambda *xs: jnp.stack(xs), *layers)}
        return params
    return jax.jit(build)(base_key(seed))
