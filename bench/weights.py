"""Seeded random weights, in the layout the serving program takes.

The benchmark makes the weights, not the program: a family's
``layer_weights`` and ``outer_weights`` (``families/``) build the whole
tree on the device for the program (one jitted call, bfloat16) and each
layer alone for the reference, so the reference sees the very values the
program serves without taking them from it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

DTYPE = jnp.bfloat16


def base_key(seed: int) -> jax.Array:
    """A key from any whole seed: the low and high 32 bits both count."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def build_params(fam, dims, seed: int, place=None) -> dict:
    """The program's whole parameter tree, made on the device in one call.

    ``place`` maps the tree's shapes to the shardings each leaf is made
    in, so that on several chips no chip ever holds more than its share;
    the values do not depend on it (the random bits are partitionable)."""
    def build(key):
        layers = [fam.layer_weights(key, l, dims)
                  for l in range(dims.n_layers)]
        params = fam.outer_weights(key, dims)
        params["groups"] = {"b0_attn": jax.tree.map(
            lambda *xs: jnp.stack(xs), *layers)}
        return params
    key = base_key(seed)
    if place is None:
        return jax.jit(build)(key)
    return jax.jit(build, out_shardings=place(jax.eval_shape(build, key)))(
        key)
