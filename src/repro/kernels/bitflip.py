"""Pallas TPU kernel: BER-parameterised bit-error injection.

Models timing-error upsets at the systolic array's int32 accumulator
registers (paper Sec. IV-A): a violating path latches a wrong bit.  For a
per-bit error rate ``p`` the probability a 32-bit word suffers at least one
upset is ``q = 1 - (1-p)**32``; for the BER regime of interest
(p <= 1e-3) multi-bit upsets per word are negligible, so the kernel flips
one uniformly chosen bit with probability ``q`` per word — the standard
first-order fault-injection approximation.

The random inputs (uniforms + bit positions) are produced by ``jax.random``
*outside* the kernel so that the pure-jnp oracle (``ref.py``) consumes
byte-identical randomness — the kernel is then a deterministic elementwise
map, tiled (block_rows, 128) over a 2-D layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bitflip_kernel(x_ref, u_ref, pos_ref, q_ref, out_ref):
    x = x_ref[...]
    u = u_ref[...]
    pos = pos_ref[...]
    q = q_ref[0, 0]
    mask = (jnp.int32(1) << pos.astype(jnp.int32))
    flip = u < q
    out_ref[...] = jnp.where(flip, jnp.bitwise_xor(x, mask), x)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def bitflip_words(x: jax.Array, u: jax.Array, pos: jax.Array,
                  q: jax.Array, *, block_rows: int = 256,
                  interpret: bool = False) -> jax.Array:
    """Flip one random bit per word where ``u < q``.

    ``x`` int32 of shape (R, 128); ``u`` float32 uniforms, ``pos`` int32 bit
    positions in [0, 32), same shape.  ``q`` scalar word-upset probability,
    shape (1,).  R must be a multiple of ``block_rows`` (ops.py pads).
    """
    R, C = x.shape
    assert C == 128 and R % block_rows == 0, (x.shape, block_rows)
    grid = (R // block_rows,)
    bspec = pl.BlockSpec((block_rows, C), lambda i: (i, 0))
    return pl.pallas_call(
        _bitflip_kernel,
        grid=grid,
        in_specs=[bspec, bspec, bspec,
                  # (1, 1) SMEM block: survives vmap like the fused
                  # kernel's scalars (see fused_aged_matmul.py)
                  pl.BlockSpec((1, 1), lambda i: (0, 0),
                               memory_space=pltpu.SMEM)],
        out_specs=bspec,
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.int32),
        interpret=interpret,
    )(x, u, pos, q.reshape(1, 1))
