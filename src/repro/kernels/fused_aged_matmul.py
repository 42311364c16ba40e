"""Pallas TPU kernel: fused int8 matmul + in-kernel accumulator bit upsets.

The paper's serving hot path (Sec. IV-A/V-A) is a systolic array whose
int32 accumulator registers latch timing-error upsets at the BER the AVS
policy admits.  The three-pass realisation (``systolic_matmul`` -> host-side
``jax.random`` materialising two output-sized arrays -> ``bitflip_words``
read-modify-write over HBM) models that faithfully but moves the int32
accumulator through HBM three times plus 8 bytes/word of randomness.  This
kernel injects the upset *at the accumulator*, in the K-final flush step of
the tiled matmul, the way hardware fault-injection frameworks do — the
accumulator tile never leaves VMEM un-faulted and no randomness is ever
materialised in HBM.

Per 32-bit word the upset model is unchanged (see ``bitflip.py``): flip one
uniformly chosen bit with probability ``q = 1 - (1-p)**32``.

Two in-kernel PRNG implementations, chosen statically:

* ``hw_prng=True`` (compiled TPU path): seed the on-core PRNG via
  ``pltpu.prng_seed`` with the fmix32-mixed (caller seed, ``tile_id =
  i * grid_n + j``) stream constant — the same mixing the counter path
  uses, so nearby seeds / adjacent tiles never alias — then draw
  ``pltpu.prng_random_bits`` in registers.  Every (bm, bn) output tile is
  an independent stream and the result is deterministic per (seed, grid).
* ``hw_prng=False`` (interpret mode / CPU CI): a counter-based murmur3-
  finalizer hash of (seed, tile_id, word-offset-in-tile).  Pure integer
  arithmetic, so it runs anywhere Pallas interprets — and ``ref.py``'s
  ``fused_aged_matmul_ref`` reproduces it *bit-exactly* in plain jnp, which
  is what the parity tests assert.

Both split one 32-bit draw per word: low 5 bits select the bit position,
the high 27 bits form the uniform for the flip decision.  ``q <= 3.2e-2``
for the policy-relevant BER <= 1e-3, so 27-bit resolution is ample.

The dequant epilogue (``acc * xs * ws``) is fused too when ``dequant=True``:
the faulted int32 accumulator is scaled to float32 in VMEM and the int32
tensor never round-trips through HBM at all.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_U = jnp.uint32


def fmix32(x: jax.Array) -> jax.Array:
    """murmur3 finalizer on uint32 — the counter-mode PRNG's mixing step.

    Shared verbatim by the kernel and the pure-jnp oracle so interpret-mode
    parity is bit-exact.
    """
    x = x ^ (x >> _U(16))
    x = x * _U(0x85EBCA6B)
    x = x ^ (x >> _U(13))
    x = x * _U(0xC2B2AE35)
    x = x ^ (x >> _U(16))
    return x


def stream_constant(seed: jax.Array, tile_id: jax.Array) -> jax.Array:
    """Per-(seed, tile) stream id — shared by BOTH PRNG paths.

    Mixed, not added: ``seed + tile_id`` would alias tile t of seed s with
    tile t-1 of seed s+1 (correlated upsets across nearby seeds).
    """
    return fmix32(seed * _U(0x9E3779B1) ^ tile_id * _U(0x7FEB352D))


def counter_bits(offset: jax.Array, seed: jax.Array,
                 tile_id: jax.Array) -> jax.Array:
    """One uint32 draw per word: hash(word offset, hash(seed, tile)).

    ``offset`` uint32 array (word offset within the tile), ``seed`` /
    ``tile_id`` uint32 scalars.  Two fmix32 rounds decorrelate the three
    inputs; sequential-counter + murmur3-finalizer is the standard
    hash-based counter RNG construction.
    """
    return fmix32(offset * _U(0x9E3779B9) ^ stream_constant(seed, tile_id))


def tile_counter_bits(M: int, N: int, seed: jax.Array, *, bm: int,
                      bn: int) -> jax.Array:
    """Counter draws for a whole (M, N) block in the kernel's tile layout.

    One uint32 per word, computed exactly as the flush step of every
    (bm, bn) grid tile computes it — ``tile_id = i * grid_n + j`` over the
    *padded* grid, ``offset = row-in-tile * bn + col-in-tile`` — so a plain
    jnp consumer (``ref.fused_aged_matmul_ref``, the sharded kernel-free
    injection in ``ops.py``) reproduces the kernel's upsets bit-exactly
    without materialising the pad region.  ``M`` / ``N`` are the *live*
    (unpadded) extents; draws for pad words are simply never computed
    (the kernel computes and discards them).
    """
    grid_n = -(-N // bn)
    row = jnp.arange(M, dtype=_U)[:, None]
    col = jnp.arange(N, dtype=_U)[None, :]
    tile_id = (row // _U(bm)) * _U(grid_n) + col // _U(bn)
    offset = (row % _U(bm)) * _U(bn) + col % _U(bn)
    return counter_bits(offset, jnp.asarray(seed, jnp.int32).astype(_U),
                        tile_id)


def upset_words(acc: jax.Array, bits: jax.Array, q: jax.Array) -> jax.Array:
    """Apply the one-bit-per-word upset given raw uint32 draws.

    Low 5 bits -> position, high 27 bits -> uniform in [0, 1); flip where
    the uniform lands below the word-upset probability ``q``.
    """
    pos = (bits & _U(31)).astype(jnp.int32)
    # via int32: Mosaic has no uint32 -> float32 cast, and the shifted
    # value is below 2**27, so the detour is exact
    u = (bits >> _U(5)).astype(jnp.int32).astype(jnp.float32) \
        * jnp.float32(2.0 ** -27)
    mask = jnp.left_shift(jnp.int32(1), pos)
    return jnp.where(u < q, jnp.bitwise_xor(acc, mask), acc)


def _inject(acc: jax.Array, seed, q, tile_id, *, hw_prng: bool) -> jax.Array:
    if hw_prng:
        pltpu.prng_seed(stream_constant(seed.astype(jnp.uint32),
                                        tile_id.astype(jnp.uint32)))
        bits = pltpu.bitcast(pltpu.prng_random_bits(acc.shape), jnp.uint32)
    else:
        r = jax.lax.broadcasted_iota(jnp.uint32, acc.shape, 0)
        c = jax.lax.broadcasted_iota(jnp.uint32, acc.shape, 1)
        offset = r * _U(acc.shape[1]) + c
        bits = counter_bits(offset, seed.astype(jnp.uint32),
                            tile_id.astype(jnp.uint32))
    return upset_words(acc, bits, q)


def _fused_kernel(seed_ref, q_ref, a_ref, b_ref, *refs, k_steps: int,
                  grid_n: int, hw_prng: bool, dequant: bool):
    if dequant:
        xs_ref, ws_ref, out_ref, acc_ref = refs
    else:
        out_ref, acc_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 tiles straight into the MXU; Mosaic has no int32 matmul
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    # computed outside pl.when: interpret mode cannot lower program_id
    # inside the cond branch
    tile_id = pl.program_id(0) * grid_n + pl.program_id(1)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        acc = _inject(acc_ref[...], seed_ref[0, 0], q_ref[0, 0], tile_id,
                      hw_prng=hw_prng)
        if dequant:
            out_ref[...] = acc.astype(jnp.float32) * xs_ref[...] \
                * ws_ref[...]
        else:
            out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def fused_aged_matmul(a: jax.Array, b: jax.Array, xs: jax.Array | None,
                      ws: jax.Array | None, ber, seed, *, bm: int = 256,
                      bn: int = 256, bk: int = 256,
                      interpret: bool = False) -> jax.Array:
    """``a (M, K) int8 @ b (K, N) int8`` with accumulator upsets at ``ber``.

    ``seed`` int32 scalar; each (bm, bn) tile draws an independent stream
    keyed on (seed, tile), so the output is deterministic per (seed, grid).
    With per-row / per-column scales ``xs (M, 1)`` / ``ws (1, N)`` the
    dequant epilogue is fused and the result is float32; with ``xs = ws =
    None`` the faulted int32 accumulator is returned.  M, N, K must be
    multiples of the block shape (``ops.py`` pads).  In interpret mode the
    counter-based PRNG is used (bit-exact vs ``ref.fused_aged_matmul_ref``);
    compiled TPU uses the on-core hardware PRNG.
    """
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    assert a.dtype == jnp.int8 and b.dtype == jnp.int8
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    dequant = xs is not None
    assert dequant == (ws is not None)
    k_steps = K // bk
    grid = (M // bm, N // bn, k_steps)

    q = 1.0 - (1.0 - jnp.asarray(ber, jnp.float32)) ** 32
    # scalars live in SMEM as (1, 1) blocks: under vmap (fleet lanes) each
    # becomes a (lanes, 1, 1) array blocked (squeezed, 1, 1), whose trailing
    # dims equal the array's, which Mosaic accepts
    scalar_spec = pl.BlockSpec((1, 1), lambda i, j, k: (0, 0),
                               memory_space=pltpu.SMEM)
    in_specs = [scalar_spec, scalar_spec,
                pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))]
    operands = [jnp.asarray(seed, jnp.int32).reshape(1, 1), q.reshape(1, 1),
                a, b]
    if dequant:
        assert xs.shape == (M, 1) and ws.shape == (1, N), (xs.shape, ws.shape)
        in_specs += [pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
                     pl.BlockSpec((1, bn), lambda i, j, k: (0, j))]
        operands += [xs.astype(jnp.float32), ws.astype(jnp.float32)]
    out_dtype = jnp.float32 if dequant else jnp.int32

    return pl.pallas_call(
        functools.partial(_fused_kernel, k_steps=k_steps, grid_n=grid[1],
                          hw_prng=not interpret, dequant=dequant),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
