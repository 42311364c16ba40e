"""Pallas TPU kernel: int8 x int8 -> int32 systolic-array matmul.

The paper's accelerator is a 256x256 systolic array with 8-bit multipliers
and 32-bit accumulators (Sec. V-A).  On TPU that abstraction maps directly
onto the MXU: this kernel is the TPU-native realisation — an MXU-aligned
tiled matmul that keeps an int32 accumulator tile resident in VMEM across
the K-reduction, exactly as the systolic array keeps partial sums in the PE
grid.

Tiling: grid = (M/bm, N/bn, K/bk); A blocks (bm, bk), B blocks (bk, bn),
accumulator scratch (bm, bn) int32 in VMEM.  Defaults bm = bn = 256, bk = 256
echo the paper's array and are MXU-aligned (int8 min tile (32, 128)); the
kernel-bench sweeps block shapes (see EXPERIMENTS.md §Perf).

VMEM working set at defaults: 256*256 (A) + 256*256 (B) int8 + 256*256 int32
= 64 KiB + 64 KiB + 256 KiB ≈ 0.38 MiB — comfortably inside the ~16 MiB/core
VMEM budget, leaving room for double-buffered pipelining.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(a_ref, b_ref, out_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 tiles straight into the MXU; Mosaic has no int32 matmul
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def systolic_matmul(a: jax.Array, b: jax.Array, *, bm: int = 256,
                    bn: int = 256, bk: int = 256,
                    interpret: bool = False) -> jax.Array:
    """``a (M, K) int8 @ b (K, N) int8 -> (M, N) int32``.

    M, N, K must be multiples of the block shape (``ops.py`` pads).
    """
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    assert a.dtype == jnp.int8 and b.dtype == jnp.int8
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    k_steps = K // bk

    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps),
        grid=(M // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
