"""Operations and bytes of one generate call, from the model's shapes.

These count the work the algorithm needs, not what an implementation
happens to do: the causal half of attention, the lm_head at the one
position that is sampled in prefill, a weight read once per step, and the
context that exists at each decode step rather than the whole cache.
``dims`` is a family's sizes (``families/``): its ``linear_shapes``,
``n_layers``, ``n_heads``, ``head_dim``, ``d_model``, ``vocab`` and
``window``.
"""
from __future__ import annotations


def _linear_kn(dims) -> int:
    return sum(k * n for _, k, n in dims.linear_shapes)


def generate_flops(dims, batch: int, prompt: int, new: int) -> float:
    """Model FLOPs of one call: prefill of ``prompt`` tokens, then
    ``new - 1`` decode steps (the first new token comes from prefill)."""
    L, H, hd = dims.n_layers, dims.n_heads, dims.head_dim
    lin = 2.0 * _linear_kn(dims) * L
    head = 2.0 * dims.d_model * dims.vocab

    def attn(q_pos):          # QK^T and PV for a query at 0-based q_pos
        span = q_pos + 1
        if dims.window is not None:
            span = min(span, dims.window)
        return 4.0 * H * hd * span * L

    prefill = batch * (prompt * lin + head
                       + sum(attn(p) for p in range(prompt)))
    decode = sum(batch * (lin + head + attn(prompt + t - 1))
                 for t in range(1, new))
    return prefill + decode


def aged_matmul_cost(m: int, k: int, n: int) -> tuple:
    """(int8 ops, bytes) of one faulted matmul (m, k) @ (k, n): int8
    activations and weights in, per-row and per-column float32 scales in,
    float32 products out."""
    return 2.0 * m * k * n, m * k + k * n + 4.0 * (m + n) + 4.0 * m * n


def aged_matmul_floor_s(dims, batch: int, prompt: int, new: int,
                        peak_ops: float, peak_bytes: float,
                        chips: int = 1) -> float:
    """Least time one chip needs for its part of one call's faulted weight
    matmuls: each layer's matmuls bound by operations or by bytes,
    whichever is slower, in prefill (M = batch * prompt) and in each decode
    step (M = batch).  Over several chips the program splits a matmul's
    output columns evenly where they divide, each chip running the kernel
    on its block; a matmul whose columns do not divide takes a route
    without the kernel, and is left out."""
    def floor(m):
        total = 0.0
        for _, k, n in dims.linear_shapes:
            if n % chips:
                continue
            ops, byts = aged_matmul_cost(m, k, n // chips)
            total += max(ops / peak_ops, byts / peak_bytes)
        return total * dims.n_layers
    return floor(batch * prompt) + (new - 1) * floor(batch)
