"""The plain reference's parts that every family shares, in float32: matrix
products, norms, rotary embedding, activations and causal attention.  A
family module (``families/``) writes its block, embedding and head from
these, after the published architecture.

No kernels, no cache, no batching tricks: the whole sequence goes through
every layer at once, with every matrix product at ``Precision.HIGHEST``
(a TPU otherwise runs float32 products in bfloat16 passes).  It imports
nothing of the serving program; its weights come from the family's
generator.

``prec`` lowers chosen products to symmetric integer arithmetic, for the
control that decides whether a limit can tell a lower precision apart:
``{"linear": bits, "attn": bits, "head": bits}``, each ``None`` for
float32.  Activations are scaled per row and weights per output column,
each by its absolute maximum over the contracted axis; the integer
products are exact in float32 until the sums pass 2**24, which the
widths here keep to a relative error near 1e-7.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = {"linear": None, "attn": None, "head": None}
Q_CHUNK = 256


def quantize(x, axis: int, bits: int):
    top = 2.0 ** (bits - 1) - 1.0
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-30) / top
    return jnp.clip(jnp.round(x / scale), -top, top), scale


def matmul(a, b, bits: Optional[int] = None):
    """``a @ b`` in float32, or in ``bits``-bit integers (a per row, b per
    column, both over the contracted axis).  ``b`` is cast to float32
    here, one matrix at a time, so a layer's weights stay in their own
    type until each is used."""
    b = b.astype(jnp.float32)
    if bits is None:
        return jnp.matmul(a, b, precision=HIGHEST)
    qa, sa = quantize(a, -1, bits)
    qb, sb = quantize(b, -2, bits)
    return jnp.matmul(qa, qb, precision=HIGHEST) * sa * sb


def norm(x, p, kind: str, eps: float):
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    if kind == "rms":
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def rope(x, theta: float):
    """Rotary embedding at positions 0..T-1, the two halves of each head
    rotated together (the published models' ``rotate_half`` form).
    x: (n, T, heads, hd)."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def attention(q, k, v, window: Optional[int], bits: Optional[int]):
    """Causal softmax attention, q (n, T, H, hd), k/v (n, T, KV, hd); query
    head h reads key/value head h // (H // KV).  Queries go in chunks of
    Q_CHUNK so the scores of a long prompt fit."""
    n, T, H, hd = q.shape
    group = H // k.shape[2]
    k = jnp.repeat(k, group, axis=2).transpose(0, 2, 3, 1)     # n H hd T
    v = jnp.repeat(v, group, axis=2).transpose(0, 2, 1, 3)     # n H T hd
    q = (q * hd ** -0.5).transpose(0, 2, 1, 3)                 # n H T hd
    pad = (-T) % Q_CHUNK
    qc = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qc = qc.reshape(n, H, -1, Q_CHUNK, hd).transpose(2, 0, 1, 3, 4)
    kpos = jnp.arange(T)

    def one(args):
        qi, start = args
        qpos = start + jnp.arange(Q_CHUNK)
        ok = kpos[None, :] <= qpos[:, None]
        if window is not None:
            ok &= qpos[:, None] - kpos[None, :] < window
        s = jnp.where(ok, matmul(qi, k, bits), -jnp.inf)
        return matmul(jax.nn.softmax(s, axis=-1), v, bits)

    starts = jnp.arange(qc.shape[0]) * Q_CHUNK
    out = jax.lax.map(one, (qc, starts))                      # c n H Q hd
    out = out.transpose(1, 2, 0, 3, 4).reshape(n, H, -1, hd)[:, :, :T]
    return out.transpose(0, 2, 1, 3)                           # n T H hd
