"""Shared model layers with per-operator fault-injection hooks.

Every matmul in the zoo flows through :func:`op_linear` /
:func:`op_batched_matmul`, tagged with its operator-domain name (the paper's
Table II rows).  With a :class:`FaultConfig` attached, the op is executed the
way the paper's accelerator executes it — int8 systolic matmul + BER
bit-error injection at that operator's current admitted BER (from
``repro.core.runtime``).  Without one (training / dry-run) it is a clean
dense op, keeping the lowered HLO free of simulation artefacts.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import (constrain_replicated,
                                        serve_shard_map_info)
from repro.kernels import ops as kops


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Per-operator error-injection config for serving-time evaluation.

    Randomness enters the weight matmuls as *seeds*, not materialised
    random arrays: :meth:`seed_for` hashes (base key, operator, salt, step)
    down to an int32 scalar that the fused kernel's in-core PRNG expands
    in-register.  ``fused=False`` routes through the legacy three-pass
    injection (kept as the oracle path); the batched qkt/sv activation
    matmuls always use it (:func:`op_batched_matmul` has no 2-D tiling to
    fuse into).

    The config is a registered pytree: the BERs, key, per-op seed bases and
    ``step`` are *leaves*, so it enters jitted serve steps as a traced
    argument — advancing device age (new BER values) or the decode position
    (new ``step``) re-jits nothing.  :meth:`for_step` folds a scan index
    into every stream, giving each generated token its own deterministic
    upsets per (call, operator, step); inside ``lax.scan`` the fold is pure
    in-trace integer mixing (:func:`repro.kernels.ops.fold_seed` on the
    fused path), with no materialised randoms and no per-step retrace.
    """
    bers: Dict[str, jax.Array]          # op -> BER (scalar or (S,) per-shard)
    key: jax.Array                      # base PRNG key
    seeds: Optional[Dict[str, jax.Array]] = None  # op -> int32 stream base
    step: jax.Array | int = 0           # decode-step index (folded in-trace)
    use_systolic_kernel: bool = True    # int8 Pallas path for weight matmuls
    fused: bool = True                  # single-pass in-kernel injection

    def ber_for(self, op: str):
        return self.bers.get(op, jnp.float32(0.0))

    def for_step(self, step) -> "FaultConfig":
        """This config at decode step ``step`` (traced-safe, zero retrace)."""
        return dataclasses.replace(self, step=step)

    def with_seeds(self) -> "FaultConfig":
        """Precompute the per-operator int32 stream bases.

        Call *outside* the decode scan (the serve engine does, once per
        generate call): ``seed_for`` then derives the per-(salt, step)
        stream with two integer mixes instead of a threefry chain, keeping
        the scanned decode body free of per-token key hashing.
        """
        seeds = {op: kops.seed_from_key(jax.random.fold_in(
            self.key, _op_salt(op))) for op in self.bers}
        return dataclasses.replace(self, seeds=seeds)

    def key_for(self, op: str, salt) -> jax.Array:
        k = jax.random.fold_in(self.key, _op_salt(op))
        k = jax.random.fold_in(k, salt)
        return jax.random.fold_in(k, self.step)

    def seed_for(self, op: str, salt) -> jax.Array:
        """int32 seed for the fused kernel's per-tile PRNG streams."""
        base = (self.seeds or {}).get(op)
        if base is None:      # no precomputed base: hash the key path down
            base = kops.seed_from_key(jax.random.fold_in(
                self.key, _op_salt(op)))
        return kops.fold_seed(base, salt, self.step)


jax.tree_util.register_dataclass(
    FaultConfig, data_fields=("bers", "key", "seeds", "step"),
    meta_fields=("use_systolic_kernel", "fused"))


_OP_IDS = {op: i for i, op in enumerate(
    ("q", "k", "v", "qkt", "sv", "o", "gate", "up", "down", "router",
     "embed", "head", "r", "g", "w", "conv"))}


def _op_salt(op: str) -> int:
    return _OP_IDS.get(op, 31)


def op_linear(x: jax.Array, w, op: str,
              fi: Optional[FaultConfig] = None, salt=0) -> jax.Array:
    """``x (..., K) @ w (K, N)`` through the operator domain ``op``.

    With ``fi``, ``w`` may be a :class:`~repro.kernels.ops.QuantizedWeight`
    prepared ahead (``repro.models.transformer.quantize_faulted_weights``);
    ``aged_linear`` then skips the weight's quantise.

    Outputs pass :func:`~repro.distributed.sharding.constrain_replicated`
    — a no-op except under a serve-mesh scope, where pinning every op
    boundary replicated over "model" keeps the sharded graph bit-exact.
    A per-shard ``(S,)`` BER vector in ``fi`` flips each output-column
    block at its own shard's admitted rate with counter streams keyed on
    ``fold_seed(seed_for(op, salt), shard)``.  When a serve mesh is in
    scope (``serve_shard_map_info``) and the fused-kernel flags are on, the
    matmul is shard_mapped so each shard runs the ONE fused Pallas kernel
    on its local column block; otherwise the bit-identical kernel-free
    GSPMD route runs (see ``aged_linear`` — routing is performance-only).
    """
    if fi is None:
        return constrain_replicated(x @ w)
    ber = fi.ber_for(op)
    if jnp.ndim(ber) == 1:
        mesh = axis = None
        if fi.fused and fi.use_systolic_kernel:
            n_out = (w.q if isinstance(w, kops.QuantizedWeight)
                     else w).shape[-1]
            info = serve_shard_map_info(n_out)
            if info is not None and info[2] == int(ber.shape[0]):
                mesh, axis = info[0], info[1]
        return constrain_replicated(kops.aged_linear(
            x, w, ber=ber, seed=fi.seed_for(op, salt),
            use_kernel=fi.use_systolic_kernel, fused=fi.fused,
            shard_axis=axis, mesh=mesh))
    if fi.fused and fi.use_systolic_kernel:
        return constrain_replicated(kops.aged_linear(
            x, w, ber=ber, seed=fi.seed_for(op, salt),
            use_kernel=True, fused=True))
    # legacy routes keep the full 64-bit key stream (pre-fused behaviour)
    return constrain_replicated(kops.aged_linear(
        x, w, ber=ber, key=fi.key_for(op, salt),
        use_kernel=fi.use_systolic_kernel, fused=False))


def op_einsum(spec: str, x: jax.Array, w, op: str,
              fi: Optional[FaultConfig] = None, salt=0) -> jax.Array:
    """Einsum variant for fused head layouts; falls back to 2-D for faults.

    Supports specs whose contraction letters form a *suffix* of the x spec
    and a *prefix* of the w spec (all uses here: "bsd,dhk->bshk",
    "bshk,hkd->bsd") — the faulted path flattens both to one 2-D systolic
    matmul, matching how the accelerator executes the fused layout.  A
    :class:`~repro.kernels.ops.QuantizedWeight` already holds that
    ``(K, N)`` view and passes through as it is.
    """
    if fi is None:
        return constrain_replicated(jnp.einsum(spec, x, w))
    ins, out_spec = spec.split("->")
    x_spec, w_spec = ins.split(",")
    contract = [c for c in x_spec if c in w_spec]
    nc = len(contract)
    assert x_spec[-nc:] == w_spec[:nc] == "".join(contract), spec
    lead, k = x.shape[:x.ndim - nc], math.prod(x.shape[x.ndim - nc:])
    if isinstance(w, kops.QuantizedWeight):
        w2, out_dims = w, w.out_dims
    else:
        w2, out_dims = w.reshape(k, -1), w.shape[nc:]
    out = op_linear(x.reshape(*lead, k), w2, op, fi, salt)
    return out.reshape(*lead, *out_dims)


def op_batched_matmul(a: jax.Array, b: jax.Array, op: str,
                      fi: Optional[FaultConfig] = None, salt=0) -> jax.Array:
    """Activation x activation matmul (QK^T / SV domains): ``a @ b`` over
    leading batch dims, int8-quantised with accumulator upsets when faulted.

    Scalar BER keeps the historical stream (Pallas injection on the kernel
    path, its bit-exact jnp oracle otherwise — identical outputs either
    way).  A per-shard ``(S,)`` BER vector maps shards onto the flattened
    head axis (shard ``s`` owns heads ``[s*H//S, (s+1)*H//S)`` — the heads
    whose projections it owns in the serve layout) with shard-distinct
    fmix32 streams.
    """
    if fi is None:
        return constrain_replicated(a @ b)
    aq, ascale = kops.quantize_int8(a, axis=-1)
    bq, bscale = kops.quantize_int8(b, axis=-2)
    acc = jnp.einsum("...ik,...kj->...ij", aq.astype(jnp.int32),
                     bq.astype(jnp.int32))
    ber = fi.ber_for(op)
    if jnp.ndim(ber) == 1:
        # (B, *heads, M, N) -> (B, H, M, N): blocks of flattened heads,
        # counter streams (matches op_linear's sharded seed plumbing — no
        # threefry chain inside the decode scan)
        flat = acc.reshape(acc.shape[0], -1, *acc.shape[-2:])
        flat = kops.inject_bitflips_sharded(flat, ber,
                                            seed=fi.seed_for(op, salt),
                                            axis=1)
        acc = flat.reshape(acc.shape)
    elif fi.use_systolic_kernel:
        acc = kops.inject_bitflips(acc, ber, fi.key_for(op, salt))
    else:
        acc = kops.inject_bitflips_ref(acc, ber, fi.key_for(op, salt))
    return constrain_replicated(
        (acc.astype(jnp.float32) * ascale * bscale).astype(a.dtype))


# --------------------------------------------------------------------------- #
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) \
        * scale


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array | None = None,
               eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * scale
    if bias is not None:
        out = out + bias
    return out.astype(x.dtype)


def norm(x: jax.Array, p: Dict, kind: str) -> jax.Array:
    """``kind`` "rms", or a LayerNorm ("ln", "ln_nobias") with the bias
    ``p`` holds, if any."""
    if kind == "rms":
        return rms_norm(x, p["scale"])
    return layer_norm(x, p["scale"], p.get("bias"))


def init_norm(kind: str, d: int, dtype) -> Dict:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "ln":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


# --------------------------------------------------------------------------- #
def rope_frequencies(hd: int, theta: float) -> jax.Array:
    return theta ** (-jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               interleaved: bool = False) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S).

    Rotates the two halves of each head together (``rotate_half``), or,
    ``interleaved``, each pair (2i, 2i+1) by the i-th angle (GPT-J,
    Cohere)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                       # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs    # (..., S, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    xf = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                        axis=-1).reshape(x.shape)
    else:
        x1, x2 = jnp.split(xf, 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1)
    return out.astype(x.dtype)


def sinusoid_positions(seq: int, d: int) -> jax.Array:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / (10000.0 ** (dim / (d // 2)))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------------------------- #
def mlp_apply(x: jax.Array, p: Dict, variant: str,
              fi: Optional[FaultConfig] = None, salt=0) -> jax.Array:
    if variant == "gated":
        g = op_linear(x, p["w_gate"], "gate", fi, salt)
        u = op_linear(x, p["w_up"], "up", fi, salt)
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(op_linear(x, p["w_up"], "up", fi, salt))
    return op_linear(h, p["w_down"], "down", fi, salt)


def mlp_init(key, d: int, f: int, variant: str, dtype) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in, s_out = d ** -0.5, f ** -0.5
    p = {"w_up": jax.random.normal(k2, (d, f), dtype) * s_in,
         "w_down": jax.random.normal(k3, (f, d), dtype) * s_out}
    if variant == "gated":
        p["w_gate"] = jax.random.normal(k1, (d, f), dtype) * s_in
    return p
