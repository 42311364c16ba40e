"""RWKV6 (Finch) time-mix with data-dependent decay — chunked-parallel form.

Per head (dim N), per step the matrix-valued state S (N x N) evolves as

    S_t = diag(w_t) S_{t-1} + k_t v_t^T
    o_t = (r_t)^T (S_{t-1} + diag(u) k_t v_t^T)

with data-dependent per-channel decay ``w_t = exp(-exp(ww_t))`` (LoRA-
parameterised from x_t) and a bonus ``u`` for the current token.  Training /
prefill uses the standard chunked linear-attention algorithm: within a chunk
the quadratic form with decay masks, across chunks a scanned state carry —
O(S * N^2 / chunk + S * chunk * N) work, parallel over (B, H).

Decode is the O(N^2) single-step update.  The token-shift mixers use the
static interpolation form (the LoRA-dynamic token-shift of the reference
implementation is an accuracy refinement orthogonal to system structure —
recorded in DESIGN.md).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import FaultConfig, op_linear

DECAY_LORA = 64

# Dry-run cost probes: run the whole sequence as ONE chunk so the chunk scan
# has a single trip (XLA cost_analysis counts scan bodies once — see
# repro.launch.dryrun.probe_mode).
FORCE_SINGLE_CHUNK = False


def rwkv_time_mix_init(key, d: int, hd: int, dtype) -> Dict:
    ks = jax.random.split(key, 9)
    s = d ** -0.5
    H = d // hd
    return {
        "w_r": jax.random.normal(ks[0], (d, d), dtype) * s,
        "w_k": jax.random.normal(ks[1], (d, d), dtype) * s,
        "w_v": jax.random.normal(ks[2], (d, d), dtype) * s,
        "w_g": jax.random.normal(ks[3], (d, d), dtype) * s,
        "w_o": jax.random.normal(ks[4], (d, d), dtype) * s,
        "decay_base": jnp.asarray(
            jax.random.uniform(ks[5], (d,), jnp.float32, -7.0, -5.0)),
        "decay_lora_a": jax.random.normal(ks[6], (d, DECAY_LORA), dtype) * s,
        "decay_lora_b": jax.random.normal(
            ks[7], (DECAY_LORA, d), dtype) * DECAY_LORA ** -0.5,
        "bonus_u": jnp.asarray(
            jax.random.normal(ks[8], (H, hd), jnp.float32) * 0.1),
        "mix": jnp.full((5, d), 0.5, dtype),   # r,k,v,g,w token-shift mixes
    }


def rwkv_channel_mix_init(key, d: int, f: int, dtype) -> Dict:
    k1, k2 = jax.random.split(key)
    return {
        "w_in": jax.random.normal(k1, (d, f), dtype) * d ** -0.5,
        "w_out": jax.random.normal(k2, (f, d), dtype) * f ** -0.5,
        "mix": jnp.full((d,), 0.5, dtype),
    }


def _token_shift(x, x_prev1):
    """shifted(x)[t] = x[t-1]; first step uses carried x_prev1 (B, d)."""
    return jnp.concatenate([x_prev1[:, None], x[:, :-1]], axis=1)


def _chunked_wkv(r, k, v, w_log, u, chunk: int, s0):
    """Chunked linear attention with per-channel decay.

    r,k,v: (B, S, H, N); w_log: (B, S, H, N) log-decay (<0); u: (H, N);
    s0: (B, H, N, N) initial state.  Returns (out (B,S,H,N), sT).
    """
    B, S, H, N = r.shape
    nc = S // chunk
    rc = r.reshape(B, nc, chunk, H, N)
    kc = k.reshape(B, nc, chunk, H, N)
    vc = v.reshape(B, nc, chunk, H, N)
    wc = w_log.reshape(B, nc, chunk, H, N).astype(jnp.float32)

    def step(s, inp):
        rb, kb, vb, wb = inp                       # (B, chunk, H, N) each
        cum = jnp.cumsum(wb, axis=1)               # inclusive decay sums
        total = cum[:, -1:]                        # (B,1,H,N)
        # inter-chunk: o_inter[t] = (r_t * exp(cum[t-1])) @ s
        decay_in = jnp.exp(cum - wb)               # exp(cum[t-1]) = cum - w_t
        o_inter = jnp.einsum("bthn,bhnm->bthm", rb * decay_in, s)
        # intra-chunk quadratic with decay mask:
        # A[t,s] = r_t . (exp(cum[t-1]-cum[s]) * k_s)   for s < t
        #          r_t . (u * k_t)                      for s == t
        q_ = rb * decay_in                          # (B,t,H,N)
        k_ = kb * jnp.exp(-cum)                     # (B,s,H,N)
        att = jnp.einsum("bthn,bshn->bhts", q_, k_)
        tri = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
        att = jnp.where(tri[None, None], att, 0.0)
        diag = jnp.einsum("bthn,hn,bthn->bth", rb, u, kb)
        o_intra = jnp.einsum("bhts,bshn->bthn", att, vb) \
            + diag[..., None] * vb
        # state update: s' = diag(exp(total)) s + sum_s exp(total-cum[s]) k v^T
        k_carry = kb * jnp.exp(total - cum)
        s_new = jnp.exp(total)[:, 0, :, :, None] * s \
            + jnp.einsum("bshn,bshm->bhnm", k_carry, vb)
        return s_new, o_inter + o_intra

    s_fin, outs = jax.lax.scan(
        step, s0,
        (rc.transpose(1, 0, 2, 3, 4), kc.transpose(1, 0, 2, 3, 4),
         vc.transpose(1, 0, 2, 3, 4), wc.transpose(1, 0, 2, 3, 4)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, S, H, N)
    return out, s_fin


def rwkv_time_mix(x, p, hd: int, *, state: Optional[Dict] = None,
                  chunk: int = 128,
                  fi: Optional[FaultConfig] = None, salt=0
                  ) -> Tuple[jax.Array, Optional[Dict]]:
    """x: (B, S, d).  state: {"shift": (B,d), "wkv": (B,H,N,N)}."""
    B, S, d = x.shape
    H = d // hd
    if FORCE_SINGLE_CHUNK:
        chunk = S
    xp = _token_shift(x, state["shift"] if state
                      else jnp.zeros((B, d), x.dtype))
    mixed = [x * p["mix"][i] + xp * (1 - p["mix"][i]) for i in range(5)]
    r = op_linear(mixed[0], p["w_r"], "q", fi, salt).reshape(B, S, H, hd)
    k = op_linear(mixed[1], p["w_k"], "k", fi, salt).reshape(B, S, H, hd)
    v = op_linear(mixed[2], p["w_v"], "v", fi, salt).reshape(B, S, H, hd)
    g = jax.nn.silu(op_linear(mixed[3], p["w_g"], "g", fi, salt))
    ww = p["decay_base"] + jnp.tanh(
        mixed[4] @ p["decay_lora_a"]) @ p["decay_lora_b"]
    # clamp per-step decay rate: faster than 0.25/step is numerically
    # indistinguishable from full decay within a chunk, and the clamp keeps
    # exp(-cum) inside float32 range in the separated chunked form.
    w_log = -jnp.clip(jnp.exp(ww.astype(jnp.float32)), 1e-6, 0.25) \
        .reshape(B, S, H, hd)

    s0 = state["wkv"] if state else jnp.zeros((B, H, hd, hd), jnp.float32)
    if S == 1 and state is not None:                    # decode fast path
        rt, kt, vt = (t[:, 0].astype(jnp.float32) for t in (r, k, v))
        wt = jnp.exp(w_log[:, 0])
        kv = jnp.einsum("bhn,bhm->bhnm", kt, vt)
        out = jnp.einsum("bhn,bhnm->bhm", rt,
                         s0 + p["bonus_u"][None, :, :, None] * kv)
        s_fin = wt[..., None] * s0 + kv
        out = out[:, None].reshape(B, 1, d)
    else:
        rf, kf, vf = (t.astype(jnp.float32) for t in (r, k, v))
        pad = (-S) % chunk
        if pad:
            z = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
            rf, kf, vf = z(rf), z(kf), z(vf)
            w_log = jnp.pad(w_log, ((0, 0), (0, pad), (0, 0), (0, 0)))
        out, s_fin = _chunked_wkv(rf, kf, vf, w_log, p["bonus_u"],
                                  min(chunk, rf.shape[1]), s0)
        out = out[:, :S].reshape(B, S, d)
    out = op_linear(out.astype(x.dtype) * g, p["w_o"], "o", fi, salt)
    new_state = ({"shift": x[:, -1], "wkv": s_fin}
                 if state is not None else None)
    return out, new_state


def rwkv_channel_mix(x, p, *, state: Optional[jax.Array] = None,
                     fi: Optional[FaultConfig] = None, salt=0):
    B, S, d = x.shape
    xp = _token_shift(x, state if state is not None
                      else jnp.zeros((B, d), x.dtype))
    xm = x * p["mix"] + xp * (1 - p["mix"])
    h = jnp.square(jax.nn.relu(op_linear(xm, p["w_in"], "up", fi, salt)))
    out = op_linear(h, p["w_out"], "down", fi, salt)
    return out, (x[:, -1] if state is not None else None)


def rwkv_init_state(batch: int, d: int, hd: int,
                    dtype=jnp.bfloat16) -> Dict:
    H = d // hd
    return {
        "tm": {"shift": jnp.zeros((batch, d), dtype),
               "wkv": jnp.zeros((batch, H, hd, hd), jnp.float32)},
        "cm_shift": jnp.zeros((batch, d), dtype),
    }
