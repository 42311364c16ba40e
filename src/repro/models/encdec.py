"""Whisper-style encoder-decoder backbone (conv frontend stubbed).

``input_specs`` provides precomputed frame embeddings (B, encoder_seq, d) —
the paper's AVS technique concerns the matmul operator domains, which the
conv frontend does not add to (it is a fixed preprocessing stage on the
paper's accelerator too).  Encoder: bidirectional attention + plain-GELU
MLP, sinusoidal positions.  Decoder: causal self-attention + cross-attention
into the encoder output + MLP, learned positions.  Both stacks scan over
stacked layers.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.distributed.sharding import constrain_replicated
from . import attention as attn_lib
from .layers import (FaultConfig, init_norm, layer_norm, mlp_apply, mlp_init,
                     norm, op_einsum, sinusoid_positions)
from .transformer import _attn_init, unembed

MAX_DEC_POS = 8192  # learned decoder position table (paper backbone stub)

# Dry-run cost probes: fully unroll the layer scans so XLA cost_analysis
# (which counts a scan body once) sees every layer (repro.launch.dryrun).
PROBE_UNROLL = False


def _scan(f, init, xs, n: int):
    return jax.lax.scan(f, init, xs, unroll=n if PROBE_UNROLL else 1)


def init_params(cfg: ModelConfig, key, dtype=jnp.bfloat16) -> Dict:
    d = cfg.d_model
    keys = jax.random.split(key, 8)

    def enc_layer(k):
        k1, k2 = jax.random.split(k)
        return {"norm1": init_norm(cfg.norm, d, dtype),
                "attn": _attn_init(k1, cfg, dtype),
                "norm2": init_norm(cfg.norm, d, dtype),
                "ffn": mlp_init(k2, d, cfg.d_ff, cfg.mlp, dtype)}

    def dec_layer(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"norm1": init_norm(cfg.norm, d, dtype),
                "self_attn": _attn_init(k1, cfg, dtype),
                "norm_x": init_norm(cfg.norm, d, dtype),
                "cross_attn": _attn_init(k2, cfg, dtype),
                "norm2": init_norm(cfg.norm, d, dtype),
                "ffn": mlp_init(k3, d, cfg.d_ff, cfg.mlp, dtype)}

    return {
        "embed": jax.random.normal(keys[0], (cfg.vocab, d), dtype) * 0.02,
        "dec_pos": jax.random.normal(keys[1], (MAX_DEC_POS, d), dtype) * 0.01,
        "enc_layers": jax.vmap(enc_layer)(
            jax.random.split(keys[2], cfg.n_encoder_layers)),
        "dec_layers": jax.vmap(dec_layer)(
            jax.random.split(keys[3], cfg.n_layers)),
        "enc_final": init_norm(cfg.norm, d, dtype),
        "final_norm": init_norm(cfg.norm, d, dtype),
        "lm_head": jax.random.normal(keys[4], (d, cfg.vocab),
                                     dtype) * d ** -0.5,
    }


def _self_attn(h, ap, cfg, *, causal, fi=None, salt=0, cache=None,
               cache_len=None):
    q = op_einsum("bsd,dhk->bshk", h, ap["wq"], "q", fi, salt)
    k = op_einsum("bsd,dhk->bshk", h, ap["wk"], "k", fi, salt)
    v = op_einsum("bsd,dhk->bshk", h, ap["wv"], "v", fi, salt)
    new_cache = None
    if cache is not None and q.shape[1] == 1:
        idx = cache_len - 1
        kc = jax.lax.dynamic_update_slice_in_dim(cache["k"],
                                                 k.astype(cache["k"].dtype),
                                                 idx, 1)
        vc = jax.lax.dynamic_update_slice_in_dim(cache["v"],
                                                 v.astype(cache["v"].dtype),
                                                 idx, 1)
        out = attn_lib.decode_attention(q, kc, vc, cache_len, fi=fi,
                                        salt=salt)
        new_cache = {"k": kc, "v": vc}
    elif cache is not None:
        # prefill-with-cache: run full attention AND stash the prompt's
        # K/V in slots [0, S) so subsequent decode steps attend over the
        # prompt (learned positions are applied pre-projection, so raw
        # K/V slots are position-correct)
        S = k.shape[1]
        pad = cache["k"].shape[1] - S
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0),
                         (0, 0))).astype(cache["k"].dtype)
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0),
                         (0, 0))).astype(cache["v"].dtype)
        out = attn_lib.attention(q, k, v, causal=causal, fi=fi, salt=salt)
        new_cache = {"k": kc, "v": vc}
    else:
        out = attn_lib.attention(q, k, v, causal=causal, fi=fi, salt=salt)
    return out, new_cache


def _cross_attn(h, enc_kv, ap, cfg, *, fi=None, salt=0):
    q = op_einsum("bsd,dhk->bshk", h, ap["wq"], "q", fi, salt)
    out = attn_lib.attention(q, enc_kv["k"], enc_kv["v"], causal=False,
                             fi=fi, salt=salt)
    return out


def encode(params, cfg: ModelConfig, frames, *,
           fi: Optional[FaultConfig] = None, remat: bool = False):
    """frames: (B, S_enc, d) precomputed frame embeddings (stub frontend)."""
    x = frames.astype(params["embed"].dtype)
    x = x + sinusoid_positions(x.shape[1], cfg.d_model).astype(x.dtype)

    def step(carry, lp):
        x = carry
        h = norm(x, lp["norm1"], cfg.norm)
        out, _ = _self_attn(h, lp["attn"], cfg, causal=False, fi=fi)
        x = x + op_einsum("bshk,hkd->bsd", out, lp["attn"]["wo"], "o", fi)
        h2 = norm(x, lp["norm2"], cfg.norm)
        return x + mlp_apply(h2, lp["ffn"], cfg.mlp, fi), None

    if remat:
        step = jax.checkpoint(
            step, policy=jax.checkpoint_policies
            .dots_with_no_batch_dims_saveable)
    x, _ = _scan(step, x, params["enc_layers"], cfg.n_encoder_layers)
    return norm(x, params["enc_final"], cfg.norm)


def cross_kv(params, cfg: ModelConfig, enc_out, *,
             fi: Optional[FaultConfig] = None):
    """Precompute per-decoder-layer cross-attention K/V (stacked (L, ...))."""
    def one(lp):
        ap = lp["cross_attn"]
        k = op_einsum("bsd,dhk->bshk", enc_out, ap["wk"], "k", fi)
        v = op_einsum("bsd,dhk->bshk", enc_out, ap["wv"], "v", fi)
        return {"k": k, "v": v}
    return jax.vmap(one)(params["dec_layers"])


def decode(params, cfg: ModelConfig, tokens, enc_out=None, kv=None, *,
           fi: Optional[FaultConfig] = None, cache=None, cache_len=None,
           pos_offset=0, remat: bool = False, last_only: bool = False):
    """Teacher-forced decoder (full seq) or single-step (with cache).
    ``last_only`` (prefill) computes the head at the last position alone."""
    if kv is None:
        kv = cross_kv(params, cfg, enc_out, fi=fi)
    x = params["embed"][tokens]
    S = tokens.shape[1]
    pos = jnp.arange(S) + pos_offset
    x = x + params["dec_pos"][pos][None]

    def step(carry, inp):
        x = carry
        lp, lkv, lcache, lidx = inp
        h = norm(x, lp["norm1"], cfg.norm)
        out, new_c = _self_attn(h, lp["self_attn"], cfg, causal=True, fi=fi,
                                salt=lidx, cache=lcache if cache else None,
                                cache_len=cache_len)
        x = x + op_einsum("bshk,hkd->bsd", out, lp["self_attn"]["wo"], "o",
                          fi, lidx)
        hx = norm(x, lp["norm_x"], cfg.norm)
        xo = _cross_attn(hx, lkv, lp["cross_attn"], cfg, fi=fi, salt=lidx)
        x = x + op_einsum("bshk,hkd->bsd", xo, lp["cross_attn"]["wo"], "o",
                          fi, lidx)
        h2 = norm(x, lp["norm2"], cfg.norm)
        x = x + mlp_apply(h2, lp["ffn"], cfg.mlp, fi, lidx)
        return x, (new_c if cache else jnp.zeros((0,)))

    dummy_cache = cache if cache is not None else \
        {"k": jnp.zeros((cfg.n_layers, 0)), "v": jnp.zeros((cfg.n_layers, 0))}
    if remat:
        step = jax.checkpoint(
            step, policy=jax.checkpoint_policies
            .dots_with_no_batch_dims_saveable)
    x, new_cache = _scan(
        step, x, (params["dec_layers"], kv, dummy_cache,
                  jnp.arange(cfg.n_layers)), cfg.n_layers)
    if last_only:
        x = x[:, -1:]
    with jax.named_scope("head"):
        x = norm(x, params["final_norm"], cfg.norm)
        logits = constrain_replicated(
            (x @ params["lm_head"]).astype(jnp.float32))
    return logits, (new_cache if cache is not None else None)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict:
    shp = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}
