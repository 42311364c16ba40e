"""Decoder-only LM assembly: block patterns, grouped layer scan, KV caches.

Depth is handled by ``jax.lax.scan`` over *stacked* layer groups so compile
time and HLO size are O(1) in depth (DESIGN.md Sec. 5).  A group is one
period of ``cfg.block_pattern`` (e.g. ("rec","rec","attn") for
RecurrentGemma); layers beyond the last full period form an unstacked tail.

The same assembly serves dense, MoE, hybrid, SSM (RWKV) and VLM (prefix
embeddings + prefix-bidirectional mask) families; whisper's encoder/decoder
live in :mod:`repro.models.encdec` on top of the same block functions.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.distributed.sharding import constrain_replicated
from repro.kernels.ops import QuantizedWeight, quantize_weight
from repro.obs.metrics import REGISTRY
from . import attention as attn_lib
from .layers import (FaultConfig, apply_rope, init_norm, mlp_apply, mlp_init,
                     norm, op_einsum, op_linear)
from .moe import moe_apply, moe_init
from .rglru import rglru_block, rglru_init, rglru_init_state
from .rwkv6 import (rwkv_channel_mix, rwkv_channel_mix_init, rwkv_init_state,
                    rwkv_time_mix, rwkv_time_mix_init)


# attention blocks traced with a cache, by how they write it: "token" (a
# decode step writes one slot) or "slab" (a prefill writes its layer whole).
# Ticks when the Python body runs, i.e. while jax traces a jitted caller.
KV_WRITES = REGISTRY.trace_counter("kv_writes")


# --------------------------------------------------------------------------- #
# block parameter init
# --------------------------------------------------------------------------- #
def _attn_init(key, cfg: ModelConfig, dtype) -> Dict:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "wq": jax.random.normal(ks[0], (d, H, hd), dtype) * s,
        "wk": jax.random.normal(ks[1], (d, KV, hd), dtype) * s,
        "wv": jax.random.normal(ks[2], (d, KV, hd), dtype) * s,
        "wo": jax.random.normal(ks[3], (H, hd, d), dtype) * (H * hd) ** -0.5,
    }
    if cfg.qk_norm:
        per_head = cfg.qk_norm == "per_head"
        p["q_norm"] = jnp.ones((H, hd) if per_head else (hd,), dtype)
        p["k_norm"] = jnp.ones((KV, hd) if per_head else (hd,), dtype)
    return p


def _block_init(key, kind: str, cfg: ModelConfig, dtype) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    p = {"norm1": init_norm(cfg.norm, d, dtype)}
    if not (kind == "attn" and cfg.parallel_block):
        p["norm2"] = init_norm(cfg.norm, d, dtype)
    if kind == "attn":
        p["attn"] = _attn_init(k1, cfg, dtype)
        p["ffn"] = (moe_init(k2, d, f, cfg.moe, cfg.mlp, dtype) if cfg.moe
                    else mlp_init(k2, d, f, cfg.mlp, dtype))
    elif kind == "rec":
        p["rglru"] = rglru_init(k1, d, dtype)
        p["ffn"] = mlp_init(k2, d, f, cfg.mlp, dtype)
    elif kind == "rwkv":
        p["tm"] = rwkv_time_mix_init(k1, d, cfg.rwkv_head_dim, dtype)
        p["cm"] = rwkv_channel_mix_init(k2, d, f, dtype)
    else:
        raise ValueError(kind)
    return p


def _layer_kinds(cfg: ModelConfig):
    pat = cfg.block_pattern
    return [pat[i % len(pat)] for i in range(cfg.n_layers)]


def init_params(cfg: ModelConfig, key, dtype=jnp.bfloat16) -> Dict:
    kinds = _layer_kinds(cfg)
    pat = cfg.block_pattern
    n_groups = cfg.n_layers // len(pat)
    tail_kinds = kinds[n_groups * len(pat):]

    keys = jax.random.split(key, 8)
    d = cfg.d_model
    params: Dict = {
        "embed": jax.random.normal(keys[0], (cfg.vocab, d), dtype) * 0.02,
        "final_norm": init_norm(cfg.norm, d, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(keys[1], (d, cfg.vocab),
                                              dtype) * d ** -0.5
    if cfg.prefix_tokens:
        params["prefix_proj"] = jax.random.normal(keys[2], (d, d),
                                                  dtype) * d ** -0.5

    def one_group(gkey):
        gks = jax.random.split(gkey, len(pat))
        return {f"b{i}_{kind}": _block_init(gks[i], kind, cfg, dtype)
                for i, kind in enumerate(pat)}

    if n_groups:
        gkeys = jax.random.split(keys[3], n_groups)
        params["groups"] = jax.vmap(one_group)(gkeys)
    if tail_kinds:
        tks = jax.random.split(keys[4], len(tail_kinds))
        params["tail"] = [
            {f"b0_{kind}": _block_init(tks[i], kind, cfg, dtype)}
            for i, kind in enumerate(tail_kinds)]
    return params


# --------------------------------------------------------------------------- #
# int8 weight quantisation (EXPERIMENTS.md §Perf HC3 — paper-native: the
# accelerator's systolic array is int8; serving weights live in HBM as int8
# + per-output-channel scales and are dequantised PER LAYER GROUP inside the
# scan body, so the bf16 copy only ever exists for the layer being computed.
# Halves weight HBM residency/traffic and any weight collectives.
# --------------------------------------------------------------------------- #
def quantize_params(params: Dict) -> Dict:
    """bf16/f32 param tree -> int8 {"int8_q","int8_s"} leaves (>=2-D only)."""
    def q(leaf):
        if not hasattr(leaf, "ndim") or leaf.ndim < 2:
            return leaf
        amax = jnp.max(jnp.abs(leaf.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        s = jnp.maximum(amax, 1e-8) / 127.0
        qv = jnp.clip(jnp.round(leaf.astype(jnp.float32) / s),
                      -127, 127).astype(jnp.int8)
        return {"int8_q": qv, "int8_s": s.astype(jnp.float32)}
    return jax.tree.map(q, params)


def _is_qleaf(x) -> bool:
    return isinstance(x, dict) and "int8_q" in x


def dequant_tree(tree, dtype=jnp.bfloat16):
    """Dequantise every int8 leaf (called inside the layer-scan body)."""
    return jax.tree.map(
        lambda x: (x["int8_q"].astype(dtype) * x["int8_s"].astype(dtype)
                   if _is_qleaf(x) else x),
        tree, is_leaf=lambda x: _is_qleaf(x) or not isinstance(x, dict))


# Faulted weights quantised once per generate call: each weight matmul's
# leaf -> the number of its leading dims the matmul contracts.
_FAULTED_WEIGHTS = {"attn": {"wq": 1, "wk": 1, "wv": 1, "wo": 2},
                    "ffn": {"w_gate": 1, "w_up": 1, "w_down": 1}}


def _quantize_weight(w, n_contract: int, stacked: bool):
    """One float leaf -> its ``QuantizedWeight`` in the ``(K, N)`` view
    that ``op_einsum`` / ``op_linear`` build, over the stacked group axis
    when ``stacked``; an HC3 int8 leaf as it is."""
    if _is_qleaf(w):
        return w
    lead = w.shape[:int(stacked)]
    cut = len(lead) + n_contract
    out_dims = w.shape[cut:]
    w2 = w.reshape(*lead, -1, math.prod(out_dims))
    return QuantizedWeight(*quantize_weight(w2), out_dims)


def _quantize_block(bp: Dict, stacked: bool) -> Dict:
    out = dict(bp)
    for part, leaves in _FAULTED_WEIGHTS.items():
        sub = bp.get(part)
        if not isinstance(sub, dict) or "w_router" in sub:   # MoE: float
            continue
        out[part] = {n: (_quantize_weight(w, leaves[n], stacked)
                         if n in leaves else w) for n, w in sub.items()}
    return out


def quantize_faulted_weights(params: Dict) -> Dict:
    """Quantise every attention projection (``wq``/``wk``/``wv``/``wo``)
    and dense FFN weight of ``groups`` and ``tail`` to a
    :class:`~repro.kernels.ops.QuantizedWeight`, bit-identical to the
    in-place ``quantize_weight(w2)`` of ``aged_linear``; the faulted
    matmuls then skip it.  Every other leaf stays as it is and keeps the
    in-place quantise: MoE FFNs, RG-LRU, RWKV, encoder-decoder trees,
    HC3 int8 leaves, the unfaulted embedding and head.  Under a serve mesh
    the int8 leaves inherit the float weights' column sharding."""
    out = dict(params)
    if "groups" in params:
        out["groups"] = {k: _quantize_block(bp, stacked=True)
                         for k, bp in params["groups"].items()}
    if "tail" in params:
        out["tail"] = [{k: _quantize_block(bp, stacked=False)
                        for k, bp in blk.items()} for blk in params["tail"]]
    return out


# --------------------------------------------------------------------------- #
# block application
# --------------------------------------------------------------------------- #
def _layer_of(buf, layer):
    """Layer ``layer`` of a stacked state leaf ``(n_groups, ...)``; the leaf
    itself when ``layer`` is None (an unstacked tail block's state)."""
    if layer is None:
        return buf
    return jax.lax.dynamic_index_in_dim(buf, layer, keepdims=False)


def _write_layer(buf, new, layer):
    """Write one layer's whole state ``new`` into the stack ``buf``."""
    if layer is None:
        return new
    return jax.lax.dynamic_update_index_in_dim(buf, new.astype(buf.dtype),
                                               layer, 0)


def _write_token(buf, new, idx, layer):
    """Write a decode token ``new`` (B, 1, ...) at ring slot ``idx`` of the
    layer's ``(B, kv_len, ...)`` ring: one update slice for a scalar
    ``idx``, a scatter of each row at its own slot for a ``(B,)`` vector."""
    new = new.astype(buf.dtype)
    lead = () if layer is None else (layer,)
    if jnp.ndim(idx):            # per-row depths (continuous-batching slots)
        rows = jnp.arange(new.shape[0])
        return buf.at[lead + (rows, idx)].set(new[:, 0])
    zero = jnp.zeros((), jnp.int32)
    starts = lead + (zero, idx) + (zero,) * (new.ndim - 2)
    return jax.lax.dynamic_update_slice(
        buf, new.reshape((1,) * len(lead) + new.shape), starts)


def _read_ring(buf, new, idx, layer):
    """Layer ``layer``'s ring ``(B, kv_len, ...)`` read from the written
    stack ``buf``, with the token ``new`` selected in at slot ``idx``.

    The select gives back what :func:`_write_token` just stored, so the
    values are the stack's own.  It is there for XLA's TPU compiler, which
    rewrites the faulted route's int8 QK^T and SV matvecs over a plain
    slice of the carried stack into multiply-reduce loops on the vector
    unit, far slower than the MXU dots it keeps over the select (PERF.md
    Findings; ``tests/test_tpu_compile.py``)."""
    ring = _layer_of(buf, layer)
    slot = jnp.arange(ring.shape[1]) == jnp.reshape(idx, (-1, 1))
    slot = slot.reshape(slot.shape + (1,) * (ring.ndim - 2))
    return jnp.where(slot, new.astype(ring.dtype), ring)


def _attn_block(x, bp, cfg: ModelConfig, *, positions, prefix_len,
                cache=None, cache_len=None, fi=None, salt=0, layer=None):
    """Self-attention + FFN block.  With ``cache`` (decode): single token.

    Sequential (``x += attn(n1(x)); x += ffn(n2(x))``), or with
    ``cfg.parallel_block`` both branches read one norm of the same input:
    ``x + attn(n1(x)) + ffn(n1(x))``.

    With ``layer``, ``cache`` is the whole stack ``(n_groups, B, kv_len,
    KV, hd)`` carried through the layer scan: a decode step writes its
    token into layer ``layer``'s ring in place and attends over that
    layer's ring read from the stack, a prefill writes its layer whole.

    Each attention call runs under ``jax.named_scope("attention")``, which
    names its ops (scores, softmax, SV, their injection) in the metadata
    a profiler trace's reduction reads."""
    h = norm(x, bp["norm1"], cfg.norm)
    ap = bp["attn"]
    q = op_einsum("bsd,dhk->bshk", h, ap["wq"], "q", fi, salt)
    k = op_einsum("bsd,dhk->bshk", h, ap["wk"], "k", fi, salt)
    v = op_einsum("bsd,dhk->bshk", h, ap["wv"], "v", fi, salt)
    if cfg.qk_norm:      # over head_dim; a (heads, hd) weight is per head
        q = norm(q, {"scale": ap["q_norm"]}, cfg.norm)
        k = norm(k, {"scale": ap["k_norm"]}, cfg.norm)
    if cfg.pos == "rope":
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_interleaved)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_interleaved)

    new_cache = None
    if cache is not None:
        qcache = _is_qleaf(cache["k"])   # int8 KV cache (§Perf HC3)
        kbuf = cache["k"]["int8_q"] if qcache else cache["k"]
        kv_len = kbuf.shape[-3]
        if q.shape[1] == 1:      # decode: ring-write to cache, attend
            KV_WRITES["token"] += 1
            # ring addressing: token t lives at slot t % kv_len (identity for
            # full-length caches; wraps for windowed local attention)
            idx = jnp.remainder(cache_len - 1, kv_len)
            if qcache:
                knew, vnew = quantize_cache_entry(k), quantize_cache_entry(v)
            else:
                knew, vnew = k, v
            write = lambda buf, new: _write_token(buf, new, idx, layer)
            kc = jax.tree.map(write, cache["k"], knew)
            vc = jax.tree.map(write, cache["v"], vnew)
            read = lambda buf, new: _read_ring(buf, new, idx, layer)
            k_at = jax.tree.map(read, kc, knew)
            v_at = jax.tree.map(read, vc, vnew)
            if qcache:
                k_at = k_at["int8_q"].astype(q.dtype) \
                    * k_at["int8_s"].astype(q.dtype)
                v_at = v_at["int8_q"].astype(q.dtype) \
                    * v_at["int8_s"].astype(q.dtype)
            with jax.named_scope("attention"):
                out = attn_lib.decode_attention(q, k_at, v_at, cache_len,
                                                fi=fi, salt=salt)
        else:                    # prefill: run full attn, stash K/V
            KV_WRITES["slab"] += 1
            with jax.named_scope("attention"):
                out = attn_lib.attention(q, k, v, causal=True,
                                         window=cfg.window,
                                         prefix_len=prefix_len, fi=fi,
                                         salt=salt)
            S = k.shape[1]
            if S >= kv_len:      # windowed: keep the last kv_len tokens,
                                 # rolled so token t sits at slot t % kv_len
                kc = jnp.roll(k[:, -kv_len:], S % kv_len, axis=1)
                vc = jnp.roll(v[:, -kv_len:], S % kv_len, axis=1)
            else:
                pad = kv_len - S
                kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            if qcache:
                kc, vc = quantize_cache_entry(kc), quantize_cache_entry(vc)
            write = lambda buf, new: _write_layer(buf, new, layer)
            kc = jax.tree.map(write, cache["k"], kc)
            vc = jax.tree.map(write, cache["v"], vc)
        new_cache = {"k": kc, "v": vc}
    else:
        with jax.named_scope("attention"):
            out = attn_lib.attention(q, k, v, causal=True, window=cfg.window,
                                     prefix_len=prefix_len, fi=fi, salt=salt)
    x = x + op_einsum("bshk,hkd->bsd", out, ap["wo"], "o", fi, salt)

    h2 = h if cfg.parallel_block else norm(x, bp["norm2"], cfg.norm)
    if cfg.moe:
        y, aux = moe_apply(h2, bp["ffn"], cfg.moe, cfg.mlp, fi, salt)
    else:
        y, aux = mlp_apply(h2, bp["ffn"], cfg.mlp, fi, salt), 0.0
    return x + y, new_cache, aux


def _rec_block(x, bp, cfg: ModelConfig, *, state=None, fi=None, salt=0):
    h = norm(x, bp["norm1"], cfg.norm)
    out, new_state = rglru_block(h, bp["rglru"], state=state, fi=fi,
                                 salt=salt)
    x = x + out
    h2 = norm(x, bp["norm2"], cfg.norm)
    return x + mlp_apply(h2, bp["ffn"], cfg.mlp, fi, salt), new_state, 0.0


def _rwkv_block(x, bp, cfg: ModelConfig, *, state=None, fi=None, salt=0):
    h = norm(x, bp["norm1"], cfg.norm)
    out, tm_state = rwkv_time_mix(h, bp["tm"], cfg.rwkv_head_dim,
                                  state=state["tm"] if state else None,
                                  fi=fi, salt=salt)
    x = x + out
    h2 = norm(x, bp["norm2"], cfg.norm)
    out2, cm_shift = rwkv_channel_mix(h2, bp["cm"],
                                      state=state["cm_shift"] if state
                                      else None, fi=fi, salt=salt)
    new_state = ({"tm": tm_state, "cm_shift": cm_shift}
                 if state is not None else None)
    return x + out2, new_state, 0.0


def _apply_block(x, bp, kind, cfg, *, positions, prefix_len, state, cache_len,
                 fi, salt, layer=None):
    """One block.  ``state`` is the block's stacked state when ``layer`` is
    given (see :func:`_run_blocks`): attention writes its slot or slab into
    it in place, a recurrent state is read and written back whole."""
    if kind == "attn":
        return _attn_block(x, bp, cfg, positions=positions,
                           prefix_len=prefix_len, cache=state,
                           cache_len=cache_len, fi=fi, salt=salt, layer=layer)
    block = {"rec": _rec_block, "rwkv": _rwkv_block}.get(kind)
    if block is None:
        raise ValueError(kind)
    st = None if state is None else \
        jax.tree.map(lambda a: _layer_of(a, layer), state)
    x, ns, aux = block(x, bp, cfg, state=st, fi=fi, salt=salt)
    if state is not None:
        ns = jax.tree.map(lambda a, n: _write_layer(a, n, layer), state, ns)
    return x, ns, aux


def _run_blocks(x, params, cfg: ModelConfig, *, positions, prefix_len=0,
                states=None, cache_len=None, fi=None, remat=False):
    """Scan the grouped blocks (+ tail); threads per-block state pytrees.

    The stacked group states ride in the scan's carry, not in its xs/ys:
    each group writes its own layer of the stack in place (a decode step
    one slot of each ring), so no step slices a layer's state out of the
    stack or restacks it.  On a serve mesh the carried stack is pinned
    replicated, as every activation there is.

    ``remat=True`` rematerialises each layer group in the backward pass
    (activation checkpointing at group granularity: stored activations are
    O(n_groups * B * S * d) instead of every intermediate — the standard
    memory/compute trade for the train_4k cells; matmul outputs with no
    batch dims are kept per ``dots_with_no_batch_dims_saveable``).
    """
    pat = cfg.block_pattern
    n_groups = cfg.n_layers // len(pat)
    have_state = states is not None

    def group_step(carry, inp):
        from repro.distributed.sharding import constrain_activation
        x, aux, gstates = carry
        x = constrain_activation(x)   # pin batch sharding across the scan
        gstates = jax.tree.map(constrain_replicated, gstates)  # serve mesh
        gparams, gidx = inp
        gparams = dequant_tree(gparams, x.dtype)   # no-op unless int8 leaves
        for i, kind in enumerate(pat):
            key = f"b{i}_{kind}"
            salt = gidx * len(pat) + i
            x, ns, a = _apply_block(x, gparams[key], kind, cfg,
                                    positions=positions,
                                    prefix_len=prefix_len,
                                    state=gstates.get(key),
                                    cache_len=cache_len, fi=fi, salt=salt,
                                    layer=gidx)
            if have_state:
                gstates[key] = ns
            aux = aux + a
        return (x, aux, gstates), None

    new_states = {}
    aux_total = jnp.zeros((), jnp.float32)
    if n_groups:
        step_fn = group_step
        if remat:
            step_fn = jax.checkpoint(
                group_step,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        (x, aux_total, gstates), _ = jax.lax.scan(
            step_fn, (x, aux_total, states["groups"] if have_state else {}),
            (params["groups"], jnp.arange(n_groups)))
        if have_state:
            new_states["groups"] = gstates
    for t, tp in enumerate(params.get("tail", [])):
        tp = dequant_tree(tp, x.dtype)
        (key,) = tp.keys()
        kind = key.split("_", 1)[1]
        st = states["tail"][t][key] if have_state else None
        x, ns, a = _apply_block(x, tp[key], kind, cfg, positions=positions,
                                prefix_len=prefix_len, state=st,
                                cache_len=cache_len, fi=fi,
                                salt=n_groups * len(pat) + t)
        aux_total = aux_total + a
        if have_state:
            new_states.setdefault("tail", []).append({key: ns})
    return x, (new_states if have_state else None), aux_total


# --------------------------------------------------------------------------- #
# public API
# --------------------------------------------------------------------------- #
def embed_tokens(params, cfg: ModelConfig, tokens, prefix_embeds=None,
                 dtype=jnp.bfloat16, with_prefix=True):
    emb = params["embed"]
    if _is_qleaf(emb):        # gather int8 rows, dequantise the slice only
        x = emb["int8_q"][tokens].astype(dtype) \
            * emb["int8_s"][tokens].astype(dtype)
    else:
        x = emb[tokens]
    if cfg.scale_embeds:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if cfg.prefix_tokens and with_prefix:
        assert prefix_embeds is not None
        proj = dequant_tree({"p": params["prefix_proj"]}, x.dtype)["p"]
        pe = op_linear(prefix_embeds.astype(x.dtype), proj, "embed")
        x = jnp.concatenate([pe, x], axis=1)
    # serve mesh: the gather from a vocab-sharded table psums exact zeros —
    # pin the result replicated so downstream ops see full activations
    return constrain_replicated(x)


def unembed(params, cfg: ModelConfig, x):
    w = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    w = dequant_tree({"w": w}, x.dtype)["w"]
    if cfg.tie_embeddings:
        w = w.T
    logits = (x @ w).astype(jnp.float32)
    if cfg.logit_scale != 1.0:
        logits = logits * cfg.logit_scale
    return constrain_replicated(logits)


def apply_head(params, cfg: ModelConfig, x):
    """Final norm, unembed and logit scale, under ``jax.named_scope("head")``
    (it names the ops for a profiler trace's reduction)."""
    with jax.named_scope("head"):
        return unembed(params, cfg, norm(x, params["final_norm"], cfg.norm))


def forward_logits(params, cfg: ModelConfig, tokens, *, prefix_embeds=None,
                   fi: Optional[FaultConfig] = None,
                   states=None, cache_len=None, remat=False,
                   last_only: bool = False):
    """Full-sequence forward (train / prefill).  tokens: (B, S_text).

    ``last_only`` (prefill) computes the head at the last position alone:
    logits (B, 1, V) in place of (B, S, V)."""
    x = embed_tokens(params, cfg, tokens, prefix_embeds)
    S = x.shape[1]
    positions = jnp.arange(S)[None, :]
    x, new_states, aux = _run_blocks(
        x, params, cfg, positions=positions, prefix_len=cfg.prefix_tokens,
        states=states, cache_len=cache_len, fi=fi, remat=remat)
    if last_only:
        x = x[:, -1:]
    return apply_head(params, cfg, x), new_states, aux


def quantize_cache_entry(x):
    """bf16 (B, 1, KV, hd) -> int8 + per-(token, head) scale."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    s = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127) \
        .astype(jnp.int8)
    return {"int8_q": q, "int8_s": s.astype(jnp.float32)}


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, quantized: bool = False) -> Dict:
    """Decode-state pytree mirroring the grouped param structure.

    ``quantized=True`` stores attention K/V as int8 + per-(token, head)
    scales (§Perf HC3): the cache — the dominant HBM traffic of decode — is
    halved; dequantisation fuses into the attention matmul's operand read.
    """
    pat = cfg.block_pattern
    n_groups = cfg.n_layers // len(pat)
    tail_kinds = _layer_kinds(cfg)[n_groups * len(pat):]

    def one(kind):
        if kind == "attn":
            kv_len = min(max_len, cfg.window) if cfg.window else max_len
            shp = (batch, kv_len, cfg.n_kv_heads, cfg.hd)
            if quantized:
                z = {"int8_q": jnp.zeros(shp, jnp.int8),
                     "int8_s": jnp.zeros(shp[:-1] + (1,), jnp.float32)}
                return {"k": dict(z),
                        "v": {k: jnp.copy(v) for k, v in z.items()}}
            return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}
        if kind == "rec":
            return rglru_init_state(batch, cfg.d_model, dtype)
        if kind == "rwkv":
            return rwkv_init_state(batch, cfg.d_model, cfg.rwkv_head_dim,
                                   dtype)
        raise ValueError(kind)

    out: Dict = {}
    if n_groups:
        out["groups"] = {
            f"b{i}_{kind}": jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_groups,) + a.shape), one(kind))
            for i, kind in enumerate(pat)}
    if tail_kinds:
        out["tail"] = [{f"b0_{kind}": one(kind)} for kind in tail_kinds]
    return out


def decode_step(params, cfg: ModelConfig, token, cache, cache_len, *,
                fi: Optional[FaultConfig] = None):
    """One decode step.  token: (B, 1) int32; cache_len includes this token.

    For windowed attention the cache is ring-indexed by the caller keeping
    ``cache_len <= window`` (the serve engine rolls it); here we index
    directly — correct for cache_len within capacity.

    ``cache_len`` is a scalar (static-batch decode: every row at the same
    depth) or a ``(B,)`` vector of per-row depths — the continuous-batching
    slot path, where each slot decodes at its own position and ring-writes
    its own cache row.  An all-equal vector is bit-identical to the scalar.
    """
    x = embed_tokens(params, cfg, token, with_prefix=False)
    if jnp.ndim(cache_len) == 0:
        positions = jnp.full((1, 1), cache_len - 1, jnp.int32)
    else:
        positions = (cache_len - 1).astype(jnp.int32)[:, None]    # (B, 1)
    x, new_cache, _ = _run_blocks(x, params, cfg, positions=positions,
                                  states=cache, cache_len=cache_len, fi=fi)
    return apply_head(params, cfg, x), new_cache
