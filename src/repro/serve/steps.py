"""Serve-step builders: prefill, decode, and whole-generation functions.

Three layers of API, all sharing the same model code paths:

* :func:`make_prefill_fn` / :func:`make_decode_fn` — steps that take the
  :class:`~repro.models.layers.FaultConfig` as a *runtime argument* (it is
  a registered pytree: BERs/keys/seeds are traced leaves).  One jitted
  instance serves every device age — advancing the runtime between calls
  re-jits nothing.  These are what :class:`repro.serve.engine.ServeEngine`
  caches and what the eager-loop oracle path dispatches per token.
* :func:`make_generate_fn` — the serving hot path: prefill + a
  ``lax.scan`` decode loop + in-graph sampling fused into ONE function,
  jitted once per (config, n_steps, top_k) bucket.  A whole generation is
  a single device dispatch: no per-token host sync, no per-token argmax
  round-trip, per-step fault streams derived in-trace by folding the scan
  index into the ``FaultConfig`` streams (``fi.for_step(t)``).
* :func:`make_prefill_step` / :func:`make_decode_step` — the legacy
  builders (``fi`` captured at build time), kept for the dry-run /
  hillclimb lowering cells that jit them with explicit shardings.

``decode_step`` consumes/produces the KV-cache pytree whose shardings come
from ``repro.distributed.sharding.cache_specs`` (sequence-sharded over
"model" when KV heads cannot split — partial-softmax decode attention).

Fault injection: ``fi`` threads the per-operator BERs from the AVS runtime
into every matmul domain.  The config carries only scalars — BERs plus
int32 *seed* streams the fused kernel expands in-register, so the weight
matmuls (``op_linear`` domains) lower with no output-sized random arrays.
The activation x activation qkt/sv domains (``op_batched_matmul``) still
route through the three-pass injection.  ``fi=None`` lowers the clean
graph (what the roofline measures).  Under a serve-mesh scope with
``(S,)`` per-shard BER vectors and the fused flags on, the weight-matmul
domains shard_map the fused kernel per column block
(``repro.kernels.ops.aged_linear`` — same streams as the kernel-free
GSPMD route, so routing never changes sampled tokens).

``TRACE_COUNTS`` ticks once per *trace* of each built function (the Python
body only runs while jax traces) — the regression tests assert repeated
``generate()`` calls on an aged runtime add zero counts.

:func:`make_generate_fn` additionally returns a
:class:`repro.obs.taps.Telemetry` bundle of per-step serving-health
scalars next to the tokens.  The taps are computed unconditionally inside
the one trace (O(batch) per step — see :func:`repro.obs.taps.logit_taps`),
so enabling/disabling telemetry at the engine layer neither retraces nor
perturbs the sampled tokens.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.models import encdec
from repro.models import transformer as tf
from repro.models.layers import FaultConfig
from repro.obs.metrics import REGISTRY
from repro.obs.taps import Telemetry, logit_taps

# name -> number of times jax traced that step body.  jit caches traces, so
# a steady-state serve loop must not tick these; see
# tests/test_serve_scanned.py::test_repeated_generate_zero_retrace.
# Registry-homed (``repro.obs.metrics.trace_counts`` folds it into the
# unified retrace guard) but still a plain ``collections.Counter``.
TRACE_COUNTS = REGISTRY.trace_counter("serve_steps")


def _fi_step(fi: Optional[FaultConfig], step):
    return None if fi is None else fi.for_step(step)


def _compute_dtype(params):
    """The model's compute dtype (the embedding's; bf16 for an int8 one).

    Cache slots are allocated in it: the layer scan writes each layer's
    K/V and recurrent state into the carried stack, whose dtype is fixed
    by its allocation."""
    return getattr(params["embed"], "dtype", jnp.bfloat16)


# --------------------------------------------------------------------------- #
# runtime-fi steps (the engine path)
# --------------------------------------------------------------------------- #
def make_prefill_fn(cfg: ModelConfig, max_len: int) -> Callable:
    """(params, tokens, fi[, prefix_embeds/frames]) -> (logits_last, cache
    [, kv]).

    The cache is allocated at ``max_len``, in the model's compute dtype,
    so subsequent decode steps reuse it in place.  The final norm and the
    head run at the last prompt position only: the one whose logits are
    sampled.  ``fi`` is a runtime argument (pytree) — one jitted instance
    covers every device age of a fault flavour.
    """
    if cfg.n_encoder_layers:
        def prefill(params, tokens, fi, frames):
            TRACE_COUNTS["prefill"] += 1
            B, S = tokens.shape
            enc = encdec.encode(params, cfg, frames, fi=fi)
            kv = encdec.cross_kv(params, cfg, enc, fi=fi)
            cache = encdec.init_cache(cfg, B, max_len,
                                      dtype=_compute_dtype(params))
            logits, cache = encdec.decode(
                params, cfg, tokens, kv=kv, fi=fi, cache=cache,
                cache_len=jnp.asarray(S, jnp.int32), last_only=True)
            return logits[:, -1], cache, kv
        return prefill

    if cfg.prefix_tokens:
        def prefill(params, tokens, fi, prefix_embeds):
            TRACE_COUNTS["prefill"] += 1
            B, S = tokens.shape
            cache = tf.init_cache(cfg, B, max_len,
                                  dtype=_compute_dtype(params))
            logits, cache, _ = tf.forward_logits(
                params, cfg, tokens, states=cache,
                cache_len=jnp.asarray(S + cfg.prefix_tokens, jnp.int32),
                fi=fi, prefix_embeds=prefix_embeds, last_only=True)
            return logits[:, -1], cache
        return prefill

    def prefill(params, tokens, fi):
        TRACE_COUNTS["prefill"] += 1
        B, S = tokens.shape
        cache = tf.init_cache(cfg, B, max_len,
                              dtype=_compute_dtype(params))
        logits, cache, _ = tf.forward_logits(
            params, cfg, tokens, states=cache,
            cache_len=jnp.asarray(S, jnp.int32), fi=fi, last_only=True)
        return logits[:, -1], cache
    return prefill


def make_decode_fn(cfg: ModelConfig) -> Callable:
    """(params, token (B,1), cache, cache_len, fi[, kv]) -> (logits, cache).

    ``fi`` is a runtime argument; engines donate the cache operand so the
    eager loop updates it in place on backends that support aliasing.
    """
    if cfg.n_encoder_layers:
        def decode(params, token, cache, cache_len, fi, kv):
            TRACE_COUNTS["decode"] += 1
            logits, new_cache = encdec.decode(
                params, cfg, token, kv=kv, fi=fi, cache=cache,
                cache_len=cache_len, pos_offset=cache_len - 1)
            return logits[:, -1], new_cache
        return decode

    def decode(params, token, cache, cache_len, fi):
        TRACE_COUNTS["decode"] += 1
        logits, new_cache = tf.decode_step(params, cfg, token, cache,
                                           cache_len, fi=fi)
        return logits[:, -1], new_cache
    return decode


# --------------------------------------------------------------------------- #
# in-graph sampling
# --------------------------------------------------------------------------- #
def sample_token(logits: jax.Array, key: jax.Array, temperature,
                 top_k: Optional[int] = None) -> jax.Array:
    """Greedy/temperature/top-k sampling as a pure graph op.

    ``temperature`` is a traced scalar: ``temperature == 0`` selects the
    argmax (exact greedy, not a limit), anything positive samples from
    ``softmax(logits / temperature)``; ``top_k`` (static) masks all but the
    k highest logits first.  Because the selection is a ``jnp.where`` and
    not Python control flow, the same compiled generation covers greedy
    and sampled serving without retracing.
    """
    greedy = jnp.argmax(logits, axis=-1)
    if top_k is not None:
        vals = jax.lax.top_k(logits, top_k)[0]
        logits = jnp.where(logits < vals[..., -1:], -jnp.inf, logits)
    t = jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-6)
    sampled = jax.random.categorical(key, logits / t, axis=-1)
    pick = jnp.where(jnp.asarray(temperature, jnp.float32) > 0,
                     sampled, greedy)
    return pick.astype(jnp.int32)


# --------------------------------------------------------------------------- #
# whole-generation (scanned) serving
# --------------------------------------------------------------------------- #
def make_generate_fn(cfg: ModelConfig, max_len: int, n_steps: int,
                     top_k: Optional[int] = None) -> Callable:
    """Build the single-dispatch generation function.

    Returns ``generate(params, prompts, fi, key, temperature[, extras])
    -> (tokens (B, n_steps), telemetry)`` where ``extras`` is
    ``prefix_embeds`` for prefix (VLM) families and ``frames`` for
    encoder-decoder families.  ``telemetry`` is a
    :class:`repro.obs.taps.Telemetry` of per-step ``(n_steps,)`` health
    series (:func:`repro.obs.taps.logit_taps`), always computed in-graph;
    callers that ignore it pay one dead-code-eliminated tuple slot, and
    the tokens are bit-identical whether or not anyone reads it.
    Prefill, a ``lax.scan`` over ``n_steps - 1`` decode steps, and
    sampling all live in one trace:

    * the KV cache never leaves the device or the trace: it lives in the
      carry of both scans, the decode loop's and each step's layer scan,
      and each decode step writes one slot per layer in place
      (:func:`repro.models.transformer._run_blocks`);
    * sampling keys thread through the carry with one ``split`` per step
      — the same derivation the eager oracle performs, so token sequences
      are bit-exact between the two paths;
    * fault streams per step come from ``fi.for_step(t)`` — in-trace
      integer folds, no materialised randoms, no per-step retrace;
    * with ``fi``, the faulted layer weights are quantised to int8 once
      per call, ahead of the prefill
      (:func:`repro.models.transformer.quantize_faulted_weights`), and the
      prefill and every decode step read those: the loop quantises
      activations only.  The eager per-token oracle quantises in place;
      the two give the same bits.

    Tokens generated past a ring-buffered (windowed) cache's capacity
    follow the same ring semantics as the eager loop (both call the same
    ``decode_step``).

    The prompt pass runs under ``jax.named_scope("prefill")`` and each
    decode step under ``"decode"``: the scopes live only in the ops'
    metadata (``op_name``), where a profiler trace's reduction finds
    them.
    """
    prefill = make_prefill_fn(cfg, max_len)
    decode = make_decode_fn(cfg)
    has_kv = bool(cfg.n_encoder_layers)

    def generate(params, prompts, fi, key, temperature, *extras):
        TRACE_COUNTS["generate"] += 1
        S = prompts.shape[1]
        with jax.named_scope("prefill"):
            if fi is not None:
                # hoist the per-op threefry stream bases out of the scan
                # body: in-loop derivation is then pure fmix32 integer folds
                fi = fi.with_seeds()
                # and the weights' int8 quantise: once per call, not per step
                params = tf.quantize_faulted_weights(params)
            out = prefill(params, prompts, fi, *extras)
            logits, cache = out[0], out[1]
            kv = out[2] if has_kv else None
            key, sub = jax.random.split(key)
            tok = sample_token(logits, sub, temperature, top_k)
            tap0 = logit_taps(logits)
        cache_len0 = S + cfg.prefix_tokens

        @jax.named_scope("decode")
        def body(carry, t):
            tok, cache, key = carry
            cache_len = jnp.asarray(cache_len0 + t, jnp.int32)
            fi_t = _fi_step(fi, t)
            if has_kv:
                logits, cache = decode(params, tok[:, None], cache,
                                       cache_len, fi_t, kv)
            else:
                logits, cache = decode(params, tok[:, None], cache,
                                       cache_len, fi_t)
            key, sub = jax.random.split(key)
            tok = sample_token(logits, sub, temperature, top_k)
            return (tok, cache, key), (tok, logit_taps(logits))

        (_, _, _), (toks, taps) = jax.lax.scan(
            body, (tok, cache, key), jnp.arange(1, n_steps, dtype=jnp.int32))
        if n_steps > 1:
            tokens = jnp.concatenate([tok[:, None], toks.T], axis=1)
            series = {k: jnp.concatenate([tap0[k][None], taps[k]])
                      for k in tap0}
        else:
            tokens = tok[:, None]
            series = {k: tap0[k][None] for k in tap0}
        return tokens, Telemetry(series)
    return generate


# --------------------------------------------------------------------------- #
# legacy builders (fi captured at build time) — dry-run / hillclimb surface
# --------------------------------------------------------------------------- #
def make_prefill_step(cfg: ModelConfig, max_len: int,
                      fi: Optional[FaultConfig] = None) -> Callable:
    """(params, tokens[, prefix_embeds/frames]) -> (logits_last, cache).

    ``fi`` is closed over — what the dry-run lowers for the ``prefill_*``
    shape cells.  Engines use :func:`make_prefill_fn` instead.
    """
    fn = make_prefill_fn(cfg, max_len)
    if cfg.n_encoder_layers:
        return lambda params, tokens, frames: fn(params, tokens, fi, frames)
    if cfg.prefix_tokens:
        return lambda params, tokens, prefix_embeds=None: \
            fn(params, tokens, fi, prefix_embeds)
    return lambda params, tokens: fn(params, tokens, fi)


def make_decode_step(cfg: ModelConfig,
                     fi: Optional[FaultConfig] = None) -> Callable:
    """(params, token (B,1), cache, cache_len[, kv]) -> (logits, cache)."""
    fn = make_decode_fn(cfg)
    if cfg.n_encoder_layers:
        return lambda params, token, cache, cache_len, kv: \
            fn(params, token, cache, cache_len, fi, kv)
    return lambda params, token, cache, cache_len: \
        fn(params, token, cache, cache_len, fi)
