"""Aging-aware serving engine — the paper's technique as a runtime feature.

The engine serves one device of an AVS runtime — a legacy
:class:`repro.core.runtime.AgingAwareRuntime` or (the fleet-scale path) one
:class:`repro.core.fleet.FleetRuntime` device — with one AVS voltage domain
per operator class (the paper's Table II rows).  Before each
generation call it snapshots the runtime's current per-operator BERs into a
:class:`FaultConfig`, so every matmul executes at exactly the error rate the
fault-tolerant AVS policy admits at the device's current age.  Advancing the
simulated age between calls re-jits nothing: ``FaultConfig`` is a pytree,
the BERs enter as traced leaves of a cached compiled function (see
``tests/test_serve_scanned.py`` for the zero-retrace regression guards).

Serving model: static-batch generate.  The default path compiles prefill +
the whole decode loop + sampling into ONE dispatch
(:func:`repro.serve.steps.make_generate_fn` — a ``lax.scan`` decode with
in-graph sampling and in-trace per-step fault streams; no per-token host
sync).  The legacy per-token Python loop survives as the oracle path
(``scan=False``) and is bit-exact against the scanned path.  Compiled
functions are cached per (config, n_steps/top_k bucket, fault flavour,
shapes) at module level, shared across engine instances.

:class:`FleetServeEngine` vmaps the same generation function over the N
devices of a :class:`~repro.core.fleet.FleetRuntime`: each lane receives
its own per-operator BER vector straight from the fleet snapshot (the
array-native ``op_ber_array`` accessor — no per-device ``DeviceView``
round-trips), so a heterogeneous-age fleet serves a sharded prompt batch
in a single dispatch.

Continuous batching lives one layer up: :mod:`repro.serve.online` runs a
LIVE request queue on fixed slots over the same scanned decode — slot
refills between compiled chunks are traced-leaf updates (no re-jit), and
the measured slot occupancy replays into the fleet's aging recursion.
This module stays the static-batch engine underneath it.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig
from repro.core.fleet import FleetRuntime
from repro.models.layers import FaultConfig
from repro.obs import metrics as obs_metrics
from repro.obs.spans import span
from repro.obs.taps import taps_enabled, telemetry_to_host
from . import steps


@dataclasses.dataclass
class GenerateResult:
    tokens: np.ndarray           # (B, steps) generated ids
    bers: Dict[str, float]       # per-operator BER used
    age_years: float
    power_w: float
    # per-step tap series ({name: (n_steps,)}) when taps are enabled
    # (repro.obs.taps.enable_taps); None otherwise — the compiled graph
    # and the tokens are identical either way
    telemetry: Optional[Dict[str, np.ndarray]] = None


@dataclasses.dataclass
class FleetGenerateResult:
    tokens: np.ndarray           # (N, B, steps) generated ids per lane
    bers: np.ndarray             # (N, O) per-operator BER served per lane
    operators: tuple             # column order of ``bers``
    ages_years: np.ndarray       # (N,)
    power_w: np.ndarray          # (N,)
    telemetry: Optional[Dict[str, np.ndarray]] = None   # {name: (N, steps)}


# --------------------------------------------------------------------------- #
# module-level compile caches: engines with the same config share traces
# --------------------------------------------------------------------------- #
# Online serving is a long-lived process: an unbounded cache of compiled
# functions (each jit wrapper owns its XLA executables) is a slow memory
# leak across config/shape churn.  Every serve-side compile cache is a
# bounded LRU registered here — ``cache_stats()`` / ``clear_caches()``
# expose and reset them fleet-wide (``repro.serve.online`` registers its
# slot-prefill/decode-chunk caches through the same mechanism).
COMPILE_CACHE_MAXSIZE = 32

# The registry itself now lives in the (dependency-free) obs layer so
# health snapshots and exporters can read cache stats without importing
# serve; this module keeps the historical name as an alias to the SAME
# list object — ``CompiledFnCache.__init__`` still appends here.
_COMPILE_CACHES: list = obs_metrics._CACHES


class CompiledFnCache:
    """Bounded LRU over a compiled-function *builder*.

    Keys are the builder's (hashable) positional args; values are jitted
    wrappers.  Evicting an entry drops the only reference to its jit
    wrapper — and with it the wrapper's compiled executables — so a
    long-lived serving process cannot grow compiled-fn memory without
    bound.  ``maxsize`` is mutable (tests shrink it to exercise eviction).
    """

    def __init__(self, name: str, builder,
                 maxsize: int = COMPILE_CACHE_MAXSIZE):
        self.name = name
        self._builder = builder
        self.__doc__ = builder.__doc__
        self.maxsize = maxsize
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self.hits = self.misses = self.evictions = 0
        _COMPILE_CACHES.append(self)

    def __call__(self, *key):
        if key in self._entries:
            self._entries.move_to_end(key)
            self.hits += 1
            return self._entries[key]
        self.misses += 1
        fn = self._builder(*key)
        self._entries[key] = fn
        while len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)
            self.evictions += 1
        return fn

    def clear(self):
        self._entries.clear()

    def stats(self) -> Dict[str, int]:
        return {"currsize": len(self._entries), "maxsize": self.maxsize,
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions}


def compile_cache(name: str):
    """Decorator: route a builder through a registered bounded LRU."""
    return lambda builder: CompiledFnCache(name, builder)


def cache_stats() -> Dict[str, Dict[str, int]]:
    """Per-cache ``{currsize, maxsize, hits, misses, evictions}``.

    Back-compat alias for :func:`repro.obs.metrics.cache_stats`.
    """
    return obs_metrics.cache_stats()


def clear_caches() -> None:
    """Drop every cached compiled function (and its XLA executables).

    Back-compat alias for :func:`repro.obs.metrics.clear_caches`.
    """
    obs_metrics.clear_caches()


@compile_cache("step_fns")
def _step_fns(cfg: ModelConfig, max_len: int):
    """Jitted (prefill, decode) taking ``fi`` as a runtime pytree argument.

    One cache entry per (config, max_len); jax's own jit cache then keys
    on shapes and on the fault flavour (the ``fi`` treedef: clean ``None``
    vs faulted, fused vs oracle meta flags).  The decode cache operand is
    donated so the eager loop updates it in place where the backend
    supports aliasing (TPU; CPU falls back to a copy).
    """
    prefill = jax.jit(steps.make_prefill_fn(cfg, max_len))
    decode = jax.jit(steps.make_decode_fn(cfg), donate_argnums=(2,))
    return prefill, decode


@compile_cache("generate")
def _generate_fn(cfg: ModelConfig, max_len: int, n_steps: int,
                 top_k: Optional[int]):
    """The single-dispatch generation function, jitted."""
    return jax.jit(steps.make_generate_fn(cfg, max_len, n_steps, top_k))


@compile_cache("fleet_generate")
def _fleet_generate_fn(cfg: ModelConfig, max_len: int, n_steps: int,
                       top_k: Optional[int]):
    """vmap of the generation function over fleet lanes.

    params and temperature broadcast; prompts, the FaultConfig leaves
    (per-lane BER vectors, keys, steps) and any extras map over axis 0.
    """
    gen = steps.make_generate_fn(cfg, max_len, n_steps, top_k)
    n_extras = 1 if (cfg.n_encoder_layers or cfg.prefix_tokens) else 0
    in_axes = (None, 0, 0, 0, None) + (0,) * n_extras
    return jax.jit(jax.vmap(gen, in_axes=in_axes))


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *,
                 runtime=None, device: int = 0,
                 max_len: int = 512, use_systolic_kernel: bool = False,
                 use_fused_kernel: bool = True, seed: int = 0):
        """``runtime`` accepts a legacy ``AgingAwareRuntime``, a vectorised
        :class:`FleetRuntime` (served from fleet device ``device``), or any
        object exposing ``op_bers / age_years / total_power``.

        With ``use_systolic_kernel=True`` every weight matmul runs on the
        Pallas int8 path; ``use_fused_kernel`` (default) selects the
        single-pass kernel that draws upsets with its in-core PRNG from a
        per-(call, operator, step) seed — the engine hands the graph seeds,
        never materialised random tensors.  Set it False to route through
        the legacy three-pass injection (the oracle path)."""
        self.cfg = cfg
        self.params = params
        if isinstance(runtime, FleetRuntime):
            runtime = runtime.device(device)
        self.runtime = runtime
        self.max_len = max_len
        self.use_kernel = use_systolic_kernel
        self.use_fused = use_fused_kernel
        self._key = jax.random.PRNGKey(seed)

    # ------------------------------------------------------------------ #
    def _fault_config(self) -> Optional[FaultConfig]:
        if self.runtime is None:
            return None
        self._key, sub = jax.random.split(self._key)
        bers = {op: jnp.float32(ber)
                for op, ber in self.runtime.op_bers().items()}
        return FaultConfig(bers=bers, key=sub, step=jnp.int32(0),
                           use_systolic_kernel=self.use_kernel,
                           fused=self.use_fused)

    def _extras(self, prefix_embeds, frames):
        cfg = self.cfg
        if cfg.n_encoder_layers:
            assert frames is not None, "enc-dec family needs frames="
            return (jnp.asarray(frames),)
        if cfg.prefix_tokens:
            assert prefix_embeds is not None, "prefix family needs " \
                                              "prefix_embeds="
            return (jnp.asarray(prefix_embeds),)
        return ()

    @staticmethod
    def _temperature(greedy, temperature):
        """Resolve the legacy ``greedy`` flag against ``temperature``."""
        if temperature is None:
            temperature = 0.0 if greedy else 1.0
        return jnp.float32(temperature)

    # ------------------------------------------------------------------ #
    def generate(self, prompts: np.ndarray, n_steps: int, *,
                 prefix_embeds=None, frames=None, greedy: bool = True,
                 temperature: Optional[float] = None,
                 top_k: Optional[int] = None,
                 scan: bool = True) -> GenerateResult:
        """prompts: (B, S) int32.  Returns ``n_steps`` generated tokens.

        ``temperature=0`` (or the legacy ``greedy=True``) is exact argmax;
        positive temperature samples ``softmax(logits / T)`` restricted to
        the ``top_k`` highest logits when given.  Both resolve *in-graph*:
        changing them between calls re-jits nothing (``top_k`` is a static
        bucket).  ``scan=False`` runs the per-token eager loop — the
        oracle path, bit-exact with the default scanned path.
        """
        with span("serve.generate"):
            with span("serve.prepare"):
                cfg = self.cfg
                fi = self._fault_config()
                self._key, call_key = jax.random.split(self._key)
                temp = self._temperature(greedy, temperature)
                prompts = jnp.asarray(prompts, jnp.int32)
                extras = self._extras(prefix_embeds, frames)

            telem = None
            if scan:
                m0 = _generate_fn.misses
                gen = _generate_fn(cfg, self.max_len, int(n_steps), top_k)
                with span("serve.dispatch") as dispatch:
                    tokens_dev, telem = gen(self.params, prompts, fi,
                                            call_key, temp, *extras)
                with span("serve.wait") as wait:
                    tokens = np.asarray(tokens_dev)
            else:
                tokens = self._generate_eager(prompts, int(n_steps), fi,
                                              call_key, temp, top_k, extras)

            with span("serve.finish"):
                telemetry = None
                # host-side only: whether to transfer + record the aux
                # leaves; the compiled dispatch above is identical either way
                if telem is not None and taps_enabled():
                    telemetry = telemetry_to_host(telem)
                    self._record(tokens, telemetry,
                                 dispatch.seconds + wait.seconds,
                                 cold=_generate_fn.misses > m0)
                bers = (self.runtime.op_bers() if self.runtime else {})
                return GenerateResult(
                    tokens=tokens,
                    bers={k: float(v) for k, v in bers.items()},
                    age_years=(self.runtime.age_years if self.runtime
                               else 0.0),
                    power_w=(self.runtime.total_power() if self.runtime
                             else 0.0),
                    telemetry=telemetry,
                )

    def _record(self, tokens, telemetry, span_s: float, cold: bool) -> None:
        """Fold one generate call into the metrics registry (host-side)."""
        reg = obs_metrics.REGISTRY
        reg.counter("serve_generate_calls", "generate() dispatches").inc()
        reg.counter("serve_tokens", "tokens generated").inc(tokens.size)
        name = ("serve_generate_compile_s" if cold
                else "serve_generate_warm_s")
        obs_metrics.observe_span(name, span_s)
        for sig in ("logit_max", "logit_margin"):
            if telemetry and sig in telemetry:
                reg.histogram("serve_" + sig, "per-step serving health") \
                   .observe_many(np.asarray(telemetry[sig]).ravel())
        if self.runtime is not None:
            bers = self.runtime.op_bers()
            if bers:
                reg.gauge("serve_admitted_ber_max",
                          "worst per-operator BER served") \
                   .set(max(float(v) for v in bers.values()))

    def _generate_eager(self, prompts, n_steps, fi, key, temp, top_k,
                        extras) -> np.ndarray:
        """Per-token oracle loop: one dispatch + host sync per token.

        Kept for parity testing and as the reference semantics; the key /
        fault-stream derivation mirrors the scanned path exactly, so token
        sequences are bit-exact between the two.
        """
        cfg = self.cfg
        prefill, decode = _step_fns(cfg, self.max_len)
        out = prefill(self.params, prompts, fi, *extras)
        logits, cache = out[0], out[1]
        kv = out[2] if cfg.n_encoder_layers else None
        key, sub = jax.random.split(key)
        tok = steps.sample_token(logits, sub, temp, top_k)
        toks = [np.asarray(tok)]
        cache_len0 = prompts.shape[1] + cfg.prefix_tokens
        for t in range(1, n_steps):
            fi_t = None if fi is None else fi.for_step(jnp.int32(t))
            cache_len = jnp.asarray(cache_len0 + t, jnp.int32)
            if cfg.n_encoder_layers:
                logits, cache = decode(self.params, tok[:, None], cache,
                                       cache_len, fi_t, kv)
            else:
                logits, cache = decode(self.params, tok[:, None], cache,
                                       cache_len, fi_t)
            key, sub = jax.random.split(key)
            tok = steps.sample_token(logits, sub, temp, top_k)
            toks.append(np.asarray(tok))
        return np.stack(toks, axis=1)

    # ------------------------------------------------------------------ #
    def score(self, tokens: np.ndarray, *, prefix_embeds=None,
              frames=None) -> float:
        """Mean next-token NLL of a token batch under the aged device."""
        from repro.models import encdec
        from repro.models import transformer as tf
        from repro.train.steps import softmax_xent
        cfg = self.cfg
        fi = self._fault_config()
        tokens = jnp.asarray(tokens, jnp.int32)
        inp, lab = tokens[:, :-1], tokens[:, 1:]
        if cfg.n_encoder_layers:
            enc = encdec.encode(self.params, cfg, frames, fi=fi)
            logits, _ = encdec.decode(self.params, cfg, inp, enc_out=enc,
                                      fi=fi)
        else:
            logits, _, _ = tf.forward_logits(self.params, cfg, inp,
                                             prefix_embeds=prefix_embeds,
                                             fi=fi)
            if cfg.prefix_tokens:
                logits = logits[:, cfg.prefix_tokens:]
        return float(softmax_xent(logits, lab))


# --------------------------------------------------------------------------- #
class FleetServeEngine:
    """Serve the WHOLE fleet in one dispatch.

    Where :class:`ServeEngine` serves one device of a
    :class:`~repro.core.fleet.FleetRuntime`, this engine vmaps the
    single-dispatch generation function over all N lanes: device ``i``
    executes its slice of the prompt batch at its own policy-admitted
    per-operator BERs (one row of ``fleet.op_ber_array()``).  Params are
    broadcast, fault streams are decorrelated per lane, and the entire
    heterogeneous-age fleet generation — prefill, decode scan, sampling,
    upsets — is one compiled call.
    """

    def __init__(self, cfg: ModelConfig, params, fleet: FleetRuntime, *,
                 max_len: int = 512, use_systolic_kernel: bool = False,
                 use_fused_kernel: bool = True, seed: int = 0,
                 router=None, workload="diurnal", loads=None,
                 apply_load_kw=None):
        """``router`` (a name from ``repro.sched.router.ROUTER_REGISTRY``
        or a Router instance) ages the fleet under routed traffic before
        serving: the served per-lane BERs then reflect *traffic-dependent*
        age rather than the static mission profile.  ``workload`` /
        ``loads`` select the arrival trace and ``apply_load_kw`` passes
        any further knobs (``utilization``, ``n_epochs``, ``horizon_s``,
        ``capacity``, ``key``, ...) through to
        :meth:`repro.core.fleet.FleetRuntime.apply_load`, which this
        forwards to."""
        assert getattr(fleet, "n_shards", 1) == 1, \
            "FleetServeEngine vmaps whole devices; a shard-granular fleet " \
            "(n_shards > 1) is served by repro.serve.sharded.MeshServeEngine"
        self.cfg = cfg
        self.params = params
        self.fleet = fleet
        self.max_len = max_len
        self.use_kernel = use_systolic_kernel
        self.use_fused = use_fused_kernel
        self._key = jax.random.PRNGKey(seed)
        if router is not None:
            fleet.apply_load(loads=loads, workload=workload, router=router,
                             **(apply_load_kw or {}))

    @property
    def n_devices(self) -> int:
        return self.fleet.n_devices

    # ------------------------------------------------------------------ #
    def _fleet_fault_config(self, call_key) -> FaultConfig:
        """Batched FaultConfig: every leaf carries the fleet axis (N, ...).

        BER columns come straight from the fleet snapshot's (N, O) array —
        no per-device ``DeviceView`` round-trips — and each lane gets an
        independent fold of the call key.  The source is the fleet's
        *cached jax-native* view (``op_ber_jax``): between age changes the
        host->device transfer has already happened, so building the config
        is pure jnp slicing.
        """
        N = self.fleet.n_devices
        ber = self.fleet.op_ber_jax()                        # (N, O) jnp
        bers = {op: ber[:, i]
                for i, op in enumerate(self.fleet.operators)}
        keys = jax.random.split(call_key, N)                 # (N, key)
        return FaultConfig(bers=bers, key=keys,
                           step=jnp.zeros((N,), jnp.int32),
                           use_systolic_kernel=self.use_kernel,
                           fused=self.use_fused)

    def _shard(self, x, name: str, lane_ndim: int) -> jax.Array:
        """Per-lane input (rank ``lane_ndim``, leading N) passes through;
        a flat batch (one rank lower) is sharded over lanes.  Dispatch is
        by rank, not leading dim — a flat (N, S) batch with one prompt per
        lane is sharding, not an N-lane rank-1 prompt."""
        N = self.fleet.n_devices
        x = jnp.asarray(x)
        if x.ndim == lane_ndim:
            assert x.shape[0] == N, \
                f"{name} lane dim {x.shape[0]} != fleet size {N}"
            return x
        assert x.ndim == lane_ndim - 1, \
            f"{name} must be rank {lane_ndim} (per-lane) or " \
            f"{lane_ndim - 1} (flat batch), got rank {x.ndim}"
        assert x.shape[0] % N == 0, \
            f"{name} leading dim {x.shape[0]} not divisible by fleet " \
            f"size {N}"
        return x.reshape(N, x.shape[0] // N, *x.shape[1:])

    # ------------------------------------------------------------------ #
    def generate(self, prompts: np.ndarray, n_steps: int, *,
                 prefix_embeds=None, frames=None,
                 temperature: float = 0.0,
                 top_k: Optional[int] = None) -> FleetGenerateResult:
        """prompts: (N, B, S) per-lane, or (N*B, S) sharded across lanes.

        Returns per-lane token blocks plus the (N, O) BER matrix actually
        served.  Repeated calls after ``fleet.advance(...)`` reuse the
        compiled function — ages enter as traced leaves.
        """
        cfg = self.cfg
        self._key, call_key = jax.random.split(self._key)
        prompts = self._shard(jnp.asarray(prompts, jnp.int32), "prompts",
                              lane_ndim=3)
        fi = self._fleet_fault_config(call_key)
        keys = jax.random.split(jax.random.fold_in(call_key, 1),
                                self.fleet.n_devices)
        extras = ()
        if cfg.n_encoder_layers:
            assert frames is not None, "enc-dec family needs frames="
            extras = (self._shard(frames, "frames", lane_ndim=4),)
        elif cfg.prefix_tokens:
            assert prefix_embeds is not None, "prefix family needs " \
                                              "prefix_embeds="
            extras = (self._shard(prefix_embeds, "prefix_embeds",
                                  lane_ndim=4),)

        m0 = _fleet_generate_fn.misses
        gen = _fleet_generate_fn(cfg, self.max_len, int(n_steps), top_k)
        with span("fleet.generate") as call:
            tokens, telem = gen(self.params, prompts, fi, keys,
                                jnp.float32(temperature), *extras)
            tokens = np.asarray(tokens)
        telemetry = None
        if taps_enabled():
            # vmapped dispatch: every tap leaf carries the lane axis (N, T)
            telemetry = telemetry_to_host(telem)
            reg = obs_metrics.REGISTRY
            reg.counter("fleet_generate_calls",
                        "fleet generate() dispatches").inc()
            reg.counter("serve_tokens", "tokens generated").inc(tokens.size)
            obs_metrics.observe_span(
                "fleet_generate_compile_s"
                if _fleet_generate_fn.misses > m0
                else "fleet_generate_warm_s", call.seconds)

        snap = self.fleet.snapshot()
        return FleetGenerateResult(
            tokens=tokens,
            bers=np.asarray(snap.ber),
            operators=self.fleet.operators,
            ages_years=np.asarray(self.fleet.ages_years),
            power_w=np.asarray(self.fleet.fleet_power()),
            telemetry=telemetry,
        )
