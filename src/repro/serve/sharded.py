"""Mesh-sharded serving: one big model, ONE sharded dispatch, per-shard aging.

Where :class:`repro.serve.engine.FleetServeEngine` vmaps N whole devices
over replicated params (fleet of small models), this engine serves ONE
model that is too big for a single device by sharding prefill + the scanned
decode + in-graph sampling over a ``jax.sharding`` mesh — tensor/expert
parallelism over the ``"model"`` axis using the *serve layout* rules in
:mod:`repro.distributed.sharding` (output-dim-only sharding, replicated
fallbacks, activations pinned replicated at op boundaries).  That layout is
**bit-exact** against the single-device scanned path: no float contraction
ever spans shards, so GSPMD's only collectives are all-gathers
(``tests/test_serve_sharded.py`` locks this down).

Aging is *heterogeneous inside the dispatch*: with a shard-granular
:class:`repro.core.fleet.FleetRuntime` (``n_shards == tp``), each mesh
shard carries its own (age, dVth, BER) aging unit, and the
:class:`~repro.models.layers.FaultConfig` handed to the graph holds
``(S,)`` per-operator BER *vectors* — every weight matmul's output-column
block (the columns shard ``s`` physically owns under the serve layout)
flips at shard ``s``'s policy-admitted rate, from a shard-distinct fmix32
stream (:func:`repro.kernels.ops.inject_bitflips_sharded`).  The BER
vectors, keys and step enter as traced pytree leaves ``device_put``
replicated over the mesh with one consistent sharding, so advancing shard
ages between calls re-jits nothing (``steps.TRACE_COUNTS`` guards).

The engine casts floating-point params to ``serve_dtype`` (default
bfloat16) at construction: bf16 GEMM column slices are bit-exact on the
reference backend, float32 ones are not — the measured fact the exactness
contract rests on (see the module docstring of
``repro.distributed.sharding``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ModelConfig
from repro.core.fleet import FleetRuntime
from repro.distributed import sharding as shrules
from repro.models.layers import FaultConfig
from repro.obs import metrics as obs_metrics
from repro.obs.spans import span
from repro.obs.taps import taps_enabled, telemetry_to_host
from . import steps
from .engine import ServeEngine, compile_cache


@dataclasses.dataclass
class MeshGenerateResult:
    tokens: np.ndarray           # (B, steps) generated ids
    bers: np.ndarray             # (S, O) per-shard BERs served ((1, O) uniform)
    operators: tuple             # column order of ``bers``
    ages_years: np.ndarray       # (S,) per-shard ages
    power_w: float
    telemetry: Optional[Dict[str, np.ndarray]] = None   # {name: (steps,)}


def default_serve_mesh(tp: Optional[int] = None) -> Mesh:
    """("data", "model") mesh over the visible devices, model=tp (all)."""
    n = len(jax.devices())
    tp = n if tp is None else int(tp)
    assert n % tp == 0, (n, tp)
    return shrules.make_mesh((n // tp, tp), ("data", "model"))


@compile_cache("mesh_generate")
def _mesh_generate_fn(cfg: ModelConfig, max_len: int, n_steps: int,
                      top_k: Optional[int], mesh: Mesh):
    """The single-dispatch sharded generation function, jitted.

    The serve-mesh scope is entered *inside* the function body, i.e. at
    trace time: every ``constrain_replicated`` hook in the model lowers to
    a with_sharding_constraint against this mesh, and the hook stays a
    no-op for every other trace in the process.
    """
    gen = steps.make_generate_fn(cfg, max_len, n_steps, top_k)

    def sharded_gen(params, prompts, fi, key, temp, *extras):
        with shrules.serve_mesh_scope(mesh):
            return gen(params, prompts, fi, key, temp, *extras)

    # prompts and the call key are freshly device_put per call and never
    # reused — donate their buffers so XLA can alias them into the decode
    # carry (a no-op on backends without donation, e.g. CPU CI).  params
    # and fi are NOT donated: params persist across calls and fi's BER
    # leaves are cached between age updates.
    return jax.jit(sharded_gen, donate_argnums=(1, 3))


class MeshServeEngine:
    """Serve one mesh-sharded model with per-shard aging in one dispatch."""

    def __init__(self, cfg: ModelConfig, params, *,
                 mesh: Optional[Mesh] = None, tp: Optional[int] = None,
                 fleet: Optional[FleetRuntime] = None, device: int = 0,
                 runtime=None, max_len: int = 512, seed: int = 0,
                 serve_dtype=jnp.bfloat16, use_fused_kernel: bool = True):
        """``fleet`` (shard-granular, ``n_shards == tp``) drives per-shard
        BERs for fleet device ``device``; alternatively a legacy
        single-device ``runtime`` serves shard-uniform BERs (the legacy
        scalar fault streams — bit-exact with ``ServeEngine``'s oracle).
        Neither: clean sharded serving.  ``params`` may live anywhere;
        they are cast (floats -> ``serve_dtype``) and laid out over
        ``mesh`` with the serve-layout rules here, once.

        ``use_fused_kernel`` (fleet path only) routes every divisible
        weight matmul through the shard_map-wrapped fused Pallas kernel —
        per-shard int8 matmul + in-flush upsets + dequant in ONE kernel —
        instead of the kernel-free three-pass GSPMD route.  Both routes
        draw identical counter streams, so generated tokens are
        bit-identical; only bytes/compile-time change.  The legacy
        ``runtime=`` path always stays kernel-free (scalar streams are the
        pre-shard_map threefry contract, pinned by parity tests)."""
        self.cfg = cfg
        if mesh is None:
            mesh = default_serve_mesh(tp)
        self.mesh = mesh
        self.tp = shrules._tp(mesh)
        assert fleet is None or runtime is None, \
            "pass a shard-granular fleet= OR a uniform runtime=, not both"
        if fleet is not None:
            assert fleet.n_shards == self.tp, \
                f"fleet n_shards={fleet.n_shards} != mesh tp={self.tp}"
            assert 0 <= device < fleet.n_devices
        self.fleet = fleet
        self.device = device
        if isinstance(runtime, FleetRuntime):
            runtime = runtime.device(device)
        self.runtime = runtime
        self.use_fused_kernel = bool(use_fused_kernel)
        self.max_len = max_len
        self._key = jax.random.PRNGKey(seed)
        self._repl = NamedSharding(mesh, P())
        # dispatch-overhead caches: the replicated step-0 constant and the
        # per-op BER leaves (invalidated when the fleet publishes a new
        # shard-BER table, i.e. on age advance — not per generate call)
        self._step0 = jax.device_put(jnp.int32(0), self._repl)
        self._ber_cache: Optional[tuple] = None

        cast = jax.tree.map(
            lambda x: jnp.asarray(x).astype(serve_dtype)
            if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
            else jnp.asarray(x), params)
        self.specs = shrules.param_specs(cast, cfg, mesh, layout="serve")
        self.params = shrules.shard_tree(cast, self.specs, mesh)

    # ------------------------------------------------------------------ #
    def _fault_config(self) -> Optional[FaultConfig]:
        """(S,)-vector BERs from the fleet's shard row, or uniform scalars.

        The fleet path honours ``use_fused_kernel``: vector-BER matmuls
        then take the shard_map fused-kernel route inside the serve-mesh
        scope (kernel-free GSPMD otherwise — identical streams either
        way).  The legacy ``runtime`` path forces the kernel-free scalar
        paths (``use_systolic_kernel=False``): a scalar-BER ``pallas_call``
        is a single-device program that does not partition under GSPMD,
        and its threefry streams are the pinned pre-shard_map contract.

        BER leaves are device_put replicated once per fleet BER table (the
        table object is cached inside ``FleetRuntime`` between age scans),
        not once per generate call — only the per-call subkey is put fresh.
        """
        if self.fleet is None and self.runtime is None:
            return None
        self._key, sub = jax.random.split(self._key)
        fused = False
        if self.fleet is not None:
            fused = self.use_fused_kernel
            tab = self.fleet.op_ber_shard_jax()
            if self._ber_cache is None or self._ber_cache[0] is not tab:
                ber = tab[self.device]                           # (S, O)
                bers = {op: jax.device_put(ber[:, i], self._repl)
                        for i, op in enumerate(self.fleet.operators)}
                self._ber_cache = (tab, bers)
            bers = self._ber_cache[1]
        else:
            vals = tuple(sorted(self.runtime.op_bers().items()))
            if self._ber_cache is None or self._ber_cache[0] != vals:
                bers = {op: jax.device_put(jnp.float32(b), self._repl)
                        for op, b in vals}
                self._ber_cache = (vals, bers)
            bers = self._ber_cache[1]
        return FaultConfig(bers=bers, key=jax.device_put(sub, self._repl),
                           step=self._step0,
                           use_systolic_kernel=fused, fused=fused)

    def _extras(self, prefix_embeds, frames) -> tuple:
        cfg = self.cfg
        if cfg.n_encoder_layers:
            assert frames is not None, "enc-dec family needs frames="
            return (jnp.asarray(frames),)
        if cfg.prefix_tokens:
            assert prefix_embeds is not None, "prefix family needs " \
                                              "prefix_embeds="
            return (jnp.asarray(prefix_embeds),)
        return ()

    # ------------------------------------------------------------------ #
    def generate(self, prompts: np.ndarray, n_steps: int, *,
                 prefix_embeds=None, frames=None, greedy: bool = True,
                 temperature: Optional[float] = None,
                 top_k: Optional[int] = None) -> MeshGenerateResult:
        """prompts: (B, S) int32 -> ``n_steps`` tokens from ONE dispatch.

        Host span ``mesh.prepare`` covers the fault config and the device
        puts, ``mesh.generate`` the dispatch and the tokens' return.
        Every runtime input (prompts, FaultConfig leaves, key,
        temperature) enters replicated over the mesh with the same
        NamedSharding on every call, so age advances and shard-BER updates
        between calls hit the compiled executable — zero retrace.  BER
        leaves are re-put only when the fleet publishes a new table;
        prompts and the call key are donated to the executable.
        """
        cfg = self.cfg
        with span("mesh.prepare"):
            fi = self._fault_config()
            self._key, call_key = jax.random.split(self._key)
            put = lambda t: jax.device_put(t, self._repl)
            prompts = put(jnp.asarray(prompts, jnp.int32))
            extras = tuple(put(e)
                           for e in self._extras(prefix_embeds, frames))
            # fi leaves are already replicated by _fault_config (BERs
            # cached across calls, key/step put there) — no per-call tree
            # device_put
            temp = put(ServeEngine._temperature(greedy, temperature))
            call_key = put(call_key)

        m0 = _mesh_generate_fn.misses
        gen = _mesh_generate_fn(cfg, self.max_len, int(n_steps), top_k,
                                self.mesh)
        with span("mesh.generate") as call:
            tokens, telem = gen(self.params, prompts, fi, call_key, temp,
                                *extras)
            tokens = np.asarray(tokens)
        telemetry = None
        if taps_enabled():
            # taps are replicated scalars per step under the serve layout —
            # one host transfer, no extra collectives
            telemetry = telemetry_to_host(telem)
            obs_metrics.REGISTRY.counter(
                "mesh_generate_calls", "sharded generate() dispatches").inc()
            obs_metrics.observe_span(
                "mesh_generate_compile_s"
                if _mesh_generate_fn.misses > m0
                else "mesh_generate_warm_s", call.seconds)

        if self.fleet is not None:
            ops = self.fleet.operators
            bers = np.asarray(self.fleet.op_ber_shard_array()[self.device])
            ages = np.asarray(self.fleet.ages_years).reshape(
                self.fleet.n_devices, self.fleet.n_shards)[self.device]
            power = float(self.fleet.fleet_power()[self.device])
        elif self.runtime is not None:
            d = self.runtime.op_bers()
            ops = tuple(d)
            bers = np.asarray([[d[o] for o in ops]])
            ages = np.asarray([self.runtime.age_years])
            power = float(self.runtime.total_power())
        else:
            ops, bers = (), np.zeros((1, 0))
            ages, power = np.zeros(1), 0.0
        return MeshGenerateResult(tokens=tokens, bers=bers, operators=ops,
                                  ages_years=ages, power_w=power,
                                  telemetry=telemetry)
