"""Zero-retrace in-scan telemetry taps.

The taps are *aux outputs of the one jitted dispatch*: cheap per-step /
per-epoch scalars (top-logit health, ΔVth, guardband headroom, boost
counts, ...) computed **unconditionally inside the already-traced graph**
and returned alongside the primary result as a :class:`Telemetry`
pytree.  The on/off toggle (:func:`enable_taps` / :func:`taps_enabled`)
is **host-side only**: it controls whether engines transfer the aux
leaves to host and record them into :data:`repro.obs.metrics.REGISTRY`
— never what gets traced.  Two properties follow by construction:

* **zero-retrace** — toggling or re-reading taps dispatches the same
  compiled executable (the unified :func:`repro.obs.metrics.trace_counts`
  guard asserts this across serve, online, sharded and co-sim paths);
* **bit-exact** — the primary outputs are the same jaxpr either way, so
  tokens/trajectories with taps enabled are *identical* to disabled.

The aux scalars themselves cost O(batch) FLOPs per step against the
O(batch·d_model²) matmuls of the step body — the ≤1.10× overhead guard
in ``benchmarks/obs_bench.py`` measures the *host* read/record cost.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

__all__ = ["Telemetry", "taps_enabled", "enable_taps", "logit_taps",
           "cosim_taps", "telemetry_to_host"]


@jax.tree_util.register_pytree_node_class
class Telemetry:
    """A named bundle of traced telemetry arrays.

    A thin pytree wrapper over ``{signal name: array}`` so tap bundles
    flow through ``jit`` / ``scan`` / ``vmap`` / GSPMD like any other
    output: under :func:`repro.serve.engine.FleetServeEngine`'s vmapped
    dispatch every leaf simply gains the lane axis.  Keys are sorted into
    the treedef (static), values are the leaves (traced).
    """

    def __init__(self, series: Optional[Dict[str, Any]] = None):
        self.series: Dict[str, Any] = dict(series or {})

    def __getitem__(self, key: str):
        return self.series[key]

    def __contains__(self, key: str) -> bool:
        return key in self.series

    def keys(self):
        return self.series.keys()

    def items(self):
        return self.series.items()

    def __repr__(self):
        return f"Telemetry({sorted(self.series)})"

    def tree_flatten(self):
        names = tuple(sorted(self.series))
        return tuple(self.series[k] for k in names), names

    @classmethod
    def tree_unflatten(cls, names, leaves):
        return cls(dict(zip(names, leaves)))


# --------------------------------------------------------------------------- #
# host-side toggle — deliberately NOT visible to any traced function
# --------------------------------------------------------------------------- #
_ENABLED = [False]


def taps_enabled() -> bool:
    """Whether engines read telemetry back to host and record it."""
    return _ENABLED[0]


@contextlib.contextmanager
def enable_taps(on: bool = True):
    """Context manager flipping the host-side taps toggle.

    Purely host state: the jitted graphs always compute their aux
    outputs, so entering/leaving this context can never trigger a
    retrace or perturb the primary results.
    """
    prev = _ENABLED[0]
    _ENABLED[0] = bool(on)
    try:
        yield
    finally:
        _ENABLED[0] = prev


# --------------------------------------------------------------------------- #
# traced tap builders
# --------------------------------------------------------------------------- #
def logit_taps(logits: jnp.ndarray,
               active: Optional[jnp.ndarray] = None) -> Dict[str, Any]:
    """Per-step serving-health scalars from a ``(batch, vocab)`` logit slab.

    Two signals that degrade monotonically as admitted BER corrupts the
    forward pass: the batch-mean max logit (bit-flips in late layers
    crater it) and the batch-mean top1−top2 margin (sampling confidence).
    ``active`` (online serving) masks out idle slots whose logits are
    garbage; with no live slot the masked means are 0 by convention.
    Its ops carry ``jax.named_scope("taps")`` in their metadata.
    """
    with jax.named_scope("taps"):
        top2 = jax.lax.top_k(logits, 2)[0]          # (batch, 2)
        peak = top2[:, 0]
        margin = top2[:, 0] - top2[:, 1]
        if active is not None:
            w = active.astype(logits.dtype)
            denom = jnp.maximum(jnp.sum(w), 1.0)
            return {"logit_max": jnp.sum(peak * w) / denom,
                    "logit_margin": jnp.sum(margin * w) / denom}
        return {"logit_max": jnp.mean(peak),
                "logit_margin": jnp.mean(margin)}


def cosim_taps(cos, scenario) -> "Telemetry":
    """Derive the per-epoch aging odometer from a co-sim trajectory.

    Input is a :class:`repro.sched.lifetime.CoSimTrajectory` (epoch axis
    leading, fields ``(E, N, O)``); output leaves are device-leading
    ``(N, E)`` per-device series:

    * ``dvth_eff_mv`` — effective PMOS ΔVth, worst operator domain: the
      paper's aging-monitor readout (recovery-aware when the short-term
      pool ran);
    * ``dvth_mono_mv`` — the monotone total from the per-population
      state, whose gap to ``dvth_eff_mv`` is recovered headroom;
    * ``headroom_s`` — guardband headroom ``t_clk − delay`` (worst
      operator), the timing-margin sensor;
    * ``vdd_v`` — the AVS-chosen supply (max over domains);
    * ``util`` — routed utilization;
    * ``t_node_k`` — closed-loop thermal-node temperature (when run);
    * ``boosts`` — per-epoch AVS boost-event counts (when recorded).

    Pure post-processing of arrays the scan already produced — reading
    the odometer never adds a trace.
    """
    from repro.core import aging
    dvp = jnp.asarray(cos.dvp)                          # (E, N, O) effective
    dv = jnp.asarray(cos.dv)                            # (E, N, O, P) monotone
    pm = jnp.asarray(aging.IS_PMOS, dv.dtype)
    mono_p = jnp.sum(dv * pm, axis=-1)                  # (E, N, O)
    t_clk = jnp.asarray(scenario.t_clk, dvp.dtype).reshape(-1)  # (N,) or (1,)
    dev = lambda x: jnp.moveaxis(x, 0, 1)               # (E, N) -> (N, E)
    series = {
        "dvth_eff_mv": dev(jnp.max(dvp, axis=-1)),
        "dvth_mono_mv": dev(jnp.max(mono_p, axis=-1)),
        "headroom_s": dev(t_clk - jnp.max(jnp.asarray(cos.delay), axis=-1)),
        "vdd_v": dev(jnp.max(jnp.asarray(cos.V), axis=-1)),
        "util": dev(jnp.asarray(cos.util)),
    }
    if getattr(cos, "t_node", None) is not None:
        series["t_node_k"] = dev(jnp.asarray(cos.t_node))
    if getattr(cos, "boosts", None) is not None:
        series["boosts"] = dev(jnp.asarray(cos.boosts))
    return Telemetry(series)


def telemetry_to_host(telem: Optional["Telemetry"]) -> Optional[Dict[str, Any]]:
    """One blocking device->host transfer of every tap leaf (numpy)."""
    if telem is None:
        return None
    import numpy as np
    return {k: np.asarray(v) for k, v in telem.items()}
