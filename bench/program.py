"""The system under test, as the benchmark drives it: the one place that
imports the serving program.

A cell's configuration file becomes the program's ``ModelConfig`` (through
its family, ``families/``); its traffic file's ``device`` becomes an aged
``FleetRuntime`` device served through the fused Pallas kernel, or no
runtime at all (clean serving).  On one chip the engine is a
``ServeEngine``; on several, a ``MeshServeEngine`` that splits every
layer over the chips in the program's serve layout, each chip a shard of
the fleet's device with an age of its own.
"""
from __future__ import annotations

import os
import sys

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from repro.configs import ModelConfig  # noqa: E402
from repro.core.fleet import FleetRuntime  # noqa: E402
from repro.distributed import sharding as shrules  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.obs.metrics import cache_stats, trace_counts  # noqa: E402
from repro.serve.engine import ServeEngine  # noqa: E402
from repro.serve.sharded import MeshServeEngine  # noqa: E402

__all__ = ["ModelConfig", "serve_mesh", "param_shardings", "aged_runtime",
           "aged_fleet", "engines", "enable_compile_cache", "compile_counts"]


def serve_mesh(devices):
    """The serving mesh over ``devices``: every layer split over all of
    them."""
    return shrules.make_mesh((1, len(devices)), ("data", "model"),
                             devices=devices)


def param_shardings(cfg, mesh, abstract_params):
    """Where each parameter lives on ``mesh``: the program's serve
    layout."""
    specs = shrules.param_specs(abstract_params, cfg, mesh, layout="serve")
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)


def aged_runtime(device: dict):
    """The served device: one FleetRuntime device at the stated age."""
    fleet = FleetRuntime(n_devices=1, policy=device["policy"])
    fleet.set_age(years=float(device["age_years"]))
    return fleet.device(0)


def aged_fleet(device: dict, chips: int) -> FleetRuntime:
    """The served device split over ``chips`` shards, each at its own age:
    ``shard_ages_years``, one per chip, or ``age_years`` for all."""
    ages = device.get("shard_ages_years",
                      [device.get("age_years")] * chips)
    if len(ages) != chips:
        raise ValueError(f"shard_ages_years has {len(ages)} ages for "
                         f"{chips} chips")
    fleet = FleetRuntime(n_devices=1, n_shards=chips,
                         policy=device["policy"])
    for s, age in enumerate(ages):
        fleet.set_age(years=float(age), device=0, shard=s)
    return fleet


class FaultFree:
    """The aged device, or shard-granular fleet, with every admitted BER
    set to 0: the same compiled program and kernel, with no upsets.
    Everything else is the aged one's."""

    def __init__(self, runtime):
        self._rt = runtime
        self._zeros = None

    def __getattr__(self, name):
        return getattr(self._rt, name)

    def op_bers(self):
        return {op: 0.0 for op in self._rt.op_bers()}

    def op_ber_shard_array(self):
        return np.zeros_like(self._rt.op_ber_shard_array())

    def op_ber_shard_jax(self):
        # one object for the engine's cache, which is keyed on identity
        if self._zeros is None:
            self._zeros = jnp.zeros_like(self._rt.op_ber_shard_jax())
        return self._zeros


def engines(cfg, params, device: dict, max_len: int, seed: int,
            mesh=None):
    """(engine, fault-free engine or None) for the cell's device, on one
    chip or, given a ``mesh``, split over its chips."""
    if device["route"] not in ("clean", "fused_kernel"):
        raise ValueError(f"unknown route {device['route']!r}")
    clean = device["route"] == "clean"
    if mesh is not None:
        kw = dict(mesh=mesh, max_len=max_len, seed=seed,
                  use_fused_kernel=True)
        if clean:
            return MeshServeEngine(cfg, params, **kw), None
        fleet = aged_fleet(device, mesh.devices.size)
        return (MeshServeEngine(cfg, params, fleet=fleet, **kw),
                MeshServeEngine(cfg, params, fleet=FaultFree(fleet), **kw))
    if clean:
        return ServeEngine(cfg, params, runtime=None, max_len=max_len,
                           seed=seed), None
    rt = aged_runtime(device)
    kw = dict(max_len=max_len, use_systolic_kernel=True, seed=seed)
    return (ServeEngine(cfg, params, runtime=rt, **kw),
            ServeEngine(cfg, params, runtime=FaultFree(rt), **kw))


def compile_counts() -> dict:
    """The program's own counts of traces and compiled-function misses."""
    out = {f"trace:{k}": v for k, v in trace_counts().items()}
    out.update({f"miss:{k}": s["misses"] for k, s in cache_stats().items()})
    return out
