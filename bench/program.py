"""The system under test, as the benchmark drives it: the one place that
imports the serving program.

A cell's configuration file becomes the program's ``ModelConfig``; its
traffic file's ``device`` becomes an aged ``FleetRuntime`` device served
through the fused Pallas kernel, or no runtime at all (clean serving).
"""
from __future__ import annotations

import os
import sys

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

from repro.configs import ModelConfig  # noqa: E402
from repro.core.fleet import FleetRuntime  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.obs.metrics import cache_stats, trace_counts  # noqa: E402
from repro.serve.engine import ServeEngine  # noqa: E402

__all__ = ["model_config", "aged_runtime", "engines", "enable_compile_cache",
           "compile_counts"]


def model_config(dims) -> ModelConfig:
    return ModelConfig(
        name=dims.name, family="dense", n_layers=dims.n_layers,
        d_model=dims.d_model, n_heads=dims.n_heads,
        n_kv_heads=dims.n_kv_heads, d_ff=dims.d_ff, vocab=dims.vocab,
        head_dim=dims.head_dim, mlp=dims.mlp, norm=dims.norm, pos="rope",
        rope_theta=dims.rope_theta, window=dims.window)


def aged_runtime(device: dict):
    """The served device: one FleetRuntime device at the stated age."""
    fleet = FleetRuntime(n_devices=1, policy=device["policy"])
    fleet.set_age(years=float(device["age_years"]))
    return fleet.device(0)


class FaultFree:
    """The aged device with every admitted BER set to 0: the same compiled
    program and kernel, with no upsets."""

    def __init__(self, runtime):
        self._rt = runtime

    def op_bers(self):
        return {op: 0.0 for op in self._rt.op_bers()}

    @property
    def age_years(self):
        return self._rt.age_years

    def total_power(self):
        return self._rt.total_power()


def engines(cfg, params, device: dict, max_len: int, seed: int):
    """(engine, fault-free engine or None) for the cell's device."""
    if device["route"] == "clean":
        return ServeEngine(cfg, params, runtime=None, max_len=max_len,
                           seed=seed), None
    if device["route"] != "fused_kernel":
        raise ValueError(f"unknown route {device['route']!r}")
    rt = aged_runtime(device)
    kw = dict(max_len=max_len, use_systolic_kernel=True, seed=seed)
    return (ServeEngine(cfg, params, runtime=rt, **kw),
            ServeEngine(cfg, params, runtime=FaultFree(rt), **kw))


def compile_counts() -> dict:
    """The program's own counts of traces and compiled-function misses."""
    out = {f"trace:{k}": v for k, v in trace_counts().items()}
    out.update({f"miss:{k}": s["misses"] for k, s in cache_stats().items()})
    return out
