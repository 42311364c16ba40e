"""Production mesh builders (functions, not constants — importing this module
never touches jax device state)."""
from __future__ import annotations

import jax

from repro.distributed.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axes: batch shards over ("pod", "data"); tensor/expert parallelism over
    "model".  Requires 256 (512 multi-pod) visible devices — the dry-run
    sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any
    jax import to fake them on CPU.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int = 1):
    """Whatever devices exist, data-major (CPU tests / small runs)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return make_mesh((n // model, model), ("data", "model"))
