"""JAX's persistent compilation cache, at one fixed place per checkout.

A chip run compiles the serving step for tens of seconds; the persistent
cache lets the next process on the same machine skip that.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it.  Otherwise the cache lives at ``<checkout>/.jax_cache``: the
path is part of the cache key, so it must not move between runs.  CPU
compiles are left uncached: they take seconds and the test suite should
not write into the checkout.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent cache on; returns its directory (None: off)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
