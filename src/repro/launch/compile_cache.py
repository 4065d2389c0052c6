"""JAX's persistent compilation cache for the serve entry points.

A cold run compiles every shape bucket of the store's gather and scatter
plus the dense forward.  With the cache on, a second run in the same
checkout reads them back instead.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# One fixed path inside the checkout: the cache key covers the path, so a
# directory that moved (a temporary name, a pid, a time) would never hit.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this changes nothing.  Otherwise the cache goes to ``.jax_cache/`` at
    the root of the checkout, and every program is cached, however fast it
    compiled (the store's bucketed programs each compile in well under
    JAX's default one-second threshold).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(CACHE_DIR)
