"""JAX's persistent compilation cache, kept at one fixed place.

The cache key includes the cache directory, so a directory that moves
between runs never hits. Entry points call ``enable_compile_cache()`` from
their ``main()``, before the first compile; importing this module sets
nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

# the checkout root: src/repro/launch/compile_cache.py -> three levels up
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing; otherwise the cache goes to ``.jax_cache/`` at the
    checkout root."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
