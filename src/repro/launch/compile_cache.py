"""JAX's persistent compilation cache, at one fixed place.

A TPU compile of a full model takes tens of seconds; the persistent cache
lets the next process in the same checkout read it back instead.  The
cache's path is part of what it is keyed on, so it must not move between
runs: never a temp name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and nothing here overrides it; otherwise the cache goes to
    ``.jax_cache/`` in the checkout."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
