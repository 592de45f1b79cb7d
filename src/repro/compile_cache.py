"""JAX's persistent compile cache, at one fixed place per checkout."""

from __future__ import annotations

import os

import jax


def enable_compile_cache(checkout: str) -> str:
    """Turn on the persistent compile cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing is set here. Otherwise the cache is ``<checkout>/.jax_cache``.
    The path is part of what a later run must match to hit the cache, so
    it is fixed: never temporary, per-process or time-stamped. Call this
    before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
