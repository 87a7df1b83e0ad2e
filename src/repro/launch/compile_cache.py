"""Where JAX keeps its persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; where it is set, this
module sets nothing. Otherwise the cache goes to ``.jax_cache/`` at the
root of this checkout: a fixed path (the path is part of the cache key, so
a directory that moved would never hit), listed in ``.gitignore``.
"""

from __future__ import annotations

import os

import jax

CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
