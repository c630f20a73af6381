"""Persistent XLA compile cache for the command-line entry points.

A cold chip run spends most of a minute compiling the decision pipeline;
this cache lets the next process skip that.  Only entry points call
:func:`enable_compile_cache` (``chip_smoke.py``, the ``sweep`` main,
``benchmarks/run.py``): importing a library module never changes where
JAX caches.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the cache used when ``JAX_COMPILATION_CACHE_DIR`` is unset — a fixed
#: path in the checkout (the directory is part of the cache key, so a
#: path that moved between runs would never hit)
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
