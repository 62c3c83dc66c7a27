"""JAX's persistent compilation cache for the entry points.

Call :func:`use_compile_cache` at the start of an entry point's ``main``,
never at import: it changes process-wide JAX configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself, and no
    other directory is set here.  Otherwise the cache is kept at the fixed
    ``<checkout>/.jax_cache``: the directory is part of what a later run
    must find again, so it never depends on a temp name, pid or time.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
