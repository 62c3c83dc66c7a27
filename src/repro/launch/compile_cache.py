"""JAX's persistent compilation cache for the entry points.

Call :func:`use_compile_cache` at the start of an entry point's ``main``,
never at import: it changes process-wide JAX configuration.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax
from jax.experimental.compilation_cache import compilation_cache

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> Optional[str]:
    """Turn on the persistent compilation cache; return its directory.

    On the CPU it stays off (None), even where ``JAX_COMPILATION_CACHE_DIR``
    is set: an XLA:CPU executable read back from it loses the ``Format``
    pinned on the stacked K/V leaves, so a served step refuses its own
    cache, and its loader warns of a machine-feature mismatch.  Elsewhere
    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself where it is set, else
    the cache is kept at the fixed ``<checkout>/.jax_cache``: a later run
    must find it again, so it never depends on a temp name, pid or time.
    """
    if jax.default_backend() == "cpu":
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()  # JAX decides once, at first use
        return None
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
