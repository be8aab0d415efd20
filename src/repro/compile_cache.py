"""JAX persistent compilation cache placement for the entry points.

A Table III sweep spends tens of seconds compiling before any work runs;
the persistent cache lets a second run in the same checkout skip that. The
cache key includes the directory, so it lives at one fixed place.
"""

from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache (this file is <checkout>/src/repro/compile_cache.py)
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as JAX reads it and
    nothing else is set; otherwise the cache goes to ``<checkout>/.jax_cache``.
    Call once from an entry point before its first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
