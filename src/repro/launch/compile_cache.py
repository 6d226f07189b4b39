"""JAX's persistent compilation cache, configured from outside the program.

``JAX_COMPILATION_CACHE_DIR``, when set, is where the cache lives (JAX
reads the variable itself, so no other directory is set here).  Otherwise
the cache goes to ``.jax_cache`` at the root of the checkout: a fixed
path, because the path is part of every entry's key.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.  Call from a
    program's entry point, never at import.  Pallas kernels compile in
    one or two seconds, so every compile is persisted, however short."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
