"""JAX's persistent compilation cache, placed from outside the program.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this module
sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a
fixed path, because the path is part of what a later process must find
again (``.gitignore`` lists it).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

FALLBACK = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(FALLBACK)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
