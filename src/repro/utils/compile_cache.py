"""Where JAX keeps its persistent compilation cache.

Call :func:`enable_compile_cache` before the first compile. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is set
here. Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed path, so
a later process on the same checkout finds what an earlier one compiled (the
path is part of what JAX keys the cache on).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
