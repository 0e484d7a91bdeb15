"""JAX's persistent compilation cache for the chip entry points.

Call :func:`enable_compile_cache` before the first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
else is set.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a
fixed path, because the directory is part of what a later run must find
again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
