"""JAX persistent compilation cache for entry points.

Call :func:`enable_compile_cache` from a script's ``main`` — never at
library import, so tests and library users stay uncached unless they ask.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(checkout: str) -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it; this
    sets nothing.  Otherwise the cache lives at ``<checkout>/.jax_cache``:
    a fixed path, because the directory is part of the cache key.
    """
    if os.environ.get(ENV):
        return os.environ[ENV]
    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
