"""JAX's persistent compilation cache, placed from outside.

Entry points call :func:`init_compile_cache` before their first compile.
``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and this
sets nothing. Otherwise the cache lives at ``<checkout>/.jax_cache`` — a
fixed path, because the path is part of what a cached entry is found by.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        os.pardir, os.pardir, os.pardir))


def init_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
