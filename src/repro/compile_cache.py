"""Where JAX keeps its persistent compilation cache.

Call :func:`enable_compile_cache` before the first compile.  A cache
directory placed from outside (``JAX_COMPILATION_CACHE_DIR``, which JAX
reads itself) wins; otherwise the cache lives at the fixed path
``<repo>/.jax_cache``.  The path is part of each entry's key, so it must
not move between runs.
"""
from __future__ import annotations

import os

import jax

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
