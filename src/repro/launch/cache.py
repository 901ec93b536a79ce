"""Persistent JAX compilation cache for the entry points.

Called from each entry point's ``main()`` (never at import): a compile of
the full-width serving steps takes minutes, and a warm cache turns the next
run's compile into a read.
"""
from __future__ import annotations

import os
import pathlib

import jax

# a fixed directory inside the checkout: the path is part of the cache key,
# so it never comes from a temp name, a pid or the time
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    when it is set, else at :data:`DEFAULT_DIR`.  Sets no other cache option.
    Returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
