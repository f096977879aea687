"""Where the launchers keep JAX's persistent compilation cache.

The path is part of what makes a cache hit: a directory that moves between
runs never hits.  So the cache lives at one fixed directory inside the
checkout, ``.jax_cache/`` (git-ignored), unless ``JAX_COMPILATION_CACHE_DIR``
places it elsewhere — JAX reads that variable itself, and then nothing here
changes any setting.  Entry points call :func:`enable_compile_cache` from
their ``main``; importing this module changes no state, and neither library
code nor the tests call it.
"""

from __future__ import annotations

import os

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

# <checkout>/.jax_cache — this file is <checkout>/src/repro/launch/
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
