"""Persistent XLA compilation cache for the entry points that jit for the
chip (``python3 -m chipbench.run``, the examples).

Library code (``make_reader``, the loaders) sets no global JAX config;
only a ``main`` calls :func:`ensure_compile_cache`, before its first
compile.
"""
from __future__ import annotations

import os

#: ``<checkout>/.jax_cache`` — fixed, derived from the package location:
#: the directory is part of every cache key, so a path that moves (a
#: tempdir, a pid, a timestamp) would never hit.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def ensure_compile_cache() -> str:
    """Make compiles persistent and return the cache directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is configured in code — whoever placed the cache from outside
    keeps control of it. Otherwise the cache lives at
    :data:`CHECKOUT_CACHE_DIR`."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
