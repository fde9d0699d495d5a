"""JAX's persistent compile cache for the entry points.

``JAX_COMPILATION_CACHE_DIR``, where set, wins: JAX reads it itself and
nothing is set here. Otherwise the cache lives in ``.jax_cache/`` at the
root of the checkout (git-ignored). The path is fixed, never derived from
a temp dir, pid or clock, so a later run of the same checkout finds what
an earlier one compiled. Entry points call ``enable_compile_cache`` from
their ``main``; tests and library code never do.
"""
from __future__ import annotations

import os

import jax

#: the checkout's own cache directory (``src/repro/launch`` -> root)
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX at the persistent compile cache; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
