"""Persistent XLA compilation cache for the entry points.

A cold run compiles every tick program again, which costs tens of
seconds per fleet size.  The entry points (``launch.sim``, ``launch.sweep``,
``launch.tune``, ``launch.dist_worker``, ``benchmarks.engine_bench`` and
``chip_smoke.py``) call :func:`enable_compile_cache` before they compile.
Library code and tests never do.
"""
from __future__ import annotations

import os
import pathlib

import jax

# one fixed directory inside the checkout: a cache only hits when the next
# run looks in the same place
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
