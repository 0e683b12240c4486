"""JAX's persistent compilation cache, kept in one fixed place.

Every entry point (``chip_smoke.py``, ``examples/*``, ``benchmarks/run.py``)
calls :func:`enable_compile_cache` before its first jit, so a later run
finds the programs an earlier one compiled instead of compiling them
again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing.  Otherwise the cache goes to ``<repo>/.jax_cache``:
    a fixed path, never a temporary, per-process or timestamped one.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
