"""Persistent XLA compilation cache for the entry points.

Every entry point (``repro.launch.train``, ``repro.launch.serve``,
``benchmarks/run.py``, ``chip_smoke.py``) calls :func:`enable_compile_cache`
before its first compile, so a second run of the same program on the same
machine loads its executables instead of compiling them again.  Library
imports and tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# Fixed, inside the checkout (listed in .gitignore): the directory is part of
# the cache key, so a name that moved between runs would never hit.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is changed here; otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
