"""Persistent XLA compilation cache for the repo's entry-point scripts.

``chip_smoke.py`` and ``benchmarks/run.py`` call :func:`enable_compile_cache`
before their first compile; importing the package never touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    when that is set, else at ``.jax_cache/`` in the repo root -- a fixed
    path, because the directory is part of what a later run must find
    again.  Leaves the environment as it is; returns the directory."""
    import jax

    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
