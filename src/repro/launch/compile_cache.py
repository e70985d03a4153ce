"""JAX's persistent compilation cache for the program's entry points.

Entry points (``repro.launch.serve``, the benchmarks, ``chip_smoke.py``)
call :func:`enable_compile_cache` once at start-up; library code and the
tests never do. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here. Otherwise the cache lives in a fixed
directory inside the checkout (``.jax_cache``, gitignored). The path is
part of what a later process looks up, so it is never built from a
temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
