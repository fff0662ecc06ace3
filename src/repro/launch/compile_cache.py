"""JAX's persistent compilation cache, switched on by the entry points.

Entry points (``repro.launch.serve``, ``chip_smoke.py``, the benchmark
drivers) call :func:`enable_compile_cache` once before their first compile;
library code and tests never do.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and the cache lives there.  Otherwise it lives at a fixed
directory inside the checkout: the cache is keyed by its path, so a path that
moved from run to run would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Switch the persistent cache on and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
