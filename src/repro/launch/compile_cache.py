"""JAX's persistent compilation cache, for the repo's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the benchmark
mains) call :func:`enable_compile_cache` once at start-up; no library
module calls it, so importing the package never changes JAX's config.
"""

from __future__ import annotations

import os
import pathlib

import jax

# Fixed, never derived from a temp name, a pid or the time: the cache
# directory is part of every entry's key, so a moving directory never hits.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set. Otherwise the cache lives at the repo's
    gitignored ``.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
