"""JAX's persistent compilation cache for this repository's entry points.

Every script that compiles (``chip_smoke.py``, ``repro.launch.serve``, the
examples and benchmarks) calls ``enable_compile_cache()`` before its first
compile, so a second run finds the programs the first one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    stands. Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed
    path, because the directory is part of what a later run must find.
    Programs are cached however fast they compiled (JAX skips those under
    1 s by default), unless ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``
    says otherwise: a run's many small kernels and eager ops add up."""
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
