"""Placement of JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.train``, the fleet-benchmark
worker, ``examples/*.py``) call :func:`enable_compile_cache` once, before
their first compile; importing the library never touches the cache.

The cache key includes the cache path, so the default is a fixed directory,
``<repo root>/.jax_cache`` (git-ignored), and never one derived from a
tempdir, a pid or the time. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and this module sets nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
