"""Persistent compile cache for the entry scripts (``chip_smoke.py``,
``benchmarks/run.py``, ``examples/*.py``).

A cold run compiles every scan and kernel; the persistent cache lets a
later process on the same checkout skip that. The cache key includes the
directory, so the directory must never move: ``$JAX_COMPILATION_CACHE_DIR``
when set (JAX reads it itself and nothing here overrides it), otherwise
the fixed, git-ignored ``<checkout>/.jax_cache``. Nothing calls this at
import time, so library users and the test suite keep JAX's defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


__all__ = ["enable_compile_cache", "CHECKOUT_CACHE_DIR"]
