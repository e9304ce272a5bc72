"""JAX's persistent compilation cache, at one fixed path.

Entry points (``chip_smoke.py``, ``repro.serving.server.main``,
``benchmarks/run.py``) call ``enable_compile_cache()`` once at start-up;
nothing enables it at import. ``JAX_COMPILATION_CACHE_DIR``, when set,
wins: JAX reads it itself, and the helper sets no other directory.
Otherwise the cache lives in ``<checkout>/.jax_cache`` — a fixed path,
because the directory is part of what a later run must find again (a
temp, pid or time path would never hit).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root is three up
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
