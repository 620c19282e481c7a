"""JAX's persistent compilation cache, placed from outside the library.

Entry points (``chip_smoke.py``, ``examples/*.py``, ``benchmarks/*.py``)
call :func:`enable_compile_cache` once at start-up; importing the library
never does.  The cache's path is part of its key, so it never moves:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads that setting itself and
  this function sets no other directory;
* otherwise — ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# src/repro/launch/compile_cache.py → the checkout root is three levels up
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
