"""JAX's persistent compilation cache at a fixed path in the checkout.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/*``) call
``use_compile_cache()`` before their first compile.  Importing ``repro``
never touches the cache, and the tests never call this.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "use_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compile cache; returns the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    this sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``:
    the path is part of the cache key, so a fixed one lets later runs of the
    same checkout hit.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
