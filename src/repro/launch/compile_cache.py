"""Persistent XLA compilation cache for the entry points.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; where it is set, that
directory holds the cache and nothing here overrides it.  Otherwise the
entry points keep compiled programs in ``.jax_cache`` at the root of the
checkout: a fixed path, so a later run from the same checkout finds what an
earlier one compiled.  Tests never call this: they compile cold.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "use_compile_cache"]

# src/repro/launch/compile_cache.py → the checkout root is three levels up
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in force."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
