"""Where JAX keeps its persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so when that is set the
cache goes where it says and nothing is set here. Otherwise the cache sits
at one fixed path inside the checkout, ``<checkout>/.jax_cache``, so that
every run from this checkout finds what earlier runs compiled; a path that
moved between runs (a temporary name, a pid, a time) would start empty
each time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/utils/compile_cache.py -> the checkout holding src/
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory. Call it before the first compilation: JAX settles on a
    cache once per process."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
