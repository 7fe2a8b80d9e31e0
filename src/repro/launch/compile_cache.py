"""Where the repo's entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself, and nothing
here overrides it. Otherwise the cache goes to one fixed directory inside
the checkout (``<checkout>/.jax_cache``, git-ignored): a fixed path, because
the path is part of the cache key and a directory that moves never hits.
Called by ``chip_smoke.py`` and ``benchmarks/run.py`` before their first
compile; tests leave the cache alone.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def place_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; return it."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
