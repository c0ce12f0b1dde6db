"""Persistent XLA compile cache for the repo's entry points.

``enable_compile_cache()`` is called by the scripts' ``main()`` (and
``chip_smoke.py``), never on import: the compile tests keep the cache off
(a compile for a described TPU is written to the cache but cannot be read
back without the chip).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing. Otherwise the cache is the fixed ``<checkout>/.jax_cache`` —
the path is part of the cache key, so a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
