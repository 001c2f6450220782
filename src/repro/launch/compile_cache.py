"""JAX's persistent compilation cache for the command-line entry points.

A fresh process on the chip recompiles every program it runs; the cache
lets a second run of the same command skip that. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here. Otherwise the cache lives at one fixed path inside the checkout
(``<repo>/.jax_cache``, git-ignored): the path is part of the cache key,
so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
