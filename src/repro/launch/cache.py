"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
this module sets nothing.  Otherwise the cache is the fixed directory
``<repo>/.jax_cache`` — fixed because the path is part of the cache key,
so a name that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's compilation cache at its directory; returns the path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
