"""JAX's persistent compilation cache, shared by every entry point.

The cache's path is part of each entry's key, so it never moves: the
directory named by ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX
reads the variable itself), else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
