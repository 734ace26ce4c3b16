"""Where JAX keeps its persistent compilation cache.

A chip run compiles every step program afresh unless the cache from an
earlier run is found again, and JAX keys the cache by its directory: a
path that moves between runs never hits. So the directory is either the
one the environment names in ``JAX_COMPILATION_CACHE_DIR`` (JAX reads that
variable itself, and this module then sets nothing) or the fixed
``.jax_cache/`` at the repository root, which git ignores.

Entry points call :func:`enable_compile_cache` once, before they compile
anything. Tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


__all__ = ["enable_compile_cache", "REPO_CACHE_DIR"]
