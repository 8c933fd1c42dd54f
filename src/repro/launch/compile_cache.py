"""JAX's persistent compilation cache for the entry points.

The cache key includes the directory, so the directory must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
the variable itself, so no other directory is set in code), else the fixed
``<checkout>/.jax_cache``.  Entry points call ``enable_compile_cache()``;
nothing calls it at import.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache lives in."""
    return os.environ.get(ENV) or str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    and return that directory."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
