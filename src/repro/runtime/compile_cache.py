"""Where the persistent XLA compile cache lives.

Every entry point (``chip_smoke.py``, ``bn_learn``, ``bn_serve``, the
benchmarks) calls :func:`use_compile_cache` before it compiles anything, so
repeat runs on one machine reuse compiled programs instead of compiling from
cold. The cache key includes the directory, so it never moves between runs:
``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it itself and
nothing here overrides it); otherwise the cache is ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["use_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
