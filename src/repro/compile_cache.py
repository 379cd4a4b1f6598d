"""JAX's persistent compilation cache, at one fixed place.

Every entry point (``chip_smoke.py``, ``benchmarks/run.py``, the examples)
calls ``enable_compile_cache`` before its first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
changed.  Otherwise the cache goes to ``.jax_cache/`` at the repository
root: a path that never moves, since the path is part of what a later run
looks up.
"""
from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
