"""Persistent XLA compilation cache for the entry-point scripts.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``)
call :func:`enable_compile_cache` once, before their first compile, so a
rerun on the same machine loads compiled programs instead of rebuilding
them.  ``import repro`` never calls it: library users and tests keep
whatever cache configuration they chose.
"""
from __future__ import annotations

import os

#: The cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset: fixed
#: at the repository root, because the path is part of the cache key.
REPO_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.  Otherwise the cache lives at
    :data:`REPO_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
