"""JAX's persistent compilation cache for the command-line entry points.

Entry points (``repro.launch.*`` and ``chip_smoke.py``) call
``enable_compile_cache()`` before their first compile; library modules never
do. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing. Otherwise the cache lives at ``<repo>/.jax_cache``: a fixed
path, because the directory is part of each entry's key.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
