"""Process-level JAX setup shared by the entry points that run on a chip."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, so that every run of this checkout finds what earlier runs compiled
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing is changed; otherwise the cache goes to ``.jax_cache``
    at the root of the repository."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
