"""JAX's persistent compilation cache for the launchers and the chip smoke.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already caches there and
this sets nothing.  Otherwise the cache lives at a fixed path inside the
checkout (``<repo>/.jax_cache``, git-ignored): the directory is part of
each entry's key, so a path that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
