"""JAX's persistent compilation cache, kept in one fixed place."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed path: the cache directory is part of what a hit needs, so a
# directory named after a pid, a temporary or the time would never hit
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".cache" / "jax"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other location is set here; otherwise the cache is ``<repo>/.cache/jax``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
