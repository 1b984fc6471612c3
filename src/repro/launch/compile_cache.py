"""JAX's persistent compilation cache, placed from outside the program.

Call ``enable_compile_cache()`` once, before the first compile.  A cache
directory is part of the cache key, so it is either the one the caller
names in ``$JAX_COMPILATION_CACHE_DIR`` (JAX reads that variable itself and
nothing is set here) or a fixed, git-ignored ``<repo>/.jax_cache`` that a
later run from the same checkout finds again.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    named = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if named:
        return named
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
