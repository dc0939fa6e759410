"""JAX's persistent compilation cache, for the repo's entry points."""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compilation_cache"]

#: Fixed per checkout, so every process of one checkout finds the others'
#: compiled programs (a path made from a temp name, pid or time never hits).
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set; otherwise the cache is ``<repo>/.jax_cache``.
    Entry points call this once before they compile.  Importing the
    library never does, and the tests do not.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
