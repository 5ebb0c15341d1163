"""JAX's persistent compilation cache, kept in one fixed directory."""
from __future__ import annotations

import os
import pathlib

import jax

REPO = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    `JAX_COMPILATION_CACHE_DIR`, where set, is the directory and no other is
    set.  Otherwise the cache is `<repo>/.jax_cache` (gitignored): a fixed
    path, never one made from a temporary name, a pid or the time, because
    a directory that moves between runs never hits.  Call it from entry
    points only, never at import.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
