"""JAX's persistent compilation cache, kept in one place per checkout."""
from __future__ import annotations

import os

import jax

# fixed, because a cached program is found again only under the same path
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other path is set here; otherwise the cache is ``<repo>/.jax_cache``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
