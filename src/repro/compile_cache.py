"""Where entry points keep JAX's persistent compilation cache.

``enable_compile_cache()`` is called once by every entry point (the
chip smoke, the bench mains, the examples) before it compiles anything.
If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads the cache
location from it and nothing is set in code.  Otherwise the cache goes
to the fixed ``<repo>/.jax_cache`` (git-ignored): the directory is part
of the cache key, so it is never derived from a temp dir, a pid or the
time.  Tests do not call this.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its home; returns the
    directory in use."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
