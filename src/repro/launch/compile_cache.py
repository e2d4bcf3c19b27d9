"""JAX's persistent compilation cache, placed once for every entry point.

Call `enable_compile_cache()` from a program's `main` (never at import of
a library module).  Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already
reads it and nothing is changed here.  Otherwise the cache lives at a
fixed path inside the checkout, `<repo>/.jax_cache`: the directory is
part of what a later process must find again, so it is never derived
from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
