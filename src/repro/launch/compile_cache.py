"""JAX's persistent compilation cache, placed once per process.

Entry points call :func:`enable_compile_cache` before their first compile,
so a second run of the same programs reads them back instead of compiling
them again.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
directory and nothing here overrides it.  Otherwise the cache lives at a
FIXED path inside the checkout (``<repo>/.jax_cache``, git-ignored): the
directory is part of what a later run must find, so it is never derived
from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
