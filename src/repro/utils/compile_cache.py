"""JAX persistent compilation cache for the repo's entry points.

Call `enable_compile_cache()` from a `main()` before the first compile,
never at import: tests and library users keep JAX's default (no cache).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# A fixed path (<repo>/src/repro/utils/ -> <repo>/.jax_cache): a cache
# directory that moves between runs never hits.
REPO_CACHE = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def cache_dir() -> str:
    """Where compiled programs are cached: $JAX_COMPILATION_CACHE_DIR when
    set (JAX reads it itself), else `<repo>/.jax_cache`."""
    return os.environ.get(ENV) or REPO_CACHE


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Where the
    environment names a directory, JAX already uses it and nothing else
    is set here."""
    path = cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
