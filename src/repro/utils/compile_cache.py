"""JAX persistent compilation cache for the repo's entry points, and the
log of what set-up spends on compiling.

Call `enable_compile_cache()` from a `main()` before the first compile,
never at import: tests and library users keep JAX's default (no cache).
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import List, Tuple

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# A fixed path (<repo>/src/repro/utils/ -> <repo>/.jax_cache): a cache
# directory that moves between runs never hits.
REPO_CACHE = str(Path(__file__).resolve().parents[3] / ".jax_cache")

# JAX's own compile events (jax._src.dispatch): tracing a function to a
# jaxpr, lowering the jaxpr to an MLIR module, and the backend compile,
# which on a persistent-cache hit is the cache load.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_EVENTS = (TRACE_EVENT, LOWER_EVENT, COMPILE_EVENT)

# (event, fun_name, start, end), times in seconds since the epoch.
_log: List[Tuple[str, str, float, float]] = []
_listening = False


def _record(event: str, start: float, end: float, **kwargs) -> None:
    if event in _EVENTS:
        _log.append((event, str(kwargs.get("fun_name", "")), start, end))


def compile_log() -> List[Tuple[str, str, float, float]]:
    """Every trace, lowering and backend-compile span of this process
    since `enable_compile_cache()` first ran, in the order they ended:
    (event, fun_name, start, end). Spans nest (tracing a jit traces the
    jits it calls)."""
    return list(_log)


def cache_dir() -> str:
    """Where compiled programs are cached: $JAX_COMPILATION_CACHE_DIR when
    set (JAX reads it itself), else `<repo>/.jax_cache`."""
    return os.environ.get(ENV) or REPO_CACHE


def enable_compile_cache() -> str:
    """Turn the persistent cache on, start the compile log (once per
    process) and return the cache's directory. Where the environment
    names a directory, JAX already uses it and nothing else is set
    here."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_time_span_listener(_record)
        _listening = True
    path = cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
