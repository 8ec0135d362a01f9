"""The program's compile log (`repro.utils.compile_cache.compile_log`:
JAX's own trace, lowering and backend-compile spans) read as the seconds
set-up spent in some of those events."""
from __future__ import annotations

from typing import Iterable, Optional

from chipbench import trace

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
# Compile, or on a persistent-cache hit the cache load.
COMPILE = "/jax/core/compile/backend_compile_duration"


def union_s(events: Iterable[str]) -> Optional[float]:
    """Seconds covered by the logged spans of `events`, nested and
    overlapping spans counted once; None where the program keeps no log
    or logged none of them."""
    try:
        from repro.utils.compile_cache import compile_log
    except ImportError:
        return None
    events = set(events)
    spans = [(start, end) for event, _, start, end in compile_log()
             if event in events]
    if not spans:
        return None
    return sum(b - a for a, b in trace.merge(spans))
