"""Host time spent preparing chunks, per round, in ms: the summed
durations of the program's spans `defl.materialize`, `defl.chunk_inputs`
and `defl.snapshot` inside the window, over the window's rounds."""
from chipbench import trace

SPANS = ("defl.materialize", "defl.chunk_inputs", "defl.snapshot")


def read(ctx):
    spans = [e for e in trace.in_window(ctx.trace, ctx.trace.host)
             if e.name in SPANS]
    if not spans or ctx.rounds <= 0:
        return None
    return 1e-6 * sum(e.dur for e in spans) / ctx.rounds
