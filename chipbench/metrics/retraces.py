"""Compiles of the chunk in the window: `Simulator.trace_count` after the
window minus before it (0 expected)."""


def read(ctx):
    return ctx.retraces
