"""Device time of the compiled round chunk (the XLA module of the jitted
`chunk_step`), per round, in ms, averaged over the chips."""
from chipbench import trace


def read(ctx):
    s = trace.module_s(ctx.trace, "chunk_step")
    if s <= 0 or ctx.rounds <= 0:
        return None
    return 1e3 * s / ctx.rounds
