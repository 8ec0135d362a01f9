"""Share of the traced window in which no operation ran on the chips
(1 - busy union / window, averaged over the chips), in %."""
from chipbench import trace


def read(ctx):
    share = trace.idle_share(ctx.trace)
    return None if share is None else 100.0 * share
