"""Device time of one eval (the XLA module of the jitted `eval_acc`),
in ms, averaged over the chips."""
from chipbench import trace


def read(ctx):
    s = trace.module_s(ctx.trace, "eval_acc")
    if s <= 0 or ctx.evals <= 0:
        return None
    return 1e3 * s / ctx.evals
