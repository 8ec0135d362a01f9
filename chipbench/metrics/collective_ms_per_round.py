"""Cross-chip exchange time per round, in ms: the self time of the
collective operations on the busiest chip, over the rounds."""
from chipbench import trace


def read(ctx):
    per_chip = trace.collective_s(ctx.trace)
    if not per_chip or ctx.rounds <= 0:
        return None
    worst = max(per_chip.values())
    return None if worst <= 0 else 1e3 * worst / ctx.rounds
