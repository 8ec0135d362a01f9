"""Whole-round share of the chips' bf16 peak, in %: the training FLOPs of
every client sample trained in the window (6 per forward MAC) plus the
forward FLOPs of every eval sample, over window x chips x peak."""


def read(ctx):
    flops = (ctx.train_samples * ctx.train_flops_per_sample
             + ctx.eval_samples * ctx.eval_flops_per_sample)
    peak = ctx.chips * ctx.peaks["bf16_flops_per_s"]
    if flops <= 0:
        return None
    return 100.0 * flops / (ctx.window_s * peak)
