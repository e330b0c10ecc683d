"""Local-training forward and backward operations of the traced rounds,
counted from the shapes with no recompute, over the traced window times
the chip's bf16 peak."""


def read(ctx):
    if not ctx.trace or ctx.trace.busy_s <= 0:
        return None
    flops = ctx.counts["round_flops"] * ctx.rounds
    return 100.0 * flops / (ctx.trace.window_s * ctx.peaks["bf16_flops_per_s"])
