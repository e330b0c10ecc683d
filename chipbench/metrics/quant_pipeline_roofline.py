"""Share of its memory roofline the fused quantize→EF→pack kernel
reaches: the bytes it must move (message and cache read, packed words
and cache written) over the HBM bandwidth, against its device time."""


def read(ctx):
    s = ctx.trace.kernel_s("quant_pipeline") if ctx.trace else 0.0
    if s <= 0:
        return None
    least = ctx.counts["quant_pipeline_bytes"] * ctx.rounds / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / s
