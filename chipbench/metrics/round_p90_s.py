"""90th percentile of the wall times of all rounds of the window."""
from chipbench.harness import percentile


def read(ctx):
    return percentile(ctx.round_times, 90)
