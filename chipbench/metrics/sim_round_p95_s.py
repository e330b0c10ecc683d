"""95th percentile of the wall times of all ``Experiment`` rounds of the
window (engine, round dispatch and host reads)."""
from chipbench.harness import percentile


def read(ctx):
    return percentile(ctx.round_times, 95)
