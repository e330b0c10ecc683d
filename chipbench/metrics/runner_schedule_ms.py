"""Host time of the engine's call a round, from the program's own span
``runner.schedule`` (``engine_ms`` times the same call from outside)."""
from chipbench.runner_spans import child_s, per_round_ms


def read(ctx):
    return per_round_ms(ctx, lambda tr: child_s(tr, "runner.schedule"))
