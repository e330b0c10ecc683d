"""Host time a round under ``runner.dispatch``: the round's device inputs
(participation mask, key) and the call of the jitted round."""
from chipbench.runner_spans import child_s, per_round_ms


def read(ctx):
    return per_round_ms(ctx, lambda tr: child_s(tr, "runner.dispatch"))
