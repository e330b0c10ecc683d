"""Host time a round inside ``runner.round`` but outside its
``runner.schedule`` and ``runner.dispatch``: masks, quorum, byte
accounting, the error function and the round's log."""
from chipbench.runner_spans import child_s, per_round_ms, round_s


def read(ctx):
    return per_round_ms(ctx, lambda tr: round_s(tr) - child_s(tr, "runner.schedule")
                        - child_s(tr, "runner.dispatch"))
