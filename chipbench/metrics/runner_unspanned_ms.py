"""Window time a round outside every ``runner.round`` span: what the
program's spans do not see (the loop between rounds, the window's end)."""
from chipbench.runner_spans import per_round_ms, round_s


def read(ctx):
    return per_round_ms(ctx, lambda tr: tr.window_s - round_s(tr))
