"""Device busy time per round of the traced window."""


def read(ctx):
    if not ctx.trace or ctx.trace.busy_s <= 0:
        return None
    return 1e3 * ctx.trace.busy_s / ctx.rounds
