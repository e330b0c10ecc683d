"""Device time under the ``fedlt.local_train`` scope per round."""


def read(ctx):
    s = ctx.trace.scope_s("fedlt.local_train") if ctx.trace else 0.0
    return 1e3 * s / ctx.rounds if s > 0 else None
