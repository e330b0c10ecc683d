"""Device time under the ``fedlt.aggregate`` and ``fedlt.downlink``
scopes per round."""


def read(ctx):
    if not ctx.trace:
        return None
    s = ctx.trace.scope_s("fedlt.aggregate") + ctx.trace.scope_s("fedlt.downlink")
    return 1e3 * s / ctx.rounds if s > 0 else None
