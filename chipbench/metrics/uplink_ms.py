"""Device time under the ``fedlt.uplink`` scope (quantize, EF, pack and
unpack) per round."""


def read(ctx):
    s = ctx.trace.scope_s("fedlt.uplink") if ctx.trace else 0.0
    return 1e3 * s / ctx.rounds if s > 0 else None
