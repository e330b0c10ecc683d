"""Mean host time of one call of the engine's ``run_round``."""


def read(ctx):
    spans = ctx.spans.get("engine.run_round")
    return 1e3 * sum(spans) / len(spans) if spans else None
