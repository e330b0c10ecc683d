"""Communication rounds of ``Experiment.run`` completed per window second."""


def read(ctx):
    return ctx.rounds / ctx.window_s
