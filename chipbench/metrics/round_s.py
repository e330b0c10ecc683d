"""Window seconds over the rounds completed in it."""


def read(ctx):
    return ctx.window_s / ctx.rounds
