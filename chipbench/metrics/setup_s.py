"""Set-up: process start to the start of the window (compiles included)."""


def read(ctx):
    return ctx.setup_s
