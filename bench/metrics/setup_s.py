"""Set-up: process start to the window's start (imports, inputs from the
seed, the warm call with its compiles or cache loads)."""


def read(ctx):
    return ctx.setup_s
