"""Process start to the opening of the measured window."""


def read(ctx):
    return ctx.setup_s
