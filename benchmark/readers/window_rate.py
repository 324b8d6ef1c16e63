"""Rows the engine accepted from the generator in the measured window over
the window's seconds — all the work and all the time of the window."""


def read(ctx):
    return ctx.rows_in_window / (ctx.t_close - ctx.t_open)
