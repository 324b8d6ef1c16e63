"""Share of the traced stretch in which no operation ran on the device:
100 x (1 - union of the device's op intervals / traced seconds)."""


def read(ctx):
    if not ctx.trace or not ctx.trace["busy_s"]:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
