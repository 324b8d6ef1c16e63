"""One label value's share of a counter family's growth over the measured
window: growth of `family{label=value}` over growth of the whole family,
times `scale` (100 reads in per cent). `None` where the program has no such
family or it did not grow in the window."""
from engine import metric_growth


def read(ctx, family: str, label: str, value: str, scale: float = 1.0):
    if ctx.marks0 is None or ctx.marks1 is None:
        return None
    whole = metric_growth(ctx.marks0, ctx.marks1, family)
    if whole <= 0:
        return None
    part = metric_growth(ctx.marks0, ctx.marks1, family, **{label: value})
    return scale * part / whole
