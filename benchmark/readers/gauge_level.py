"""A gauge family's level at the end of the measured window, summed over
its samples, times `scale`: e.g. the rung the order-statistic buffers ended
the window on. `None` where the program has no such family (a commit
without it)."""
from engine import metric_total


def read(ctx, family: str, scale: float = 1.0):
    if ctx.marks1 is None or family not in ctx.marks1["metrics"]:
        return None
    return scale * metric_total(ctx.marks1["metrics"], family)
