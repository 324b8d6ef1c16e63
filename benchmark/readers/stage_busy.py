"""Host busy time of one engine stage over the measured window: the growth of
`kuiper_op_stage_us_total{stage=...}` (summed over the nodes that report it)
between the window's open and close, over the window's length. `scale` 1
reads in cores (a pooled stage can exceed 1), 100 in per cent of one core."""
from engine import metric_growth


def read(ctx, stage: str, scale: float = 1.0):
    if ctx.marks0 is None or ctx.marks1 is None:
        return None
    busy_us = metric_growth(ctx.marks0, ctx.marks1,
                            "kuiper_op_stage_us_total", stage=stage)
    if busy_us <= 0:
        return None  # the stage did not run on this path
    return scale * busy_us / ((ctx.marks1["t"] - ctx.marks0["t"]) * 1e6)
