"""Time the host stages were open with their thread off the core, over the
measured window: the growth of `kuiper_op_stage_us_total` (wall) less that
of `kuiper_op_stage_cpu_us_total` (thread CPU), summed over `stages` and
the nodes that report them, in per cent of one core. A stage is off the
core while it waits — for the interpreter lock, another lock, the device,
a full ring. `None` where the program keeps no CPU time per stage."""
from engine import metric_growth


def read(ctx, stages):
    if ctx.marks0 is None or ctx.marks1 is None:
        return None
    if "kuiper_op_stage_cpu_us_total{" not in ctx.marks1["metrics"]:
        return None
    off_us = sum(
        metric_growth(ctx.marks0, ctx.marks1, "kuiper_op_stage_us_total",
                      stage=stage)
        - metric_growth(ctx.marks0, ctx.marks1,
                        "kuiper_op_stage_cpu_us_total", stage=stage)
        for stage in stages)
    return 100.0 * off_us / ((ctx.marks1["t"] - ctx.marks0["t"]) * 1e6)
