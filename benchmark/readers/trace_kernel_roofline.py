"""A kernel's share of its roofline from the device trace.

The least time the chip could take is the bytes the *algorithm* needs over the
HBM peak: per folded row the bytes of its kernel inputs plus one read and one
write of the state it touches, from the configuration's `fold_shapes` — never
the bytes of whatever program was compiled, so the number means the same work
whatever implements the fold. (The fold is a scatter with a handful of
operations per row: HBM-bound, and `fold_shapes.bound` says so.) The kernel's
time is the device time of the programs whose name contains `program` in the
traced stretch; the rows are the growth of `kuiper_op_stage_rows_total` for
`stage` over the same stretch.
"""
from engine import metric_growth
from peaks import peaks_for


def needed_bytes_per_row(fold_shapes: dict) -> float:
    return float(sum(fold_shapes["input_bytes_per_row"].values())
                 + sum(fold_shapes["state_bytes_per_row"].values()))


def read(ctx, program: str, stage: str):
    if not ctx.trace or ctx.trace_marks0 is None \
            or "fold_shapes" not in ctx.cfg:
        return None
    seconds = sum(s for name, s in ctx.trace["programs"].items()
                  if program in name)
    rows = metric_growth(ctx.trace_marks0, ctx.trace_marks1,
                         "kuiper_op_stage_rows_total", stage=stage)
    if seconds <= 0 or rows <= 0:
        return None  # the kernel is not on this path, or not found by name
    peak = peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    least = rows * needed_bytes_per_row(ctx.cfg["fold_shapes"]) / peak
    return 100.0 * least / seconds
