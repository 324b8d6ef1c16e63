"""A per-call kernel's share of its roofline from the device trace.

`trace_kernel_roofline` reckons per folded row; a kernel that runs once a
boundary over the whole state is reckoned per *call*. The least time the chip
could take is the bytes the algorithm needs for one call — from the
configuration's `shapes` key: every live pane of every key slot read once, the
compact result written once; never the bytes of whatever program was
compiled — times the calls in the traced stretch (the growth of
`kuiper_op_stage_calls_total` for `stage`), over the HBM peak. The kernel's
time is the device time of the programs whose name contains `program` in the
same stretch. `None` where the program, the stage or the shapes are not
there (a commit without the stage, a cell without the kernel).
"""
from engine import metric_growth
from peaks import peaks_for


def needed_bytes_per_call(shapes: dict) -> float:
    slots = float(shapes["key_slots"])
    return (float(shapes["panes_read"]) * slots
            * sum(shapes["read_bytes_per_key_per_pane"].values())
            + slots * sum(shapes["write_bytes_per_key"].values()))


def read(ctx, program: str, stage: str, shapes: str):
    if not ctx.trace or ctx.trace_marks0 is None or shapes not in ctx.cfg:
        return None
    seconds = sum(s for name, s in ctx.trace["programs"].items()
                  if program in name)
    calls = metric_growth(ctx.trace_marks0, ctx.trace_marks1,
                          "kuiper_op_stage_calls_total", stage=stage)
    if seconds <= 0 or calls <= 0:
        return None  # the kernel is not on this path, or not found by name
    peak = peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    least = calls * needed_bytes_per_call(ctx.cfg[shapes]) / peak
    return 100.0 * least / seconds
