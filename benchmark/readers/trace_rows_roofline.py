"""A boundary kernel's share of its roofline from the device trace, reckoned
by the rows it reads.

`trace_call_roofline` reckons per call over key slots and panes; a kernel
that reads a row buffer needs bytes in proportion to the rows buffered, not
to the key slots. The least bytes over the traced stretch, from the
configuration's `shapes` key: every row put in the buffers (the growth of
the counter `rows_family`) read `windows_per_row` times at `bytes_per_row`,
plus per call (the growth of `kuiper_op_stage_calls_total` for `stage`)
`key_slots` x `write_bytes_per_key` written; never the bytes of whatever
program was compiled, nor of the buffers' empty rungs. That over the HBM
peak, over the device time of the programs whose name contains `program`.
`None` where the program, the stage, the counter or the shapes are not there
(a commit without the kernel, a cell that does not run it).
"""
from engine import metric_growth
from peaks import peaks_for


def needed_bytes(shapes: dict, rows: float, calls: float) -> float:
    return (rows * float(shapes["windows_per_row"])
            * float(shapes["bytes_per_row"])
            + calls * float(shapes["key_slots"])
            * float(shapes["write_bytes_per_key"]))


def read(ctx, program: str, stage: str, shapes: str, rows_family: str):
    if not ctx.trace or ctx.trace_marks0 is None or shapes not in ctx.cfg \
            or rows_family not in ctx.trace_marks1["metrics"]:
        return None
    seconds = sum(s for name, s in ctx.trace["programs"].items()
                  if program in name)
    calls = metric_growth(ctx.trace_marks0, ctx.trace_marks1,
                          "kuiper_op_stage_calls_total", stage=stage)
    rows = metric_growth(ctx.trace_marks0, ctx.trace_marks1, rows_family)
    if seconds <= 0 or calls <= 0 or rows <= 0:
        return None  # the kernel is not on this path, or not found by name
    peak = peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    least = needed_bytes(ctx.cfg[shapes], rows, calls) / peak
    return 100.0 * least / seconds
