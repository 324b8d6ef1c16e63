"""Growth of a per-op counter family over the measured window, summed over
the ops that report a given stage, over the window's length. The ops are
found by the stage they run (`kuiper_op_stage_us_total{stage=...}`), so no
node name is written into the benchmark: `kuiper_op_idle_us_total` of the
ops that report `fold` is the time the fold's worker sat in its empty input
queue. `scale` 100 reads in per cent of one core. `None` where the program
has no such counter, no op reports the stage, or the counter did not grow."""
import re

from engine import metric_growth

_OP = re.compile(r'\bop="([^"]*)"')


def ops_with_stage(text: str, stage: str) -> set:
    want = f'stage="{stage}"'
    return {m.group(1) for line in text.splitlines()
            if line.startswith("kuiper_op_stage_us_total") and want in line
            for m in [_OP.search(line)] if m}


def read(ctx, family: str, of_stage: str, scale: float = 1.0):
    if ctx.marks0 is None or ctx.marks1 is None:
        return None
    grown = sum(metric_growth(ctx.marks0, ctx.marks1, family, op=op)
                for op in ops_with_stage(ctx.marks1["metrics"], of_stage))
    if grown <= 0:
        return None
    return scale * grown / ((ctx.marks1["t"] - ctx.marks0["t"]) * 1e6)
