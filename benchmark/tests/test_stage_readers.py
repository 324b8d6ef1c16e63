"""CPU self-checks of the readers PR 27 added, each on two hand-made marks
(`/metrics` text at the window's open and close). Run by hand:
`JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider`."""
from __future__ import annotations

import os
import sys
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.dirname(BENCH), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from readers import counter_share, hist_quantile, stage_offcpu  # noqa: E402

OP = 'rule="r",op="window_agg",type="op"'
SRC = 'rule="__shared__",op="bench_in",type="source"'


def marks(t: float, lines: list) -> dict:
    return {"t": t, "metrics": "\n".join(lines) + "\n", "status": {}}


def ctx_of(open_lines: list, close_lines: list, seconds: float = 10.0):
    return SimpleNamespace(marks0=marks(100.0, open_lines),
                           marks1=marks(100.0 + seconds, close_lines))


def test_counter_share_sums_over_the_ops_that_report_the_stage():
    ctx = ctx_of(
        [f'kuiper_op_stage_us_total{{{OP},stage="fold"}} 10',
         f'kuiper_op_stage_us_total{{{SRC},stage="decode"}} 10',
         f"kuiper_op_idle_us_total{{{OP}}} 1000000",
         f"kuiper_op_idle_us_total{{{SRC}}} 5000000"],
        [f'kuiper_op_stage_us_total{{{OP},stage="fold"}} 20',
         f'kuiper_op_stage_us_total{{{SRC},stage="decode"}} 20',
         f"kuiper_op_idle_us_total{{{OP}}} 3500000",
         f"kuiper_op_idle_us_total{{{SRC}}} 9000000"])
    args = {"family": "kuiper_op_idle_us_total", "of_stage": "fold"}
    # 2.5 s of 10 s, of the op that folds alone: the source idles elsewhere
    assert counter_share.read(ctx, scale=100.0, **args) == pytest.approx(25.0)
    assert counter_share.read(ctx, **args) == pytest.approx(0.25)
    assert counter_share.read(
        ctx, family="kuiper_op_idle_us_total", of_stage="sink") is None
    # a program without the counter (the parent commit): nothing to read
    old = ctx_of([ctx.marks0["metrics"].splitlines()[0]],
                 [ctx.marks1["metrics"].splitlines()[0]])
    assert counter_share.read(old, **args) is None
    assert counter_share.read(
        SimpleNamespace(marks0=None, marks1=None), **args) is None


def test_stage_offcpu_is_wall_less_cpu_over_the_window():
    def lines(fold_wall, fold_cpu, sink_wall, sink_cpu, cpu=True):
        out = [f'kuiper_op_stage_us_total{{{OP},stage="fold"}} {fold_wall}',
               f'kuiper_op_stage_us_total{{{OP},stage="sink"}} {sink_wall}',
               f'kuiper_op_stage_us_total{{{SRC},stage="decode"}} 999999']
        if cpu:
            out += [
                f'kuiper_op_stage_cpu_us_total{{{OP},stage="fold"}} '
                f"{fold_cpu}",
                f'kuiper_op_stage_cpu_us_total{{{OP},stage="sink"}} '
                f"{sink_cpu}",
                f'kuiper_op_stage_cpu_us_total{{{SRC},stage="decode"}} 1']
        return out

    ctx = ctx_of(lines(1_000_000, 900_000, 0, 0),
                 lines(5_000_000, 2_900_000, 3_000_000, 2_500_000))
    # fold: 4 s wall, 2 s cpu; sink: 3 s wall, 2.5 s cpu; decode not asked
    assert stage_offcpu.read(ctx, stages=["fold", "sink", "emit"]) == \
        pytest.approx(25.0)
    assert stage_offcpu.read(ctx, stages=["sink"]) == pytest.approx(5.0)
    old = ctx_of(lines(1, 0, 0, 0, cpu=False), lines(9, 0, 9, 0, cpu=False))
    assert stage_offcpu.read(old, stages=["fold", "sink"]) is None


def _hist(phase: str, cumulative: dict) -> list:
    lab = f'rule="r",phase="{phase}"'
    out = [f'kuiper_boundary_ms_bucket{{{lab},le="{le}"}} {n}'
           for le, n in cumulative.items()]
    top = max(cumulative.values())
    return out + [f'kuiper_boundary_ms_bucket{{{lab},le="+Inf"}} {top}',
                  f"kuiper_boundary_ms_sum{{{lab}}} 1.5",
                  f"kuiper_boundary_ms_count{{{lab}}} {top}"]


def test_hist_quantile_reads_the_growth_between_the_marks():
    # warm-up left 10 samples under 1 ms; the window adds 40 in (20, 30]
    before = _hist("emit", {"1": 10, "20": 10, "30": 10, "50": 10}) + \
        _hist("sink", {"1": 0, "20": 0, "30": 0, "50": 0})
    after = _hist("emit", {"1": 10, "20": 10, "30": 50, "50": 50}) + \
        _hist("sink", {"1": 0, "20": 4, "30": 4, "50": 8})
    ctx = ctx_of(before, after)
    fam = {"family": "kuiper_boundary_ms"}
    assert hist_quantile.read(ctx, q=0.5, phase="emit", **fam) == \
        pytest.approx(25.0)  # the warm-up's samples do not pull it down
    assert hist_quantile.read(ctx, q=0.95, phase="emit", **fam) == \
        pytest.approx(29.5)
    assert hist_quantile.read(ctx, q=0.5, phase="sink", **fam) == \
        pytest.approx(20.0)
    assert hist_quantile.read(ctx, q=0.75, phase="sink", **fam) == \
        pytest.approx(40.0)
    # nothing recorded in the window, or no such family: nothing to read
    assert hist_quantile.read(ctx_of(before, before), q=0.5, phase="emit",
                              **fam) is None
    assert hist_quantile.read(ctx, q=0.5, phase="trigger_delay",
                              **fam) is None


def test_hist_quantile_in_the_open_bucket_reads_the_last_bound():
    assert hist_quantile.quantile(
        {1.0: 0, 60000.0: 0, float("inf"): 3}, 0.5) == 60000.0
