"""CPU self-checks of the DEBS 2014 Query 2 cell (run by hand, with the
others): the smart-plug load generator at the cell's size, the hopping
median reference against a loop, exact answers passing and each control
failing, the per-layer readers the cell adds, and the new cell at a tiny
size with its timed path sound and broken underneath.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""
from __future__ import annotations

import copy
import json
import os
import statistics
import sys
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.dirname(BENCH), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run  # noqa: E402
from generators import smartplug_loads  # noqa: E402
from readers import (counter_per_stage_rows, gauge_level,  # noqa: E402
                     stage_busy, trace_rows_roofline)
from references import hopping_median as hm  # noqa: E402

CFG = run.load_json("configs", "debs14q2.json")
BIG = 2 ** 31 + 4646  # a seed past 32 signed bits, as runs are given
IDS = ("house_id", "household_id", "plug_id")


def small_rows(pool_rows=65536, drain_rows=512, n_plugs=120) -> dict:
    rows = copy.deepcopy(CFG["rows"])
    rows.update(pool_rows=pool_rows, drain_rows=drain_rows)
    rows["plugs"]["n_plugs"] = n_plugs
    return rows


def params(**over) -> dict:
    return {**small_rows(), **CFG["reference_params"],
            "micro_batch_rows": 2048, **over}


# -------------------------------------------------------------- generator
def test_generator_at_the_cells_size():
    """2,125 plugs over 40 houses, every plug about equally often, about
    105 B a row, the seven fields in the source's order, a quarter of the
    plugs mostly at exactly 0 W, a heavy tail to kW, the JSON's loads."""
    pool = smartplug_loads.make(BIG, dict(CFG["rows"]))
    assert pool.n_keys == 2125 and pool.keys.shape == (1024, 4096)
    assert sorted(set(pool.ids[:, 0].tolist())) == list(range(40))
    per = np.bincount(pool.keys.ravel(), minlength=2125)
    assert per.min() >= 1973 and per.max() <= 1974
    size = np.mean([len(r) for r in pool.drains[7]])
    assert 95 < size < 115
    row = json.loads(pool.drains[5][9])
    assert list(row) == ["id", "timestamp", "value", "property", "plug_id",
                         "household_id", "house_id"]
    assert row["value"] == pool.values[5, 9] and row["property"] == 1
    assert tuple(row[k] for k in reversed(IDS)) == tuple(
        pool.ids[pool.keys[5, 9]][::-1])
    zero = (pool.values == 0).mean()
    assert 0.15 < zero < 0.3
    assert pool.values.max() > 1000


def test_generator_follows_the_seed():
    a, b, c = (smartplug_loads.make(s, small_rows())
               for s in (BIG, BIG, BIG + 1))
    assert a.drains == b.drains and np.array_equal(a.values, b.values)
    assert a.drains != c.drains


# -------------------------------------------------------------- reference
def _sent_and_cuts(pool):
    sent = list(range(128)) + list(range(40))  # the pool cycles
    # due: 86,016 rows (whole micro-batches of 2,048); the last window
    # holds the last hop alone, so the last two cuts coincide
    cuts = np.array([0, 20480, 40960, 43008, 65536, 86016, 86016])
    return sent, cuts


def _payloads(pool, cuts, got):
    out = []
    for j in range(len(cuts) - 1):
        ws = 1_700_000_000_000 + 1000 * j
        msgs = []
        for k in np.flatnonzero(got["c"][j]):
            med = got["median"][j, k]
            msgs.append({**dict(zip(IDS, pool.ids[k].tolist())),
                         "ws": ws, "we": ws + 2000, "c": int(got["c"][j, k]),
                         "median_load": None if np.isnan(med)
                         else float(med)})
        out.append(msgs)
    return out


def _windows_of(payloads):
    return [SimpleNamespace(index=i, t=0.0, n_groups=len(p), payload=p)
            for i, p in enumerate(payloads)]


def test_reference_agrees_with_a_loop():
    prm = params()
    pool = smartplug_loads.make(5, small_rows())
    sent, cuts = _sent_and_cuts(pool)
    stream = hm.Sent(pool, sent, prm)
    assert stream.due == 86016
    for j, (cnt, med) in hm.exact_windows(stream, cuts):
        lo, hi = int(cuts[max(j - 1, 0)]), int(cuts[j + 1])
        keys, v32 = stream.key[lo:hi], stream.v32[lo:hi]
        for k in range(pool.n_keys):
            vals = [float(x) for x in v32[keys == k]]
            assert cnt[k] == len(vals)
            if vals:
                assert med[k] == statistics.median(vals)


def test_check_passes_exact_answers_and_fails_each_control():
    prm = params()
    pool = smartplug_loads.make(9, small_rows())
    sent, cuts = _sent_and_cuts(pool)
    got = hm.exact_answers(pool, sent, cuts, prm)
    windows = _windows_of(_payloads(pool, cuts, got))
    assert sum(hm.window_rows(w.payload, prm) for w in windows) == 86016
    verdict = hm.check(pool, sent, windows, prm)
    assert all(v <= lim for v, lim in verdict["numbers"].values()), verdict
    assert verdict["failed"] == 0
    for name, control in hm.CONTROLS.items():
        numbers = control(pool, sent, windows, prm)["numbers"]
        assert any(v > lim for v, lim in numbers.values()), (name, numbers)


@pytest.mark.parametrize("fault,number", [
    ("two_ulps", "median_off"), ("a_plug_renamed", "ids_not_sent_integers"),
    ("ids_as_strings", "ids_not_sent_integers"),
    ("window_short", "window_length_off"), ("null_median", "median_off"),
    ("count_off", "window_counts_off")])
def test_a_wrong_answer_is_not_correct(fault, number):
    prm = params()
    pool = smartplug_loads.make(11, small_rows())
    sent, cuts = _sent_and_cuts(pool)
    payloads = _payloads(pool, cuts, hm.exact_answers(pool, sent, cuts, prm))
    m = next(m for m in payloads[2] if m["median_load"])
    if fault == "two_ulps":
        m["median_load"] += 2 * float(np.spacing(np.float32(
            m["median_load"])))
    elif fault == "a_plug_renamed":
        m["plug_id"] += 1000
    elif fault == "ids_as_strings":
        m["house_id"] = str(m["house_id"])
    elif fault == "window_short":
        for m in payloads[3]:
            m["we"] -= 1
    elif fault == "null_median":
        m["median_load"] = None
    else:
        m["c"] += 1
    got = hm.check(pool, sent, _windows_of(payloads), prm)["numbers"]
    assert got[number][0] > got[number][1], got


# ----------------------------------------------------------------- readers
def _marks(t: float, lines: list) -> dict:
    return {"t": t, "metrics": "\n".join(lines), "status": {}}


def _lines(rows_fold: int, buffered=None, select_calls=None,
           select_us=None) -> list:
    fam = 'kuiper_op_stage_{}{{rule="bench_rule",op="w",type="op",' \
          'stage="{}"}} {}'
    lines = [fam.format("rows_total", "fold", rows_fold)]
    if buffered is not None:
        lines += ["# TYPE kuiper_orderstat_rows_total counter",
                  f'kuiper_orderstat_rows_total{{rule="bench_rule",'
                  f'op="w"}} {buffered}']
    if select_calls is not None:
        lines.append(fam.format("calls_total", "orderstat_select",
                                select_calls))
        lines.append(fam.format("us_total", "orderstat_select", select_us))
    return lines


def test_orderstat_rows_share_is_buffered_rows_over_rows_folded():
    spec = run.load_json("layers", "orderstat_rows_share.json")
    ctx = SimpleNamespace(marks0=_marks(0.0, _lines(1000, 1000)),
                          marks1=_marks(40.0, _lines(9000, 9000)))
    assert counter_per_stage_rows.read(ctx, **spec["args"]) == 100.0
    ctx.marks0, ctx.marks1 = _marks(0, _lines(10)), _marks(40, _lines(90))
    assert counter_per_stage_rows.read(ctx, **spec["args"]) is None


def test_orderstat_buffer_rows_reads_the_rung_the_window_ended_on():
    spec = run.load_json("layers", "orderstat_buffer_rows.json")
    gauge = ['# TYPE kuiper_orderstat_buffer_rows gauge',
             'kuiper_orderstat_buffer_rows{rule="bench_rule",op="w"} 4194304']
    ctx = SimpleNamespace(marks0=_marks(0.0, _lines(0)),
                          marks1=_marks(40.0, _lines(9000) + gauge))
    assert gauge_level.read(ctx, **spec.get("args", {})) == 4194304
    ctx.marks1 = _marks(40.0, _lines(9000))  # the parent: none
    assert gauge_level.read(ctx, **spec.get("args", {})) is None


def test_orderstat_select_share_reads_its_own_stage():
    spec = run.load_json("layers", "orderstat_select_share.json")
    ctx = SimpleNamespace(
        marks0=_marks(0.0, _lines(0, 0, 0, 0)),
        marks1=_marks(10.0, _lines(9000, 9000, 10, 700_000)))
    assert stage_busy.read(ctx, **spec["args"]) == pytest.approx(7.0)
    ctx.marks1 = _marks(10.0, _lines(9000))  # the parent: none
    assert stage_busy.read(ctx, **spec["args"]) is None


def test_orderstat_select_roofline_reckons_rows_not_the_rung():
    """Least bytes: buffered rows x 2 windows x 8 B + calls x slots x 8 B,
    over the HBM peak, over the selection programs' device time; nothing
    where the program, the counter or the shapes are not there."""
    spec = run.load_json("layers", "orderstat_select_roofline.json")
    cfg = {"select_shapes": CFG["select_shapes"]}
    rows, calls = 2_800_000 * 8, 8
    ctx = SimpleNamespace(
        cfg=cfg, device={"kind": "TPU v5 lite"},
        trace={"programs": {"jit__orderstat_select_impl": 0.08,
                            "jit__fold_impl": 0.5}},
        trace_marks0=_marks(0.0, _lines(0, 0, 0, 0)),
        trace_marks1=_marks(8.0, _lines(rows, rows, calls, 1)))
    least = (rows * 2 * 8 + calls * 4096 * 8) / 819e9
    assert trace_rows_roofline.read(ctx, **spec["args"]) == pytest.approx(
        100.0 * least / 0.08)
    ctx.trace = {"programs": {"jit__fold_impl": 0.5}}
    assert trace_rows_roofline.read(ctx, **spec["args"]) is None
    ctx.trace_marks1 = _marks(8.0, _lines(rows))  # the parent: no family
    assert trace_rows_roofline.read(ctx, **spec["args"]) is None
    ctx.cfg = {}
    assert trace_rows_roofline.read(ctx, **spec["args"]) is None


def test_the_new_cell_owes_the_new_metrics_and_the_old_ones():
    traced = [n for _, n in run.load_cell("debs14q2.sat", True).metrics]
    assert {"orderstat_select_share", "orderstat_select_roofline",
            "orderstat_rows_share", "orderstat_buffer_rows", "fold_roofline",
            "decode_busy_cores", "upload_busy_share", "device_idle_share",
            "fold_dispatch_share", "ingest_busy_share", "emit_busy_share",
            "sink_busy_share", "fold_starved_share", "host_offcpu_share",
            "key_encode_share", "decode_skipped_share", "hbm_peak_gb",
            "worker_cpu_share", "fold_h2d_share"} <= set(traced)
    assert not {"hh_encode_share", "int_exact_share",
                "emit_landed_share"} & set(traced)
    plain = [n for _, n in run.load_cell("debs14q2.sat", False).metrics]
    assert plain == ["rows_per_s", "setup_s"]
    for cell in ("tumbling10k.sat", "hll1m.sat", "hophh10k.sat",
                 "slidingpct10k.sat", "nexmarkq12.sat", "nexmarkq17.sat",
                 "tumbling10k.paced"):
        owed = [n for _, n in run.load_cell(cell, True).metrics]
        assert not {"orderstat_select_share", "orderstat_select_roofline",
                    "orderstat_rows_share", "orderstat_buffer_rows"
                    } & set(owed)


# ------------------------- the rest of a run, sound and broken underneath
def _tiny(tmp_path, seconds=4.5):
    import test_benchmark as tbm

    cell = tbm._tiny_cell("debs14q2.sat")
    cell.cfg["rows"]["plugs"]["n_plugs"] = 400  # a CPU's worth
    return tbm._drive(cell, tmp_path, seconds=seconds)


def test_the_cell_is_correct_at_a_tiny_size(tmp_path):
    result = _tiny(tmp_path)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0


def test_a_median_altered_under_the_timed_path_is_not_correct(
        tmp_path, monkeypatch):
    """(test_benchmark.py's `an_answer_altered` alters `c`: its twin for
    the median is here.)"""
    from ekuiper_tpu.io import memory

    subscribe = memory.subscribe

    def altered(pattern, fn):
        if pattern != "bench/out":
            return subscribe(pattern, fn)

        def wrapped(topic, payload):
            for m in payload[::5]:
                if m["median_load"]:
                    m["median_load"] *= 1.001
            fn(topic, payload)
        return subscribe(pattern, wrapped)

    monkeypatch.setattr(memory, "subscribe", altered)
    result = _tiny(tmp_path)
    assert result["correct"] is False, result["checks"]
    assert result["checks"]["median_off"][0] > 0
