"""CPU self-checks of the benchmark (not tier-1; run by hand):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

They hold the yardstick to itself: generators, references, the latency
arithmetic, the trace reduction, the shape of BENCHMARK.json and its metric
files — and that a run whose timed path is broken underneath, or whose answers
come from a control, does not come out `correct`.
"""
from __future__ import annotations

import copy
import json
import math
import os
import re
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run  # noqa: E402
import trace_reduce  # noqa: E402
from generators import keyed_rows  # noqa: E402
from readers import emit_latency, trace_kernel_roofline  # noqa: E402
from references import hll_distinct, tumbling_agg  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def bench_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def small_rows(cfg_name: str) -> dict:
    """A configuration's rows, cut to a size a test can hold."""
    cfg = run.load_json("configs", cfg_name + ".json")
    rows = dict(cfg["rows"])
    rows.update(n_keys=50, pool_rows=4000, block_rows=2000, drain_rows=100)
    return rows


# ------------------------------------------------------------- generators
@pytest.mark.parametrize("cfg_name", ["tumbling10k", "hll1m"])
def test_generator_follows_the_seed(cfg_name):
    rows = small_rows(cfg_name)
    big = 2 ** 31 + 12345  # the driver's seeds pass 32 signed bits
    a, b, c = (keyed_rows.make(s, rows) for s in (big, big, big + 1))
    assert a.drains == b.drains and np.array_equal(a.keys, b.keys)
    assert a.drains != c.drains
    assert a.keys.shape == (40, 100) and len(a.drains[0]) == 100
    for block in a.keys.reshape(2, -1):  # every key in every block
        assert len(np.unique(block)) == 50
    row = json.loads(a.drains[3][7])
    assert row[rows["key_column"]] == "dev_%d" % a.keys[3, 7]
    assert np.float32(row[rows["value"]["column"]]) \
        == np.float32(a.values[3, 7])


# ------------------------------------------------------------- references
def _windows_of(payloads):
    return [SimpleNamespace(index=i, t=0.0, n_groups=len(p), payload=p)
            for i, p in enumerate(payloads)]


def test_tumbling_reference_agrees_with_a_loop():
    rows = small_rows("tumbling10k")
    rows.update(pool_rows=1000, block_rows=1000)
    pool = keyed_rows.make(5, rows)
    sent = [0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 0, 1, 2]  # the pool cycles
    want = tumbling_agg.reference(pool, sent, rows)
    cnt, tot = {}, {}
    mn, mx = {}, {}
    for d in sent:  # brute force, row by row
        for k, v in zip(pool.keys[d].tolist(), pool.values[d].tolist()):
            cnt[k] = cnt.get(k, 0) + 1
            tot[k] = tot.get(k, 0.0) + v
            mn[k] = min(mn.get(k, math.inf), v)
            mx[k] = max(mx.get(k, -math.inf), v)
    for k in range(50):
        assert want["cnt"][k] == cnt[k]
        assert want["tot"][k] == pytest.approx(tot[k], rel=1e-12)
        assert want["mn"][k] == mn[k] and want["mx"][k] == mx[k]


def _tumbling_payloads(pool, sent, split):
    """Exact per-window answers for the sent rows cut at `split`."""
    flat_k = pool.keys[sent].ravel()
    flat_v = pool.values[sent].ravel().astype(np.float64)
    out = []
    for lo, hi in zip([0] + split, split + [len(flat_k)]):
        msgs = []
        for k in np.unique(flat_k[lo:hi]):
            v = flat_v[lo:hi][flat_k[lo:hi] == k]
            msgs.append({"deviceId": "dev_%d" % k, "c": len(v),
                         "a": float(v.mean()), "mn": float(v.min()),
                         "mx": float(v.max())})
        out.append(msgs)
    return out


def test_tumbling_check_passes_exact_answers_and_fails_each_control():
    cfg = run.load_json("configs", "tumbling10k.json")
    rows = small_rows("tumbling10k")
    params = {**rows, **cfg["reference_params"]}
    pool = keyed_rows.make(9, rows)
    sent = list(range(40)) + list(range(15))
    windows = _windows_of(_tumbling_payloads(pool, sent, [1234, 3999]))
    ok = tumbling_agg.check(pool, sent, windows, params)
    assert all(v <= lim for v, lim in ok["numbers"].values()), ok
    assert ok["failed"] == 0 and ok["attempted"] == 5500
    for name, control in tumbling_agg.CONTROLS.items():
        bad = control(pool, sent, windows, params)
        assert any(v > lim for v, lim in bad["numbers"].values()), name
    windows[1].payload[0]["c"] += 1  # one answer altered
    bad = tumbling_agg.check(pool, sent, windows, params)
    assert bad["numbers"]["keys_miscounted"][0] == 1 and bad["failed"] == 1


def test_hll_reference_agrees_with_a_loop_and_fails_its_control():
    cfg = run.load_json("configs", "hll1m.json")
    rows = small_rows("hll1m")
    rows["value"] = dict(rows["value"], high=40)  # repeats within a key
    params = {**rows, **cfg["reference_params"], "window_rows": 1000,
              "micro_batch_rows": 100}
    pool = keyed_rows.make(3, rows)
    sent = list(range(40)) + list(range(5))  # 4.5 windows of 1,000 rows
    exact = hll_distinct._exact(pool, sent, 2, params)
    seen = set()
    for d in sent[20:30]:
        seen.update(zip(pool.keys[d].tolist(), pool.values[d].tolist()))
    for k in range(50):
        assert exact[k] == sum(1 for kk, _ in seen if kk == k)
    payloads = []
    for w in range(4):
        e = hll_distinct._exact(pool, sent, w, params)
        payloads.append([{"deviceId": "dev_%d" % k, "uniq": int(e[k])}
                         for k in range(50)])
    windows = _windows_of(payloads)
    windows[0].payload = windows[2].payload = None  # two of four are kept
    ok = hll_distinct.check(pool, sent, windows, params)
    assert all(v <= lim for v, lim in ok["numbers"].values()), ok
    assert ok["attempted"] == 4000 and ok["failed"] == 0
    bad = hll_distinct.CONTROLS["batch_short"](pool, sent, windows, params)
    assert any(v > lim for v, lim in bad["numbers"].values())
    lost = hll_distinct.check(pool, sent, windows[:3], params)
    assert lost["numbers"]["windows_missing"][0] == 1
    assert lost["failed"] == 1000


# ----------------------------------------------------- latency arithmetic
def test_latency_maps_rows_to_the_drain_that_was_due():
    # four rows a drain, due every 10 ms from t=100 s
    due = [100.0 + 0.010 * i for i in range(10)]
    # window 0 holds 6 rows: its last row is row 6, in drain 1 (due 100.01)
    # window 1 holds 10 more: last row 16, drain 3 (due 100.03)
    # window 2 holds 0 rows and is skipped; window 3 ends in drain 9
    lat = emit_latency.latencies_ms(
        [100.050, 100.070, 100.080, 100.200], [6, 10, 0, 24], due, 4,
        100.0, 101.0)
    assert lat == pytest.approx([40.0, 40.0, 110.0])
    # only the boundaries that closed inside the measured window
    lat = emit_latency.latencies_ms(
        [100.050, 100.070, 100.080, 100.200], [6, 10, 0, 24], due, 4,
        100.060, 100.100)
    assert lat == pytest.approx([40.0])


# ------------------------------------------------------------ closed loop
def test_closed_loop_keeps_a_bounded_number_of_rows_unanswered():
    pool = SimpleNamespace(drains=[[b"x"] * 100] * 4, drain_rows=100)
    rule = SimpleNamespace(wait_shallow=lambda depth: None)
    sink = SimpleNamespace(answered_rows=0)
    feed = run.Feed(rule, "bench/in", pool,
                    {"queue_depth": 8, "max_unanswered_rows": 200}, sink)
    feed.publish_to = lambda drain: None
    end = time.time() + 0.3
    feed.closed(lambda: time.time() >= end)
    assert len(feed.sent) == 3  # 300 rows out, none answered: it waits
    sink.answered_rows = 10 ** 9
    end = time.time() + 0.05
    feed.closed(lambda: time.time() >= end)
    assert len(feed.sent) > 10 and feed.sent[:5] == [0, 1, 2, 3, 0]


# -------------------------------------------------------- trace reduction
def test_trace_reduce_on_hand_made_planes():
    planes = [
        ("/host:CPU", [("python", [("noise", 0, 10 ** 9)])]),
        ("/device:TPU:0", [
            ("XLA Modules", [("jit_fold(123)", 0, 300), ("jit_fold(456)",
                                                         1000, 300),
                             ("jit_finalize(9)", 2000, 100)]),
            ("XLA Ops", [("scatter.1 = f32[8] scatter(...)", 0, 200),
                         ("fusion.2", 100, 200),
                         ("scatter.1 = f32[8] scatter(...)", 1000, 300),
                         ("copy.3", 2000, 100)]),
        ]),
    ]
    got = trace_reduce.reduce_planes(planes, window_s=4e-6)
    assert got["busy_s"] == pytest.approx(700e-9)  # overlap counted once
    assert got["programs"]["jit_fold"] == pytest.approx(600e-9)
    assert got["ops"]["jit_fold/scatter.1"] == pytest.approx(500e-9)
    assert got["top_ops"][0][0] == "jit_fold/scatter.1"
    assert dict(got["top_gaps"])["before jit_fold/scatter.1"] \
        == pytest.approx(700e-9)
    assert got["device_planes"] == 1
    empty = trace_reduce.reduce_planes(planes[:1])
    assert empty["busy_s"] == 0 and empty["device_planes"] == 0


def test_trace_reduce_on_the_recorded_trace():
    path = os.path.join(BENCH, "tests", "recorded", "hll1m_sat.xplane.pb")
    got = trace_reduce.reduce_file(path)
    with open(os.path.join(BENCH, "tests", "recorded",
                           "hll1m_sat.expected.json")) as fh:
        want = json.load(fh)
    assert got["device_planes"] == want["device_planes"]
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < got["busy_s"] < got["window_s"]
    for name, seconds in want["programs"].items():
        assert got["programs"][name] == pytest.approx(seconds, rel=1e-9)
    assert any("fold" in name for name in got["programs"])


def test_needed_bytes_come_from_the_shapes_alone():
    cfg = run.load_json("configs", "tumbling10k.json")
    assert trace_kernel_roofline.needed_bytes_per_row(
        cfg["fold_shapes"]) == 4 + 4 + 1 + 16 + 16
    cfg = run.load_json("configs", "hll1m.json")
    assert trace_kernel_roofline.needed_bytes_per_row(
        cfg["fold_shapes"]) == 4 + 4 + 1 + 1 + 1


# ------------------------------------------------- BENCHMARK.json's shape
def test_benchmark_json_names_units_and_files():
    bench = bench_json()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for group in (bench["workloads"], bench["configs"], bench["end_to_end"],
                  bench["per_layer"]):
        for entry in group:
            assert NAME.match(entry["name"]), entry["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in cells.values():
        assert NAME.match(w["traffic"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))
    for c in configs.values():
        with open(os.path.join(ROOT, c["file"])) as fh:
            held = json.load(fh)
        assert held["source"] == c["source"] and len(c["source"]) <= 200
        assert held["reduced"] == c["reduced"]
    assert "setup_s" in e2e and all(
        0.01 <= m["bound"] <= 0.25 for m in e2e.values())

    def reported_by(metric):
        return set(metric.get("workloads", cells))

    for m in bench["per_layer"]:
        spec = run.load_json("layers", m["name"] + ".json")
        assert spec["unit"] == m["unit"] and spec["layer"] == m["layer"]
        assert spec["moves"] == m["moves"]
        assert os.path.exists(os.path.join(
            BENCH, "readers", spec["reader"] + ".py"))
        # each of its cells reports the end-to-end metric it should move
        assert m["moves"] in e2e
        if "workloads" in m:
            assert reported_by(m) <= reported_by(e2e[m["moves"]])
    for m in e2e.values():
        spec = run.load_json("end_to_end", m["name"] + ".json")
        assert spec["unit"] == m["unit"]
    for name in cells:  # every cell: setup_s, one more, one per-layer
        cell = run.load_cell(name, False)
        assert ("end_to_end", "setup_s") in cell.metrics
        assert len(cell.metrics) >= 2
        assert run.load_cell(name, True).metrics


# ------------------------- the rest of a run, with the timed path broken
def _tiny_cell(name: str):
    cell = copy.deepcopy(run.load_cell(name, False))
    cfg = cell.cfg
    if "window_s" in cfg:
        cfg["rows"].update(n_keys=200, pool_rows=204800, block_rows=204800)
    else:
        cfg["rows"].update(n_keys=5000, pool_rows=131072, block_rows=65536)
        cfg["sql"] = cfg["sql"].replace("2097152", "65536")
        cfg["options"]["micro_batch_rows"] = 8192
        cfg["reference_params"].update(window_rows=65536,
                                       micro_batch_rows=8192)
        # 13 distinct values a key here, not 2: the sketch's own error is
        # larger than at the cell's size, and so are the limits
        cfg["reference_params"]["limits"].update(
            outside_tol_share=0.05, mean_abs_err=1.0)
    return cell


def _drive(cell, tmp_path, seconds=2.5):
    from ekuiper_tpu.io import memory
    from ekuiper_tpu.utils import jaxcache

    jaxcache.setup()
    device = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
    seen: dict = {}
    try:
        return run.run_cell(cell, 2 ** 31 + 7, seconds, False, device,
                            str(tmp_path / "run"), t_start=time.time(),
                            keep=seen)
    finally:  # the next test starts an engine of its own in this process
        if "rule" in seen:
            seen["rule"].engine.close()
        memory.reset()


FAULTS = ["none", "half_of_each_drain_left_out", "an_answer_altered",
          "every_second_fold_returns_its_state_unchanged"]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize(
    "name", [w["name"] for w in bench_json()["workloads"]])
def test_a_broken_timed_path_is_not_correct(name, fault, tmp_path,
                                            monkeypatch):
    """Skips the look for a chip and drives the rest of a run at a tiny
    size on the CPU. Sound, it is correct; with a fault planted under the
    timed path, it is not. (The fourth fault of the builder's list, the
    exchange between chips, does not exist in a one-chip cell.)"""
    from ekuiper_tpu.io import memory
    from ekuiper_tpu.ops.groupby import DeviceGroupBy

    if fault == "half_of_each_drain_left_out":
        publish = memory.publish
        monkeypatch.setattr(memory, "publish", lambda topic, drain: publish(
            topic, drain[:len(drain) // 2] if topic == "bench/in" else drain))
    elif fault == "an_answer_altered":
        subscribe = memory.subscribe

        def altered(pattern, fn):
            if pattern != "bench/out":  # the engine's own subscriptions
                return subscribe(pattern, fn)

            def wrapped(topic, payload):
                for m in payload[::2]:  # every second answer
                    for key in ("c", "uniq"):
                        if key in m:
                            m[key] += 40
                fn(topic, payload)
            return subscribe(pattern, wrapped)
        monkeypatch.setattr(memory, "subscribe", altered)
    elif fault == "every_second_fold_returns_its_state_unchanged":
        fold, calls = DeviceGroupBy.fold, [0]

        def lazy(self, state, *args, **kwargs):
            calls[0] += 1
            if calls[0] % 2:
                return state
            return fold(self, state, *args, **kwargs)
        monkeypatch.setattr(DeviceGroupBy, "fold", lazy)
    result = _drive(_tiny_cell(name), tmp_path)
    assert result["correct"] is (fault == "none"), result["checks"]
    assert list(result)[-1] == "checks"
    if fault == "none":
        assert result["failed"] == 0 and result["attempted"] > 0
        assert len(result["metrics"]) >= 2
        assert all(m["value"] > 0 for m in result["metrics"].values())
