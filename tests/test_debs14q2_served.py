"""DEBS 2014 Grand Challenge Query 2 on the served path: the rule of
`benchmark/configs/debs14q2.json` over seeded smart-plug load readings,
created over REST on a memory stream, lands on the device-fused plan with
`median` an exact order statistic (ops/orderstat.py); its windows equal a
plain numpy reference within one float32 ulp and the host window operator
within float32 rounding, keys as the JSON integers that went in; the
counters count what they say; and every placement that cannot carry the
row buffers is named in `/explain`."""
import json
import os
import statistics
import sys
import time

import numpy as np
import pytest

import ekuiper_tpu.io.memory as mem
from ekuiper_tpu.server.processors import StreamProcessor
from ekuiper_tpu.server.rest import RestApi
from ekuiper_tpu.store import kv

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.append(BENCH)  # after the repo: its names shadow nothing

from generators import smartplug_loads  # noqa: E402
from references import hopping_median  # noqa: E402

DRAIN = 2048
HOP_DRAINS = 2  # drains a 1 s hop
N_HOPS = 4
with open(os.path.join(BENCH, "configs", "debs14q2.json")) as _fh:
    CFG = json.load(_fh)
IDS = ("house_id", "household_id", "plug_id")


def seeded_pool(seed: int, n_plugs: int = 300):
    rows = dict(CFG["rows"], drain_rows=DRAIN,
                pool_rows=DRAIN * HOP_DRAINS * N_HOPS,
                plugs=dict(CFG["rows"]["plugs"], n_plugs=n_plugs))
    return smartplug_loads.make(seed, rows)


def _stream(name: str) -> None:
    store = kv.get_store()
    if name not in StreamProcessor(store).show():
        StreamProcessor(store).exec_stmt(
            f'CREATE STREAM {name} ({CFG["stream_fields"]}) WITH '
            f'(DATASOURCE="{name}/in", TYPE="memory", FORMAT="JSON")')


def _start_rule(rule_id: str, stream: str, options=None, sql=None):
    _stream(stream)
    api = RestApi(kv.get_store())
    got = []
    mem.subscribe(f"{rule_id}/out", lambda _t, payload: got.append(payload))
    code, body = api.dispatch("POST", "/rules", {
        "id": rule_id, "sql": (sql or CFG["sql"]).format(stream=stream),
        "options": {"key_slots": 4096, "micro_batch_rows": DRAIN,
                    "micro_batch_linger_ms": 50, "decodePoolSize": 2,
                    **(options or {})},
        "actions": [{"memory": {"topic": f"{rule_id}/out"}}]}, {})
    assert code in (200, 201), body
    deadline = time.time() + 20
    while time.time() < deadline:
        rs = api.rules.state(rule_id)
        if rs is not None and rs.topo is not None and rs.topo._open:
            break
        time.sleep(0.05)
    return api, got


def _fused(api, rule_id):
    return next(n for n in api.rules.state(rule_id).topo.ops
                if type(n).__name__ == "FusedWindowAggNode")


def _drive(mock_clock, topic, pool, gots):
    """N_HOPS hops of HOP_DRAINS drains, each then its boundary, and one
    boundary more for the window that holds the last hop alone; waits for
    every sink after each. Returns the drains sent per hop."""
    hops = []
    for h in range(N_HOPS + 1):
        before = [len(g) for g in gots]
        ids = (list(range(h * HOP_DRAINS, (h + 1) * HOP_DRAINS))
               if h < N_HOPS else [])
        for i in ids:
            mem.publish(topic, pool.drains[i])
        mock_clock.advance(60)  # the linger flush (a full batch cut itself)
        time.sleep(0.4)  # decode pool -> fused worker, in real threads
        mock_clock.advance(940)  # the boundary
        deadline = time.time() + 20
        while time.time() < deadline and any(
                len(g) <= n for g, n in zip(gots, before)):
            time.sleep(0.02)
        assert all(len(g) > n for g, n in zip(gots, before)), \
            "a window never reached its sink"
        hops.append(ids)
    return hops


def _by_plug(msgs):
    return {tuple(m[k] for k in IDS): m for m in msgs}


def window_reference(pool, hops, j):
    """{(house, household, plug): (count, median)} over hops j-1 and j, in
    numpy: the median of the float32-rounded loads, in float64."""
    idx = [i for h in hops[max(j - 1, 0):j + 1] for i in h]
    keys = pool.keys[idx].ravel()
    v32 = pool.values[idx].ravel().astype(np.float32)
    cnt, med = hopping_median.medians(keys, v32, pool.n_keys)
    return {tuple(int(x) for x in pool.ids[k]): (int(cnt[k]), float(med[k]))
            for k in np.flatnonzero(cnt)}


def ulp32(x: float) -> float:
    """One float32 ulp of `x`: the device holds the loads as float32."""
    return float(abs(np.spacing(np.float32(x))))


@pytest.mark.parametrize("seed", [14, 2 ** 31 + 11])
def test_served_q2_equals_the_reference_and_the_host_path(mock_clock, seed):
    mock_clock.set(1_700_000_000_000)  # windows on a real-looking grid
    pool = seeded_pool(seed)
    api, got = _start_rule("q2_dev", "q2_loads")
    _, host = _start_rule("q2_host", "q2_loads",
                          {"use_device_kernel": False})
    try:
        code, explain = api.dispatch("GET", "/rules/q2_dev/explain", None,
                                     {})
        assert code == 200 and explain["path"] == "device-fused", explain
        (buf,) = explain["order_stats"]["buffers"]
        assert buf["column"] == ".value" and buf["rung"] >= 128 * DRAIN
        assert [f["kind"] for f in buf["fractions"]] == ["median"]
        fused = _fused(api, "q2_dev")
        assert fused._async_os and not fused._prefinalize_ok
        hops = _drive(mock_clock, "q2_loads/in", pool, [got, host])
        fused._drain_async_emits()
        assert len(got) == len(host) == N_HOPS + 1
        n_even = 0
        for j, (dev, hst) in enumerate(zip(got, host)):
            want = window_reference(pool, hops, j)
            dev, hst = _by_plug(dev), _by_plug(hst)
            assert set(dev) == set(hst) == set(want)
            for key, (n, med) in want.items():
                d, h = dev[key], hst[key]
                assert all(type(d[k]) is int for k in IDS)
                assert d["c"] == h["c"] == n
                assert d["we"] - d["ws"] == 2000 and d["ws"] % 1000 == 0
                # one float32 ulp of the numpy reference: the device holds
                # float32 loads, the midpoint may be taken either side
                assert abs(d["median_load"] - med) <= ulp32(med), (key, d)
                # the host operator reads the loads as float64: the two
                # differ by the loads' float32 rounding, at most an ulp
                assert abs(d["median_load"] - h["median_load"]) \
                    <= ulp32(h["median_load"]), (key, d, h)
                n_even += n % 2 == 0
        assert n_even > 0  # the midpoint path was taken
        rows = N_HOPS * HOP_DRAINS * DRAIN
        assert fused.orderstat_rows == rows
        stats = fused.stats.snapshot()["stage_timings"]
        assert stats["fold"]["rows"] == rows
        assert stats["orderstat_select"]["calls"] == len(got)
        enc = fused.keytable_encode_rows()
        assert enc.get("int_multi") == rows and enc["hashed"] == 0
        _, text = api.dispatch("GET", "/metrics", None, {})
        text = text if isinstance(text, str) else text.decode()
        assert (f'kuiper_orderstat_rows_total{{rule="q2_dev",'
                f'op="{fused.name}"}} {rows}') in text
        assert (f'kuiper_orderstat_buffer_rows{{rule="q2_dev",'
                f'op="{fused.name}"}} {fused.orderstat_buffer_rows}') in text
        assert 'path="int_multi"' in text
        status = api.rules.state("q2_dev").topo.status()
        assert [v for k, v in status.items()
                if k.endswith("_orderstat_rows")] == [rows]
        assert not any(v for k, v in status.items()
                       if k.endswith("_exceptions_total"))
    finally:
        api.rules.stop_all()


def test_the_buffers_climb_a_rung_and_drop_no_row(mock_clock):
    """A hop of more rows than the first rung holds: the buffers double on
    the device between two micro-batches, the counter says so, and the
    window still equals the reference."""
    mock_clock.set(1_700_000_000_000)
    pool = seeded_pool(3, n_plugs=40)
    api, got = _start_rule("q2_rung", "q2_rung_loads",
                           {"micro_batch_rows": 32})
    try:
        fused = _fused(api, "q2_rung")
        first = fused.orderstat_buffer_rows
        assert first == 128 * 32
        before = len(got)
        for i in range(5):  # 10,240 rows into one pane
            mem.publish("q2_rung_loads/in", pool.drains[i])
        mock_clock.advance(60)
        time.sleep(0.4)
        mock_clock.advance(940)
        deadline = time.time() + 20
        while time.time() < deadline and len(got) <= before:
            time.sleep(0.02)
        fused._drain_async_emits()
        assert fused.orderstat_grows >= 2
        assert fused.orderstat_buffer_rows >= 5 * DRAIN > first
        _, explain = api.dispatch("GET", "/rules/q2_rung/explain", None, {})
        (buf,) = explain["order_stats"]["buffers"]
        assert buf["rung"] == fused.orderstat_buffer_rows
        want = window_reference(pool, [[0, 1, 2, 3, 4]], 0)
        dev = _by_plug(got[-1])
        assert set(dev) == set(want)
        for key, (n, med) in want.items():
            assert dev[key]["c"] == n
            assert abs(dev[key]["median_load"] - med) <= ulp32(med)
    finally:
        api.rules.stop_all()


# ------------------------------------------- what cannot carry the buffers
HOST_PLACEMENTS = [
    ("sliding_ring", {}, "SLIDINGWINDOW(ss, 2) OVER (WHEN value > 1000)"),
    ("mesh", {"planOptimizeStrategy": {"mesh": {"rows": 1, "keys": 2}}},
     None),
    ("qos_checkpoint", {"qos": 1}, None),
    ("window", {}, "COUNTWINDOW(100)"),
]


@pytest.mark.parametrize("placement,options,window", HOST_PLACEMENTS)
def test_a_placement_that_cannot_carry_the_buffers_is_named(
        placement, options, window):
    """The rule goes to the host window operator, which is exact, and
    `/explain` says which placement sent it there."""
    sql = CFG["sql"]
    if window is not None:
        sql = sql.replace("HOPPINGWINDOW(ss, 2, 1)", window)
    rule_id = f"q2_{placement}"
    api, _ = _start_rule(rule_id, "q2_placed", options, sql=sql)
    try:
        code, explain = api.dispatch("GET", f"/rules/{rule_id}/explain",
                                     None, {})
        assert code == 200 and explain["path"] == "host", explain
        section = explain["order_stats"]
        assert section["path"] == "host"
        (why,) = section["declined"]
        assert why["placement"] == placement
        assert why["reason"] == "orderstat_placement"
    finally:
        api.rules.stop_all()


def test_a_device_plan_names_every_placement_it_declines():
    """Mesh, shared fold, tier store, sliding ring, pre-issue and the qos
    checkpoint are each named with the reason; the shared-fold decision
    and the tier store say so where they are decided too."""
    api, _ = _start_rule("q2_named", "q2_named_loads",
                         {"tierStore": "on", "tierHotMb": 1})
    try:
        code, explain = api.dispatch("GET", "/rules/q2_named/explain",
                                     None, {})
        assert code == 200 and explain["path"] == "device-fused", explain
        named = {d["placement"]: d["reason"]
                 for d in explain["order_stats"]["declined"]}
        assert set(named) == {"mesh", "shared_fold", "tier_store",
                              "sliding_ring", "pre_issue",
                              "qos_checkpoint"}
        assert all(named.values())
        assert explain["sharing"]["decision"] == "private"
        assert explain["sharing"]["reason"] == named["shared_fold"]
        fused = _fused(api, "q2_named")
        assert fused.tier is None and not fused._prefinalize_ok
    finally:
        api.rules.stop_all()


# --------------------------------------------------- integer arguments
def _one_window(mock_clock, topic, rows, gots):
    """Publish `rows` (dicts) as one drain, then the boundary that closes
    their pane; waits for every sink."""
    before = [len(g) for g in gots]
    mem.publish(topic, [json.dumps(r).encode() for r in rows])
    mock_clock.advance(60)
    time.sleep(0.4)
    mock_clock.advance(940)
    deadline = time.time() + 20
    while time.time() < deadline and any(
            len(g) <= n for g, n in zip(gots, before)):
        time.sleep(0.02)
    assert all(len(g) > n for g, n in zip(gots, before))
    return [g[n] if isinstance(g[n], list) else [g[n]]
            for g, n in zip(gots, before)]


BIG_IDS = {1: [2 ** 24 + 1, 2 ** 24 + 3, 2 ** 24 + 5],
           2: [2 ** 53 - 1, 3],
           3: [2 ** 40 + 7]}
INT_SQL = ("SELECT house_id, median(id) AS m, percentile_disc(id, 0.5) AS d, "
           "percentile_cont(id, 0.25) AS pc, count(*) AS c FROM {stream} "
           "GROUP BY house_id, HOPPINGWINDOW(ss, 2, 1)")


def test_an_order_statistic_of_a_bigint_column_stays_exact_on_the_host(
        mock_clock):
    """The row buffers hold float32, which rounds a BIGINT past 2^24: a
    median / percentile of a column the stream declares BIGINT keeps the
    rule on the host operator, named in `/explain` (placement `bigint`),
    and its answers equal the host operator's and the integers' own
    order statistics, value for value and type for type."""
    from ekuiper_tpu.functions.funcs_agg import _percentile

    mock_clock.set(1_700_000_000_000)
    api, got = _start_rule("q2_big", "q2_big_ids", sql=INT_SQL)
    _, host = _start_rule("q2_big_host", "q2_big_ids",
                          {"use_device_kernel": False}, sql=INT_SQL)
    try:
        _, explain = api.dispatch("GET", "/rules/q2_big/explain", None, {})
        assert explain["path"] == "host", explain
        (why,) = explain["order_stats"]["declined"]
        assert why["placement"] == "bigint"
        assert why["reason"] == "orderstat_placement"
        rows = [{"id": v, "house_id": h, "household_id": 0, "plug_id": 0,
                 "value": 1.0, "property": 1, "timestamp": 0}
                for h, vals in BIG_IDS.items() for v in vals]
        dev, hst = _one_window(mock_clock, "q2_big_ids/in", rows,
                               [got, host])
        dev = {m["house_id"]: m for m in dev}
        hst = {m["house_id"]: m for m in hst}
        assert set(dev) == set(hst) == set(BIG_IDS)
        for h, vals in BIG_IDS.items():
            want = {"m": float(statistics.median(vals)),
                    "d": _percentile(vals, 0.5, cont=False),
                    "pc": _percentile(vals, 0.25, cont=True),
                    "c": len(vals)}
            for k, v in want.items():
                assert dev[h][k] == hst[h][k] == v, (h, k, dev[h])
                assert type(dev[h][k]) is type(hst[h][k]) is type(v)
        assert dev[1]["m"] == 2 ** 24 + 3  # float32 would say 2^24 + 4
    finally:
        api.rules.stop_all()


def test_schemaless_integers_past_2_24_are_counted_not_silent(mock_clock):
    """A stream that declares no types keeps the device plan: integer
    values float32 cannot hold are counted under
    kuiper_agg_int_fallback_total{reason}; below 2^24 the answers equal the
    host operator's, a percentile_disc an integer as there."""
    store = kv.get_store()
    if "q2_loose" not in StreamProcessor(store).show():
        StreamProcessor(store).exec_stmt(
            'CREATE STREAM q2_loose () WITH (DATASOURCE="q2_loose/in", '
            'TYPE="memory", FORMAT="JSON")')
    mock_clock.set(1_700_000_000_000)
    sql = ("SELECT k, median(v) AS m, percentile_disc(v, 0.5) AS d, "
           "count(*) AS c FROM {stream} GROUP BY k, TUMBLINGWINDOW(ss, 1)")
    api, got = _start_rule("q2_loose_dev", "q2_loose", sql=sql)
    _, host = _start_rule("q2_loose_host", "q2_loose",
                          {"use_device_kernel": False}, sql=sql)
    try:
        _, explain = api.dispatch("GET", "/rules/q2_loose_dev/explain",
                                  None, {})
        assert explain["path"] == "device-fused", explain
        rows = [{"k": 1, "v": v} for v in (5, 2 ** 24 + 1, 7, 9, 11)] + [
            {"k": 2, "v": 2 ** 31 + 1}]
        dev, hst = _one_window(mock_clock, "q2_loose/in", rows,
                               [got, host])
        dev = {m["k"]: m for m in dev}
        hst = {m["k"]: m for m in hst}
        assert dev[1] == hst[1] == {"k": 1, "m": 9.0, "d": 9, "c": 5}
        assert type(dev[1]["d"]) is int and type(dev[1]["m"]) is float
        fused = _fused(api, "q2_loose_dev")
        assert fused.agg_int_fallback == {"not_declared_bigint": 2}
    finally:
        api.rules.stop_all()
