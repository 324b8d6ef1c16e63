"""NEXmark Query 17 on the served path (PR 42): the rule of
`benchmark/configs/nexmarkq17.json` over seeded Bid rows, created over REST
on a memory stream, lands on the device-fused plan with exact int64 partials
for its BIGINT sum / avg / min / max; its windows equal a plain numpy int64
reference and the host operator path, value for value and type for type, at
prices past 2^24 and sums past 2^32."""
import json
import os
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

from generators import nexmark_auction_bids  # noqa: E402
from references import tumbling_bidstats  # noqa: E402

DRAIN = 2048
WINDOW_DRAINS = 3  # drains a 2 s window
N_WINDOWS = 3
with open(os.path.join(BENCH, "configs", "nexmarkq17.json")) as _fh:
    CFG = json.load(_fh)
PARAMS = {**CFG["rows"], **CFG["reference_params"]}
VALUES = tumbling_bidstats.VALUES


def seeded_pool(seed: int):
    rows = dict(CFG["rows"], drain_rows=DRAIN,
                pool_rows=DRAIN * WINDOW_DRAINS * N_WINDOWS)
    return nexmark_auction_bids.make(seed, rows)


def _start_rule(rule_id: str, options=None):
    store = kv.get_store()
    if "q17_bids" not in StreamProcessor(store).show():
        StreamProcessor(store).exec_stmt(
            f'CREATE STREAM q17_bids ({CFG["stream_fields"]}) WITH '
            '(DATASOURCE="q17/in", TYPE="memory", FORMAT="JSON")')
    api = RestApi(store)
    got = []
    mem.subscribe(f"{rule_id}/out", lambda _t, payload: got.append(payload))
    code, _ = api.dispatch("POST", "/rules", {
        "id": rule_id, "sql": CFG["sql"].format(stream="q17_bids"),
        "options": {"key_slots": 4096, "micro_batch_rows": DRAIN,
                    "micro_batch_linger_ms": 50, "decodePoolSize": 2,
                    **(options or {})},
        "actions": [{"memory": {"topic": f"{rule_id}/out"}}]}, {})
    assert code in (200, 201)
    deadline = time.time() + 20
    while time.time() < deadline:
        rs = api.rules.state(rule_id)
        if rs is not None and rs.topo is not None and rs.topo._open:
            break
        time.sleep(0.05)
    return api, got


def _publish(mock_clock, drains):
    for drain in drains:
        mem.publish("q17/in", drain)
    mock_clock.advance(60)  # the linger flush
    time.sleep(0.4)  # decode pool -> fused worker, in real threads


def _drive(mock_clock, pool, gots):
    """N_WINDOWS windows of WINDOW_DRAINS drains each, then each boundary;
    waits for every sink. The last drain of a window goes out after the
    pre-issue's snapshot (500 ms before the boundary at the default lead):
    the device result and the host shadow each hold part of the window.
    Returns the drains sent, in order."""
    sent = []
    for w in range(N_WINDOWS):
        before = [len(g) for g in gots]
        ids = list(range(w * WINDOW_DRAINS, (w + 1) * WINDOW_DRAINS))
        _publish(mock_clock, [pool.drains[i] for i in ids[:-1]])
        mock_clock.advance(1460)  # past the pre-issue at 1,500 ms
        time.sleep(0.3)
        _publish(mock_clock, [pool.drains[ids[-1]]])
        mock_clock.advance(420)  # the boundary
        deadline = time.time() + 20
        while time.time() < deadline and any(
                len(g) <= n for g, n in zip(gots, before)):
            time.sleep(0.02)
        assert all(len(g) > n for g, n in zip(gots, before)), \
            "a window never reached its sink"
        sent.extend(ids)
    return sent


def _windows(got):
    from types import SimpleNamespace

    return [SimpleNamespace(index=i, t=0.0, n_groups=len(p), payload=p)
            for i, p in enumerate(got)]


def _fused(api, rule_id):
    return next(n for n in api.rules.state(rule_id).topo.ops
                if type(n).__name__ == "FusedWindowAggNode")


def numpy_reference(pool, sent_window):
    """Per auction of one window, the query in plain numpy int64."""
    rows = pool.keys[sent_window].ravel()
    price = pool.values[sent_window].ravel().astype(np.int64)
    out = {}
    for k in np.unique(rows):
        p = price[rows == k]
        s = int(p.sum(dtype=np.int64))
        out[int(pool.ids[k])] = {
            "total_bids": len(p), "rank1_bids": int((p < 10000).sum()),
            "rank2_bids": int(((p >= 10000) & (p < 1000000)).sum()),
            "rank3_bids": int((p >= 1000000).sum()),
            "min_price": int(p.min()), "max_price": int(p.max()),
            "avg_price": s // len(p), "sum_price": s}
    return out


@pytest.mark.parametrize("seed", [17, 2 ** 31 + 7])
def test_served_q17_equals_the_int64_reference_and_the_host_path(
        mock_clock, seed):
    mock_clock.set(1_700_000_000_000)  # windows on a real-looking grid
    pool = seeded_pool(seed)
    # the generator's prices reach past 2^24 here, and a hot auction's
    # window sum past 2^32 is what float32 partials could never hold
    assert (pool.values > 2 ** 24).any()
    api, got = _start_rule("q17_dev")
    _, host = _start_rule("q17_host", {"use_device_kernel": False})
    try:
        _, explain = api.dispatch("GET", "/rules/q17_dev/explain", None, {})
        assert explain["path"] == "device-fused", explain
        assert [a["path"] for a in explain["int_aggregates"]["aggregates"]] \
            == ["exact"] * 4
        sent = _drive(mock_clock, pool, [got, host])
        fused = _fused(api, "q17_dev")
        fused._drain_async_emits()
        # the pre-issued device result, merged with the shadow's tail
        assert fused.emit_sources == {"device": N_WINDOWS}
        assert len(got) == len(host) == N_WINDOWS
        big_sum = False
        for w, (dev_msgs, host_msgs) in enumerate(zip(got, host)):
            want = numpy_reference(
                pool, sent[w * WINDOW_DRAINS:(w + 1) * WINDOW_DRAINS])
            for msgs in (dev_msgs, host_msgs):
                by_key = {m["auction"]: {c: m[c] for c in VALUES}
                          for m in msgs}
                assert by_key == want
                # type for type: JSON integers, never floats
                assert all(type(m[c]) is int for m in msgs
                           for c in VALUES + ("auction",))
            big_sum |= max(v["sum_price"] for v in want.values()) > 2 ** 32
        assert big_sum
        # the benchmark's reference over all windows, limits 0
        for answers in (got, host):
            verdict = tumbling_bidstats.check(pool, sent, _windows(answers),
                                              PARAMS)
            assert all(v <= lim for v, lim in verdict["numbers"].values()), \
                verdict
        # every row took the exact path; nothing fell back to float32
        assert fused.fold_int_exact_rows == len(sent) * DRAIN
        assert fused.agg_int_fallback == {}
        _, text = api.dispatch("GET", "/metrics", None, {})
        text = text if isinstance(text, str) else text.decode()
        assert ('kuiper_fold_int_exact_rows_total{rule="q17_dev",'
                f'op="{fused.name}"}} {len(sent) * DRAIN}') in text
        timings = fused.stats.snapshot()["stage_timings"]
        assert timings["shadow_fold"]["rows"] == N_WINDOWS * DRAIN
        assert timings["int_combine"]["calls"] == N_WINDOWS
        assert "int_combine" in fused.stats.nested_stages
        # 4,096 key slots are two micro-batches: the shadow's full path
        assert fused.shadow_fold_compact_rows == 0
    finally:
        api.rules.stop_all()


def test_a_shadow_at_four_micro_batches_of_slots_folds_compact(mock_clock):
    """`key_slots` four times `micro_batch_rows`, as the cell's 262,144
    slots are eight times its 32,768 rows: the pre-issue's host shadow sums
    its counts and int64 sums over the slots each micro-batch touches
    (HostShadow.fold's compact path), and every window still equals the
    host operator's int64 answers and a numpy int64 reference."""
    mock_clock.set(1_700_000_000_000)
    pool = seeded_pool(44)
    api, got = _start_rule("q17_compact", {"key_slots": 4 * DRAIN})
    _, host = _start_rule("q17_compact_host", {"use_device_kernel": False})
    try:
        sent = _drive(mock_clock, pool, [got, host])
        fused = _fused(api, "q17_compact")
        fused._drain_async_emits()
        assert fused.kt.capacity == 4 * DRAIN
        assert fused.emit_sources == {"device": N_WINDOWS}
        assert len(got) == len(host) == N_WINDOWS
        for w, (dev_msgs, host_msgs) in enumerate(zip(got, host)):
            want = numpy_reference(
                pool, sent[w * WINDOW_DRAINS:(w + 1) * WINDOW_DRAINS])
            for msgs in (dev_msgs, host_msgs):
                assert {m["auction"]: {c: m[c] for c in VALUES}
                        for m in msgs} == want
                assert all(type(m[c]) is int for m in msgs for c in VALUES)
        # every row the shadows took went by the compact path
        rows = fused.stats.snapshot()["stage_timings"]["shadow_fold"]["rows"]
        assert rows == N_WINDOWS * DRAIN
        assert fused.shadow_fold_compact_rows == rows
        _, text = api.dispatch("GET", "/metrics", None, {})
        text = text if isinstance(text, str) else text.decode()
        assert ('kuiper_shadow_fold_compact_rows_total{rule="q17_compact",'
                f'op="{fused.name}"}} {rows}') in text
        _, status = api.dispatch("GET", "/rules/q17_compact/status", None, {})
        assert [v for k, v in status.items()
                if k.endswith("_shadow_fold_compact_rows")] == [rows]
    finally:
        api.rules.stop_all()


def test_schemaless_integers_past_2_24_are_counted_not_silent(mock_clock):
    """A stream that declares no types: the column turns out integral at
    run time, after the state's dtypes were fixed, so its sum / avg / min /
    max keep float32 partials — and every integer row float32 cannot hold
    is counted under kuiper_agg_int_fallback_total{reason}, which /explain
    names."""
    store = kv.get_store()
    if "q17_loose" not in StreamProcessor(store).show():
        StreamProcessor(store).exec_stmt(
            'CREATE STREAM q17_loose () WITH (DATASOURCE="q17/loose", '
            'TYPE="memory", FORMAT="JSON")')
    api = RestApi(store)
    got = []
    mem.subscribe("q17_loose/out", lambda _t, p: got.append(p))
    code, _ = api.dispatch("POST", "/rules", {
        "id": "q17_loose",
        "sql": "SELECT auction, sum(price) AS s, max(price) AS mx FROM "
               "q17_loose GROUP BY auction, TUMBLINGWINDOW(ss, 2)",
        "options": {"micro_batch_rows": 64, "micro_batch_linger_ms": 50},
        "actions": [{"memory": {"topic": "q17_loose/out"}}]}, {})
    assert code in (200, 201)
    try:
        _, explain = api.dispatch("GET", "/rules/q17_loose/explain", None,
                                  {})
        assert explain["path"] == "device-fused"
        assert {(a["path"], a["reason"]) for a in
                explain["int_aggregates"]["aggregates"]} == {
            ("float32", "not_declared_bigint")}
        time.sleep(0.5)
        prices = [5, 2 ** 24 + 1, 2 ** 31 + 1, 7]
        mem.publish("q17/loose", [json.dumps(
            {"auction": 1, "price": p}).encode() for p in prices])
        mock_clock.advance(60)
        time.sleep(0.4)
        mock_clock.advance(1940)
        deadline = time.time() + 20
        while not got and time.time() < deadline:
            time.sleep(0.02)
        msgs = got[0] if isinstance(got[0], list) else [got[0]]
        assert type(msgs[0]["s"]) is int  # truncated: an integer
        fused = _fused(api, "q17_loose")
        # two rows past 2^24, once each though two aggregates read them
        assert fused.agg_int_fallback == {"not_declared_bigint": 2}
        assert fused.fold_int_exact_rows == 0
        _, text = api.dispatch("GET", "/metrics", None, {})
        text = text if isinstance(text, str) else text.decode()
        assert ('kuiper_agg_int_fallback_total{rule="q17_loose",'
                f'op="{fused.name}",reason="not_declared_bigint"}} 2') in text
    finally:
        api.rules.stop_all()


def test_a_bigint_compared_past_2_24_keeps_the_rule_exact_on_the_host(
        mock_clock):
    """A FILTER comparing the BIGINT price with a constant float32 cannot
    hold declines the device plan by name; the host operator answers it,
    exactly."""
    sql = CFG["sql"].replace("price >= 1000000", "price >= 16777217")
    store = kv.get_store()
    _start_rule("q17_warm")  # creates the stream
    api = RestApi(store)
    got = []
    mem.subscribe("q17_edge/out", lambda _t, p: got.append(p))
    code, _ = api.dispatch("POST", "/rules", {
        "id": "q17_edge", "sql": sql.format(stream="q17_bids"),
        "options": {"micro_batch_rows": 64},
        "actions": [{"memory": {"topic": "q17_edge/out"}}]}, {})
    assert code in (200, 201)
    try:
        _, explain = api.dispatch("GET", "/rules/q17_edge/explain", None, {})
        assert explain["path"] == "host"
        assert [d["reason"] for d in
                explain["int_aggregates"]["declined"]] == ["bigint_const"]
        time.sleep(0.5)
        prices = [16777216, 16777217, 2 ** 40]
        mem.publish("q17/in", [json.dumps(
            {"auction": 3, "price": p}).encode() for p in prices])
        mock_clock.advance(60)
        time.sleep(0.4)
        mock_clock.advance(1940)
        deadline = time.time() + 20
        while not got and time.time() < deadline:
            time.sleep(0.02)
        msgs = got[0] if isinstance(got[0], list) else [got[0]]
        (msg,) = [m for m in msgs if m["auction"] == 3]
        assert msg["rank3_bids"] == 2 and msg["sum_price"] == sum(prices)
    finally:
        api.rules.stop_all()


@pytest.mark.parametrize("device", [True, False])
def test_a_null_price_is_no_value_on_either_path(mock_clock, device):
    """A null or absent BIGINT price (an object column of ints and None on
    the device plan) is no value: sum / min / max / avg take the rest,
    exactly, count(*) every row; a group whose prices are all null sums to
    NULL."""
    rows = [json.dumps({"auction": a, "price": p}).encode()
            for a, p in ((7, 2 ** 40 + 3), (7, None), (7, -5), (8, None))]
    rows.append(b'{"auction":7}')
    api, got = _start_rule("q17_null_%d" % device,
                           {"use_device_kernel": device})
    try:
        _publish(mock_clock, [rows])
        mock_clock.advance(1940)
        deadline = time.time() + 20
        while not got and time.time() < deadline:
            time.sleep(0.02)
        msgs = got[0] if isinstance(got[0], list) else [got[0]]
        by_key = {m["auction"]: m for m in msgs}
        assert (by_key[7]["sum_price"], by_key[7]["min_price"],
                by_key[7]["max_price"], by_key[7]["avg_price"]) == (
            2 ** 40 - 2, -5, 2 ** 40 + 3, (2 ** 40 - 2) // 2)
        assert by_key[7]["total_bids"] == 4
        assert by_key[8]["sum_price"] is None
        assert by_key[8]["total_bids"] == 1
    finally:
        api.rules.stop_all()
