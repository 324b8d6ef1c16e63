"""NEXmark Query 12 on the served path (PR 37): the rule of
`benchmark/configs/nexmarkq12.json` over seeded Bid rows, created over REST,
lands on the device-fused plan; its windows hold what the plain reference
(`benchmark/references/tumbling_count.py`) and the host operator path say;
BIGINT bidders come back as JSON integers, under the generator's hot-key
skew; the shared source decodes the one column the rule reads."""
import importlib.util
import json
import os
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

import ekuiper_tpu.io.memory as mem
from ekuiper_tpu.server.processors import StreamProcessor
from ekuiper_tpu.server.rest import RestApi
from ekuiper_tpu.store import kv

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DRAIN = 2048
WINDOW_DRAINS = 3  # drains a 2 s window
N_WINDOWS = 4


def _load(*parts):
    """A file of the benchmark, by path: nothing of it is on sys.path."""
    path = os.path.join(ROOT, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location(
        "q12_" + parts[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # a dataclass looks its module up there
    spec.loader.exec_module(mod)
    return mod


tumbling_count = _load("references", "tumbling_count.py")
nexmark_bids = _load("generators", "nexmark_bids.py")
with open(os.path.join(ROOT, "benchmark", "configs",
                       "nexmarkq12.json")) as _fh:
    CFG = json.load(_fh)
PARAMS = {**CFG["rows"], **CFG["reference_params"]}


def seeded_pool(seed: int):
    rows = dict(CFG["rows"], drain_rows=DRAIN,
                pool_rows=DRAIN * WINDOW_DRAINS * N_WINDOWS)
    return nexmark_bids.make(seed, rows)


def _start_rule(rule_id: str, options=None, sql=None):
    store = kv.get_store()
    if "q12_bids" not in StreamProcessor(store).show():
        StreamProcessor(store).exec_stmt(
            f'CREATE STREAM q12_bids ({CFG["stream_fields"]}) WITH '
            '(DATASOURCE="q12/in", TYPE="memory", FORMAT="JSON")')
    api = RestApi(store)
    got = []
    mem.subscribe(f"{rule_id}/out", lambda _t, payload: got.append(payload))
    code, _ = api.dispatch("POST", "/rules", {
        "id": rule_id,
        "sql": (sql or CFG["sql"]).format(stream="q12_bids"),
        "options": {"key_slots": 4096, "micro_batch_rows": DRAIN,
                    "micro_batch_linger_ms": 50, "decodePoolSize": 2,
                    "prefinalizeLeadMs": 0, **(options or {})},
        "actions": [{"memory": {"topic": f"{rule_id}/out"}}]}, {})
    assert code in (200, 201)
    deadline = time.time() + 20
    while time.time() < deadline:
        rs = api.rules.state(rule_id)
        if rs is not None and rs.topo is not None and rs.topo._open:
            break
        time.sleep(0.05)
    return api, got


def _drive_window(mock_clock, drains, gots):
    """One 2 s window of drains, then its boundary; waits for every sink."""
    before = [len(g) for g in gots]
    for drain in drains:
        mem.publish("q12/in", drain)
    mock_clock.advance(60)  # the linger flush
    time.sleep(0.4)  # decode pool -> fused worker, in real threads
    mock_clock.advance(1940)  # the boundary
    deadline = time.time() + 20
    while time.time() < deadline and any(
            len(g) <= n for g, n in zip(gots, before)):
        time.sleep(0.02)
    assert all(len(g) > n for g, n in zip(gots, before)), \
        "a window never reached its sink"


def _windows(got):
    return [SimpleNamespace(index=i, t=0.0, n_groups=len(p), payload=p)
            for i, p in enumerate(got)]


def _drive_pool(mock_clock, pool, gots):
    sent = []
    for w in range(N_WINDOWS):
        ids = list(range(w * WINDOW_DRAINS, (w + 1) * WINDOW_DRAINS))
        _drive_window(mock_clock, [pool.drains[i] for i in ids], gots)
        sent.extend(ids)
    return sent


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 5, 977])
def test_served_q12_against_the_reference_and_the_host_path(
        mock_clock, seed):
    mock_clock.set(1_700_000_000_000)  # windows on a real-looking grid
    pool = seeded_pool(seed)
    api, got = _start_rule("q12_dev")
    _, host = _start_rule("q12_host", {"use_device_kernel": False})
    try:
        code, explain = api.dispatch("GET", "/rules/q12_dev/explain", None, {})
        assert code == 200 and explain["path"] == "device-fused", explain
        _, host_explain = api.dispatch(
            "GET", "/rules/q12_host/explain", None, {})
        assert host_explain["path"] == "host"
        sent = _drive_pool(mock_clock, pool, [got, host])
        fused = next(n for n in api.rules.state("q12_dev").topo.ops
                     if type(n).__name__ == "FusedWindowAggNode")
        fused._drain_async_emits()
        # the plain reference: every bid once, under its bidder, integer
        # keys, windows of 2,000 ms on the grid and disjoint
        for answers in (got, host):
            verdict = tumbling_count.check(
                pool, sent, _windows(answers), PARAMS)
            assert all(v <= lim for v, lim in verdict["numbers"].values()), \
                verdict
            assert verdict["failed"] == 0
            assert verdict["attempted"] == len(sent) * DRAIN
        # ... and the two paths, window by window
        assert len(got) == len(host) == N_WINDOWS

        def as_set(msgs):
            return {(m["bidder"], m["c"], m["ws"], m["we"]) for m in msgs}

        for dev_msgs, host_msgs in zip(got, host):
            assert as_set(dev_msgs) == as_set(host_msgs)
            assert all(type(m["bidder"]) is int and type(m["c"]) is int
                       for m in dev_msgs)
        status = api.rules.state("q12_dev").topo.status()
        assert not any(v for k, v in status.items()
                       if k.endswith("_exceptions_total"))
    finally:
        api.rules.stop_all()


def test_hot_bidder_takes_three_bids_in_four_of_a_window(mock_clock):
    """The generator's skew reaches the fold: in every window one slot
    takes about three rows in four, and its count is exact."""
    pool = seeded_pool(5)
    api, got = _start_rule("q12_hot")
    try:
        sent = _drive_pool(mock_clock, pool, [got])
        for w, msgs in enumerate(got):
            rows = pool.keys[sent[w * WINDOW_DRAINS:(w + 1) * WINDOW_DRAINS]]
            exact = np.bincount(rows.ravel(), minlength=pool.n_keys)
            by_key = {m["bidder"]: m["c"] for m in msgs}
            assert by_key == {int(pool.ids[k]): int(c)
                              for k, c in enumerate(exact) if c}
            # (a window of 6,144 bids meets two or three hot bidders: the
            # hot id moves every 4,600)
            assert 0.68 < sum(sorted(by_key.values())[-3:]) / rows.size < 0.85
    finally:
        api.rules.stop_all()


def test_shared_source_decodes_the_one_column_the_rule_reads(mock_clock):
    pool = seeded_pool(3)
    api, got = _start_rule("q12_cols")
    try:
        _drive_window(mock_clock, pool.drains[:WINDOW_DRAINS], [got])
        _, explain = api.dispatch("GET", "/rules/q12_cols/explain", None, {})
        assert explain["source_columns"] == {"q12_bids": {
            "pipeline": "shared", "reads": ["bidder"],
            "decoded": ["bidder"]}}
        topo = api.rules.state("q12_cols").topo
        status = topo.status()
        assert [v for k, v in status.items()
                if k.endswith("_decoded_columns")] == [["bidder"]]
        src = topo.live_shared()[0][0].source
        n = WINDOW_DRAINS * DRAIN
        assert src.decode_tally["kept"] == n  # bidder, every row
        assert src.decode_tally["skipped"] == 6 * n
        assert src.decode_tally["bytes"] == sum(
            len(r) for d in pool.drains[:WINDOW_DRAINS] for r in d)
        _, text = api.dispatch("GET", "/metrics", None, {})
        text = text if isinstance(text, str) else text.decode()
        assert ('kuiper_source_decode_fields_total{rule="__shared__",'
                f'op="q12_bids",fate="skipped"}} {6 * n}') in text
        assert ('kuiper_source_decode_bytes_total{rule="__shared__",'
                'op="q12_bids"}') in text
        # the group key's encode is a stage of its own, inside upload
        encoded = sum(
            v["stage_timings"]["key_encode"]["rows"]
            for v in (n_.stats.snapshot() for n_ in
                      list(topo.all_nodes()) + list(
                          topo.live_shared()[0][0].nodes))
            if "key_encode" in v["stage_timings"])
        assert encoded >= n
    finally:
        api.rules.stop_all()


@pytest.mark.parametrize("device", [True, False])
def test_a_bid_without_a_bidder_groups_under_one_null_key(
        mock_clock, device):
    """A null or absent BIGINT key is not bidder 0: both paths answer it
    apart from every real id (the device plan under its nil key "", as for
    a STRING column; the host operator under null)."""
    rows = [json.dumps({"auction": 1, "bidder": b, "price": 5}).encode()
            for b in (1000, 1000, 0, 1001)]
    rows += [b'{"auction":1,"bidder":null,"price":5}',
             b'{"auction":1,"price":5}']
    api, got = _start_rule("q12_null", {"use_device_kernel": device})
    try:
        _drive_window(mock_clock, [rows], [got])
        by_key = {m.get("bidder"): m["c"] for m in got[0]}
        assert by_key == {1000: 2, 0: 1, 1001: 1, "" if device else None: 2}
    finally:
        api.rules.stop_all()


def _encode_rows(topo):
    """(the shared source's, the fused node's own) key-table rows by path."""
    src = topo.live_shared()[0][0].source
    fused = next(n for n in topo.ops
                 if type(n).__name__ == "FusedWindowAggNode")
    return dict(src.keytable_encode_rows()), dict(fused.keytable_encode_rows())


def test_the_bigint_key_is_encoded_by_the_native_int_table(mock_clock):
    """Every row after warm-up is slot-encoded on the `native_int` path —
    also after a micro-batch with null bidders took the `hashed` one — and
    the answers are the host operator path's, keys as JSON integers."""
    from ekuiper_tpu.io import fastjson

    if not fastjson.has_keytab("keytab_encode_i64"):
        fastjson.ensure_native(background=False)
    if not fastjson.has_keytab("keytab_encode_i64"):
        pytest.skip("native key table unavailable (no toolchain)")
    pool = seeded_pool(2 ** 31 + 39)
    api, got = _start_rule("q12_int")
    _, host = _start_rule("q12_int_host", {"use_device_kernel": False})
    try:
        topo = api.rules.state("q12_int").topo
        _drive_window(mock_clock, pool.drains[:WINDOW_DRAINS], [got, host])
        warm_src, _ = _encode_rows(topo)
        assert warm_src["native_int"] == WINDOW_DRAINS * DRAIN, warm_src
        # a drain whose every 8th bid has no bidder: one micro-batch's key
        # column is an object column (None cells), the `hashed` path
        holed = [json.dumps({**json.loads(r), "bidder": None}).encode()
                 if i % 8 == 0 else r
                 for i, r in enumerate(pool.drains[WINDOW_DRAINS])]
        _drive_window(mock_clock, [holed], [got, host])
        mid_src, _ = _encode_rows(topo)
        assert mid_src["hashed"] == DRAIN
        assert mid_src["native_int"] == warm_src["native_int"]
        more = pool.drains[WINDOW_DRAINS + 1:3 * WINDOW_DRAINS + 1]
        _drive_window(mock_clock, more[:WINDOW_DRAINS], [got, host])
        _drive_window(mock_clock, more[WINDOW_DRAINS:], [got, host])
        src_rows, own_rows = _encode_rows(topo)
        n_more = len(more) * DRAIN
        assert src_rows == {**mid_src,
                            "native_int": mid_src["native_int"] + n_more}
        assert src_rows["sorted"] == src_rows["native_str"] == 0
        # the fused node mirrors new keys only, an all-int slice as int64
        fused = next(n for n in topo.ops
                     if type(n).__name__ == "FusedWindowAggNode")
        fused._drain_async_emits()
        assert sum(own_rows.values()) == fused.kt.n_keys
        assert own_rows["native_int"] > 0 and own_rows["sorted"] == 0
        assert all(type(k) is int or k == "" for k in fused.kt.decode_all())
        # same answers as the host operator, window by window
        assert len(got) == len(host) == 4
        for dev_msgs, host_msgs in zip(got, host):
            dev = {(m["bidder"], m["c"]) for m in dev_msgs}
            assert dev == {("" if m["bidder"] is None else m["bidder"],
                            m["c"]) for m in host_msgs}
            assert all(type(b) is int or b == "" for b, _ in dev)
        assert sum(m["c"] for m in got[1] if m["bidder"] == "") == DRAIN // 8
        # ... and the counter where an operator reads it
        status = topo.status()
        assert [v for k, v in status.items()
                if k.endswith("q12_bids_0_keytable_encode_rows")] == [src_rows]
        _, text = api.dispatch("GET", "/metrics", None, {})
        text = text if isinstance(text, str) else text.decode()
        for path, n in src_rows.items():
            assert ('kuiper_keytable_encode_rows_total{rule="__shared__",'
                    f'op="q12_bids",path="{path}"}} {n}') in text
        assert ('kuiper_keytable_encode_rows_total{rule="q12_int",'
                f'op="{fused.name}",path="native_int"}} '
                f'{own_rows["native_int"]}') in text
    finally:
        api.rules.stop_all()


def test_without_the_native_int_table_the_mirror_keeps_the_order(
        mock_clock, monkeypatch):
    """No `keytab_encode_i64` (no toolchain, a stale module): the shared
    table numbers a micro-batch's new bidders in sorted order, or first
    seen when a null cell made the column an object column — the fused
    node's mirror has to follow either, or its windows name other bidders
    than the ones counted. Answers against the host operator path."""
    import ekuiper_tpu.ops.keytable as ktmod

    real = ktmod._native_keytab_module
    monkeypatch.setattr(
        ktmod, "_native_keytab_module",
        lambda api="keytab_encode": None if api == "keytab_encode_i64"
        else real(api))
    pool = seeded_pool(2 ** 31 + 41)
    api, got = _start_rule("q12_noint")
    _, host = _start_rule("q12_noint_host", {"use_device_kernel": False})
    try:
        topo = api.rules.state("q12_noint").topo
        holed = [json.dumps({**json.loads(r), "bidder": None}).encode()
                 if i % 8 == 0 else r for i, r in enumerate(pool.drains[3])]
        _drive_window(mock_clock, pool.drains[:3], [got, host])
        _drive_window(mock_clock, [holed] + pool.drains[4:6], [got, host])
        _drive_window(mock_clock, pool.drains[6:9], [got, host])
        src_rows, own_rows = _encode_rows(topo)
        assert src_rows == {"native_int": 0, "native_str": 0,
                            "hashed": DRAIN, "sorted": 8 * DRAIN}
        fused = next(n for n in topo.ops
                     if type(n).__name__ == "FusedWindowAggNode")
        fused._drain_async_emits()
        assert fused._shared_slots_ok is True  # still riding the shared encode
        assert own_rows["hashed"] == fused.kt.n_keys and own_rows["sorted"] == 0
        nkt = fused._shared_nkt
        assert fused.kt.decode_all() == nkt.keys_slice(0, fused.kt.n_keys)
        assert len(got) == len(host) == 3
        for dev_msgs, host_msgs in zip(got, host):
            dev = {(m["bidder"], m["c"]) for m in dev_msgs}
            assert dev == {("" if m["bidder"] is None else m["bidder"],
                            m["c"]) for m in host_msgs}
    finally:
        api.rules.stop_all()
