"""Shared-source subtopology: N rules over one stream share one ingest +
decode pipeline (reference: internal/topo/subtopo.go, subtopo_pool.go)."""
import time

import numpy as np

from ekuiper_tpu.planner.planner import RuleDef, plan_rule
from ekuiper_tpu.runtime import subtopo
from ekuiper_tpu.server.processors import StreamProcessor
from ekuiper_tpu.store import kv
import ekuiper_tpu.io.memory as mem


def _mk_stream(store):
    StreamProcessor(store).exec_stmt(
        'CREATE STREAM demo (deviceId STRING, temperature FLOAT) '
        'WITH (DATASOURCE="t/shared", TYPE="memory", FORMAT="JSON")'
    )


def _rule(rule_id, threshold, qos=0):
    return RuleDef(
        id=rule_id,
        sql=(f"SELECT deviceId, temperature FROM demo "
             f"WHERE temperature > {threshold}"),
        actions=[{"memory": {"topic": f"res/{rule_id}"}}],
        options={"qos": qos} if qos else {},
    )


def _results(sink):
    out = []
    for item in list(sink.results):
        out.extend(item if isinstance(item, list) else [item])
    return out


class TestSubtopoPool:
    def test_two_rules_one_source(self, mock_clock):
        store = kv.get_store()
        _mk_stream(store)
        t1 = plan_rule(_rule("r1", 25), store)
        t2 = plan_rule(_rule("r2", 10), store)
        # both rules rode the pool: no private sources, same subtopo key;
        # the live instance resolves at open()
        assert not t1.sources and not t2.sources
        assert t1.shared[0][0].key == t2.shared[0][0].key
        t1.open()
        t2.open()
        assert subtopo.pool_size() == 1
        st = t1._live_shared[0][0]
        assert st is t2._live_shared[0][0]
        assert st.ref_count() == 2
        try:
            mem.publish("t/shared", {"deviceId": "a", "temperature": 30.0})
            mem.publish("t/shared", {"deviceId": "b", "temperature": 20.0})
            mock_clock.advance(20)  # linger flush
            deadline = time.time() + 5
            while time.time() < deadline and not (
                t1.sinks[0].results and t2.sinks[0].results
            ):
                time.sleep(0.01)
            r1 = _results(t1.sinks[0])
            r2 = _results(t2.sinks[0])
            # one decode, two different filters applied per rule
            assert [m["deviceId"] for m in r1] == ["a"]
            assert sorted(m["deviceId"] for m in r2) == ["a", "b"]
        finally:
            t1.close()
            assert st.ref_count() == 1  # r2 still attached, source still live
            t2.close()
        assert st.ref_count() == 0
        assert subtopo.pool_size() == 0  # closed and evicted on last detach

    def test_qos_rule_gets_private_source(self):
        store = kv.get_store()
        _mk_stream(store)
        t1 = plan_rule(_rule("rq", 5, qos=1), store)
        assert t1.sources and not t1.shared
        assert subtopo.pool_size() == 0

    def test_different_options_do_not_share(self):
        store = kv.get_store()
        _mk_stream(store)
        t1 = plan_rule(_rule("ra", 5), store)
        r = _rule("rb", 5)
        r.options = {"micro_batch_rows": 128}
        t2 = plan_rule(r, store)
        assert t1.shared[0][0].key != t2.shared[0][0].key
        t1.open(); t2.open()
        try:
            assert subtopo.pool_size() == 2
        finally:
            t1.close(); t2.close()

    def test_reopen_after_pool_close(self, mock_clock):
        """A rule opened AFTER the pooled subtopo closed (last peer
        detached) must get a fresh, working pipeline."""
        store = kv.get_store()
        _mk_stream(store)
        t1 = plan_rule(_rule("rr1", 0), store)
        t2 = plan_rule(_rule("rr2", 0), store)
        t1.open()
        t1.close()  # last detach -> subtopo closes and is evicted
        assert subtopo.pool_size() == 0
        t2.open()  # must resolve a FRESH subtopo, not the dead one
        try:
            assert subtopo.pool_size() == 1
            mem.publish("t/shared", {"deviceId": "x", "temperature": 1.0})
            mock_clock.advance(20)
            deadline = time.time() + 5
            while time.time() < deadline and not t2.sinks[0].results:
                time.sleep(0.01)
            assert any(m["deviceId"] == "x" for m in _results(t2.sinks[0]))
        finally:
            t2.close()

    def test_share_source_off(self):
        store = kv.get_store()
        _mk_stream(store)
        r = _rule("rc", 5)
        r.options = {"share_source": False}
        t = plan_rule(r, store)
        assert t.sources and not t.shared

    def test_fanout_survives_detach_during_traffic(self, mock_clock):
        """Detaching one rule mid-stream must not break the other's feed
        (copy-on-write outputs)."""
        store = kv.get_store()
        _mk_stream(store)
        t1 = plan_rule(_rule("rd1", 0), store)
        t2 = plan_rule(_rule("rd2", 0), store)
        t1.open(); t2.open()
        try:
            for i in range(5):
                mem.publish("t/shared", {"deviceId": f"d{i}", "temperature": 1.0})
            mock_clock.advance(20)
            t1.close()  # detach while t2 keeps consuming
            mem.publish("t/shared", {"deviceId": "after", "temperature": 1.0})
            mock_clock.advance(20)
            deadline = time.time() + 5
            while time.time() < deadline:
                if any(m["deviceId"] == "after" for m in _results(t2.sinks[0])):
                    break
                time.sleep(0.01)
            assert any(m["deviceId"] == "after" for m in _results(t2.sinks[0]))
        finally:
            t2.close()


# ------------------------------------------------- union pruning (PR 37)
WIDE_FIELDS = ["id", "url", "note", "v"]


def _mk_wide(store):
    StreamProcessor(store).exec_stmt(
        'CREATE STREAM wide (id BIGINT, url STRING, note STRING, v FLOAT) '
        'WITH (DATASOURCE="t/wide", TYPE="memory", FORMAT="JSON")')


def _wide_rule(rule_id, select, **options):
    return RuleDef(
        id=rule_id, sql=f"SELECT {select} FROM wide",
        actions=[{"memory": {"topic": f"res/{rule_id}"}}],
        options={"micro_batch_rows": 64, "micro_batch_linger_ms": 10,
                 "decodePoolSize": 2, **options})


def _wide_row(i):
    import json

    return json.dumps({"id": i, "url": f"http://x/{i}", "note": f"n{i}",
                       "v": float(i)}).encode()


def _wait(cond, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline and not cond():
        time.sleep(0.01)
    return cond()


class TestDecodeUnion:
    def test_source_decodes_the_union_and_a_detach_leaves_the_others(self):
        store = kv.get_store()
        _mk_wide(store)
        topos = {name: plan_rule(_wide_rule(name, select), store)
                 for name, select in
                 (("ua", "id"), ("ub", "id, url"), ("uc", "v"))}
        topos["ua"].open()
        st = topos["ua"]._live_shared[0][0]
        try:
            assert st.source.decoded_columns() == ["id"]
            assert st.source._fast_spec == (("id", 1),)
            topos["ub"].open()
            assert st.source.decoded_columns() == ["id", "url"]
            topos["uc"].open()
            assert st.read_union() == {"id", "url", "v"}
            topos["ub"].close()  # the others' columns stay, url goes
            assert st.source.decoded_columns() == ["id", "v"]
            assert [f.name for f in st.source.schema.fields] == ["id", "v"]
        finally:
            for t in topos.values():
                t.close()

    def test_select_star_keeps_every_column(self, mock_clock):
        store = kv.get_store()
        _mk_wide(store)
        narrow = plan_rule(_wide_rule("sa", "id"), store)
        star = plan_rule(_wide_rule("sb", "*"), store)
        narrow.open()
        st = narrow._live_shared[0][0]
        star.open()
        try:
            assert st.read_union() is None
            assert st.source.decoded_columns() == sorted(WIDE_FIELDS)
            mem.publish("t/wide", [_wide_row(i) for i in range(5)])
            mock_clock.advance(20)
            assert _wait(lambda: len(_results(star.sinks[0])) == 5
                         and len(_results(narrow.sinks[0])) == 5)
            assert all(set(m) == set(WIDE_FIELDS)
                       for m in _results(star.sinks[0]))
            # the narrow rider's own projection stays in its entry
            assert all(set(m) == {"id"} for m in _results(narrow.sinks[0]))
        finally:
            narrow.close()
            star.close()

    def test_a_rule_that_widens_the_union_sees_its_column_from_its_first_batch(
            self):
        """A second rule that reads `url` attaches to a pipeline that is
        decoding `id` alone, with micro-batches decoded for `id` held in
        the ring: it is handed none of those, and every row it is handed
        bears its url."""
        import threading

        store = kv.get_store()
        _mk_wide(store)
        first = plan_rule(_wide_rule("wa", "id"), store)
        second = plan_rule(_wide_rule("wb", "id, url"), store)
        first.open()
        src = first._live_shared[0][0].source
        gate = threading.Event()
        gate.set()
        emit, held = src._emit_decoded, []

        def gated(batch):  # the ring's ordered drain, stopped at will
            if not gate.is_set():
                held.append(batch)
            assert gate.wait(10)
            emit(batch)

        src._emit_decoded = gated  # the pool starts at the first rows
        stop = threading.Event()
        sent = [0]

        def feed():
            while not stop.is_set():  # a micro-batch fills every 64 rows
                mem.publish("t/wide", [_wide_row(sent[0] + k)
                                       for k in range(16)])
                sent[0] += 16
                time.sleep(0.001)

        feeder = threading.Thread(target=feed, daemon=True)
        feeder.start()
        try:
            assert _wait(lambda: len(_results(first.sinks[0])) > 640)
            gate.clear()
            assert _wait(lambda: src.extra_pending() >= 2)  # the ring fills
            second.open()
            gate.set()
            assert _wait(lambda: len(_results(second.sinks[0])) > 640)
        finally:
            gate.set()
            stop.set()
            feeder.join(timeout=5)
            assert not feeder.is_alive()
            got = _results(second.sinks[0])
            first.close()
            second.close()
        assert held and held[0].decoded == {"id"}
        narrow_ids = {int(i) for b in held if not b.covers({"id", "url"})
                      for i in b.columns["id"]}
        assert narrow_ids and not narrow_ids & {m["id"] for m in got}
        assert got and all(m.get("url") == f"http://x/{m['id']}"
                           for m in got)

    def test_one_micro_batch_is_decoded_with_one_column_set(self):
        """Riders come and go while rows arrive: whatever the plan was when
        a micro-batch was handed over is what all of it was decoded with."""
        import threading

        from ekuiper_tpu.data.types import DataType, Field, Schema
        from ekuiper_tpu.io.converters import JsonConverter
        from ekuiper_tpu.runtime.nodes_source import SourceNode

        schema = Schema(fields=[
            Field("id", DataType.BIGINT), Field("url", DataType.STRING),
            Field("note", DataType.STRING), Field("v", DataType.FLOAT)])
        src = SourceNode(
            "s", connector=type("C", (), {
                "open": lambda self, cb: None,
                "close": lambda self: None})(),
            schema=schema, converter=JsonConverter(), micro_batch_rows=128,
            decode_pool_size=3, ring_depth=3, prep_upload=False)
        got = []
        src.broadcast = got.append
        stop = threading.Event()
        sets = [{"id"}, {"id", "url"}, None, {"v", "note"}, set()]

        def flip():
            i = 0
            while not stop.is_set():
                src.set_decode_columns(sets[i % len(sets)])
                i += 1

        flipper = threading.Thread(target=flip, daemon=True)
        flipper.start()
        try:
            for start in range(0, 128 * 200, 32):
                src.ingest([_wide_row(start + k) for k in range(32)])
        finally:
            stop.set()
            flipper.join(timeout=5)
        src._flush()
        src.on_close()
        assert not flipper.is_alive()
        assert sum(b.n for b in got) == 128 * 200  # no row lost to a swap
        seen = set()
        for b in got:
            want = set(WIDE_FIELDS) if b.decoded is None else set(b.decoded)
            assert set(b.columns) == want
            assert all(len(c) == b.n for c in b.columns.values())
            seen.add(frozenset(want))
        assert len(seen) > 1  # the plan did change between micro-batches
