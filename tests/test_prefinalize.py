"""Latency-hiding emit pipeline (ops/prefinalize.py): the pre-issued device
finalize + host tail shadow must agree with the synchronous device finalize
bit-for-bit in structure and to float32 accumulation order in values.

Scenario mirrors the real node sequence: fold head batches → prefinalize_begin
(snapshot dispatched) → fold tail batches into device state AND HostShadow →
prefinalize_merge vs a plain finalize over everything.
"""
import numpy as np
import pytest

from ekuiper_tpu.ops.aggspec import extract_kernel_plan
from ekuiper_tpu.ops.groupby import DeviceGroupBy
from ekuiper_tpu.ops.keytable import KeyTable
from ekuiper_tpu.ops.prefinalize import HostShadow
from ekuiper_tpu.sql.parser import parse_select


def _plan(sql):
    stmt = parse_select(sql)
    plan = extract_kernel_plan(stmt)
    assert plan is not None
    return plan


def _cols_for(plan, cols, n):
    """Materialize kernel columns (incl. derived __hll__ copies) the way
    FusedWindowAggNode._fold does."""
    from ekuiper_tpu.ops.aggspec import (
        HLL_COL_PREFIX, _hll_encode_numeric, hash_column_for_hll)

    out = {}
    for name in plan.columns:
        if name.startswith(HLL_COL_PREFIX):
            raw = cols[name[len(HLL_COL_PREFIX):]]
            if raw.dtype == np.object_:
                out[name] = hash_column_for_hll(raw)
            else:
                out[name] = _hll_encode_numeric(raw)
        else:
            out[name] = np.asarray(cols[name], dtype=np.float32)
    return out


def _run_split(plan, head, tail, valid_head=None, valid_tail=None,
               capacity=64, n_panes=1, pane_head=0, pane_tail=0):
    """Fold head, pre-issue, fold tail (device + shadow), merge.
    Returns (merged_outs, merged_act, sync_outs, sync_act, n_keys)."""
    kt = KeyTable(capacity)
    gb = DeviceGroupBy(plan, capacity=capacity, n_panes=n_panes, micro_batch=32)
    state = gb.init_state()

    def fold(state, batch, valid, pane, shadow=None):
        key_col, cols = batch
        slots, grew = kt.encode_column(key_col)
        if grew:
            state = gb.grow(state, kt.capacity)
        dev_cols = _cols_for(plan, cols, len(key_col))
        gb.observe_dtypes(dev_cols)
        state = gb.fold(state, dev_cols, slots, valid, pane)
        if shadow is not None:
            shadow.fold(dev_cols, slots, valid)
        return state

    state = fold(state, head, valid_head, pane_head)
    pending = gb.prefinalize_begin(state)
    shadow = HostShadow(plan, gb.comp_specs, kt.capacity)
    state = fold(state, tail, valid_tail, pane_tail, shadow)

    n_keys = kt.n_keys
    merged_outs, merged_act = gb.prefinalize_merge(pending, shadow, n_keys)
    sync_outs, sync_act = gb.finalize(state, n_keys)
    return merged_outs, merged_act, sync_outs, sync_act, n_keys


def _batch(rng, n, n_keys, extra=None):
    keys = np.array([f"k{i}" for i in rng.integers(0, n_keys, n)],
                    dtype=np.object_)
    cols = {"temp": rng.normal(20, 5, n).astype(np.float32)}
    if extra:
        for name in extra:
            cols[name] = rng.normal(0, 10, n).astype(np.float32)
    return keys, cols


def _assert_parity(mo, ma, so, sa):
    np.testing.assert_allclose(ma, sa, rtol=1e-5)
    for m, s in zip(mo, so):
        np.testing.assert_allclose(
            np.asarray(m, dtype=np.float64), np.asarray(s, dtype=np.float64),
            rtol=1e-4, equal_nan=True)


class TestPrefinalizeParity:
    def test_basic_aggs(self):
        plan = _plan("SELECT avg(temp), count(*), min(temp), max(temp), "
                     "sum(temp), stddev(temp) FROM s "
                     "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)")
        rng = np.random.default_rng(1)
        out = _run_split(plan, _batch(rng, 100, 10), _batch(rng, 60, 10))
        _assert_parity(*out[:4])

    def test_where_and_filter(self):
        plan = _plan("SELECT count(*) FILTER (WHERE temp > 22), avg(temp) "
                     "FROM s WHERE temp > 15 "
                     "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)")
        assert plan.host_foldable
        rng = np.random.default_rng(2)
        out = _run_split(plan, _batch(rng, 80, 8), _batch(rng, 80, 8))
        _assert_parity(*out[:4])

    def test_validity_masks(self):
        plan = _plan("SELECT avg(temp), count(temp), min(temp) FROM s "
                     "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)")
        rng = np.random.default_rng(3)
        head, tail = _batch(rng, 50, 6), _batch(rng, 50, 6)
        vh = {"temp": rng.random(50) > 0.3}
        vt = {"temp": rng.random(50) > 0.3}
        out = _run_split(plan, head, tail, valid_head=vh, valid_tail=vt)
        _assert_parity(*out[:4])

    def test_sketches(self):
        plan = _plan("SELECT distinct_count_approx(temp), "
                     "percentile_approx(temp, 0.9) FROM s "
                     "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)")
        assert plan.host_foldable
        rng = np.random.default_rng(4)
        out = _run_split(plan, _batch(rng, 200, 4), _batch(rng, 200, 4))
        _assert_parity(*out[:4])

    def test_grow_during_tail(self):
        """Keys first seen in the tail exist only in the shadow; the device
        result must be padded, not truncated."""
        plan = _plan("SELECT count(*), sum(temp) FROM s "
                     "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)")
        rng = np.random.default_rng(5)
        head = _batch(rng, 30, 4)
        tail_keys = np.array([f"new{i}" for i in range(40)], dtype=np.object_)
        tail = (tail_keys, {"temp": rng.normal(0, 1, 40).astype(np.float32)})
        out = _run_split(plan, head, tail, capacity=8)
        mo, ma, so, sa, n_keys = out
        assert n_keys == 44
        _assert_parity(mo, ma, so, sa)

    def test_hopping_panes(self):
        """Tail rows land in a different pane; pre-issued finalize merged all
        panes at snapshot, shadow covers the tail regardless of pane."""
        plan = _plan("SELECT avg(temp), max(temp) FROM s "
                     "GROUP BY deviceId, HOPPINGWINDOW(ss, 10, 5)")
        rng = np.random.default_rng(6)
        out = _run_split(plan, _batch(rng, 60, 5), _batch(rng, 60, 5),
                         n_panes=2, pane_head=0, pane_tail=1)
        _assert_parity(*out[:4])

    def test_empty_tail(self):
        plan = _plan("SELECT avg(temp) FROM s "
                     "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)")
        rng = np.random.default_rng(7)
        head = _batch(rng, 50, 5)
        kt = KeyTable(32)
        gb = DeviceGroupBy(plan, capacity=32, micro_batch=32)
        state = gb.init_state()
        slots, _ = kt.encode_column(head[0])
        cols = _cols_for(plan, head[1], 50)
        state = gb.fold(state, cols, slots)
        pending = gb.prefinalize_begin(state)
        mo, ma = gb.prefinalize_merge(pending, None, kt.n_keys)
        so, sa = gb.finalize(state, kt.n_keys)
        _assert_parity(mo, ma, so, sa)

    def test_shadow_alone(self):
        """No device fetch at all (`pending` None — a sliding trigger whose
        rows all sit in its host edge shadow): the shadow is the window."""
        plan = _plan("SELECT avg(temp), count(*), min(temp), max(temp) "
                     "FROM s GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)")
        rng = np.random.default_rng(12)
        keys, raw = _batch(rng, 50, 5)
        kt = KeyTable(32)
        gb = DeviceGroupBy(plan, capacity=32, micro_batch=32)
        slots, _ = kt.encode_column(keys)
        cols = _cols_for(plan, raw, 50)
        shadow = HostShadow(plan, gb.comp_specs, kt.capacity)
        shadow.fold(cols, slots, None)
        mo, ma = gb.prefinalize_merge(None, shadow, kt.n_keys)
        state = gb.fold(gb.init_state(), cols, slots)
        so, sa = gb.finalize(state, kt.n_keys)
        _assert_parity(mo, ma, so, sa)

    def test_int_semantics(self):
        plan = _plan("SELECT sum(temp), avg(temp), count(*) FROM s "
                     "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)")
        rng = np.random.default_rng(8)
        keys = np.array(["a", "a", "b"] * 10, dtype=np.object_)
        ints = rng.integers(0, 100, 30)
        kt = KeyTable(32)
        gb = DeviceGroupBy(plan, capacity=32, micro_batch=16)
        state = gb.init_state()
        slots, _ = kt.encode_column(keys[:20])
        # int input observed -> integral sum/avg on both paths
        gb.observe_dtypes({"temp": ints[:20]})
        cols = {"temp": ints[:20].astype(np.float32)}
        state = gb.fold(state, cols, slots)
        pending = gb.prefinalize_begin(state)
        shadow = HostShadow(plan, gb.comp_specs, kt.capacity)
        slots2, _ = kt.encode_column(keys[20:])
        cols2 = {"temp": ints[20:].astype(np.float32)}
        state = gb.fold(state, cols2, slots2)
        shadow.fold(cols2, slots2, None)
        mo, ma = gb.prefinalize_merge(pending, shadow, kt.n_keys)
        so, sa = gb.finalize(state, kt.n_keys)
        assert mo[2].dtype == np.int64 and so[2].dtype == np.int64
        _assert_parity(mo, ma, so, sa)


class TestTailGrow:
    def test_no_truncation_when_key_table_grew_after_the_snapshot(self):
        """A pending finalize older than a key-table growth: keys first
        seen during the tail are wider than its snapshot, and the merge
        must still emit every one of them."""
        plan = _plan("SELECT count(*) AS c, sum(temp) AS s FROM s "
                     "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)")
        kt = KeyTable(8)
        gb = DeviceGroupBy(plan, capacity=8, micro_batch=16)
        state = gb.init_state()
        slots, _ = kt.encode_column(np.array(["a", "b"] * 8, dtype=np.object_))
        state = gb.fold(state, {"temp": np.arange(16, dtype=np.float32)}, slots)
        pending = gb.prefinalize_begin(state)
        shadow = HostShadow(plan, gb.comp_specs, kt.capacity)
        slots2, grew = kt.encode_column(
            np.array([f"n{i}" for i in range(20)], dtype=np.object_))
        assert grew  # 8 -> 32
        shadow.fold({"temp": np.ones(20, dtype=np.float32)}, slots2, None)
        outs, act = gb.prefinalize_merge(pending, shadow, kt.n_keys)
        assert kt.n_keys == 22
        assert len(outs[0]) == 22 and len(act) == 22
        np.testing.assert_array_equal(outs[0][2:], np.ones(20, dtype=np.int64))


class TestColumnarNulls:
    def test_null_agg_stays_explicit_none(self):
        """A NULL aggregate (empty group min) must appear as an explicit
        None in sink messages, exactly like the dict emit path — not as an
        omitted key."""
        from ekuiper_tpu.ops.emit import build_direct_emit

        sql = ("SELECT deviceId, min(temp) AS mn FROM s "
               "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)")
        stmt = parse_select(sql)
        plan = extract_kernel_plan(stmt)
        direct = build_direct_emit(stmt, plan, ["deviceId"])
        dims = {"deviceId": np.array(["a", "b"], dtype=np.object_)}
        aggs = [np.array([3.5, np.nan], dtype=np.float32)]
        cb = direct.run_columnar(dims, aggs, 0, 10_000)
        msgs = [t.message for t in cb.to_tuples()]
        dict_msgs = direct.run(dims, aggs, 0, 10_000)
        assert msgs[1]["mn"] is None
        assert msgs == dict_msgs


WINDOWS = {"tumbling": "TUMBLINGWINDOW(ss, 10)",
           "hopping": "HOPPINGWINDOW(ss, 10, 5)"}  # length / hop = 2


def _node_bits(kind="tumbling"):
    from ekuiper_tpu.data.batch import ColumnBatch
    from ekuiper_tpu.ops.emit import build_direct_emit
    from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode

    sql = ("SELECT deviceId, avg(temp) AS a, count(*) AS c FROM s "
           f"GROUP BY deviceId, {WINDOWS[kind]}")
    stmt = parse_select(sql)
    rng = np.random.default_rng(9)

    def mkbatch(n):
        keys = np.array([f"d{i}" for i in rng.integers(0, 5, n)],
                        dtype=np.object_)
        return ColumnBatch(
            n=n, columns={"deviceId": keys,
                          "temp": rng.normal(20, 5, n).astype(np.float32)},
            timestamps=np.zeros(n, dtype=np.int64), emitter="s")

    def mknode(prefinalize, capacity=64):
        """The node as the planner builds it: its arguments, no other."""
        plan = extract_kernel_plan(stmt)
        node = FusedWindowAggNode(
            "t", stmt.window, plan,
            dims=[d.expr for d in stmt.dimensions], capacity=capacity,
            micro_batch=32,
            direct_emit=build_direct_emit(stmt, plan, ["deviceId"]),
            prefinalize_lead_ms=250 if prefinalize else 0,
        )
        node.state = node.gb.init_state()
        got = []
        node.broadcast = lambda item: got.append(item)
        return node, got

    return stmt, mkbatch, mknode


def _flat(items):
    """{deviceId: (avg, count)} of one or more emits; the comparison below
    holds the counts exact and the averages to float32 accumulation order."""
    out = []
    for item in items:
        out.extend(item if isinstance(item, list) else [item])
    msgs = [m.message if hasattr(m, "message") else m for m in out]
    return {m["deviceId"]: (pytest.approx(m["a"], rel=1e-5), m["c"])
            for m in msgs}


def _landed(node):
    """Wait (real time) for the newest pre-issued fetch to land."""
    import time

    pending = node._pipeline[-1][0]
    deadline = time.time() + 10
    while not pending.ready() and time.time() < deadline:
        time.sleep(0.005)
    assert pending.ready()


class TestNodePrefinalize:
    @pytest.mark.parametrize("kind", ["tumbling", "hopping"])
    def test_node_emits_via_pretrigger(self, kind):
        """Drive FusedWindowAggNode through PreTrigger→rows→Trigger over
        three boundaries and assert every merged emit matches a sync-emit
        node on the same data: tail rows fold to the device AND to the
        pre-issue's shadow, so the next window (tumbling) and the next
        hop's window (hopping: a row sits in two) count each row once."""
        from ekuiper_tpu.runtime.events import PreTrigger, Trigger

        _, mkbatch, mknode = _node_bits(kind)
        batches = [mkbatch(40) for _ in range(6)]
        step = 10_000 if kind == "tumbling" else 5_000

        def run(prefinalize):
            node, got = mknode(prefinalize)
            assert node.n_panes == (1 if kind == "tumbling" else 2)
            for w in range(3):
                end = step * (w + 1)
                node.process(batches[2 * w])
                if prefinalize:
                    node.on_pre_trigger(PreTrigger(ts=end))
                    assert len(node._pipeline) == 1
                    if w != 1:  # boundary 2 finds its fetch in flight or not
                        _landed(node)
                node.process(batches[2 * w + 1])
                node.on_trigger(Trigger(ts=end))
                assert node._pipeline == []
            # a boundary without a landed pre-issue defers to the emit
            # worker (_emit_late_async) — drain before comparing
            node._drain_async_emits()
            return node, got

        _, sync = run(False)
        node, merged = run(True)
        assert len(sync) == len(merged) == 3
        for a, b in zip(merged, sync):
            assert _flat([a]) == _flat([b])
        assert node.emit_sources.get("device", 0) >= 2
        assert set(node.emit_sources) <= {"device", "device-async-late"}

    def test_tail_rows_across_windows(self):
        """Rows arriving after the pre-issue fold into both device state
        and shadow; the boundary reset must leave the NEXT window counting
        only its own rows (no loss, no double count), across several
        consecutive windows."""
        from ekuiper_tpu.runtime.events import PreTrigger, Trigger

        _, mkbatch, mknode = _node_bits()
        batches = [mkbatch(40) for _ in range(8)]
        node, got = mknode(True)
        sync_node, sync_got = mknode(False)
        for w in range(4):
            for i in range(2):
                node.process(batches[2 * w + i])
                sync_node.process(batches[2 * w + i])
                if i == 0:
                    node.on_pre_trigger(PreTrigger(ts=10_000 * (w + 1)))
            node.on_trigger(Trigger(ts=10_000 * (w + 1)))
            sync_node.on_trigger(Trigger(ts=10_000 * (w + 1)))
        # boundaries without a landed pre-issue defer to the emit worker
        # (_emit_late_async) — drain before asserting, like the count/
        # sliding async tests; without this the check raced the worker
        node._drain_async_emits()
        sync_node._drain_async_emits()
        assert len(got) == len(sync_got) == 4
        for a, b in zip(got, sync_got):
            assert _flat([a]) == _flat([b])

    def test_emit_sources_add_up_over_boundaries(self):
        """The cumulative twin of last_emit_info: one count per emitted
        window by the path that answered it, kept after the next boundary
        overwrites the per-boundary record, and shown in the rule status."""
        from ekuiper_tpu.runtime.events import PreTrigger, Trigger
        from ekuiper_tpu.runtime.topo import Topo

        _, mkbatch, mknode = _node_bits()
        node, got = mknode(True)
        topo = Topo("r_emit")
        topo.add_op(node)
        assert node.emit_sources == {}
        # pre-issue at boundaries 1 and 3 only; boundary 2 finds nothing
        # pre-issued and dispatches its finalize for the emit worker
        for w, pre_issue in enumerate([True, False, True]):
            end = 10_000 * (w + 1)
            node.process(mkbatch(40))
            if pre_issue:
                node._drain_async_emits()  # no backlog ahead of a landed fetch
                node.on_pre_trigger(PreTrigger(ts=end))
                _landed(node)
            node.process(mkbatch(40))
            node.on_trigger(Trigger(ts=end))
        node._drain_async_emits()
        assert len(got) == 3
        assert node.emit_sources == {"device": 2, "device-async": 1}
        assert sum(node.emit_sources.values()) == len(got)
        assert topo.status()["op_t_0_emit_sources"] == {
            "device": 2, "device-async": 1}

    def test_boundary_waits_for_the_device(self):
        """A boundary whose fetch has not landed is delivered by the emit
        worker from the device snapshot, never from the host shadow alone,
        and the windows equal a sync node's."""
        from ekuiper_tpu.ops.prefinalize import PendingFinalize
        from ekuiper_tpu.runtime.events import PreTrigger, Trigger

        _, mkbatch, mknode = _node_bits()
        batches = [mkbatch(40) for _ in range(6)]
        node, got = mknode(True)
        sync_node, sync_got = mknode(False)

        class LandsLate(PendingFinalize):
            def ready(self):
                return False

        orig = node.gb.prefinalize_begin
        node.gb.prefinalize_begin = lambda state, panes=None: LandsLate(
            orig(state, panes).stacked, node.gb.capacity,
            node.gb._components_layout())
        # boundary 2 has no pre-issue at all; 1 and 3 have one in flight
        for w, pre_issue in enumerate([True, False, True]):
            end = 10_000 * (w + 1)
            for n in (node, sync_node):
                n.process(batches[2 * w])
            if pre_issue:
                node.on_pre_trigger(PreTrigger(ts=end))
            for n in (node, sync_node):
                n.process(batches[2 * w + 1])
                n.on_trigger(Trigger(ts=end))
            assert len(node._pipeline) == 0  # the next window opens empty
        node._drain_async_emits()
        sync_node._drain_async_emits()
        assert node.emit_sources == {"device-async-late": 2,
                                     "device-async": 1}
        assert len(got) == len(sync_got) == 3
        for a, b in zip(got, sync_got):
            assert _flat([a]) == _flat([b])

    def test_inflight_fetch_cap(self):
        """No more than two un-landed device fetches may stack: each is a
        full components download (r02 post-mortem)."""
        from ekuiper_tpu.ops.prefinalize import PendingFinalize
        from ekuiper_tpu.runtime.events import PreTrigger

        _, mkbatch, mknode = _node_bits()
        node, _ = mknode(True)
        node.process(mkbatch(40))

        class NeverReady(PendingFinalize):
            def ready(self):
                return False

        orig = node.gb.prefinalize_begin
        node.gb.prefinalize_begin = lambda state, panes=None: NeverReady(
            orig(state, panes).stacked, node.gb.capacity,
            node.gb._components_layout())
        for _ in range(5):
            node.on_pre_trigger(PreTrigger(ts=10_000))
        assert len(node._pipeline) == 2

    @pytest.mark.parametrize("kind", ["tumbling", "hopping"])
    def test_snapshot_between_pretrigger_and_trigger(self, kind):
        """A snapshot taken after the pre-issue and before the boundary,
        restored into a fresh node, closes the window the uninterrupted
        node closes: the device state alone is complete (tail rows fold to
        it too), and the pre-issues are dropped, not saved."""
        import json

        from ekuiper_tpu.runtime.events import PreTrigger, Trigger

        _, mkbatch, mknode = _node_bits(kind)
        batches = [mkbatch(40) for _ in range(5)]
        step = 10_000 if kind == "tumbling" else 5_000
        node, got = mknode(True)
        node.process(batches[0])
        node.on_trigger(Trigger(ts=step))  # hopping: an older pane is live
        node.process(batches[1])
        node.on_pre_trigger(PreTrigger(ts=2 * step))
        node.process(batches[2])  # the tail: device state and shadow
        assert node._pipeline and node._pipeline[0][1].n_rows == 40
        snap = json.loads(json.dumps(node.snapshot_state()))
        assert node._pipeline == []
        fresh, fresh_got = mknode(True)
        fresh.restore_state(snap)
        for n in (node, fresh):
            n.process(batches[3])
            n.on_trigger(Trigger(ts=2 * step))
            n.process(batches[4])
            n.on_trigger(Trigger(ts=3 * step))
            n._drain_async_emits()
        assert len(got) == 3 and len(fresh_got) == 2
        for a, b in zip(got[1:], fresh_got):
            assert _flat([a]) == _flat([b])
        # and both equal a node that never pre-issued
        sync_node, sync_got = mknode(False)
        for i, b in enumerate(batches):
            sync_node.process(b)
            if i in (0, 3, 4):
                sync_node.on_trigger(Trigger(ts=step * (1 + (i + 1) // 2)))
        sync_node._drain_async_emits()
        assert [_flat([x]) for x in sync_got] == [_flat([x]) for x in got]


class TestRestoredCapacity:
    def test_snapshot_of_a_smaller_capacity_grows_before_the_fold(self):
        """restore_state keeps the key table at the node's own capacity and
        takes the state at the snapshot's: the fold must widen the state
        before rows of new keys land, or slots past it are lost."""
        from ekuiper_tpu.runtime.events import Trigger

        _, mkbatch, mknode = _node_bits()
        small, _ = mknode(False, capacity=4)
        small.process(mkbatch(8))
        snap = small.snapshot_state()
        node, got = mknode(False, capacity=64)
        node.restore_state(snap)
        assert node.gb.capacity < node.kt.capacity == 64
        node.process(mkbatch(200))  # all five keys d0..d4
        assert node.gb.capacity == 64
        node.on_trigger(Trigger(ts=10_000))
        node._drain_async_emits()
        assert sum(c for _, c in _flat(got).values()) == 208


class TestServedRule:
    """The boundary as a rule created through the planner or over REST has
    it: default options, the engine's own timers."""

    SQL = ("SELECT deviceId, avg(temp) AS a, count(*) AS c FROM {s} "
           "GROUP BY deviceId, TUMBLINGWINDOW(ss, 1)")

    @staticmethod
    def _stream(name):
        from ekuiper_tpu.server.processors import StreamProcessor
        from ekuiper_tpu.store import kv

        store = kv.get_store()
        StreamProcessor(store).exec_stmt(
            f'CREATE STREAM {name} (deviceId STRING, temp FLOAT) WITH '
            f'(DATASOURCE="{name}/in", TYPE="memory", FORMAT="JSON")')
        return store

    def _one_even_window(self, mock_clock, name):
        """A planned 1 s tumbling rule at the default lead, 100 rows every
        100 ms over one window, the worker's loop pumped on this thread.
        Returns (fused, items seen, shadow rows at the boundary, sink)."""
        import queue

        from ekuiper_tpu.planner.planner import RuleDef, plan_rule
        from ekuiper_tpu.runtime.events import PreTrigger, Trigger
        from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode

        store = self._stream(name)
        topo = plan_rule(RuleDef(
            id=name + "_r", sql=self.SQL.format(s=name),
            actions=[{"nop": {}}], options={"sharedFold": False}), store)
        fused = next(n for n in topo.ops
                     if isinstance(n, FusedWindowAggNode))
        assert fused.prefinalize_lead_ms == 250 and fused._prefinalize_ok
        _, mkbatch, _ = _node_bits()
        got = []
        fused.broadcast = got.append
        fused.state = fused.gb.init_state()
        seen, shadow_rows = [], []

        def pump():
            """The worker's loop, on this thread: what the timers queued."""
            while True:
                try:
                    item = fused.inq.get_nowait()
                except queue.Empty:
                    return
                seen.append(type(item).__name__)
                if isinstance(item, Trigger):
                    shadow_rows.append(
                        [sh.n_rows for _, sh in fused._pipeline])
                fused._dispatch(item)
                fused.inq.task_done()
                if isinstance(item, PreTrigger) and fused._pipeline:
                    _landed(fused)

        start = mock_clock.now_ms()
        assert start % 1000 == 0
        fused.on_open()  # arms the boundary and its two pre-triggers
        try:
            mock_clock.advance(50)
            for _ in range(10):  # 100 rows every 100 ms, at 50, 150 .. 950
                fused.process(mkbatch(100))
                mock_clock.advance(100)
                pump()
            fused._drain_async_emits()
        finally:
            fused.on_close()
        return fused, seen, shadow_rows, got

    def test_half_of_a_one_second_window_reaches_the_shadow(
            self, mock_clock):
        """The two pre-triggers of `_schedule_next_tick` (2 x lead and 1 x
        lead before the boundary; the second is skipped once the first has
        landed) put the snapshot 500 ms before a 1 s window closes at the
        default lead of 250 ms: every row after it is folded twice, on the
        device and into the shadow. Rows spread evenly over the window:
        one half of them reach the shadow (PERF.md, section 4)."""
        fused, seen, shadow_rows, got = self._one_even_window(
            mock_clock, "pf_half")
        assert seen == ["PreTrigger", "PreTrigger", "Trigger"]
        assert shadow_rows == [[500]]  # one pre-issue, the one at 2 x lead
        assert len(got) == 1 and int(got[0].columns["c"].sum()) == 1000
        assert fused.emit_sources == {"device": 1}
        assert shadow_rows[0][0] / 1000 == 0.5

    def test_shadow_fold_stage_counts_the_rows_the_shadow_took(
            self, mock_clock):
        """`HostShadow.fold` on the fused worker has a stage of its own
        (PR 40): opened only while a pre-issue is un-merged, one call a
        micro-batch, its rows the rows folded into shadows."""
        fused, _seen, shadow_rows, _got = self._one_even_window(
            mock_clock, "pf_stage")
        stages = fused.stats.snapshot()["stage_timings"]
        assert stages["fold"]["calls"] == 10  # every micro-batch
        assert stages["fold"]["rows"] == 1000
        # ... of which the five after the snapshot were mirrored
        assert stages["shadow_fold"]["calls"] == 5
        assert stages["shadow_fold"]["rows"] == sum(shadow_rows[0]) == 500
        assert "shadow_fold" not in fused.stats.nested_stages
        assert "shadow_fold" in fused.stats.health_sample()["stages"]
        # the fold's staging is inside `fold`, one stage a micro-batch
        assert "fold_h2d" in fused.stats.nested_stages
        assert stages["fold_h2d"]["calls"] == 10
        assert stages["fold_h2d"]["total_us"] <= stages["fold"]["total_us"]

    def test_rule_with_the_removed_tail_mode_option(self, mock_clock):
        """`tailMode` is no option any more: a rule that still carries it
        is accepted as any unknown option is, plans device-fused and emits
        what the same rule without it emits."""
        import json
        import time

        import ekuiper_tpu.io.memory as mem
        from ekuiper_tpu.runtime.rule import RunState
        from ekuiper_tpu.server.rest import RestApi

        rng = np.random.default_rng(31)
        rules = {"pf_tm": {"tailMode": "host"}, "pf_plain": {}}
        sinks = {rid: [] for rid in rules}
        for rid in rules:
            store = self._stream(f"{rid}_s")
            mem.subscribe(f"{rid}/out",
                          lambda _t, payload, r=rid: sinks[r].append(payload))
        api = RestApi(store)
        try:
            for rid, extra in rules.items():
                code, _ = api.dispatch("POST", "/rules", {
                    "id": rid, "sql": self.SQL.format(s=f"{rid}_s"),
                    "options": {"sharedFold": False, **extra},
                    "actions": [{"memory": {"topic": f"{rid}/out"}}]}, {})
                assert code in (200, 201)
            fused = {}
            for rid in sinks:
                rs = api.rules.state(rid)
                assert rs.wait_state(RunState.RUNNING, 20) and rs.topo._open
                code, explain = api.dispatch(
                    "GET", f"/rules/{rid}/explain", None, {})
                assert code == 200 and explain["path"] == "device-fused"
                fused[rid] = next(
                    n for n in api.rules.state(rid).topo.ops
                    if type(n).__name__ == "FusedWindowAggNode")
                assert fused[rid]._prefinalize_ok

            def publish(n):
                rows = [json.dumps({"deviceId": f"d{k}", "temp": float(t)})
                        .encode() for k, t in zip(
                            rng.integers(0, 7, n).tolist(),
                            rng.normal(20, 5, n).round(2).tolist())]
                for rid in sinks:
                    mem.publish(f"{rid}_s/in", rows)

            for w in range(2):  # two windows, rows before and after the
                publish(300)    # pre-issue of each
                mock_clock.advance(60)   # the source's linger flush
                time.sleep(0.3)
                mock_clock.advance(540)  # past the pre-trigger at 500
                time.sleep(0.3)
                publish(200)
                mock_clock.advance(60)
                time.sleep(0.3)
                mock_clock.advance(340)  # the boundary
                deadline = time.time() + 20
                while time.time() < deadline and \
                        any(len(v) <= w for v in sinks.values()):
                    time.sleep(0.02)
                time.sleep(0.3)
            for f in fused.values():
                f._drain_async_emits()

            def windows(payloads):
                return [_flat([p]) for p in payloads]

            assert len(sinks["pf_tm"]) == len(sinks["pf_plain"]) == 2
            assert windows(sinks["pf_tm"]) == windows(sinks["pf_plain"])
            assert [sum(c for _, c in w.values())
                    for w in windows(sinks["pf_tm"])] == [500, 500]
            for f in fused.values():
                assert set(f.emit_sources) <= {"device", "device-async-late"}
        finally:
            api.rules.stop_all()


class TestKeyTableFastPath:
    def test_miss_then_hit(self):
        kt = KeyTable(16)
        col = np.array(["a", "b", "a", None], dtype=np.object_)
        slots, _ = kt.encode_column(col)
        assert slots[0] == slots[2]
        # None normalizes to "" and aliases; next batch is a pure fast path
        slots2, _ = kt.encode_column(col)
        np.testing.assert_array_equal(slots, slots2)
        assert kt.decode(int(slots[3])) == ""

    def test_none_and_empty_share_slot(self):
        kt = KeyTable(16)
        s1, _ = kt.encode_column(np.array([None], dtype=np.object_))
        s2, _ = kt.encode_column(np.array([""], dtype=np.object_))
        assert s1[0] == s2[0]

    def test_multi_none_alias(self):
        kt = KeyTable(16)
        a = np.array(["x", None], dtype=np.object_)
        b = np.array([1, 2])
        s1, _ = kt.encode_multi([a, b])
        s2, _ = kt.encode_multi([a, b])
        np.testing.assert_array_equal(s1, s2)
        assert kt.decode(int(s1[1])) == ("", 2)

    def test_unhashable_fallback(self):
        kt = KeyTable(16)
        col = np.empty(3, dtype=np.object_)
        col[0] = [1, 2]
        col[1] = [1, 2]
        col[2] = [3]
        slots, _ = kt.encode_column(col)
        assert slots[0] == slots[1] != slots[2]

    def test_unhashable_in_tuple(self):
        kt = KeyTable(16)
        a = np.empty(2, dtype=np.object_)
        a[0] = {"x": 1}
        a[1] = {"x": 1}
        b = np.array(["u", "v"], dtype=np.object_)
        slots, _ = kt.encode_multi([a, b])
        assert slots[0] != slots[1]
        slots2, _ = kt.encode_multi([a, b])
        np.testing.assert_array_equal(slots, slots2)

    def test_growth_from_hashed_path(self):
        kt = KeyTable(2)
        slots, grew = kt.encode_column(
            np.array(["a", "b", "c"], dtype=np.object_))
        assert grew and kt.capacity == 4


class TestEngineClockTelemetry:
    """ISSUE 8 regression: PendingFinalize timing used raw time.time()
    (wall clock) — under the mock clock its fetch_ms telemetry drifted
    with real scheduling while everything else in the engine stood
    still. It now rides timex, so a frozen mock clock yields exact,
    deterministic timestamps."""

    def test_pending_finalize_rides_the_mock_clock(self, mock_clock):
        from ekuiper_tpu.ops.prefinalize import PendingFinalize

        mock_clock.set(5_000_000)
        _, mkbatch, mknode = _node_bits()
        node, _ = mknode(True)
        node.process(mkbatch(40))
        p = node.gb.prefinalize_begin(node.state)
        assert isinstance(p, PendingFinalize)
        # wall-clock epoch would be ~1.7e12 ms; the engine clock says 5e6
        assert p.t_created == 5_000_000
        p.get()  # the fetch thread lands in real time...
        # ...but stamps engine time: frozen clock -> exactly 0 ms, not
        # "whatever the OS scheduler did" (the old nondeterminism)
        assert p.t_done == 5_000_000
        assert p.fetch_ms() == 0.0

    def test_fetch_ms_engine_clock_math(self):
        from ekuiper_tpu.ops import prefinalize as pf

        # fetch_ms is pure engine-clock arithmetic on the stamps: the
        # in-flight sentinel stays -1, landed deltas are exact ms
        q = pf.PendingFinalize.__new__(pf.PendingFinalize)
        q.t_created, q.t_done = 1000, None
        assert q.fetch_ms() == -1.0
        q.t_done = 1250
        assert q.fetch_ms() == 250.0
