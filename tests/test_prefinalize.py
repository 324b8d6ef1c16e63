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


class TestFrozenTailGrow:
    def test_no_truncation_when_device_grow_deferred(self):
        """Keys first seen during a frozen (host-only) tail grow the key
        table but NOT the device state; merge must still emit them."""
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


def _node_bits():
    from ekuiper_tpu.data.batch import ColumnBatch
    from ekuiper_tpu.ops.emit import build_direct_emit
    from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode

    sql = ("SELECT deviceId, avg(temp) AS a, count(*) AS c FROM s "
           "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)")
    stmt = parse_select(sql)
    rng = np.random.default_rng(9)

    def mkbatch(n):
        keys = np.array([f"d{i}" for i in rng.integers(0, 5, n)],
                        dtype=np.object_)
        return ColumnBatch(
            n=n, columns={"deviceId": keys,
                          "temp": rng.normal(20, 5, n).astype(np.float32)},
            timestamps=np.zeros(n, dtype=np.int64), emitter="s")

    def mknode(prefinalize, tail_mode="device", backstop=True):
        plan = extract_kernel_plan(stmt)
        node = FusedWindowAggNode(
            "t", stmt.window, plan,
            dims=[d.expr for d in stmt.dimensions], capacity=64,
            micro_batch=32,
            direct_emit=build_direct_emit(stmt, plan, ["deviceId"]),
            prefinalize_lead_ms=250 if prefinalize else 0,
            tail_mode=tail_mode, prefinalize_backstop=backstop,
        )
        node.state = node.gb.init_state()
        got = []
        node.broadcast = lambda item: got.append(item)
        return node, got

    return stmt, mkbatch, mknode


def _flat(items):
    out = []
    for item in items:
        out.extend(item if isinstance(item, list) else [item])
    return {(m.message if hasattr(m, "message") else m)["deviceId"]:
            (round((m.message if hasattr(m, "message") else m)["a"], 3),
             (m.message if hasattr(m, "message") else m)["c"])
            for m in out}


class TestNodePrefinalize:
    @pytest.mark.parametrize("tail_mode", ["device", "host"])
    def test_node_emits_via_pretrigger(self, tail_mode):
        """Drive FusedWindowAggNode through PreTrigger→data→Trigger and
        assert the merged emit matches a sync-emit node on the same data,
        for both tail modes (device: tail rows fold to device AND shadow;
        host: device frozen at pre-issue, tail rows shadow-only)."""
        from ekuiper_tpu.runtime.events import PreTrigger, Trigger

        _, mkbatch, mknode = _node_bits()
        batches = [mkbatch(40) for _ in range(4)]

        def run(prefinalize):
            node, got = mknode(prefinalize, tail_mode)
            node.process(batches[0])
            node.process(batches[1])
            if prefinalize:
                node.on_pre_trigger(PreTrigger(ts=10_000))
                assert node._pipeline
            node.process(batches[2])
            node.process(batches[3])
            node.on_trigger(Trigger(ts=10_000))
            return got

        sync = run(False)
        merged = run(True)
        assert len(sync) == len(merged) > 0
        assert _flat(sync) == _flat(merged)

    def test_device_tail_mode_across_windows(self):
        """Device tail mode: rows arriving after the pre-issue fold into
        both device state and shadow; the boundary reset must leave the
        NEXT window counting only its own rows (no loss, no double
        count), across several consecutive windows."""
        from ekuiper_tpu.runtime.events import PreTrigger, Trigger

        _, mkbatch, mknode = _node_bits()
        batches = [mkbatch(40) for _ in range(8)]
        node, got = mknode(True, "device")
        sync_node, sync_got = mknode(False, "device")
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
        import time

        from ekuiper_tpu.ops.prefinalize import IdentityFinalize
        from ekuiper_tpu.runtime.events import PreTrigger, Trigger
        from ekuiper_tpu.runtime.topo import Topo

        _, mkbatch, mknode = _node_bits()
        node, got = mknode(True, "device")
        topo = Topo("r_emit")
        topo.add_op(node)
        assert node.emit_sources == {}
        # pre-issue at boundaries 1 and 3 only; boundary 2 finds nothing
        # but the identity entry and is answered by the host backstop
        for w, pre_issue in enumerate([True, False, True]):
            end = 10_000 * (w + 1)
            node.process(mkbatch(40))
            if pre_issue:
                node.on_pre_trigger(PreTrigger(ts=end))
                real = [p for p, _ in node._pipeline
                        if not isinstance(p, IdentityFinalize)]
                deadline = time.time() + 10
                while not real[0].ready() and time.time() < deadline:
                    time.sleep(0.005)
                assert real[0].ready()
            node.process(mkbatch(40))
            node.on_trigger(Trigger(ts=end))
        node._drain_async_emits()
        assert len(got) == 3
        assert node.emit_sources == {"device": 2, "backstop": 1}
        assert node.last_emit_info["source"] == "device"  # the newest only
        assert topo.status()["op_t_0_emit_sources"] == {
            "device": 2, "backstop": 1}

    def test_no_backstop_boundary_waits_for_the_device(self):
        """prefinalize_backstop=False (what the planner builds): a boundary
        whose fetch has not landed is delivered by the emit worker from the
        device snapshot, never from the host shadow alone, and the windows
        equal a sync node's."""
        from ekuiper_tpu.ops.prefinalize import PendingFinalize
        from ekuiper_tpu.runtime.events import PreTrigger, Trigger

        _, mkbatch, mknode = _node_bits()
        batches = [mkbatch(40) for _ in range(6)]
        node, got = mknode(True, "device", backstop=False)
        sync_node, sync_got = mknode(False, "device")

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
            assert len(node._pipeline) == 0  # no identity entry re-armed
        node._drain_async_emits()
        sync_node._drain_async_emits()
        assert node.emit_sources == {"device-async-late": 2,
                                     "device-async": 1}
        assert len(got) == len(sync_got) == 3
        for a, b in zip(got, sync_got):
            assert _flat([a]) == _flat([b])

    def test_planner_builds_the_node_without_backstop(self):
        """Through the normal entry point a tumbling boundary is answered
        by the device: the planner passes prefinalize_backstop=False."""
        from ekuiper_tpu.planner.planner import RuleDef, plan_rule
        from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode
        from ekuiper_tpu.server.processors import StreamProcessor
        from ekuiper_tpu.store import kv
        from ekuiper_tpu.utils.infra import PlanError

        store = kv.get_store()
        try:
            StreamProcessor(store).exec_stmt(
                'CREATE STREAM pf_s (deviceId STRING, temp FLOAT) WITH '
                '(DATASOURCE="pf/in", TYPE="memory", FORMAT="JSON")')
        except PlanError:
            pass
        rule = RuleDef(
            id="pf_r", sql="SELECT deviceId, count(*) AS c FROM pf_s "
            "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)",
            actions=[{"nop": {}}], options={"sharedFold": False})
        topo = plan_rule(rule, store)
        fused = next(n for n in topo.ops
                     if isinstance(n, FusedWindowAggNode))
        assert fused._backstop_ok and not fused._backstop

    def test_inflight_fetch_cap(self):
        """No more than two un-landed device fetches may stack: each is a
        full components download on a serialized link (r02 post-mortem)."""
        from ekuiper_tpu.ops.prefinalize import IdentityFinalize, PendingFinalize
        from ekuiper_tpu.runtime.events import PreTrigger

        _, mkbatch, mknode = _node_bits()
        node, _ = mknode(True, "device")
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
        real = [e for e in node._pipeline
                if not isinstance(e[0], IdentityFinalize)]
        assert len(real) == 2


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
        node, _ = mknode(True, "device")
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
