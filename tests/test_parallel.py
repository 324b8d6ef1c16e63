"""Sharded group-by tests on the virtual 8-device CPU mesh."""
import numpy as np
import pytest

from ekuiper_tpu.ops.aggspec import extract_kernel_plan
from ekuiper_tpu.ops.groupby import DeviceGroupBy
from ekuiper_tpu.ops.keytable import KeyTable
from ekuiper_tpu.parallel.mesh import ensure_devices, make_mesh
from ekuiper_tpu.parallel.sharded import ShardedGroupBy
from ekuiper_tpu.sql.parser import parse_select


@pytest.fixture(scope="module")
def eight_devices():
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return jax.devices()


def _plan(sql):
    return extract_kernel_plan(parse_select(sql))


class TestShardedGroupBy:
    def test_matches_single_chip(self, eight_devices):
        sql = ("SELECT avg(v), count(*), min(v), max(v), stddev(v) "
               "FROM d WHERE v > 0.1 GROUP BY k, TUMBLINGWINDOW(ss, 10)")
        plan = _plan(sql)
        mesh = make_mesh(rows=2, keys=4)
        sgb = ShardedGroupBy(plan, mesh, capacity=64, micro_batch=128)
        plan2 = _plan(sql)
        gb = DeviceGroupBy(plan2, capacity=64, micro_batch=128)
        kt = KeyTable(64)

        rng = np.random.default_rng(1)
        keys = np.array([f"k{rng.integers(12)}" for _ in range(500)], dtype=np.object_)
        vals = rng.normal(1.0, 2.0, 500).astype(np.float32)
        slots, _ = kt.encode_column(keys)
        cols = {"v": vals}

        sstate = sgb.fold(sgb.init_state(), cols, slots)
        souts, sact = sgb.finalize(sstate, kt.n_keys)

        dstate = gb.fold(gb.init_state(), cols, slots)
        douts, dact = gb.finalize(dstate, kt.n_keys)

        np.testing.assert_allclose(sact, dact, rtol=1e-5)
        for i in range(len(plan.specs)):
            np.testing.assert_allclose(
                souts[i], douts[i], rtol=1e-3, atol=1e-3,
                err_msg=f"spec {i} ({plan.specs[i].kind})",
            )

    def test_panes_match_single_chip(self, eight_devices):
        """Hopping-window pane axis: fold into 3 panes, emit merged, expire
        the oldest — sharded must equal single-chip at every step."""
        sql = ("SELECT sum(v), avg(v), min(v), max(v) "
               "FROM d GROUP BY k, HOPPINGWINDOW(ss, 30, 10)")
        plan, plan2 = _plan(sql), _plan(sql)
        mesh = make_mesh(rows=2, keys=4)
        sgb = ShardedGroupBy(plan, mesh, capacity=32, n_panes=3, micro_batch=64)
        gb = DeviceGroupBy(plan2, capacity=32, n_panes=3, micro_batch=64)
        kt = KeyTable(32)

        rng = np.random.default_rng(7)
        sstate, dstate = sgb.init_state(), gb.init_state()
        for pane in range(3):
            n = 120
            keys = np.array([f"k{rng.integers(9)}" for _ in range(n)], dtype=np.object_)
            slots, _ = kt.encode_column(keys)
            cols = {"v": rng.normal(0, 3, n).astype(np.float32)}
            sstate = sgb.fold(sstate, cols, slots, pane_idx=pane)
            dstate = gb.fold(dstate, cols, slots, pane_idx=pane)

        # merged emit over panes {0,1,2} then over the live set {1,2}
        for panes in (None, [1, 2]):
            souts, sact = sgb.finalize(sstate, kt.n_keys, panes=panes)
            douts, dact = gb.finalize(dstate, kt.n_keys, panes=panes)
            np.testing.assert_array_equal(sact, dact)
            for i in range(len(souts)):
                np.testing.assert_allclose(souts[i], douts[i], rtol=1e-5,
                                           atol=1e-5)

        sstate = sgb.reset_pane(sstate, 0)
        dstate = gb.reset_pane(dstate, 0)
        souts, _ = sgb.finalize(sstate, kt.n_keys)
        douts, _ = gb.finalize(dstate, kt.n_keys)
        for i in range(len(souts)):
            np.testing.assert_allclose(souts[i], douts[i], rtol=1e-5, atol=1e-5)

    def test_validity_masks_match_single_chip(self, eight_devices):
        """Null-bearing int column: sharded must honor per-column validity
        masks the way the single-chip fold does (not just NaN)."""
        sql = ("SELECT count(v), sum(v), min(v), avg(v) "
               "FROM d GROUP BY k, TUMBLINGWINDOW(ss, 10)")
        plan, plan2 = _plan(sql), _plan(sql)
        mesh = make_mesh(rows=2, keys=4)
        sgb = ShardedGroupBy(plan, mesh, capacity=16, micro_batch=64)
        gb = DeviceGroupBy(plan2, capacity=16, micro_batch=64)
        kt = KeyTable(16)

        rng = np.random.default_rng(3)
        n = 200
        keys = np.array([f"k{rng.integers(5)}" for _ in range(n)], dtype=np.object_)
        slots, _ = kt.encode_column(keys)
        vals = rng.integers(0, 100, n).astype(np.int64)
        valid = rng.random(n) > 0.3  # 30% nulls
        cols = {"v": vals}

        sgb.observe_dtypes(cols)
        gb.observe_dtypes(cols)
        sstate = sgb.fold(sgb.init_state(), cols, slots, {"v": valid})
        dstate = gb.fold(gb.init_state(), cols, slots, {"v": valid})
        souts, sact = sgb.finalize(sstate, kt.n_keys)
        douts, dact = gb.finalize(dstate, kt.n_keys)
        np.testing.assert_array_equal(sact, dact)
        for i in range(len(souts)):
            np.testing.assert_allclose(souts[i], douts[i], rtol=1e-5, atol=1e-5)
        # count(v) skips nulls, act counts rows
        assert souts[0].sum() == valid.sum()
        assert sact.sum() == n

    def test_grow_preserves_partials(self, eight_devices):
        """Key overflow: grow redistributes slots across key shards and
        keeps prior partials."""
        plan = _plan("SELECT sum(v), count(*) FROM d GROUP BY k, TUMBLINGWINDOW(ss, 10)")
        mesh = make_mesh(rows=1, keys=8)
        sgb = ShardedGroupBy(plan, mesh, capacity=16, micro_batch=64)
        kt = KeyTable(16)

        k1 = np.array([f"k{i}" for i in range(12)], dtype=np.object_)
        slots, grew = kt.encode_column(k1)
        assert not grew
        state = sgb.fold(sgb.init_state(), {"v": np.ones(12, np.float32)}, slots)

        k2 = np.array([f"k{i}" for i in range(40)], dtype=np.object_)
        slots2, grew2 = kt.encode_column(k2)
        assert grew2
        state = sgb.grow(state, kt.capacity)
        assert sgb.capacity == kt.capacity
        state = sgb.fold(state, {"v": np.full(40, 2.0, np.float32)}, slots2)

        outs, act = sgb.finalize(state, kt.n_keys)
        # first 12 keys: 1 + 2 per key; rest: 2
        expect = np.where(np.arange(40) < 12, 3.0, 2.0)
        np.testing.assert_allclose(outs[0], expect)
        assert act.sum() == 52

    def test_all_devices_on_keys_axis(self, eight_devices):
        plan = _plan("SELECT sum(v) FROM d GROUP BY k, TUMBLINGWINDOW(ss, 10)")
        mesh = make_mesh(rows=1, keys=8)
        sgb = ShardedGroupBy(plan, mesh, capacity=32, micro_batch=64)
        kt = KeyTable(32)
        slots, _ = kt.encode_column(
            np.array([f"k{i % 20}" for i in range(200)], dtype=np.object_)
        )
        state = sgb.fold(sgb.init_state(), {"v": np.ones(200, np.float32)}, slots)
        outs, act = sgb.finalize(state, kt.n_keys)
        assert outs[0].sum() == 200.0
        assert act.sum() == 200.0

    def test_state_is_actually_sharded(self, eight_devices):
        plan = _plan("SELECT count(*) FROM d GROUP BY k, TUMBLINGWINDOW(ss, 10)")
        mesh = make_mesh(rows=1, keys=8)
        sgb = ShardedGroupBy(plan, mesh, capacity=64, micro_batch=64)
        state = sgb.init_state()
        # capacity axis (axis 1 of (n_panes, capacity, k)) split across 8
        assert len(state["n"].addressable_shards) == 8
        assert state["n"].addressable_shards[0].data.shape[1] == 8

    def test_mesh_validation(self, eight_devices):
        with pytest.raises(ValueError):
            make_mesh(rows=3, keys=3)
        plan = _plan("SELECT count(*) FROM d GROUP BY k, TUMBLINGWINDOW(ss, 10)")
        # odd capacity rounds up to an even shard split instead of raising
        sgb = ShardedGroupBy(plan, make_mesh(rows=1, keys=8), capacity=30)
        assert sgb.capacity == 32

    def test_ensure_devices(self, eight_devices):
        devs = ensure_devices(8)
        assert len(devs) == 8

    def test_ensure_devices_never_borrows_another_platform(
            self, eight_devices, monkeypatch):
        """More devices than the default platform has is an error — on a
        TPU host that used to hand back the host's CPU devices, i.e. a
        sharded plan quietly running off the chips."""
        import jax

        with pytest.raises(RuntimeError, match="9 devices asked"):
            ensure_devices(9)

        class Chip:
            platform = "tpu"

        asked = []

        def devices(backend=None):
            asked.append(backend)
            return [Chip()]  # one chip; the CPU backend is never consulted

        monkeypatch.setattr(jax, "devices", devices)
        with pytest.raises(RuntimeError, match="tpu"):
            ensure_devices(4)
        assert asked == [None]
        assert ensure_devices(1)[0].platform == "tpu"


class TestPlannerMeshIntegration:
    """A real rule with planOptimizeStrategy.mesh runs sharded end-to-end
    and matches the unsharded rule exactly (VERDICT r1 #1: the sharded path
    must be reachable from a rule, not just from tests)."""

    def _run_rule(self, mock_clock, rule_id, options):
        import time

        from ekuiper_tpu.io import memory as mem
        from ekuiper_tpu.planner.planner import RuleDef, plan_rule
        from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode
        from ekuiper_tpu.server.processors import StreamProcessor
        from ekuiper_tpu.store import kv

        from ekuiper_tpu.utils.infra import PlanError

        store = kv.get_store()
        try:
            StreamProcessor(store).exec_stmt(
                'CREATE STREAM sh_demo (k STRING, v FLOAT) '
                'WITH (DATASOURCE="sh/in", TYPE="memory", FORMAT="JSON")'
            )
        except PlanError:
            pass  # second rule in the same test reuses the stream
        rule = RuleDef(
            id=rule_id,
            sql=("SELECT k, avg(v) AS a, count(*) AS c, max(v) AS mx "
                 "FROM sh_demo GROUP BY k, TUMBLINGWINDOW(ss, 10)"),
            actions=[{"memory": {"topic": f"sh/out/{rule_id}"}}],
            options=options,
        )
        topo = plan_rule(rule, store)
        fused = [n for n in topo.ops if isinstance(n, FusedWindowAggNode)]
        assert len(fused) == 1
        sink = topo.sinks[0]
        topo.open()
        try:
            rng = np.random.default_rng(11)
            for i in range(50):
                mem.publish(
                    "sh/in",
                    {"v": float(np.round(rng.normal(10, 2), 3)),
                     "k": f"k{i % 7}"},
                )
            mock_clock.advance(20)  # linger flush
            topo.wait_idle()
            mock_clock.advance(10_000)  # window fires
            deadline = time.time() + 5.0
            while time.time() < deadline and not sink.results:
                time.sleep(0.01)
            results = list(sink.results)
        finally:
            topo.close()
        assert results, f"no window emit from {rule_id}"
        rows = results[0] if isinstance(results[0], list) else [results[0]]
        return sorted(rows, key=lambda m: m["k"]), fused[0]

    def test_rule_runs_sharded_and_matches(self, eight_devices, mock_clock):
        from ekuiper_tpu.io import memory as mem
        from ekuiper_tpu.parallel.sharded import ShardedGroupBy

        mem.reset()
        plain, node_plain = self._run_rule(mock_clock, "r_plain", {})
        mem.reset()
        sharded, node_sh = self._run_rule(
            mock_clock, "r_sharded",
            {"planOptimizeStrategy": {"mesh": {"rows": 2, "keys": 4}}},
        )
        mem.reset()
        assert isinstance(node_sh.gb, ShardedGroupBy)
        assert not isinstance(node_plain.gb, ShardedGroupBy)
        assert len(plain) == 7
        assert plain == sharded


class TestShardedEventTime:
    """Event-time × mesh: per-row pane vectors under shard_map
    (parallel/sharded.py _build_fold_vec) match the single-chip kernel."""

    def test_pane_vector_fold_matches_single_chip(self, eight_devices):
        sql = ("SELECT avg(v), count(*), min(v), max(v), hll(v) "
               "FROM d GROUP BY k, TUMBLINGWINDOW(ss, 10)")
        plan = _plan(sql)
        mesh = make_mesh(rows=2, keys=4)
        n_panes = 4
        sgb = ShardedGroupBy(plan, mesh, capacity=32, n_panes=n_panes,
                             micro_batch=64)
        gb = DeviceGroupBy(_plan(sql), capacity=32, n_panes=n_panes,
                           micro_batch=64)
        kt = KeyTable(32)
        rng = np.random.default_rng(5)
        n = 300
        keys = np.array([f"k{rng.integers(9)}" for _ in range(n)],
                        dtype=np.object_)
        vals = rng.normal(1.0, 2.0, n).astype(np.float32)
        panes = rng.integers(0, n_panes, n).astype(np.uint8)
        slots, _ = kt.encode_column(keys)
        cols = {"v": vals}

        sstate = sgb.fold(sgb.init_state(), dict(cols), slots,
                          pane_idx=panes)
        dstate = gb.fold(gb.init_state(), dict(cols), slots, pane_idx=panes)
        # also a scalar-pane fold on top (the single-bucket fast path)
        sstate = sgb.fold(sstate, dict(cols), slots, pane_idx=1)
        dstate = gb.fold(dstate, dict(cols), slots, pane_idx=1)

        for subset in ([0, 1], [2], None, [1, 3]):
            souts, sact = sgb.finalize(sstate, kt.n_keys, panes=subset)
            douts, dact = gb.finalize(dstate, kt.n_keys, panes=subset)
            np.testing.assert_allclose(sact, dact, rtol=1e-5)
            for i in range(len(plan.specs)):
                np.testing.assert_allclose(
                    np.asarray(souts[i], dtype=np.float64),
                    np.asarray(douts[i], dtype=np.float64),
                    rtol=1e-4, atol=1e-4)

    def test_event_time_mesh_plans_to_device(self, eight_devices):
        from ekuiper_tpu.planner.planner import device_path_eligible
        from ekuiper_tpu.utils.config import RuleOptionConfig

        stmt = parse_select(
            "SELECT k, avg(v) AS a FROM d GROUP BY k, TUMBLINGWINDOW(ss, 10)")
        opts = RuleOptionConfig(
            is_event_time=True,
            plan_optimize_strategy={"mesh": {"rows": 2, "keys": 4}})
        assert device_path_eligible(stmt, opts) is not None

    def test_fused_node_event_time_on_mesh(self, eight_devices):
        """End-to-end: FusedWindowAggNode with a mesh + event time, batches
        spanning several buckets, watermark-driven emission parity against
        the single-chip node."""
        from ekuiper_tpu.data.batch import ColumnBatch
        from ekuiper_tpu.ops.emit import build_direct_emit
        from ekuiper_tpu.runtime.events import Watermark
        from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode

        sql = ("SELECT k, avg(v) AS a, count(*) AS c FROM d "
               "GROUP BY k, TUMBLINGWINDOW(ss, 2)")
        stmt = parse_select(sql)

        def make(mesh):
            plan = _plan(sql)
            node = FusedWindowAggNode(
                "ev", stmt.window, plan,
                dims=[d.expr for d in stmt.dimensions],
                capacity=32, micro_batch=64,
                direct_emit=build_direct_emit(stmt, plan, ["k"]),
                mesh=mesh, is_event_time=True, late_tolerance_ms=500)
            node.state = node.gb.init_state()
            got = []
            node.broadcast = lambda item: got.append(item)
            return node, got

        mnode, mgot = make(make_mesh(rows=2, keys=4))
        snode, sgot = make(None)
        rng = np.random.default_rng(9)
        t = 10_000
        for _ in range(6):
            n = 120
            ts = t + np.sort(rng.integers(0, 3_000, n)).astype(np.int64)
            b = ColumnBatch(
                n=n,
                columns={"k": np.array(
                    [f"k{i}" for i in rng.integers(0, 6, n)],
                    dtype=np.object_),
                    "v": rng.normal(5, 2, n).astype(np.float32)},
                timestamps=ts, emitter="d")
            for node in (mnode, snode):
                node.process(b)
            t += 2_500
            for node in (mnode, snode):
                node.on_watermark(Watermark(ts=t - 1_000))

        def collect(got):
            wins = []
            for item in got:
                if isinstance(item, Watermark):
                    continue
                msgs = item if isinstance(item, list) else [item]
                if hasattr(item, "to_messages"):
                    msgs = item.to_messages()
                wins.append(sorted(
                    (m["k"], m["c"], round(m["a"], 3)) for m in msgs))
            return wins

        assert collect(mgot) == collect(sgot)
        assert len(collect(mgot)) >= 4


class TestShardedSliding:
    """Sliding windows on the mesh: pane-vector folds + scratch refold +
    dynamic-mask finalize all run sharded; output parity with ground truth
    computed from the raw rows (same oracle as test_sliding_device)."""

    def test_eligibility_accepts_mesh(self, eight_devices):
        from ekuiper_tpu.planner.planner import device_path_eligible
        from ekuiper_tpu.utils.config import RuleOptionConfig

        stmt = parse_select(
            "SELECT k, count(*) AS c FROM s GROUP BY k, "
            "SLIDINGWINDOW(ss, 2) OVER (WHEN v > 90)")
        assert device_path_eligible(stmt, RuleOptionConfig(
            plan_optimize_strategy={"mesh": {"rows": 2, "keys": 4}})
        ) is not None
        # event-time sliding stays host-side, mesh or not
        assert device_path_eligible(stmt, RuleOptionConfig(
            is_event_time=True,
            plan_optimize_strategy={"mesh": {"rows": 2, "keys": 4}})) is None

    def test_sharded_matches_ground_truth(self, eight_devices):
        from test_sliding_device import (SQL, mkbatches, per_trigger,
                                         run_host_expected)
        from ekuiper_tpu.ops.emit import build_direct_emit
        from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode
        from ekuiper_tpu.sql.parser import parse_select as _ps

        stmt = _ps(SQL)
        plan = _plan(SQL)
        mesh = make_mesh(rows=2, keys=4)
        node = FusedWindowAggNode(
            "ssl", stmt.window, plan, dims=[d.expr for d in stmt.dimensions],
            capacity=64, micro_batch=128, mesh=mesh,
            direct_emit=build_direct_emit(stmt, plan, ["deviceId"]))
        assert isinstance(node.gb, ShardedGroupBy)
        node.state = node.gb.init_state()
        got = []
        node.broadcast = lambda item: got.append(item)
        rng = np.random.default_rng(7)
        batches = mkbatches(rng)
        for b in batches:
            node.process(b)
        node._drain_async_emits()
        expected = run_host_expected(SQL, batches)
        triggers = per_trigger(got)
        assert len(triggers) == len(expected) >= 1
        for trig, (t, per) in zip(triggers, expected):
            assert set(trig) == set(per)
            for k, vals in per.items():
                m = trig[k]
                assert m["c"] == len(vals)
                np.testing.assert_allclose(m["a"], np.mean(vals), rtol=1e-4)
                np.testing.assert_allclose(m["mn"], min(vals), rtol=1e-6)
                np.testing.assert_allclose(m["mx"], max(vals), rtol=1e-6)


class TestShardedStateAndSession:
    """STATE windows and event-time SESSION windows on the mesh: the toggle
    scan / session split are host-side; every fold and the sync finalize
    run through the sharded kernel — output must match single-chip."""

    def _state_node(self, mesh):
        from test_state_device import SQL as SSQL
        from ekuiper_tpu.ops.emit import build_direct_emit
        from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode

        stmt = parse_select(SSQL)
        plan = _plan(SSQL)
        node = FusedWindowAggNode(
            "sst", stmt.window, plan, dims=[d.expr for d in stmt.dimensions],
            capacity=64, micro_batch=128, mesh=mesh,
            direct_emit=build_direct_emit(stmt, plan, ["deviceId"]))
        node.state = node.gb.init_state()
        got = []
        node.broadcast = lambda item: got.append(item)
        return node, got

    def test_state_window_sharded_matches_single_chip(self, eight_devices):
        from test_state_device import batch, msgs_of

        mesh = make_mesh(rows=2, keys=4)
        sh, sh_got = self._state_node(mesh)
        assert isinstance(sh.gb, ShardedGroupBy)
        single, si_got = self._state_node(None)
        feeds = [
            batch(["x", "a", "a", "b", "a", "x", "b", "b"],
                  [9.0, 1.0, 2.0, 3.0, 4.0, 9.0, 10.0, 20.0],
                  [5, 1, 5, 5, 0, 5, 1, 0]),
            batch(["a", "b", "a"], [7.0, 8.0, 9.0], [1, 5, 0]),
        ]
        for b in feeds:
            sh.process(b)
            single.process(b)
        assert msgs_of(sh_got) == msgs_of(si_got)
        assert len(msgs_of(sh_got)) >= 2

    def test_event_session_sharded_matches_single_chip(self, eight_devices):
        from ekuiper_tpu.data.batch import ColumnBatch
        from ekuiper_tpu.ops.emit import build_direct_emit
        from ekuiper_tpu.runtime.events import Watermark
        from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode

        sql = ("SELECT k, count(*) AS c, avg(v) AS a FROM s "
               "GROUP BY k, SESSIONWINDOW(ss, 10, 2)")
        stmt = parse_select(sql)

        def mk(mesh):
            plan = _plan(sql)
            node = FusedWindowAggNode(
                "evs", stmt.window, plan,
                dims=[d.expr for d in stmt.dimensions],
                capacity=32, micro_batch=64, mesh=mesh, is_event_time=True,
                direct_emit=build_direct_emit(stmt, plan, ["k"]))
            node.state = node.gb.init_state()
            got = []
            node.broadcast = lambda item: got.append(item)
            return node, got

        def feed(node):
            # two sessions per key, split by a >2s gap; watermark closes
            # the first
            ts = np.array([1000, 1200, 1500, 4000, 4100], dtype=np.int64)
            node.process(ColumnBatch(
                n=5,
                columns={"k": np.array(["a", "a", "b", "a", "b"],
                                       dtype=np.object_),
                         "v": np.asarray([1, 2, 3, 4, 5], np.float32)},
                timestamps=ts, emitter="s"))
            node.on_watermark(Watermark(ts=10_000))

        sh, sh_got = mk(make_mesh(rows=2, keys=4))
        assert isinstance(sh.gb, ShardedGroupBy)
        si, si_got = mk(None)
        feed(sh)
        feed(si)

        def norm(got):
            out = []
            for item in got:
                if isinstance(item, list):
                    out.append(sorted(
                        (m["k"], m["c"], round(m["a"], 4)) for m in item))
            return out

        assert norm(sh_got) == norm(si_got)
        assert norm(sh_got), "no session emitted"


def test_event_time_mesh_state_parity(eight_devices, mock_clock):
    """Both newly-allowed flags TOGETHER: event-time STATE window on a
    mesh, parity with the host path (review finding r5 coverage gap)."""
    import time

    import ekuiper_tpu.io.memory as mem
    from ekuiper_tpu.planner.planner import RuleDef, plan_rule
    from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode
    from ekuiper_tpu.server.processors import StreamProcessor
    from ekuiper_tpu.store import kv

    mem.reset()
    store = kv.get_store()
    StreamProcessor(store).exec_stmt(
        'CREATE STREAM ems (deviceId STRING, t FLOAT, ts BIGINT) '
        'WITH (DATASOURCE="in/ems", TYPE="memory", FORMAT="JSON", '
        'TIMESTAMP="ts")')
    rows = [
        {"deviceId": "a", "t": 30.0, "ts": 1000},  # begin
        {"deviceId": "b", "t": 12.0, "ts": 2000},
        {"deviceId": "a", "t": 5.0, "ts": 3000},   # emit
        {"deviceId": "b", "t": 40.0, "ts": 4000},  # begin
        {"deviceId": "a", "t": 2.0, "ts": 5000},   # emit
    ]

    def run(rule_id, options):
        topo = plan_rule(RuleDef(
            id=rule_id,
            sql=("SELECT deviceId, count(*) AS c, avg(t) AS a FROM ems "
                 "GROUP BY deviceId, STATEWINDOW(t > 25, t < 8)"),
            actions=[{"memory": {"topic": f"o/{rule_id}"}}],
            options=options), store)
        got = []
        mem.subscribe(f"o/{rule_id}", lambda tp, p: got.append(p))
        topo.open()
        try:
            for r in rows:
                mem.publish("in/ems", r)
            mock_clock.advance(20)
            assert topo.wait_idle(30)
            deadline = time.time() + 10
            while time.time() < deadline and len(got) < 2:
                time.sleep(0.02)
        finally:
            topo.close()
        out = []
        for p in got:
            out.extend(p if isinstance(p, list) else [p])
        return sorted((m["deviceId"], m["c"], round(m["a"], 4)) for m in out), topo

    fused, ft = run("emsd", {
        "isEventTime": True, "lateTolerance": 500,
        "planOptimizeStrategy": {"mesh": {"rows": 2, "keys": 4}}})
    assert any(isinstance(n, FusedWindowAggNode) for n in ft.ops)
    host, ht = run("emsh", {
        "isEventTime": True, "lateTolerance": 500,
        "use_device_kernel": False})
    assert not any(isinstance(n, FusedWindowAggNode) for n in ht.ops)
    assert fused and fused == host
