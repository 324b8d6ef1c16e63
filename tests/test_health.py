"""Health plane (observability/health.py): SLO burn-rate windows, the
verdict FSM with hysteresis, bottleneck attribution, watermark-lag
tracking (including shared-fold members), queue-depth high-water marks,
and the on-demand profile capture — all mock-clock, CPU, tier-1."""
import json
import os
import queue

import numpy as np
import pytest

from ekuiper_tpu.observability import health
from ekuiper_tpu.observability.health import (
    BREACHING, DEGRADED, HEALTHY, HealthEvaluator, parse_slo)
from ekuiper_tpu.observability.histogram import LatencyHistogram
from ekuiper_tpu.runtime.events import recorder
from ekuiper_tpu.utils.metrics import StatManager
import ekuiper_tpu.io.memory as mem


# --------------------------------------------------------------- fixtures
class FakeNode:
    """Minimal node shape the evaluator samples: stats + inq + op_type."""

    def __init__(self, name, op_type="op", rule_id="r1"):
        self.name = name
        self.op_type = op_type
        self.stats = StatManager(op_type, name)
        self.stats.rule_id = rule_id
        self.inq = queue.Queue()


class FakeTopo:
    def __init__(self, nodes):
        self.e2e_hist = LatencyHistogram()
        self.boundary_hists = {}
        self._nodes = nodes

    def all_nodes(self):
        return self._nodes

    def live_shared(self):
        return []


def _evaluator(topo, options=None, **kw):
    kw.setdefault("up_ticks", 2)
    kw.setdefault("down_ticks", 3)
    return HealthEvaluator(lambda: [("r1", topo, options or {})], **kw)


# -------------------------------------------------------------- SLO config
class TestParseSlo:
    def test_defaults(self):
        slo = parse_slo(None)
        assert slo["latency_p99_ms"] == 1000
        assert slo["target"] == 0.99
        assert slo["max_drop_ratio"] == 0.01
        assert slo["max_watermark_lag_ms"] is None

    def test_aliases_camel_and_snake(self):
        slo = parse_slo({"slo": {"latencyP99Ms": 50, "target": 0.999,
                                 "maxDropRatio": 0.05,
                                 "max_watermark_lag_ms": 2000}})
        assert slo["latency_p99_ms"] == 50
        assert slo["target"] == 0.999
        assert slo["max_drop_ratio"] == 0.05
        assert slo["max_watermark_lag_ms"] == 2000

    def test_malformed_values_keep_defaults(self):
        slo = parse_slo({"slo": {"latencyP99Ms": "soon", "target": 7,
                                 "maxDropRatio": -1, "bogus": 1}})
        assert slo == parse_slo(None)
        assert parse_slo({"slo": "not-a-dict"}) == parse_slo(None)


# ----------------------------------------------------- histogram windows
class TestBucketCountDeltas:
    def test_roundtrip_and_delta(self):
        src = LatencyHistogram()
        for v in (3, 70, 900, 15_000):
            src.record(v)
        before = src.bucket_counts()
        src.record(70)
        delta = [c - p for c, p in zip(src.bucket_counts(), before)]
        assert sum(delta) == 1
        win = LatencyHistogram()
        win.record_bucket_counts(src.bucket_counts())
        assert win.count == 5
        # bucket-resolution reconstruction: same ≤6.25% error contract
        assert win.percentile(50) == pytest.approx(src.percentile(50))
        assert win.max >= 15_000
        win.record_bucket_counts([0] * len(before))  # no-op delta
        assert win.count == 5


# ------------------------------------------------------- burn + verdict FSM
class TestBurnRateFSM:
    def _bad(self, topo, n=100):
        for _ in range(n):
            topo.e2e_hist.record(5_000)  # default bound is 1000ms

    def _good(self, topo, n=10_000):
        for _ in range(n):
            topo.e2e_hist.record(2)

    def test_healthy_under_good_latency(self):
        topo = FakeTopo([FakeNode("src", "source")])
        ev = _evaluator(topo)
        self._good(topo, 100)
        v = ev.tick()["r1"]
        assert v["state"] == HEALTHY
        assert v["burn_rate"]["fast"] < 1.0
        assert ev.peak_burn("r1") < 1.0

    def test_escalation_needs_up_ticks(self):
        topo = FakeTopo([FakeNode("src", "source")])
        ev = _evaluator(topo)
        self._bad(topo)
        v = ev.tick()["r1"]
        # both windows burn ≥ breach threshold, but hysteresis holds one
        assert v["burn_rate"]["fast"] >= ev.breach_burn
        assert v["state"] == HEALTHY
        self._bad(topo)
        v = ev.tick()["r1"]
        assert v["state"] == BREACHING
        assert v["reasons"]
        assert ev.peak_burn("r1") >= ev.breach_burn
        evs = recorder().events(kind="rule_health", rule="r1")
        assert len(evs) == 1
        assert evs[0]["state"] == BREACHING
        assert evs[0]["previous"] == HEALTHY
        assert evs[0]["severity"] == "error"

    def test_recovery_steps_down_one_level_per_down_ticks(self):
        topo = FakeTopo([FakeNode("src", "source")])
        ev = _evaluator(topo)
        self._bad(topo)
        ev.tick()
        self._bad(topo)
        assert ev.tick()["r1"]["state"] == BREACHING
        states = []
        for _ in range(8):  # good traffic: windows flush the violations
            self._good(topo)
            states.append(ev.tick()["r1"]["state"])
        # one level per down_ticks window, never a two-level jump
        assert states[-1] == HEALTHY
        assert DEGRADED in states
        assert states.index(DEGRADED) < states.index(HEALTHY)
        trans = [(e["previous"], e["state"])
                 for e in recorder().events(kind="rule_health", rule="r1")]
        assert trans == [(HEALTHY, BREACHING), (BREACHING, DEGRADED),
                         (DEGRADED, HEALTHY)]

    def test_drop_burn_escalates(self):
        src = FakeNode("src", "source")
        topo = FakeTopo([src])
        ev = _evaluator(topo)
        src.stats.inc_in(1000)
        src.stats.inc_dropped("buffer_full", n=500)  # ratio 0.5 ≫ 0.01
        ev.tick()
        v = ev.tick()["r1"]
        assert v["state"] == BREACHING
        assert v["burn_rate"]["drop_fast"] >= ev.breach_burn
        assert any("drop burn" in r for r in v["reasons"])

    def test_single_spike_cannot_flap(self):
        """Multi-window shape: one bad tick decays out of the fast window
        before the slow window alone can escalate the verdict."""
        topo = FakeTopo([FakeNode("src", "source")])
        ev = _evaluator(topo)
        self._good(topo, 1000)
        ev.tick()
        self._bad(topo, 20)  # spike: 20 bad among the decayed good
        ev.tick()
        for _ in range(6):
            self._good(topo)
            assert ev.tick()["r1"]["state"] == HEALTHY

    def test_rules_fn_errors_are_contained(self):
        ev = HealthEvaluator(lambda: (_ for _ in ()).throw(RuntimeError()))
        assert ev.tick() == {}  # never raises
        ev2 = HealthEvaluator(lambda: [("r1", None, {}), "garbage"])
        assert ev2.tick() == {}

    def test_departed_rule_track_is_dropped(self):
        topo = FakeTopo([FakeNode("src", "source")])
        rules = [("r1", topo, {})]
        ev = HealthEvaluator(lambda: list(rules))
        ev.tick()
        assert "r1" in ev.verdicts()
        rules.clear()
        ev.tick()
        assert ev.verdicts() == {}


# ------------------------------------------------------------- bottleneck
class TestBottleneckAttribution:
    def test_dominant_stage_and_share(self):
        src = FakeNode("src", "source")
        fold = FakeNode("fused", "op")
        sink = FakeNode("sink", "sink")
        topo = FakeTopo([src, fold, sink])
        ev = _evaluator(topo)
        src.stats.observe_stage("decode", 10_000)
        fold.stats.observe_stage("upload", 5_000)
        fold.stats.observe_stage("fold", 85_000)
        v = ev.tick()["r1"]
        bn = v["bottleneck"]
        assert bn["stage"] == "fold"
        assert bn["node"] == "fused"
        assert bn["share"] == pytest.approx(0.85)
        assert bn["stage_us"]["decode"] == 10_000
        assert v["state"] == HEALTHY  # attribution alone never degrades

    def test_attribution_is_per_tick_delta(self):
        src = FakeNode("src", "source")
        topo = FakeTopo([src])
        ev = _evaluator(topo)
        src.stats.observe_stage("decode", 90_000)
        assert ev.tick()["r1"]["bottleneck"]["stage"] == "decode"
        # next tick: only NEW time counts — fold now dominates the delta
        src.stats.observe_stage("fold", 1_000)
        assert ev.tick()["r1"]["bottleneck"]["stage"] == "fold"

    def test_unstaged_busy_time_classified_by_node_kind(self):
        sink = FakeNode("sink", "sink")
        sink.stats.process_time_us_total = 50_000
        sink.stats.unstaged_us_total = 50_000
        topo = FakeTopo([FakeNode("src", "source"), sink])
        ev = _evaluator(topo)
        assert ev.tick()["r1"]["bottleneck"]["stage"] == "sink"

    def test_another_threads_stage_leaves_the_workers_remainder(self):
        """The remainder is the worker's own ledger (PR 40). It was busy
        less the node's stage rows, which the `<node>-emit` thread writes
        too: 40 ms of `emit` there took 30 ms of unstaged dispatch on the
        worker below zero, and out of the attribution."""
        import threading
        import time

        node = FakeNode("fused", "op")
        ev = _evaluator(FakeTopo([node]))

        def emit_thread():
            with node.stats.stage("emit"):
                time.sleep(0.04)

        other = threading.Thread(target=emit_thread)
        other.start()
        node.stats.process_begin()
        time.sleep(0.03)  # the worker's dispatch, in no stage
        node.stats.process_end()
        other.join()
        sample = node.stats.health_sample()
        busy = node.stats.snapshot()["process_time_us_total"]
        assert sample["stages"]["emit"] >= 40_000 > busy
        assert sample["unstaged_us"] == busy >= 30_000
        bn = ev.tick()["r1"]["bottleneck"]
        assert bn["stage_us"]["emit_combine"] >= 40_000
        assert bn["stage_us"]["other"] == sample["unstaged_us"]

    def test_backpressure_direction_upstream_of_bottleneck(self):
        src = FakeNode("src", "source")
        fold = FakeNode("fused", "op")
        sink = FakeNode("sink", "sink")
        topo = FakeTopo([src, fold, sink])
        ev = _evaluator(topo)
        fold.stats.observe_stage("fold", 80_000)
        src.stats.note_queue_depth(900)  # queue grows UPSTREAM of fold
        bp = ev.tick()["r1"]["bottleneck"]["backpressure"]
        assert bp["forming"] == "upstream"
        assert bp["upstream"]["peak"] == 900
        assert bp["downstream"]["peak"] == 0


# ------------------------------------------------------- queue-depth peaks
class TestQueueDepthPeaks:
    def test_independent_read_and_reset_marks(self):
        sm = StatManager("op", "n")
        sm.note_queue_depth(3)
        sm.note_queue_depth(9)
        sm.note_queue_depth(5)
        # two consumers, two marks: a scrape must not blind the tick
        assert sm.take_queue_peak_scrape() == 9
        assert sm.take_queue_peak_tick() == 9
        assert sm.take_queue_peak_scrape() == 0
        sm.note_queue_depth(2)
        assert sm.take_queue_peak_tick() == 2

    def test_node_put_notes_enqueue_depth(self):
        from ekuiper_tpu.runtime.node import Node

        n = Node("qp", buffer_length=8)
        for item in (1, 2, 3):
            n.put(item)
        # never dispatched: the high-water mark saw the full backlog
        assert n.stats.take_queue_peak_tick() == 3

    def test_scrape_reports_peak_not_just_live(self):
        from ekuiper_tpu.observability.prometheus import render

        node = FakeNode("spiky", "op", rule_id="rq")
        node.stats.inc_in(1)
        node.stats.note_queue_depth(77)  # spike that drained: inq empty

        class Reg:
            @staticmethod
            def list():
                return [{"id": "rq"}]

            @staticmethod
            def state(_rid):
                class RS:
                    topo = FakeTopo([node])
                return RS()

        line = [ln for ln in render(Reg()).splitlines()
                if ln.startswith('kuiper_node_queue_depth{rule="rq"')][0]
        assert line.endswith(" 77")


# ---------------------------------------------- watermark lag (e2e, REST)
@pytest.fixture
def api_env(mock_clock):
    from ekuiper_tpu.server.processors import StreamProcessor
    from ekuiper_tpu.server.rest import RestApi
    from ekuiper_tpu.store import kv

    store = kv.get_store()
    StreamProcessor(store).exec_stmt(
        'CREATE STREAM hd (deviceId STRING, temperature FLOAT, ts BIGINT) '
        'WITH (DATASOURCE="hp/d", TYPE="memory", FORMAT="JSON", '
        'TIMESTAMP="ts")')
    api = RestApi(store)
    # deterministic ticks: the test drives the evaluator by hand
    api.health_evaluator.stop()
    yield api, mock_clock
    api.rules.stop_all()


def _start_rule(api, rid, options):
    import time

    code, _out = api.dispatch("POST", "/rules", {
        "id": rid,
        "sql": "SELECT deviceId, count(*) AS c FROM hd "
               "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)",
        "actions": [{"memory": {"topic": f"hp/{rid}"}}],
        "options": options}, {})
    assert code in (200, 201)
    deadline = time.time() + 10.0
    while time.time() < deadline:
        rs = api.rules.state(rid)
        if rs is not None and rs.topo is not None:
            return rs.topo
        time.sleep(0.05)
    raise AssertionError(f"rule {rid} topo never came up")


def _publish(topo, mock_clock, ts):
    import time

    mem.publish("hp/d", {"deviceId": "a", "temperature": 1.0, "ts": ts})
    mock_clock.advance(20)  # linger flush
    assert topo.wait_idle(10)
    time.sleep(0.05)


WM_OPTIONS = {"isEventTime": True, "lateTolerance": 0,
              "slo": {"latencyP99Ms": 600_000,
                      "maxWatermarkLagMs": 2_000}}


class TestWatermarkLag:
    def test_lag_rises_breaches_and_recovers(self, api_env):
        api, clock = api_env
        topo = _start_rule(api, "hw1", WM_OPTIONS)
        ev = api.health_evaluator
        clock.advance(1_000)  # ts=0 would read as "no watermark yet"
        _publish(topo, clock, ts=clock.now_ms())
        v = ev.tick()["hw1"]
        assert v["watermark"]["event_time"] is True
        lag0 = v["watermark"]["lag_ms"]
        assert lag0 is not None and lag0 <= 100
        assert v["state"] == HEALTHY

        # event time stalls while the engine clock advances: lag rises
        clock.advance(3_000)
        v = ev.tick()["hw1"]
        assert v["watermark"]["lag_ms"] > lag0
        assert v["watermark"]["lag_ms"] > 2_000  # over bound → degrading
        assert v["state"] == HEALTHY  # hysteresis: first tick over
        v = ev.tick()["hw1"]
        assert v["state"] == DEGRADED
        assert any("watermark lag" in r for r in v["reasons"])

        # metrics family carries the rising lag
        from ekuiper_tpu.observability.prometheus import render
        text = render(api.rules)
        line = [ln for ln in text.splitlines()
                if ln.startswith('kuiper_watermark_lag_ms{rule="hw1"}')][0]
        assert float(line.split()[-1]) > 2_000
        assert 'kuiper_rule_health{rule="hw1"} 1' in text

        # 3x the bound: degraded → breaching (again two ticks)
        clock.advance(4_000)
        ev.tick()
        v = ev.tick()["hw1"]
        assert v["state"] == BREACHING

        # fresh events advance the watermark: lag collapses, then the
        # FSM walks back one level per down_ticks quiet ticks
        _publish(topo, clock, ts=clock.now_ms())
        states = []
        for _ in range(7):
            _publish(topo, clock, ts=clock.now_ms())
            states.append(ev.tick()["hw1"]["state"])
        assert states[-1] == HEALTHY
        assert DEGRADED in states
        trans = [(e["previous"], e["state"])
                 for e in recorder().events(kind="rule_health",
                                            rule="hw1")]
        assert trans == [(HEALTHY, DEGRADED), (DEGRADED, BREACHING),
                         (BREACHING, DEGRADED), (DEGRADED, HEALTHY)]

    def test_rest_endpoints_serve_verdicts(self, api_env):
        api, clock = api_env
        topo = _start_rule(api, "hw2", WM_OPTIONS)
        _publish(topo, clock, ts=clock.now_ms())
        code, v = api.dispatch("GET", "/rules/hw2/health", None, {})
        assert code == 200
        assert v["state"] in (HEALTHY, DEGRADED, BREACHING)
        assert "burn_rate" in v and "bottleneck" in v and "watermark" in v
        assert v["slo"]["max_watermark_lag_ms"] == 2_000
        code, d = api.dispatch("GET", "/diagnostics/health", None, {})
        assert code == 200
        assert "hw2" in d["rules"]
        assert d["evaluator"]["ticks"] >= 1
        assert "trend_bytes_per_min" in d["hbm"]
        json.dumps(d)  # REST serves it verbatim
        code, _ = api.dispatch("GET", "/rules/nope/health", None, {})
        assert code == 400
        # status JSON rides the last verdict without forcing a tick
        code, st = api.dispatch("GET", "/rules/hw2/status", None, {})
        assert code == 200
        assert st["health"]["state"] == v["state"]

    def test_shared_fold_members_report_lag_per_rule(self):
        from ekuiper_tpu.ops.aggspec import extract_kernel_plan
        from ekuiper_tpu.ops.emit import build_direct_emit
        from ekuiper_tpu.ops.panestore import union_plan
        from ekuiper_tpu.runtime.nodes_sharedfold import (
            MemberSpec, SharedEmitNode, SharedFoldNode)
        from ekuiper_tpu.sql.parser import parse_select

        sqls = ["SELECT deviceId, count(*) AS c FROM demo "
                "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)",
                "SELECT deviceId, count(*) AS c FROM demo "
                "GROUP BY deviceId, TUMBLINGWINDOW(ss, 20)"]
        stmts = [parse_select(s) for s in sqls]
        plans = [extract_kernel_plan(s) for s in stmts]
        union, _ = union_plan(plans)
        store = SharedFoldNode("k", "sf", union, 10_000, 4,
                               subtopo_ref=None, capacity=64,
                               micro_batch=128, is_event_time=True)
        for i, (stmt, plan) in enumerate(zip(stmts, plans)):
            w = stmt.window
            spec = MemberSpec(
                rule_id=f"m{i}", length_ms=w.length_ms(),
                interval_ms=w.interval_ms() or w.length_ms(), plan=plan,
                direct_emit=build_direct_emit(stmt, plan, ["deviceId"]),
                dims=["deviceId"], emit_columnar=True)
            assert store.attach_rule(spec, SharedEmitNode(f"m{i}_e"), None)
        # members advanced to different windows on the SAME store
        store._members["m0"].last_end_ms = 10_000
        store._members["m1"].last_end_ms = 20_000
        store._wm_node.max_ts = 25_000
        nodes = store.pipeline_nodes()
        p0 = HealthEvaluator._watermark_probe("m0", nodes, now=30_000)
        p1 = HealthEvaluator._watermark_probe("m1", nodes, now=30_000)
        assert p0["event_time"] and p1["event_time"]
        assert p0["lag_ms"] == p1["lag_ms"] == 5_000  # store watermark
        assert p0["emit_cursor_ms"] == 10_000  # but cursors are PER RULE
        assert p1["emit_cursor_ms"] == 20_000
        assert "pane_occupancy" in p0


# --------------------------------------------------- events: severity/since
class TestEventSeverityAndSince:
    def test_severity_defaults_and_clamps(self):
        recorder().record("plain")
        recorder().record("graded", severity="error")
        recorder().record("bogus", severity="catastrophic")
        sevs = {e["kind"]: e["severity"] for e in recorder().events()}
        assert sevs == {"plain": "info", "graded": "error",
                        "bogus": "info"}

    def test_since_tails_incrementally(self):
        from ekuiper_tpu.runtime.events import FlightRecorder

        fr = FlightRecorder(capacity=16)
        for i in range(5):
            fr.record("k", i=i)
        d = fr.diagnostics(limit=2)
        assert [e["i"] for e in d["events"]] == [3, 4]
        assert d["last_seq"] == 5
        tail = fr.diagnostics(since=d["last_seq"])
        assert tail["events"] == []
        assert tail["last_seq"] == 5  # caller's cursor echoed back
        fr.record("k", i=5)
        tail = fr.diagnostics(since=d["last_seq"])
        assert [e["i"] for e in tail["events"]] == [5]
        assert tail["last_seq"] == 6

    def test_rest_since_param(self, api_env):
        api, _clock = api_env
        recorder().record("a")
        recorder().record("b")
        # the global recorder's seq is monotonic across tests: tail from
        # the seq the ring itself reports for "a"
        seq_a = recorder().events(kind="a")[-1]["seq"]
        code, out = api.dispatch("GET", "/diagnostics/events", None,
                                 {"since": str(seq_a)})
        assert code == 200
        assert [e["kind"] for e in out["events"]] == ["b"]
        assert out["last_seq"] == seq_a + 1
        code, _ = api.dispatch("GET", "/diagnostics/events", None,
                               {"since": "bogus"})
        assert code == 400


# ------------------------------------------------------- profile capture
class TestProfileCapture:
    def test_bundle_dir_and_dump(self, tmp_path):
        out = health.capture_profile(duration_ms=60,
                                     out_dir=str(tmp_path / "p1"))
        assert os.path.isdir(out["dir"])
        assert out["duration_ms"] == 60
        assert "devwatch_dump.json" in out["files"]
        with open(os.path.join(out["dir"], "devwatch_dump.json")) as f:
            dump = json.load(f)
        assert "xla" in dump and "memory" in dump

    def test_duration_is_clamped(self, tmp_path):
        out = health.capture_profile(duration_ms=1,
                                     out_dir=str(tmp_path / "p2"))
        assert out["duration_ms"] == 50  # floor: a 1ms trace is noise
        assert health.PROFILE_MAX_MS == 30_000  # REST can never block long

    def test_concurrent_capture_rejected(self, tmp_path):
        assert health._profile_lock.acquire(blocking=False)
        try:
            with pytest.raises(RuntimeError):
                health.capture_profile(duration_ms=60,
                                       out_dir=str(tmp_path / "p3"))
        finally:
            health._profile_lock.release()

    def test_rest_endpoint(self, api_env):
        from ekuiper_tpu.utils.config import get_config

        api, _clock = api_env
        # over HTTP the capture must land under the store path
        out_dir = os.path.join(get_config().store.path, "profiles",
                               "test_p4")
        code, out = api.dispatch(
            "POST", "/diagnostics/profile",
            {"duration_ms": 60, "out_dir": out_dir}, {})
        assert code == 200
        assert os.path.isdir(out["dir"])
        code, _ = api.dispatch("POST", "/diagnostics/profile",
                               {"duration_ms": "soon"}, {})
        assert code == 400

    def test_rest_rejects_out_dir_escape(self, api_env, tmp_path):
        """The unauthenticated REST boundary must not allow directory
        creation / file writes outside the store path."""
        api, _clock = api_env
        for bad in (str(tmp_path / "evil"), "/etc/cron.d",
                    "data/../outside"):
            code, _ = api.dispatch(
                "POST", "/diagnostics/profile",
                {"duration_ms": 60, "out_dir": bad}, {})
            assert code == 400, bad


# ------------------------------------------------------------ evaluator
class TestEvaluatorLifecycle:
    def test_periodic_ticks_on_engine_clock(self, mock_clock):
        topo = FakeTopo([FakeNode("src", "source")])
        ev = health.install(lambda: [("r1", topo, {})], interval_ms=1000)
        try:
            assert ev.ticks == 0
            mock_clock.advance(1000)
            assert ev.ticks == 1
            mock_clock.advance(3000)  # re-arms after each fire
            assert ev.ticks >= 2
            assert "r1" in ev.verdicts()
        finally:
            health.reset()
        mock_clock.advance(1000)
        assert ev.ticks <= 4  # stopped: no further fires

    def test_rule_verdict_never_forces_tick(self):
        topo = FakeTopo([FakeNode("src", "source")])
        ev = health.install(lambda: [("r1", topo, {})], start=False)
        assert health.rule_verdict("r1") is None
        assert ev.ticks == 0
        ev.tick()
        assert health.rule_verdict("r1")["state"] == HEALTHY

    def test_tick_cost_is_recorded(self):
        topo = FakeTopo([FakeNode("src", "source")])
        ev = _evaluator(topo)
        ev.tick()
        assert ev.last_tick_us > 0  # bench reads this for the <1% check


# ------------------------------------------------- review-hardening fixes
class TestReviewHardening:
    def test_transient_rules_fn_failure_keeps_tracks(self):
        """One registry hiccup must not reset FSM state or re-seed the
        full cumulative e2e history as a single tick's delta."""
        topo = FakeTopo([FakeNode("src", "source")])
        calls = {"n": 0}

        def rules_fn():
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("registry hiccup")
            return [("r1", topo, {})]

        ev = HealthEvaluator(rules_fn)
        for _ in range(200):
            topo.e2e_hist.record(5_000)
        ev.tick()
        ev.tick()  # rules_fn raises: nothing evaluated, tracks KEPT
        assert ev.has_track("r1")
        prev_e2e = ev._tracks["r1"].prev_e2e
        assert prev_e2e is not None  # delta baseline survives
        v = ev.tick()["r1"]  # recovery: delta is empty, not full history
        assert v["latency"]["window_fast"]["count"] < 200

    def test_watermark_none_during_late_tolerance_warmup(self):
        """A tolerance-adjusted watermark ≤ 0 was never broadcast and
        must not read as a (hugely lagging) watermark."""
        from ekuiper_tpu.runtime.nodes_window import WatermarkNode

        wm = WatermarkNode("wm", late_tolerance_ms=10_000)
        assert wm.watermark_ts() is None
        wm.max_ts = 500  # first event: adjusted wm is -9500
        assert wm.watermark_ts() is None
        wm.max_ts = 10_500
        assert wm.watermark_ts() == 500

    def test_shared_node_queue_peak_seen_by_every_member(self):
        """take_queue_peak_tick is read-and-reset; a node shared by N
        member rules must report the same tick peak to all of them."""
        shared = FakeNode("shared_src", "source")
        shared.stats.note_queue_depth(500)
        ev = HealthEvaluator(
            lambda: [("r1", FakeTopo([shared]), {}),
                     ("r2", FakeTopo([shared]), {})])
        ev.tick()
        for rid in ("r1", "r2"):
            assert ev._tracks[rid].prev_queue["shared_src"] == 500

    def test_rule_health_does_not_retick_per_poll(self):
        """A rule with a track but no verdict (eval persistently raises)
        must not cost one off-cadence tick PER REST POLL — that would
        decay every other rule's burn windows and hysteresis."""
        class BadTopo(FakeTopo):
            def all_nodes(self):
                raise RuntimeError("boom")

        ev = HealthEvaluator(lambda: [("r1", BadTopo([]), {})])
        assert ev.rule_health("r1") is None  # one seeding tick
        assert ev.ticks == 1
        assert ev.rule_health("r1") is None  # track exists: no re-tick
        assert ev.rule_health("r1") is None
        assert ev.ticks == 1

    def test_since_with_limit_pages_forward(self):
        """since+limit keeps the OLDEST n so a tailer never skips events
        between its cursor and the window."""
        from ekuiper_tpu.runtime.events import FlightRecorder

        fr = FlightRecorder(capacity=16)
        for i in range(5):
            fr.record("k", i=i)
        page = fr.diagnostics(since=0, limit=2)
        assert [e["i"] for e in page["events"]] == [0, 1]
        assert page["last_seq"] == 2
        page = fr.diagnostics(since=page["last_seq"], limit=2)
        assert [e["i"] for e in page["events"]] == [2, 3]
        page = fr.diagnostics(since=page["last_seq"], limit=2)
        assert [e["i"] for e in page["events"]] == [4]
        assert page["last_seq"] == 5

    def test_mixed_level_escalation_lands_on_min_sustained(self):
        """One breach-level spike inside an otherwise-degraded pending
        run escalates to DEGRADED, not BREACHING."""
        topo = FakeTopo([FakeNode("src", "source")])
        ev = _evaluator(topo)
        for _ in range(3):
            topo.e2e_hist.record(5_000)  # ~1.x burn: degraded band
        for _ in range(100):
            topo.e2e_hist.record(2)
        assert ev.tick()["r1"]["state"] == HEALTHY  # pend=1 @ degraded
        for _ in range(500):
            topo.e2e_hist.record(5_000)  # breach-level spike
        v = ev.tick()["r1"]  # pend=2, min level sustained = degraded
        assert v["state"] == DEGRADED
        for _ in range(500):
            topo.e2e_hist.record(5_000)
        ev.tick()
        v = ev.tick()["r1"]  # breach level held for up_ticks: escalate
        assert v["state"] == BREACHING

    def test_shared_member_emit_stage_not_cross_charged(self):
        """A shared node's emit[<rule>] stage time lands only on that
        member's verdict; other members must not report it as theirs."""
        shared = FakeNode("shared_fold")
        shared.stats.observe_stage("emit[r1]", 1_000, rows=1)
        shared.stats.observe_stage("emit[r2]", 50_000, rows=1)
        shared.stats.observe_stage("fold", 100, rows=1)
        ev = HealthEvaluator(
            lambda: [("r1", FakeTopo([shared]), {}),
                     ("r2", FakeTopo([shared]), {})])
        vs = ev.tick()
        s1 = vs["r1"]["bottleneck"]["stage_us"]
        s2 = vs["r2"]["bottleneck"]["stage_us"]
        assert s1.get("emit_combine", 0) == 1_000  # r2's 50ms not charged
        assert s2.get("emit_combine", 0) == 50_000
        assert vs["r1"]["bottleneck"]["stage"] == "emit_combine"

    def test_rest_distinguishes_failing_eval_from_stopped(self, api_env):
        """A running rule whose evaluation persistently raises must not
        be reported as 'not running'."""
        api, _clock = api_env
        topo = _start_rule(api, "hf1", {})
        ev = api.health_evaluator
        # sabotage the topo's node walk: eval raises, track exists
        topo.all_nodes = lambda: (_ for _ in ()).throw(RuntimeError("x"))
        ev.tick()
        code, out = api.dispatch("GET", "/rules/hf1/health", None, {})
        assert code == 200
        assert out["state"] == "unknown"
        assert "evaluation is failing" in out["reason"]

    def test_cross_signal_burns_do_not_combine(self):
        """A fast-window-only latency burn coinciding with a slow-window
        -only drop burn must not escalate: each SIGNAL must burn in both
        of ITS OWN windows (mixing them would also emit a reason-less
        transition, since the reasons guards are per signal)."""
        topo = FakeTopo([FakeNode("src", "source")])
        ev = _evaluator(topo, up_ticks=1)
        ev.tick()  # create the track
        tr = ev._tracks["r1"]
        # latency: fast window 100% violating, slow window far under the
        # 1% budget — a spike the slow window has already absorbed. The
        # fast window carries real sample mass (burn is weighted by
        # samples observed per window; a 1-sample window can't burn)
        for _ in range(150):
            tr.fast_hist.record(5_000)
        for _ in range(20_000):
            tr.slow_hist.record(2)
        tr.slow_hist.record(5_000)
        # drops: slow window still remembers a burst the fast window has
        # fully diluted
        tr.fast_drops, tr.fast_in = 0.0, 1000.0
        tr.slow_drops, tr.slow_in = 500.0, 1000.0
        v = ev.tick()["r1"]
        br = v["burn_rate"]
        assert br["latency_fast"] >= 1.0 > br["latency_slow"]
        assert br["drop_slow"] >= 1.0 > br["drop_fast"]
        # per-window maxima both burn — but no single signal does
        assert br["fast"] >= 1.0 and br["slow"] >= 1.0
        assert v["state"] == HEALTHY
        assert "reasons" not in v

    def test_partial_health_sample_skips_node_for_tick(self):
        """A lock-race-degraded sample must not become the delta
        baseline (the next tick would replay cumulative history)."""
        node = FakeNode("op1")
        node.stats.observe_stage("fold", 10_000, rows=5)
        topo = FakeTopo([node])
        ev = _evaluator(topo)
        ev.tick()  # baseline: fold=10000 recorded in prev
        node.stats.observe_stage("fold", 500, rows=1)
        real_sample = node.stats.health_sample
        node.stats.health_sample = lambda: {**real_sample(),
                                            "stages": {}, "dropped": 0,
                                            "partial": True}
        v = ev.tick()["r1"]  # degraded sample: node skipped, prev kept
        assert not v["bottleneck"].get("stage_us")
        node.stats.health_sample = real_sample
        v = ev.tick()["r1"]  # recovery: delta vs ORIGINAL baseline
        assert v["bottleneck"]["stage_us"].get("fold", 0) == 500


class TestSampleCountAwareBurn:
    """ISSUE 10 satellite: when the evaluator ticks faster than a rule
    emits, the burn windows must hold their evidence between emissions
    instead of decaying to zero and flapping the verdict (churn_soak had
    to pin KUIPER_HEALTH_INTERVAL_MS=1500 to dodge exactly this)."""

    def _slow_emitter(self, options=None, **kw):
        topo = FakeTopo([FakeNode("src", "source")])
        # sub-second cadence: the interval only matters for the timer;
        # driving tick() directly models an evaluator far outpacing the
        # rule's ~per-window emission rate
        ev = _evaluator(topo, options=options, interval_ms=200, **kw)
        return topo, ev

    def test_breaching_slow_emitter_holds_across_empty_ticks(self):
        """A rule emitting a violating window every 5th evaluator tick
        must reach breaching and STAY there — empty ticks carry no new
        evidence and must not decay the verdict toward healthy."""
        topo, ev = self._slow_emitter(
            options={"slo": {"latencyP99Ms": 100, "target": 0.9}})
        states = []
        for i in range(20):
            if i % 5 == 0:  # one window emission: all samples violating
                for _ in range(20):
                    topo.e2e_hist.record(5_000)
            states.append(ev.tick()["r1"]["state"])
        assert BREACHING in states
        # once breaching, the verdict never steps down during the run —
        # pre-fix, the 4 empty ticks between emissions decayed the
        # windows to zero samples and the FSM flapped down every cycle
        first = states.index(BREACHING)
        assert set(states[first:]) == {BREACHING}

    def test_healthy_slow_emitter_stays_healthy(self):
        topo, ev = self._slow_emitter()
        for i in range(20):
            if i % 5 == 0:
                topo.e2e_hist.record(2)
                topo.e2e_hist.record(3)
            assert ev.tick()["r1"]["state"] == HEALTHY

    def test_single_stray_violation_cannot_degrade(self):
        """One violating sample in an otherwise-empty window is below
        the budget's statistical resolution (~1/budget samples) — the
        weighted burn must stay under the degrade line no matter how
        many sub-second ticks re-read the held window."""
        topo, ev = self._slow_emitter()  # default target 0.99
        topo.e2e_hist.record(5_000)
        for _ in range(10):
            v = ev.tick()["r1"]
            assert v["state"] == HEALTHY
            assert v["burn_rate"]["latency_fast"] < 1.0

    def test_empty_ticks_do_not_decay_drop_windows(self):
        src = FakeNode("src", "source")
        topo = FakeTopo([src])
        ev = _evaluator(topo)
        src.stats.inc_in(1000)
        src.stats.inc_dropped("buffer_full", n=500)
        ev.tick()
        states = [ev.tick()["r1"]["state"] for _ in range(8)]
        # no new traffic at all: the drop evidence holds, the verdict
        # does not silently relax back to healthy
        assert states[-1] == BREACHING

    def test_dead_traffic_rule_ages_out_of_breaching(self):
        """The evidence hold is BOUNDED (IDLE_HOLD_TICKS): a rule whose
        traffic stops entirely — dead broker, disconnected source —
        must age back to healthy instead of freezing at breaching
        forever (which would permanently trip the breach-defer
        admission gate and keep the shed plane acting on a dead
        rule)."""
        src = FakeNode("src", "source")
        topo = FakeTopo([src])
        ev = _evaluator(topo)
        src.stats.inc_in(1000)
        src.stats.inc_dropped("buffer_full", n=500)
        ev.tick()
        assert ev.tick()["r1"]["state"] == BREACHING
        states = [ev.tick()["r1"]["state"] for _ in range(40)]
        # held well past the flap horizon (sub-second-cadence evidence),
        # then decays out and steps down through the FSM
        assert states[health.IDLE_HOLD_TICKS - 2] == BREACHING
        assert states[-1] == HEALTHY

    def test_dead_latency_evidence_ages_out(self):
        topo, ev = self._slow_emitter(
            options={"slo": {"latencyP99Ms": 100, "target": 0.9}})
        for _ in range(40):
            topo.e2e_hist.record(5_000)
        ev.tick()
        assert ev.tick()["r1"]["state"] == BREACHING
        states = [ev.tick()["r1"]["state"] for _ in range(40)]
        assert states[-1] == HEALTHY

    def test_window_sample_mass_is_reported(self):
        topo, ev = self._slow_emitter()
        for _ in range(7):
            topo.e2e_hist.record(2)
        v = ev.tick()["r1"]
        assert v["latency"]["tick_samples"] == 7
        assert v["latency"]["samples_fast"] == 7
        # the observing tick decayed the window toward the next one
        # (7 -> 3); empty ticks HOLD that mass instead of halving it
        # again and again toward zero
        for _ in range(3):
            v = ev.tick()["r1"]
            assert v["latency"]["tick_samples"] == 0
            assert v["latency"]["samples_fast"] == 3


class TestSeedingSingleFlight:
    def test_concurrent_polls_tick_once(self):
        """rule_health's seeding tick runs OUTSIDE the evaluator lock
        (the clock/evaluator ABBA fix) but must stay single-flight:
        N concurrent polls for an untracked rule produce ONE
        off-cadence tick, not one each (review regression — each extra
        tick decays every rule's burn windows)."""
        import threading
        import time

        topo = FakeTopo([FakeNode("fold")])
        ev = _evaluator(topo)
        ticks = []
        orig_tick = ev.tick

        def slow_tick():
            ticks.append(1)
            time.sleep(0.05)  # widen the race window
            return orig_tick()

        ev.tick = slow_tick
        results = []
        threads = [
            threading.Thread(
                target=lambda: results.append(ev.rule_health("r1")))
            for _ in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert len(ticks) == 1, f"{len(ticks)} seeding ticks fired"
        assert len(results) == 4
        assert all(r is not None for r in results)
