"""One stage span where the work happens (PR 27): `StatManager.stage()` feeds
the stage counters, the rule's trace and the profiler's host plane; the
node fabric counts starved and blocked time between the stages; a window
boundary is split into phases; the kernels carry stable scope names."""
import glob
import json
import threading
import time

import numpy as np
import pytest

import ekuiper_tpu.io.memory as mem
from ekuiper_tpu.observability.tracer import Tracer
from ekuiper_tpu.runtime.node import Node
from ekuiper_tpu.server.processors import StreamProcessor
from ekuiper_tpu.server.rest import RestApi
from ekuiper_tpu.store import kv


@pytest.fixture
def fresh_tracer():
    old = Tracer._instance
    Tracer._instance = Tracer()
    yield Tracer._instance
    Tracer._instance = old


class _Topo:
    """The two things a bare node asks of its topo."""

    rule_id = "r"

    def drain_error(self, err, origin=""):
        raise err


def _node(cls=Node, name="n1", **kw):
    node = cls(name, **kw)
    node._topo = _Topo()
    node.stats.rule_id = "r"
    return node


def _spin(seconds: float) -> None:
    """`seconds` of this thread's own CPU: a spin on the wall clock is
    on the core for less than it asks when the machine is loaded, and
    the CPU bounds below then fail from the wrong side."""
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


class _Worker(Node):
    """2 ms on the core, then 20 ms off it, inside one stage."""

    def process(self, item):
        with self.stats.stage("work", rows=7, shard=3):
            _spin(0.002)
            time.sleep(0.02)


# ------------------------------------------------------------------ (a)
class TestStage:
    def test_accrues_wall_cpu_calls_rows(self, fresh_tracer):
        node = _node(_Worker)
        node._dispatch("x")
        node._dispatch("y")
        st = node.stats.snapshot()["stage_timings"]["work"]
        assert st["calls"] == 2 and st["rows"] == 14
        assert st["total_us"] >= 2 * 21_000
        # the sleep is off the core: CPU time is the spin, not the wall
        assert 2 * 1_000 <= st["cpu_us"] <= st["total_us"] - 2 * 15_000
        assert fresh_tracer.rule_spans("r") == []  # untraced: no span

    def test_child_span_of_the_dispatch_starts_inside_it(self, fresh_tracer):
        """The regression test for "a span's start is its end": the start
        is taken before the work, on the wall clock, and the stage span
        names the dispatch span as its parent."""
        fresh_tracer.enable("r")
        node = _node(_Worker)
        before = time.time_ns()
        node._dispatch("x")
        after = time.time_ns()
        spans = fresh_tracer.rule_spans("r")
        dispatch = next(s for s in spans if "stage" not in s)
        child = next(s for s in spans if s.get("stage") == "work")
        assert dispatch["parentSpanId"] == ""
        assert child["parentSpanId"] == dispatch["spanId"]
        assert child["traceId"] == dispatch["traceId"]
        assert child["op"] == "n1" and child["kind"] == "stage"
        assert child["rows"] == 7 and child["attributes"] == {"shard": 3}
        assert child["durationUs"] >= 21_000
        d0, c0 = dispatch["startTimeUnixNano"], child["startTimeUnixNano"]
        assert before <= d0 <= c0
        # the child starts near the dispatch's START, a whole stage
        # duration before the dispatch's end — not at it
        assert c0 - d0 < 5_000_000
        d_end = d0 + dispatch["durationUs"] * 1000
        assert c0 + child["durationUs"] * 1000 <= d_end + 1_000_000
        assert d_end <= after + 1_000_000
        assert child["startTimeMs"] == c0 // 1_000_000
        assert Tracer.current() is None  # the thread is handed back

    def test_sub_stage_is_a_span_without_a_counter_row(self, fresh_tracer):
        class N(Node):
            def process(self, item):
                with self.stats.stage("emit") as st:
                    with self.stats.span("fetch"):
                        pass
                    st.rows = 5

        fresh_tracer.enable("r")
        node = _node(N)
        node._dispatch("x")
        assert set(node.stats.snapshot()["stage_timings"]) == {"emit"}
        assert node.stats.snapshot()["stage_timings"]["emit"]["rows"] == 5
        by_stage = {s.get("stage"): s for s in fresh_tracer.rule_spans("r")}
        assert by_stage["fetch"]["parentSpanId"] == by_stage["emit"]["spanId"]
        assert by_stage["emit"]["rows"] == 5

    def test_context_crosses_the_queue_hop(self, fresh_tracer):
        """A dispatch span's parent is the span during which the item was
        emitted; the receiving worker's own context starts empty."""
        fresh_tracer.enable("r")
        up, down = _node(name="up"), _node(name="down")
        up.connect(down)

        class Item:
            pass

        up._dispatch(Item())
        down._dispatch(down.inq.get_nowait())
        spans = {s["op"]: s for s in fresh_tracer.rule_spans("r")}
        assert spans["down"]["parentSpanId"] == spans["up"]["spanId"]
        assert spans["down"]["traceId"] == spans["up"]["traceId"]


# ------------------------------------------------------------------ (b)
class TestBetweenTheStages:
    def test_slow_consumer_blocks_senders_starved_one_idles(self):
        class Slow(Node):
            def process(self, item):
                time.sleep(0.03)

        slow = _node(Slow, name="slow", buffer_length=1,
                     disable_buffer_full_discard=True)
        starved = _node(name="starved")
        slow.open()
        starved.open()
        try:
            for i in range(5):  # queue of 1: the sender waits for room
                slow.put(i)
            slow.put_control("tick")
            deadline = time.time() + 5
            while slow.inq.unfinished_tasks and time.time() < deadline:
                time.sleep(0.01)
            s = slow.stats.snapshot()
            time.sleep(0.25)  # one empty poll of the starved worker
            v = starved.stats.snapshot()
        finally:
            slow.close()
            starved.close()
            slow.join()
            starved.join()
        assert s["backpressure_us_total"] >= 3 * 25_000
        assert v["backpressure_us_total"] == 0
        assert v["idle_us_total"] >= 200_000
        # the slow node was busy nearly all the time it had input
        assert s["idle_us_total"] < s["process_time_us_total"]
        # neither is busy time: no stage row appears for them
        assert s["stage_timings"] == {} and v["stage_timings"] == {}
        assert s["dropped_total"] == {}


# ------------------------------------------------------------------ (c)
STAGES = ("kuiper:ingest", "kuiper:decode", "kuiper:upload", "kuiper:fold",
          "kuiper:jit:fold", "kuiper:emit", "kuiper:sink")


def _host_events(trace_dir: str):
    """(name, unix start ns, duration ns, stats) of every `kuiper:` event
    on a host plane, and the device planes' names."""
    from jax.profiler import ProfileData

    path = glob.glob(trace_dir + "/plugins/profile/*/*.xplane.pb")[0]
    data = ProfileData.from_file(path)
    t0 = next(dict(p.stats)["profile_start_time"] for p in data.planes
              if p.name == "Task Environment")
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("kuiper:"):
                    out.append((ev.name, t0 + int(ev.start_ns),
                                int(ev.duration_ns), dict(ev.stats)))
    return out


@pytest.fixture
def tumbling_rule(mock_clock):
    store = kv.get_store()
    StreamProcessor(store).exec_stmt(
        'CREATE STREAM spans_in (deviceId STRING, temperature FLOAT) '
        'WITH (DATASOURCE="spans/in", TYPE="memory", FORMAT="JSON")')
    api = RestApi(store)
    got = []
    mem.subscribe("spans/out", lambda _t, payload: got.append(payload))
    code, _ = api.dispatch("POST", "/rules", {
        "id": "spans1",
        "sql": "SELECT deviceId, count(*) AS c, avg(temperature) AS a "
               "FROM spans_in GROUP BY deviceId, TUMBLINGWINDOW(ss, 1)",
        "options": {"prefinalizeLeadMs": 0, "decodePoolSize": 2},
        "actions": [{"memory": {"topic": "spans/out"}}]}, {})
    assert code in (200, 201)
    deadline = time.time() + 20
    while time.time() < deadline:
        rs = api.rules.state("spans1")
        if rs is not None and rs.topo is not None and rs.topo._open:
            break
        time.sleep(0.05)
    yield api, got
    api.rules.stop_all()


def _drive_one_window(mock_clock, got, n_before=0):
    rows = [json.dumps({"deviceId": f"d{i % 4}", "temperature": float(i)}
                       ).encode() for i in range(64)]
    mem.publish("spans/in", rows)
    mock_clock.advance(20)  # the linger flush
    time.sleep(0.4)  # decode pool -> fused worker, in real threads
    mock_clock.advance(1000)  # the boundary
    deadline = time.time() + 10
    while time.time() < deadline and len(got) <= n_before:
        time.sleep(0.02)
    assert len(got) > n_before, "the window never reached the sink"


class TestProfilerAndTrace:
    def test_spans_on_the_profilers_host_plane_and_in_the_trace(
            self, tumbling_rule, mock_clock, fresh_tracer, tmp_path):
        import jax

        api, got = tumbling_rule
        _drive_one_window(mock_clock, got)  # compiles, off the record
        fresh_tracer.enable("spans1")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            _drive_one_window(mock_clock, got, n_before=len(got))
        finally:
            jax.profiler.stop_trace()
        events = _host_events(str(tmp_path))
        names = {e[0] for e in events}
        assert set(STAGES) <= names, sorted(names)
        assert {"kuiper:convert", "kuiper:deliver", "kuiper:merge"} <= names
        fold = next(e for e in events if e[0] == "kuiper:fold")
        assert fold[3]["rule"] == "spans1" and fold[3]["rows"] == 64
        # the host span that enqueued the device program lies inside the
        # stage that dispatched it
        jit = next(e for e in events if e[0] == "kuiper:jit:fold")
        assert fold[1] <= jit[1] and jit[1] + jit[2] <= fold[1] + fold[2]
        # ... after the fold's staging, which is a stage inside `fold`;
        # and the worker's dispatch brackets the whole of it (PR 40)
        h2d = next(e for e in events if e[0] == "kuiper:fold_h2d")
        assert fold[1] <= h2d[1] and h2d[1] + h2d[2] <= jit[1]
        assert any(e[0] == "kuiper:dispatch" and e[3]["op"] == "window_agg"
                   and e[1] <= fold[1]
                   and fold[1] + fold[2] <= e[1] + e[2] for e in events)

        # ---- the same work in the rule's trace, on the same clock
        spans = [s for tid in fresh_tracer.rule_traces("spans1")
                 for s in fresh_tracer.trace(tid)]
        for stage in ("ingest", "decode", "upload", "fold", "emit", "sink",
                      "convert", "deliver"):
            mine = [s for s in spans if s.get("stage") == stage]
            assert mine, stage
            theirs = [e[1] for e in events if e[0] == "kuiper:" + stage]
            for s in mine:
                gap = min(abs(s["startTimeUnixNano"] - t) for t in theirs)
                assert gap < 1_000_000, (stage, gap)

        # ---- parents: one ingest batch, then one boundary
        by_id = {s["spanId"]: s for s in spans}

        def chain(span):
            out = [(span["op"], span.get("stage", ""))]
            while span["parentSpanId"]:
                span = by_id[span["parentSpanId"]]
                out.append((span["op"], span.get("stage", "")))
            return out[::-1]

        fold_span = next(s for s in spans if s.get("stage") == "fold")
        assert chain(fold_span) == [
            ("spans_in", ""), ("spans_in", "ingest"),
            ("spans_in_shared", ""), ("window_agg", ""),
            ("window_agg", "fold")]
        decode = next(s for s in spans if s.get("stage") == "decode")
        assert chain(decode)[:2] == [("spans_in", ""),
                                     ("spans_in", "ingest")]
        deliver = next(s for s in spans if s.get("stage") == "deliver")
        path = chain(deliver)
        assert path[0] == ("window_agg", "") and \
            by_id[deliver["parentSpanId"]]["stage"] == "sink"
        assert ("window_agg", "emit") in path
        sink_dispatch = next(s for s in spans if s["op"] == path[-1][0]
                             and "stage" not in s)
        assert by_id[sink_dispatch["parentSpanId"]]["stage"] == "emit"
        trigger = next(s for s in spans if s["kind"] == "Trigger")
        assert trigger["parentSpanId"] == ""
        # every span but a root names a parent in its own trace, and
        # starts no earlier than it
        roots = [s for s in spans if not s["parentSpanId"]]
        assert {(r["op"], r["kind"]) for r in roots} <= {
            ("spans_in", "list"), ("spans_in", "LingerTimer"),
            ("window_agg", "Trigger"), ("window_agg", "PreTrigger")}
        for s in spans:
            if s["parentSpanId"]:
                parent = by_id[s["parentSpanId"]]
                assert parent["traceId"] == s["traceId"]
                assert parent["startTimeUnixNano"] <= s["startTimeUnixNano"]

    def test_boundary_phases_count_one_sample_per_window(
            self, tumbling_rule, mock_clock):
        """(e) `kuiper_boundary_ms`: one sample per phase per emitted
        window, in /metrics and in the rule's status."""
        from tools import check_metrics

        api, got = tumbling_rule
        for _ in range(3):
            _drive_one_window(mock_clock, got, n_before=len(got))
        topo = api.rules.state("spans1").topo
        deadline = time.time() + 5
        while time.time() < deadline and \
                topo.boundary_hists["sink"].count < len(got):
            time.sleep(0.02)
        n = len(got)
        code, text = api.dispatch("GET", "/metrics", None, {})
        for phase in ("trigger_delay", "emit", "sink"):
            line = (f'kuiper_boundary_ms_count{{rule="spans1",'
                    f'phase="{phase}"}} ')
            count = next(float(ln[len(line):]) for ln in text.splitlines()
                         if ln.startswith(line))
            # a boundary with no rows emits nothing and records no emit /
            # sink sample, but its Trigger still came late or on time
            assert count == n if phase != "trigger_delay" else count >= n
        status = topo.status()
        assert set(status["boundary_ms"]) == {"trigger_delay", "emit", "sink"}
        assert status["boundary_ms"]["emit"]["count"] == n
        assert 0 < status["boundary_ms"]["sink"]["p50"] <= \
            status["boundary_ms"]["sink"]["p95"]
        for fam in ("kuiper_op_idle_us_total",
                    "kuiper_op_backpressure_us_total",
                    "kuiper_op_stage_cpu_us_total"):
            assert f"# TYPE {fam} counter" in text
        assert 'stage="emit"' in text and 'stage="sink"' in text \
            and 'stage="ingest"' in text
        # every family of this live scrape is prefixed, typed, helped and
        # in the catalog — the lint's forward direction over a REAL rule
        docs = check_metrics.documented_families()
        assert check_metrics.rendered_families(text) <= docs


# ------------------------------------------------------------------ (d)
class _Ledgered(Node):
    """10 ms in a stage (5 of them in a nested one), 5 ms in none, while
    another thread holds a 40 ms `emit` stage open on the same node."""

    def process(self, item):
        other = threading.Thread(target=self._emit_thread)
        other.start()
        with self.stats.stage("work", rows=3):
            _spin(0.005)
            with self.stats.stage("inner", within="work"):
                _spin(0.005)
            with self.stats.span("piece"):
                pass
        _spin(0.005)
        other.join()

    def _emit_thread(self):
        with self.stats.stage("emit"):
            time.sleep(0.04)


def _after_a_dispatch(stats):
    """The worker's ledger read just after its next dispatch ends: idle is
    added when a `get` returns and busy when a cycle closes, so between
    dispatches up to a poll of the queue is not on the books yet; here it
    is what passed since `process_end`."""
    n = stats.messages_processed
    deadline = time.time() + 10
    while stats.messages_processed == n and time.time() < deadline:
        time.sleep(0.0005)
    assert stats.messages_processed > n, "no dispatch came"
    t = time.perf_counter_ns() // 1000
    snap = stats.snapshot()
    return t, snap


class TestCycleLedger:
    def test_only_the_workers_outermost_stages_are_staged(self):
        node = _node(_Ledgered)
        with node.stats.stage("warm"):  # no dispatch is open: not staged
            _spin(0.002)
        node._dispatch("x")
        snap = node.stats.snapshot()
        st = snap["stage_timings"]
        busy = snap["process_time_us_total"]
        assert busy >= 40_000  # the join waits for the other thread
        assert st["emit"]["total_us"] >= 40_000
        assert "inner" in node.stats.nested_stages
        # staged is `work` alone: not `inner` (inside it), not `emit`
        # (another thread's), not `warm` (no dispatch), not the span
        assert snap["unstaged_us_total"] == busy - st["work"]["total_us"]
        # ... which leaves the spin after `work` at least (what the join
        # then waits is 40 ms less `work`'s wall: as little as load makes
        # it)
        assert snap["unstaged_us_total"] >= 5_000
        cpu = snap["busy_cpu_us_total"]
        # three 5 ms spins of thread CPU, 10 of them in `work`; the join
        # sleeps. Load stretches the wall, never shortens the CPU, so
        # each bound holds from the side load cannot reach
        assert 15_000 <= cpu <= busy - 20_000
        assert snap["unstaged_cpu_us_total"] == cpu - st["work"]["cpu_us"]
        assert 5_000 <= snap["unstaged_cpu_us_total"] <= cpu - 10_000
        assert node.stats.health_sample()["unstaged_us"] == \
            snap["unstaged_us_total"]

    def test_a_stage_that_reaches_back_counts_from_its_body(self):
        """`since_ns` starts a stage's wall at a dispatch made earlier;
        the ledger takes the body's own time, so unstaged stays >= 0."""
        class N(Node):
            def process(self, item):
                with self.stats.stage(
                        "late", since_ns=time.perf_counter_ns() - 10**9):
                    _spin(0.002)

        node = _node(N)
        node._dispatch("x")
        snap = node.stats.snapshot()
        assert snap["stage_timings"]["late"]["total_us"] >= 1_000_000
        assert 0 <= snap["unstaged_us_total"] \
            <= snap["process_time_us_total"] - 2_000

    def test_identity_on_a_served_rule(self, tumbling_rule, mock_clock):
        """idle + staged + unstaged = the worker's wall, on the fused node
        of a rule created over REST; the staged part is the stages its
        worker closed, whatever its emit thread adds to the same table."""
        api, got = tumbling_rule
        _drive_one_window(mock_clock, got)  # compiles, off the record
        fused = next(n for n in api.rules.state("spans1").topo.ops
                     if n.name == "window_agg")
        rows = [json.dumps({"deviceId": f"d{i % 4}", "temperature": 1.0}
                           ).encode() for i in range(64)]

        def batch():
            mem.publish("spans/in", rows)
            mock_clock.advance(20)  # the linger flush
            return _after_a_dispatch(fused.stats)

        t0, s0 = batch()
        calls0, resident0 = fused.fold_transfers, fused.fold_resident_args
        for _ in range(8):
            time.sleep(0.1)
            batch()
        mock_clock.advance(1000)  # a boundary: Trigger, emit
        time.sleep(0.3)
        t1, s1 = batch()

        def grew(key):
            return s1[key] - s0[key]

        def stage(name, key="total_us"):
            return (s1["stage_timings"].get(name, {}).get(key, 0)
                    - s0["stage_timings"].get(name, {}).get(key, 0))

        wall = t1 - t0
        busy, idle = grew("process_time_us_total"), grew("idle_us_total")
        unstaged = grew("unstaged_us_total")
        assert wall > 1_000_000 and busy > 0
        assert abs(idle + busy - wall) <= 0.05 * wall + 5_000, (
            idle, busy, wall)
        assert 0 <= unstaged <= busy
        assert 0 <= grew("unstaged_cpu_us_total") \
            <= grew("busy_cpu_us_total") <= busy + 1_000
        # staged = what the worker's own stages took: four are its alone,
        # the rest of it is its share of `emit`
        staged = busy - unstaged
        mine = ("upload", "fold", "boundary_reset", "release")
        own = sum(stage(n) for n in mine)
        n_calls = sum(stage(n, "calls") for n in mine + ("emit",))
        assert own <= staged <= own + stage("emit") + n_calls
        # the boundary's reset and re-arm, once; a batch's release, each
        assert stage("boundary_reset", "calls") == 1
        assert stage("release", "calls") == 9 and stage("release", "rows") \
            == stage("fold", "rows")
        # the fold's staging: nested, inside `fold`, one a micro-batch,
        # and the counter says how many runtime calls it made
        assert "fold_h2d" in fused.stats.nested_stages
        assert "fold_h2d" not in fused.stats.health_sample()["stages"]
        assert stage("fold_h2d", "calls") == stage("fold", "calls") == 9
        assert 0 < stage("fold_h2d") <= stage("fold")
        # the ingest prep uploaded the column and the slots, the pane is
        # in the kernel's device-resident table: a linger flush of 64
        # rows has its row count left to stage, in one call
        assert (fused.fold_transfers - calls0,
                fused.fold_resident_args - resident0) == (9, 9)
        text = api.dispatch("GET", "/metrics", None, {})[1]
        assert (f'kuiper_fold_transfers_total{{rule="spans1",'
                f'op="window_agg"}} {fused.fold_transfers}') in text
        assert (f'kuiper_fold_resident_args_total{{rule="spans1",'
                f'op="window_agg"}} {fused.fold_resident_args}') in text
        for fam in ("kuiper_op_busy_us_total", "kuiper_op_busy_cpu_us_total",
                    "kuiper_op_unstaged_us_total",
                    "kuiper_op_unstaged_cpu_us_total"):
            assert f"# TYPE {fam} counter" in text
            assert f'{fam}{{rule="spans1",op="window_agg"' in text
        status = api.rules.state("spans1").topo.status()
        assert status["op_window_agg_0_fold_transfers"] \
            == fused.fold_transfers
        assert status["op_window_agg_0_fold_resident_args"] \
            == fused.fold_resident_args
        assert status["op_window_agg_0_unstaged_us_total"] \
            <= status["op_window_agg_0_process_time_us_total"]

    def test_the_ledger_tool_divides_the_workers_wall(
            self, tumbling_rule, mock_clock):
        """`tools/cycle_ledger.py` reads the same division from two
        `/metrics` texts, as it does around a benchmark cell's window."""
        from tools import cycle_ledger

        api, got = tumbling_rule
        _drive_one_window(mock_clock, got)

        def marks():
            return {"t": time.time(),
                    "metrics": api.dispatch("GET", "/metrics", None, {})[1]}

        m0 = marks()
        _drive_one_window(mock_clock, got, n_before=len(got))
        time.sleep(0.3)  # the worker's open `get` books its idle
        led = cycle_ledger.ledger(m0, marks())
        assert led["op"] == "window_agg" and led["micro_batches"] == 1
        assert led["staged"] + led["unstaged"] == led["busy"] > 0
        assert led["stages"]["fold"]["wall"] \
            >= led["stages"]["fold_h2d"]["wall"] > 0
        # prefinalizeLeadMs 0: the boundary's emit runs on the worker
        assert 0 < led["emit_on_worker"] <= led["stages"]["emit"]["wall"] + 2
        # 64 rows of a linger flush: the row count goes up, the pane is
        # the table's
        assert (led["transfers"], led["resident"]) == (1, 1)
        assert abs(led["identity_gap_share"]) < 0.35  # one 0.2 s poll
        text = cycle_ledger.table(led)
        assert "fold_h2d" in text and "unstaged" in text
        assert "resident arguments a staging 1.00 (hit share 50.0 %)" \
            in text

    def test_transfers_count_what_the_worker_stages_itself(self):
        """A fold handed host arrays makes ONE runtime call a chunk for
        all of them — columns, a mask, the slots, a partial chunk's row
        count — and none for a scalar pane or a full chunk's row count,
        which the kernel's device-resident table serves: 12 rows in
        micro-batches of 8 are a full chunk (1 call, 2 resident
        arguments) and a partial one (1 call, the pane resident). The
        stage opener, if one is handed, runs once a chunk around exactly
        that."""
        gb = _avg_max_kernel()
        node = _node()
        opened = []

        def h2d(rows):
            opened.append(rows)
            return node.stats.stage("fold_h2d", rows, within="fold")

        cols, slots = _host_rows(12)
        state = gb.fold(gb.init_state(), cols, slots)  # as a test calls it
        assert (gb.transfers_total, gb.resident_total) == (2, 3)
        state = gb.fold(state, cols, slots,
                        {"temp": np.ones(12, dtype=np.bool_)}, h2d=h2d)
        assert (gb.transfers_total, gb.resident_total) == (4, 6)
        assert opened == [8, 4]
        assert node.stats.snapshot()["stage_timings"]["fold_h2d"][
            "calls"] == 2
        outs, act = gb.finalize(state, 1)
        assert float(np.asarray(act)[0]) == 24.0

    @pytest.mark.parametrize("n_rows, pane, transfers, from_table", [
        (8, 1, 0, {"pane", "count"}),  # full, scalar pane: nothing to move
        (5, 1, 1, {"pane"}),  # a linger flush: its row count goes up
        (8, "vector", 1, {"count"}),  # a per-row pane vector is transferred
        (5, "vector", 1, set()),  # ... in the same one call as the count
        (8, 3, 1, {"count"}),  # a pane the table does not hold: likewise
    ])
    def test_pre_uploaded_inputs_leave_only_what_the_table_lacks(
            self, n_rows, pane, transfers, from_table):
        """What the ingest prep has put on the device is handed over as it
        is; of the two scalars left, the table serves a pane below
        `n_panes` and the row count `micro_batch`."""
        import jax.numpy as jnp

        gb = _avg_max_kernel(n_panes=3)
        cols, slots = _host_rows(8)
        dev_cols = {k: jnp.asarray(v) for k, v in cols.items()}
        dev_slots = jnp.asarray(slots.astype(np.uint16))
        seen, _ = _spy(gb, "_fold")
        if pane == "vector":
            pane = np.array([0, 1, 2, 1, 0, 1, 2, 1], dtype=np.int64)
        gb.fold(gb.init_state(), dev_cols, dev_slots, pane_idx=pane,
                n_rows=n_rows)
        assert (gb.transfers_total, gb.resident_total) \
            == (transfers, len(from_table))
        (d, s, n_valid, pane_arg), = seen
        assert d["temp"] is dev_cols["temp"] and s is dev_slots
        assert (n_valid is gb._scalars[3]) == ("count" in from_table)
        assert (pane_arg is gb._scalars[1]) == ("pane" in from_table)
        # the table: three panes and the row count, filled as used
        assert [a is not None for a in gb._scalars] == [
            False, "pane" in from_table, False, "count" in from_table]

    def test_the_table_hands_the_same_array_and_the_old_staging_s_state(
            self):
        """Folds into one pane receive the SAME device array, and the
        state equals, bit for bit, what the staging before the table
        gives: one `jnp.asarray` an array, the row count and the pane."""
        import jax.numpy as jnp

        def staged_as_before(gb, cols, slots, pane, start, end):
            pad = gb.micro_batch - (end - start)
            d = {}
            for name in gb.plan.columns:
                d[name] = jnp.asarray(np.pad(
                    cols[name][start:end].astype(np.float32), (0, pad)))
                d["__valid_" + name] = None
            if isinstance(pane, np.ndarray):
                p = jnp.asarray(np.pad(pane[start:end], (0, pad)
                                       ).astype(np.uint8))
            else:
                p = jnp.asarray(pane, dtype=jnp.int32)
            return (d, jnp.asarray(np.pad(slots[start:end], (0, pad)
                                          ).astype(np.uint16)),
                    jnp.asarray(end - start, dtype=jnp.int32), p)

        cols, _ = _host_rows(12)
        slots = (np.arange(12) % 5).astype(np.int32)
        panes = [2, 2, 0, np.arange(12, dtype=np.int64) % 3, 1]
        gb, ref = _avg_max_kernel(n_panes=3), _avg_max_kernel(n_panes=3)
        seen, site = _spy(gb, "_fold")
        state, want = gb.init_state(), ref.init_state()
        for pane in panes:  # two chunks a fold: rows 0-7 and 8-11
            state = gb.fold(state, cols, slots, pane_idx=pane)
            for start, end in ((0, 8), (8, 12)):
                want = ref._fold(want, *staged_as_before(
                    ref, cols, slots, pane, start, end))
        pane_args = [staged[3] for staged in seen]
        assert all(p is gb._scalars[2] for p in pane_args[:4])
        assert pane_args[4] is pane_args[5] is gb._scalars[0]
        counts = [staged[2] for staged in seen]
        assert all(c is gb._scalars[3] for c in counts[0::2])  # full chunks
        assert sorted(state) == sorted(want)
        for comp in state:
            assert np.array_equal(np.asarray(state[comp]),
                                  np.asarray(want[comp]),
                                  equal_nan=True), comp
        assert len(gb._scalars) == 4  # three panes and the row count
        # the fold's signature is what it was — one executable a pane
        # form (scalar, vector), whatever was resident: every call after
        # a form's first is a hit of the same table entry
        assert len(site._table) == 2 and site.hits == 10
        assert site.misses + site.disk_loads == 2

    def test_reset_pane_and_fold_masked_take_the_pane_from_the_table(self):
        import jax.numpy as jnp

        gb = _avg_max_kernel(n_panes=3)
        cols, slots = _host_rows(8)
        state = gb.fold(gb.init_state(), cols, slots, pane_idx=1)
        masked, _ = _spy(gb, "_fold_m")
        resets, _ = _spy(gb, "_reset_pane")
        dev = {"temp": jnp.asarray(cols["temp"]), "__valid_temp": None,
               "hum": jnp.asarray(cols["hum"]), "__valid_hum": None}
        state = gb.fold_masked(state, dev, jnp.asarray(
            slots.astype(np.uint16)), np.arange(8) < 3, 2)
        assert masked[0][-1] is gb._scalars[2] is not None
        assert float(np.asarray(state["act"])[2, 0]) == 3.0
        state = gb.reset_pane(state, 1)
        assert resets[0][-1] is gb._scalars[1]
        act = np.asarray(state["act"])
        assert act[1].sum() == 0.0 and act[2, 0] == 3.0
        # neither is a staging: the two counters are the fold's alone
        assert (gb.transfers_total, gb.resident_total) == (1, 2)
        assert len(gb._scalars) == 4


def _avg_max_kernel(n_panes=1):
    from ekuiper_tpu.ops.aggspec import extract_kernel_plan
    from ekuiper_tpu.ops.groupby import DeviceGroupBy
    from ekuiper_tpu.sql.parser import parse_select

    plan = extract_kernel_plan(parse_select(
        "SELECT avg(temp), max(hum) FROM demo "
        "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)"))
    return DeviceGroupBy(plan, capacity=16, n_panes=n_panes, micro_batch=8)


def _host_rows(n):
    return ({"temp": np.arange(n, dtype=np.float32),
             "hum": np.ones(n, dtype=np.float32)},
            np.zeros(n, dtype=np.int32))


def _spy(gb, site):
    """(every call's arguments after the state, the jitted site itself)
    of one of a kernel's jit sites, which keeps working."""
    seen, real = [], getattr(gb, site)

    def call(state, *rest):
        seen.append(rest)
        return real(state, *rest)

    setattr(gb, site, call)
    return seen, real


# ------------------------------------------------------------------ (e)
class TestKernelNames:
    def test_program_names_stay_and_ops_carry_kuiper_scopes(self):
        """`trace_kernel_roofline` matches the PROGRAM names by substring;
        the scopes name the ops inside them."""
        import jax
        import jax.numpy as jnp

        from ekuiper_tpu.ops.aggspec import extract_kernel_plan
        from ekuiper_tpu.ops.groupby import DeviceGroupBy
        from ekuiper_tpu.sql.parser import parse_select

        stmt = parse_select(
            "SELECT deviceId, avg(temperature) AS a, count(*) AS c, "
            "min(temperature) AS mn FROM s GROUP BY deviceId, "
            "TUMBLINGWINDOW(ss, 1)")
        plan = extract_kernel_plan(stmt)
        gb = DeviceGroupBy(plan, capacity=64, n_panes=1, micro_batch=32)
        state = gb.init_state()
        cols = {"temperature": jnp.zeros(32, jnp.float32)}
        slots = jnp.zeros(32, jnp.int32)
        pane = jnp.asarray(0, jnp.int32)
        lowered = {
            "fold": jax.jit(gb._fold_impl).lower(
                state, cols, slots, jnp.asarray(32, jnp.int32), pane),
            "finalize": jax.jit(gb._finalize_impl, static_argnums=(1,)
                                ).lower(state, (True,)),
            "reset_pane": jax.jit(gb._reset_pane_impl).lower(state, pane),
            "components": jax.jit(gb._components_impl, static_argnums=(1,)
                                  ).lower(state, (True,)),
        }
        scopes = {
            "fold": ("kuiper/fold/pad", "kuiper/fold/values",
                     "kuiper/fold/scatter_act", "kuiper/fold/scatter_s1",
                     "kuiper/fold/scatter_mn"),
            "finalize": ("kuiper/finalize/pane_merge",
                         "kuiper/finalize/values"),
            "reset_pane": ("kuiper/reset_pane/fill",),
            "components": ("kuiper/components/pane_merge",
                           "kuiper/components/stack"),
        }
        for site, low in lowered.items():
            text = low.as_text(debug_info=True)
            head = next(ln for ln in text.splitlines()
                        if ln.startswith("module @"))
            assert f"module @jit__{site}_impl" in head, head
            for scope in scopes[site]:
                assert scope in text, (site, scope)
        # the jit sites' stable trace names
        assert gb._fold.rec.trace_name == "kuiper:jit:fold"
        assert gb._reset_pane.rec.trace_name == "kuiper:jit:reset_pane"
