"""Per-node metrics — analogue of eKuiper's StatManager
(reference: internal/topo/node/metric/stats_manager.go:43-213).

Each runtime node owns a StatManager recording records in/out/error, process
latency, buffer length and last-invocation/exception info; a rule's status JSON
aggregates them per node, matching the reference's /rules/{name}/status shape.
"""
from __future__ import annotations

import threading
import time as _time
from threading import get_ident as _get_ident
from typing import Any, Dict, Optional

from ..observability.histogram import LatencyHistogram
from ..observability.tracer import Tracer
from . import timex

# jax.profiler.TraceAnnotation, bound at the first stage: importing this
# module must not import jax, and a per-call import costs a stage 1 us
_TraceAnnotation = None


class _Stage:
    """One timed piece of work, opened by `StatManager.stage()`/`span()`
    where the work happens. Its body runs inside a profiler annotation
    `kuiper:<name>` (free without a profiler session, an event on the
    profiler's host plane with one); on exit a counted stage accrues wall
    and thread-CPU microseconds, a call and `rows` to the node's stage
    table, and under a traced dispatch it is a child span of whatever
    span is open on the thread. `rows` may be set inside the body when
    the count is known only afterwards. `since_ns` (perf clock) starts the
    wall interval earlier than the body — at a dispatch made on another
    thread, whose completion the body waits for.

    A counted stage that closes on the thread of its node's open cycle
    with no other counted stage open around it also adds its body's wall
    and CPU to that cycle's `staged` time (the cycle ledger,
    `StatManager.cycle_end`): one writer, no lock."""

    __slots__ = ("sm", "name", "rows", "attrs", "counted", "since_ns",
                 "_ann", "_span", "_t0", "_c0", "_own")

    def __init__(self, sm: "StatManager", name: str, rows: int,
                 attrs: Optional[dict], counted: bool,
                 since_ns: Optional[int] = None) -> None:
        self.sm = sm
        self.name = name
        self.rows = rows
        self.attrs = attrs
        self.counted = counted
        self.since_ns = since_ns

    def __enter__(self) -> "_Stage":
        global _TraceAnnotation
        if _TraceAnnotation is None:
            from jax.profiler import TraceAnnotation as _TraceAnnotation
        sm = self.sm
        self._ann = _TraceAnnotation(
            "kuiper:" + self.name, rule=sm.rule_id, op=sm.op_id,
            rows=self.rows)
        self._ann.__enter__()
        tracer = Tracer._instance
        self._span = (
            tracer.begin(sm.rule_id, sm.op_id, "stage", self.rows,
                         stage=self.name)
            if tracer is not None and tracer.current() is not None
            and tracer.is_enabled(sm.rule_id) else None)
        if self.counted:
            self._own = own = _get_ident() == sm._dispatch_tid
            if own:
                sm._dispatch_depth += 1
            # wall read outside the CPU read on both ends: the CPU interval
            # lies inside the wall interval, so cpu <= wall per call. (The
            # CPU clock is a system call; a sub-stage does not pay for it.)
            self._t0 = _time.perf_counter_ns()
            self._c0 = _time.thread_time_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self.counted:
            sm = self.sm
            cpu_ns = _time.thread_time_ns() - self._c0
            now_ns = _time.perf_counter_ns()
            if self._own:
                sm._dispatch_depth -= 1
                if sm._dispatch_depth == 0:
                    # the body's own wall: `since_ns` reaches back before
                    # this cycle began
                    sm._staged_ns += now_ns - self._t0
                    sm._staged_cpu_ns += cpu_ns
            sm.observe_stage(
                self.name, (now_ns - (self.since_ns or self._t0)) // 1000,
                self.rows, cpu_ns // 1000)
        span = self._span
        if span is not None:
            span.rows = self.rows
            span.end(self.attrs)
        self._ann.__exit__(exc_type, exc, tb)


class StatManager:
    def __init__(self, op_type: str, op_id: str, instance: int = 0) -> None:
        self.op_type = op_type
        self.op_id = op_id
        self.instance = instance
        # owning rule, stamped by Topo.add_* — drop-burst flight events
        # need attribution even when the dropping thread (an upstream
        # connector) carries no rule context
        self.rule_id: str = ""
        self._lock = threading.Lock()
        self.records_in = 0
        self.records_out = 0
        self.messages_processed = 0
        self.exceptions = 0
        # drop taxonomy: data discarded BY DESIGN (backpressure, late
        # rows, undecodable payloads) counts here with a reason label —
        # never in `exceptions`, which means operator ERRORS. Reasons:
        # buffer_full / pane_recycle / decode_error / stale_watermark /
        # shed_qos (SLO-driven shedding, runtime/control.py).
        self.dropped: Dict[str, int] = {}
        self.last_exception: str = ""
        self.last_exception_time: int = 0
        self.last_invocation: int = 0
        self.process_latency_us: int = 0
        # cumulative busy time (wall-clock in-process), the engine's
        # per-rule CPU-usage proxy (reference: /rules/usage/cpu): the
        # worker's time outside its `get`, one cycle of its loop at a time
        self.process_time_us_total: int = 0
        # the cycle ledger of the node's worker (docs/OBSERVABILITY.md,
        # "Pipeline stages"): worker wall = idle + staged + unstaged.
        # `busy` above is staged + unstaged; `staged` is the wall of the
        # outermost counted stages that closed on the worker's thread
        # during a cycle (another thread's stages on this node add
        # nothing), `unstaged` what is left of each cycle, accrued in
        # cycle_end; the same in thread-CPU time.
        self.busy_cpu_us_total: int = 0
        self.unstaged_us_total: int = 0
        self.unstaged_cpu_us_total: int = 0
        self._dispatch_tid: int = 0  # thread of the open cycle, else 0
        self._dispatch_depth: int = 0  # counted stages open on that thread
        self._staged_ns: int = 0  # of the open cycle
        self._staged_cpu_ns: int = 0
        self._cycle_t0: int = 0
        self._cycle_c0: int = 0
        self._own_cycle = False  # the open dispatch opened the cycle itself
        self._dispatch_ann = None  # the open dispatch's profiler bracket
        self.buffer_length: int = 0
        # between the stages (runtime/node.py): time this node's worker
        # waited in its empty input queue, and time senders waited in this
        # node's put for room. Neither is busy time, so neither is a stage.
        self.idle_us_total: int = 0  # one writer: the node's worker
        self.backpressure_us_total: int = 0  # many writers: under _lock
        self._started_at: Optional[int] = None
        # perf-clock ns at which the current dispatch began (a window node
        # starts its boundary's `emit` phase from it)
        self.started_perf_ns: int = 0
        # named pipeline-stage accounting (decode/upload/fold, ...): lets
        # operators see where ingest wall time goes per node — the balance
        # of the sharded ingest pipeline is tuned from these
        self.stages: Dict[str, Dict[str, int]] = {}
        # stages opened inside another stage of this node (`within=`): they
        # have counter rows like any stage, but their time is already in
        # the enclosing stage's, so sums over a node's stages leave them out
        self.nested_stages: set = set()
        # latency DISTRIBUTIONS (observability/histogram.py): the last-value
        # process_latency_us gauge cannot express a tail — these make the
        # paper's p99 claims measurable per op. proc_hist records each
        # dispatch's busy time, queue_hist each item's wait in the input
        # queue before its dispatch began (both µs, real perf clock).
        self.proc_hist = LatencyHistogram()
        self.queue_hist = LatencyHistogram()
        # queue-depth high-water marks, noted at ENQUEUE time (node.py
        # put/put_control) so a spike that drains between observations is
        # still seen. Two marks with independent read-and-reset consumers:
        # the Prometheus scrape and the health evaluator's tick (their
        # cadences differ — one shared mark would blind whichever reads
        # second). Unlocked telemetry-grade updates: a lost increment
        # under a racing put costs one sample, never correctness.
        self._qd_peak_scrape = 0
        self._qd_peak_tick = 0

    def note_queue_depth(self, n: int) -> None:
        """Record an observed input-queue occupancy (enqueue-time)."""
        if n > self._qd_peak_scrape:
            self._qd_peak_scrape = n
        if n > self._qd_peak_tick:
            self._qd_peak_tick = n

    def take_queue_peak_scrape(self) -> int:
        """Max observed depth since the last scrape (read-and-reset)."""
        p = self._qd_peak_scrape
        self._qd_peak_scrape = 0
        return p

    def take_queue_peak_tick(self) -> int:
        """Max observed depth since the last evaluator tick
        (read-and-reset)."""
        p = self._qd_peak_tick
        self._qd_peak_tick = 0
        return p

    def inc_in(self, n: int = 1) -> None:
        # clock read OUTSIDE the stats lock: a mock advance() fires timer
        # callbacks under the CLOCK lock, and those can reach a stats
        # lock (drop-oldest -> inc_dropped) — holding stats while taking
        # clock here would complete the ABBA square (utils/lockcheck.py
        # flags it; the PR 6 health_sample fix covered only one side)
        now = timex.now_ms()
        with self._lock:
            self.records_in += n
            self.last_invocation = now

    def inc_out(self, n: int = 1) -> None:
        with self._lock:
            self.records_out += n

    def inc_exception(self, err: str, n: int = 1) -> None:
        now = timex.now_ms()  # before the lock — see inc_in
        with self._lock:
            self.exceptions += n
            self.last_exception = err
            self.last_exception_time = now

    #: drop-burst flight-recorder thresholds: an event fires when a
    #: reason's cumulative count first reaches each decade — the FIRST
    #: drop is always an event (something new is being discarded), later
    #: ones only at 10x growth so a sustained storm can't flood the ring
    _BURST_DECADES = tuple(10 ** k for k in range(10))

    def inc_dropped(self, reason: str, n: int = 1, detail: str = "") -> None:
        """Count `n` items discarded for `reason` (taxonomy above) and
        record a flight-recorder drop-burst event at decade crossings."""
        with self._lock:
            old = self.dropped.get(reason, 0)
            new = old + n
            self.dropped[reason] = new
        crossed = 0
        for t in self._BURST_DECADES:
            if old < t <= new:
                crossed = t
        if crossed:
            from ..runtime.events import recorder

            recorder().record(
                "drop_burst", rule=self.rule_id, severity="warn",
                node=self.op_id, reason=reason, total=new,
                threshold=crossed,
                **({"detail": detail} if detail else {}))

    def cycle_begin(self, t_ns: Optional[int] = None) -> None:
        """Open the ledger's books on the calling thread: the worker loop
        does at the return of its `get` (`t_ns`, the clock reading that
        closed its idle time), a dispatch made outside that loop at its
        `process_begin`."""
        self._staged_ns = self._staged_cpu_ns = 0
        self._dispatch_depth = 0
        self._dispatch_tid = _get_ident()
        self._cycle_c0 = _time.thread_time_ns()
        self._cycle_t0 = t_ns or _time.perf_counter_ns()

    def cycle_end(self) -> int:
        """Close them: everything since `cycle_begin` is busy time, and
        what no stage of this thread covered is unstaged. Returns the clock
        reading, where the worker's idle time starts again."""
        cpu_ns = _time.thread_time_ns() - self._cycle_c0
        now_ns = _time.perf_counter_ns()
        self._dispatch_tid = 0
        busy_us = (now_ns - self._cycle_t0) // 1000
        cpu_us = cpu_ns // 1000
        # stage intervals lie inside the cycle's on both clocks, so neither
        # remainder is negative
        unstaged_us = busy_us - self._staged_ns // 1000
        unstaged_cpu_us = cpu_us - self._staged_cpu_ns // 1000
        with self._lock:
            self.process_time_us_total += busy_us
            self.busy_cpu_us_total += cpu_us
            self.unstaged_us_total += unstaged_us
            self.unstaged_cpu_us_total += unstaged_cpu_us
        return now_ns

    def process_begin(self) -> None:
        global _TraceAnnotation
        if _TraceAnnotation is None:
            from jax.profiler import TraceAnnotation as _TraceAnnotation
        self._started_at = timex.now_ms()
        # `kuiper:dispatch` brackets the dispatch on the profiler's host plane
        self._dispatch_ann = _TraceAnnotation(
            "kuiper:dispatch", rule=self.rule_id, op=self.op_id)
        self._dispatch_ann.__enter__()
        # a dispatch made outside the worker loop (a test, a replay) keeps
        # the ledger's books itself
        self._own_cycle = self._dispatch_tid == 0
        if self._own_cycle:
            self.cycle_begin()
        self.started_perf_ns = _time.perf_counter_ns()

    def process_end(self) -> None:
        if self._started_at is not None:
            busy_us = (_time.perf_counter_ns() - self.started_perf_ns) // 1000
            now = timex.now_ms()  # before the lock — see inc_in
            with self._lock:
                # latency follows the engine clock (mock-deterministic in
                # tests); the dispatch's own time uses a real perf counter —
                # sub-ms work must still accrue
                self.process_latency_us = (now - self._started_at) * 1000
                self.messages_processed += 1
            self.proc_hist.record(busy_us)
            self._started_at = None
            if self._own_cycle:
                self.cycle_end()
            self._dispatch_ann.__exit__(None, None, None)

    def observe_queue_wait(self, us: float) -> None:
        """One item's input-queue dwell (enqueue→dispatch), µs."""
        self.queue_hist.record(us)

    def set_buffer_length(self, n: int) -> None:
        with self._lock:
            self.buffer_length = n

    def observe_stage(self, stage: str, us: int, rows: int = 0,
                      cpu_us: int = 0) -> None:
        """Accrue `us` wall microseconds (and optionally rows and thread-CPU
        microseconds) to a named pipeline stage. Cheap enough for per-batch
        calls; `stage()` is the way to call it around work done here."""
        with self._lock:
            st = self.stages.get(stage)
            if st is None:
                st = self.stages[stage] = {
                    "calls": 0, "total_us": 0, "rows": 0, "cpu_us": 0}
            st["calls"] += 1
            st["total_us"] += int(us)
            st["rows"] += int(rows)
            st["cpu_us"] += int(cpu_us)

    def stage(self, name: str, rows: int = 0, within: Optional[str] = None,
              since_ns: Optional[int] = None, **attrs) -> _Stage:
        """Context manager around one run of pipeline stage `name`: counters
        (wall, CPU, calls, rows), a child span under a traced dispatch, and
        a `kuiper:<name>` annotation in a profiler capture. The health plane
        sums a node's stage rows as its covered busy time, so a piece inside
        a stage is timed with span() — or, where a reader needs its counters
        (it runs once a micro-batch or once a boundary, never per row), as a
        stage that names the stage it runs `within`: counted, and left out
        of the node's sums (`nested_stages`)."""
        if within is not None:
            self.nested_stages.add(name)
            attrs["within"] = within
        return _Stage(self, name, rows, attrs or None, True, since_ns)

    def span(self, name: str, rows: int = 0, **attrs) -> _Stage:
        """A sub-stage: the span and the profiler annotation of stage()
        without a counter row of its own."""
        return _Stage(self, name, rows, attrs or None, False)

    def add_backpressure(self, us: int) -> None:
        """A sender waited `us` microseconds for room in this node's queue
        (called on the sender's thread)."""
        with self._lock:
            self.backpressure_us_total += int(us)

    def health_sample(self) -> Dict[str, Any]:
        """Cheap cumulative counters for the health evaluator's per-tick
        deltas — no histogram walks (snapshot() computes percentile
        summaries; a per-tick, per-node walk of every bucket array would
        make evaluator cost scale with histogram width).

        Deliberately LOCK-FREE: evaluator ticks can fire inside a mock
        clock's advance() (which holds the clock lock), while data-path
        threads hold this StatManager's lock and call timex.now_ms()
        (inc_in, process_end) — taking `self._lock` here would be a
        clock-lock/stats-lock ABBA deadlock. Monotonic int reads are
        atomic under the GIL; a dict resized mid-iteration just retries
        (telemetry-grade: a stale sample costs one tick's precision)."""
        for _ in range(4):
            try:
                return {
                    "unstaged_us": self.unstaged_us_total,
                    "stages": {k: v["total_us"]
                               for k, v in self.stages.items()
                               if k not in self.nested_stages},
                    "dropped": sum(self.dropped.values()),
                    "in": self.records_in,
                }
            except RuntimeError:  # dict changed size during iteration
                continue
        # retries exhausted: flag the sample so the evaluator SKIPS this
        # node for the tick instead of baselining empty stages/drops —
        # the next delta would otherwise replay the node's entire
        # cumulative history as one tick's worth
        return {"unstaged_us": self.unstaged_us_total, "stages": {},
                "dropped": 0, "in": self.records_in, "partial": True}

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            out: Dict[str, Any] = {
                "records_in_total": self.records_in,
                "records_out_total": self.records_out,
                "messages_processed_total": self.messages_processed,
                "process_latency_us": self.process_latency_us,
                "process_time_us_total": self.process_time_us_total,
                "busy_cpu_us_total": self.busy_cpu_us_total,
                "unstaged_us_total": self.unstaged_us_total,
                "unstaged_cpu_us_total": self.unstaged_cpu_us_total,
                "idle_us_total": self.idle_us_total,
                "backpressure_us_total": self.backpressure_us_total,
                "buffer_length": self.buffer_length,
                "last_invocation": self.last_invocation,
                "exceptions_total": self.exceptions,
                "last_exception": self.last_exception,
                "last_exception_time": self.last_exception_time,
                "stage_timings": {k: dict(v) for k, v in self.stages.items()},
                "dropped_total": dict(self.dropped),
            }
        # percentile summaries computed OUTSIDE the stats lock (histograms
        # carry their own): p50/p90/p99/max for the status/REST layers
        out["process_latency_us_hist"] = self.proc_hist.snapshot()
        out["queue_wait_us_hist"] = self.queue_hist.snapshot()
        return out


def flatten_status(stats: Dict[str, StatManager]) -> Dict[str, Any]:
    """Build the flat {op_id_metric: value} map used by rule status JSON
    (reference: internal/topo/rule/state.go:244-275)."""
    out: Dict[str, Any] = {}
    for op_id, sm in stats.items():
        snap = sm.snapshot()
        for metric, value in snap.items():
            out[f"{sm.op_type}_{op_id}_{sm.instance}_{metric}"] = value
    return out
