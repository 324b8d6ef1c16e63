"""Runtime node fabric — analogue of eKuiper's defaultNode goroutine/channel
fabric (internal/topo/node/node.go:113-196) and the UnaryOperator run loop
(internal/topo/node/operations.go:60-130).

Each node is one worker thread with a bounded input queue. Broadcast to
multiple downstream nodes enqueues to each; on a full buffer the oldest item
is dropped unless `disable_buffer_full_discard` — the reference's drop-oldest
backpressure semantics. All thread bodies run under safe_run so a failing
operator drains its error to the topo instead of killing the process.
"""
from __future__ import annotations

import queue
import threading
import time as _time
from collections import deque
from typing import Any, Callable, List, Optional

from ..observability.tracer import Tracer, item_stats
from ..utils.infra import logger, safe_run
from ..utils.metrics import StatManager
from .events import EOF, Barrier, ErrorEvent, PreTrigger, Trigger, Watermark


#: per-thread ingest-provenance override for emissions delivered OFF the
#: dispatch thread (the fused node's async emit worker): the issuing
#: dispatch captures its provenance into the emit queue and the worker
#: installs it here for the delivery — reading the node's live
#: _cur_ingest_ms from the worker would stamp window results with batches
#: folded AFTER the boundary, under-reporting e2e exactly when emission
#: is slow
_emit_ctx = threading.local()

#: distinct "no override installed" marker: None is a VALID override value
#: (issue-time provenance was absent — the delivery must then stamp
#: nothing, not fall back to the live _cur_ingest_ms it was shielding
#: against)
_NO_OVERRIDE = object()


def _item_stamp(item: Any, attr: str = "ingest_ms") -> Optional[int]:
    """Ingest timestamp riding an item, if any. Bare lists (multi-row
    project output) can't carry attributes, so their first element speaks
    for the emission — rows of one emission share provenance. `attr` names
    another provenance stamp carried the same way (`boundary_ns`: when a
    window's result was handed downstream, perf-clock ns)."""
    ing = getattr(item, attr, None)
    if ing is None and type(item) is list and item:
        ing = getattr(item[0], attr, None)
    return ing


def _stamp_item(item: Any, ing: int, attr: str = "ingest_ms") -> None:
    """Attach the ingest timestamp (or the stamp `attr` names) to an
    outgoing item when it can hold one (dataclasses take ad-hoc
    attributes; list elements are stamped individually; bytes/str/dict
    silently can't — their e2e sample is recorded at the last attributable
    hop)."""
    try:
        if getattr(item, attr, None) is None:
            setattr(item, attr, ing)
        return
    except (AttributeError, TypeError):
        pass
    if type(item) is list:
        for x in item:
            try:
                if getattr(x, attr, None) is None:
                    setattr(x, attr, ing)
            except (AttributeError, TypeError):
                return  # homogeneous lists: first failure ends the walk


class _Tagged:
    """Envelope recording which upstream enqueued an item — barrier
    alignment (exactly-once) must distinguish input edges, and the fabric
    uses one queue per node, not one per edge."""

    __slots__ = ("item", "from_name")

    def __init__(self, item: Any, from_name: Optional[str]) -> None:
        self.item = item
        self.from_name = from_name


#: events the QoS shed gate must NEVER discard: dropping a barrier stalls
#: checkpoint alignment, dropping a watermark/trigger stalls windows —
#: shedding is a DATA-plane relief valve only
_CONTROL_EVENTS = (Barrier, Watermark, EOF, Trigger, PreTrigger, ErrorEvent)


def _item_rows(item: Any) -> int:
    """Row count an item represents, for drop accounting (a ColumnBatch
    speaks for all its rows; a bare emission list for its elements)."""
    n = getattr(item, "n", None)
    if isinstance(n, int) and n > 0:
        return n
    if type(item) is list:
        return max(len(item), 1)
    return 1


class Node:
    def __init__(
        self,
        name: str,
        op_type: str = "op",
        buffer_length: int = 1024,
        disable_buffer_full_discard: bool = False,
    ) -> None:
        self.name = name
        self.op_type = op_type
        self.inq: "queue.Queue[Any]" = queue.Queue(maxsize=buffer_length)
        self.outputs: List["Node"] = []
        self.stats = StatManager(op_type, name)
        self.disable_buffer_full_discard = disable_buffer_full_discard
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._topo = None  # set by Topo.add
        self._input_names: set = set()  # distinct upstream node names
        # barrier bookkeeping (reference barrier_handler.go):
        # tracker (qos<=1): checkpoint_id -> barriers seen, snapshot on FIRST
        # aligner (qos==2): checkpoint_id -> {blocked edges, held-back items}
        self._barrier_seen: dict = {}
        self._align: dict = {}
        self._align_done: dict = {}  # recently completed cids (bounded)
        # set by Topo.open for qos==2 rules: data items carry their sender so
        # the aligner can hold back per edge; below that, only barriers are
        # tagged (skips a per-item envelope allocation on the hot path)
        self._tag_data = False
        # queue-wait telemetry: enqueue perf timestamps, FIFO-paired with
        # the input queue (same order; deque ops are GIL-atomic). close()'s
        # wake sentinel bypasses put(), so pairing can skew by one at
        # shutdown — telemetry-grade, guarded by emptiness checks.
        self._enq_times: deque = deque()
        # ingest→emit provenance: the most recent ingest timestamp (ms,
        # engine clock) seen on a dispatched item. emit() stamps it onto
        # outgoing items so sinks can record true end-to-end latency even
        # for window emissions that happen on trigger/worker dispatches.
        self._cur_ingest_ms: Optional[int] = None
        # boundary provenance: when the window result being dispatched was
        # handed downstream by its window node (perf-clock ns); re-stamped
        # onto what this node emits for it, so the sink can close the
        # boundary's `sink` phase. None for anything but a window result.
        self._cur_boundary_ns: Optional[int] = None
        # span attributes for the CURRENT dispatch (set by subclasses,
        # e.g. the sink's e2e latency), attached to the recorded span
        self._span_attrs: Optional[dict] = None
        self._tracing_now = False  # the current dispatch has an open span
        # QoS shed gate (runtime/control.py): fraction of incoming DATA
        # items discarded before enqueue when this rule is breaching its
        # SLO. Deterministic accumulator pattern (not random) so tests
        # and replay see the same drop positions; every shed row counts
        # in the drop taxonomy under reason="shed_qos". Concurrent put()
        # races on the accumulator are telemetry-grade: the achieved
        # fraction can skew by one item, never lose the accounting.
        self._shed_frac = 0.0
        self._shed_acc = 0.0

    # ------------------------------------------------------------------ wiring
    def connect(self, downstream: "Node") -> "Node":
        self.outputs.append(downstream)
        downstream._input_names.add(self.name)
        return downstream

    # ------------------------------------------------------------------- input
    def set_shed_fraction(self, frac: float) -> None:
        """Install/clear the QoS shed gate (control plane only). 0 = off;
        clearing also resets the accumulator so a later re-shed starts
        from a clean phase."""
        self._shed_frac = max(0.0, min(float(frac), 1.0))
        if self._shed_frac == 0.0:
            self._shed_acc = 0.0

    def put(self, item: Any, from_name: Optional[str] = None) -> None:
        """Enqueue with drop-oldest on overflow (node.go:140-196)."""
        if self._shed_frac > 0.0 and not isinstance(item, _CONTROL_EVENTS):
            self._shed_acc += self._shed_frac
            if self._shed_acc >= 1.0:
                self._shed_acc -= 1.0
                # SLO-driven shedding (runtime/control.py): THIS rule's
                # input is relieved, by design, with a taxonomy reason —
                # never the global drop-oldest path below
                self.stats.inc_dropped("shed_qos", n=_item_rows(item))
                return
        entry = _Tagged(item, from_name) if from_name is not None else item
        # enqueue-clock appended BEFORE the queue insert: the worker may
        # dequeue the instant the item lands, and a missing time would
        # orphan the FIFO pairing for every later item
        self._enq_times.append(_time.perf_counter())
        if self.disable_buffer_full_discard:
            self._put_blocking(entry)
            return
        while True:
            try:
                self.inq.put_nowait(entry)
                self.stats.note_queue_depth(self.inq.qsize())
                return
            except queue.Full:
                try:
                    dropped = self.inq.get_nowait()
                    self.inq.task_done()  # dropped items count as handled
                    if self._enq_times:
                        self._enq_times.popleft()  # its wait sample goes too
                    # a backpressure drop is the fabric WORKING AS DESIGNED,
                    # not an operator error: it counts in the drop taxonomy
                    # (kuiper_node_dropped_total{reason="buffer_full"}),
                    # never in exceptions_total
                    self.stats.inc_dropped("buffer_full")
                    logger.debug("%s: buffer full, dropped %r", self.name, type(dropped))
                except queue.Empty:
                    continue

    def put_control(self, item: Any) -> None:
        """Enqueue a control event (window trigger, session timer) —
        BLOCKING, never subject to drop-oldest — while keeping the
        queue-wait clock FIFO-paired with the queue (a bare inq.put would
        desync every later wait sample)."""
        self._enq_times.append(_time.perf_counter())
        self._put_blocking(item)

    def _put_blocking(self, entry: Any) -> None:
        """Enqueue, waiting for room; a wait that began on a full queue is
        this node's backpressure on its senders
        (kuiper_op_backpressure_us_total), counted here on the sender's
        thread."""
        if self.inq.full():
            t0 = _time.perf_counter_ns()
            self.inq.put(entry)
            self.stats.add_backpressure(
                (_time.perf_counter_ns() - t0) // 1000)
        else:
            self.inq.put(entry)
        # enqueue-time high-water mark: a backpressure spike that drains
        # before the next Prometheus scrape / evaluator tick must still be
        # visible to the health plane's burn-rate math
        self.stats.note_queue_depth(self.inq.qsize())

    def send_to(self, out: "Node", item: Any) -> None:
        """Single place encoding the sender-tagging contract: barriers are
        always tagged (alignment identifies edges); data is tagged only when
        the receiver runs exactly-once (_tag_data)."""
        if getattr(out, "_tag_data", False) or isinstance(item, Barrier):
            out.put(item, self.name)
        else:
            out.put(item)

    def broadcast(self, item: Any) -> None:
        for out in self.outputs:
            self.send_to(out, item)

    # --------------------------------------------------------------- lifecycle
    def open(self) -> None:
        """Synchronous setup (on_open) on the caller thread, then start the
        worker. Matches the reference where source.Open subscribes before
        Topo.Open returns — data published right after open() is never lost."""
        self._stop.clear()
        err = safe_run(self.on_open)
        if err is not None:
            if self._topo is not None:
                self._topo.drain_error(err, self.name)
            return
        self._thread = threading.Thread(
            target=self._run_safe, name=f"node-{self.name}", daemon=True
        )
        self._thread.start()

    def close(self) -> None:
        self._stop.set()
        try:
            self.inq.put_nowait(None)  # wake the worker (it also polls at 0.2s)
        except queue.Full:
            pass

    def join(self, timeout: float = 5.0) -> None:
        if self._thread is not None:
            self._thread.join(timeout)

    def _run_safe(self) -> None:
        err = safe_run(self._run)
        if err is not None and self._topo is not None:
            self._topo.drain_error(err, self.name)

    def _run(self) -> None:
        from ..utils.rulelog import set_rule_context

        set_rule_context(getattr(self._topo, "rule_id", None))
        self.on_worker_start()
        try:
            stats = self.stats
            # the worker's wall, cut by one clock: idle while it waits in
            # get(), a cycle of the ledger (StatManager.cycle_begin) from
            # there to the next wait — the dispatch, this loop's own
            # bookkeeping and the release of the item
            t_mark = _time.perf_counter_ns()
            while not self._stop.is_set():
                # starved time (kuiper_op_idle_us_total): what the worker
                # spends in get() with nothing to dispatch
                try:
                    entry = self.inq.get(timeout=0.2)
                except queue.Empty:
                    continue
                finally:
                    now = _time.perf_counter_ns()
                    stats.idle_us_total += (now - t_mark) // 1000
                    t_mark = now
                stats.cycle_begin(now)
                item = None
                if self._enq_times:
                    try:
                        self.stats.observe_queue_wait(
                            (_time.perf_counter()
                             - self._enq_times.popleft()) * 1e6)
                    except IndexError:
                        pass  # raced another consumer draining at close
                try:
                    if entry is None:
                        continue
                    if isinstance(entry, _Tagged):
                        item, from_name = entry.item, entry.from_name
                    else:
                        item, from_name = entry, None
                    self.stats.set_buffer_length(self.inq.qsize())
                    self._dispatch(item, from_name)
                finally:
                    rows = getattr(item, "n", None)
                    if isinstance(rows, int) and rows > 1:
                        # a batch dies where its last reference goes: here,
                        # unless another thread still holds it — an object
                        # column's strings, its shared device uploads
                        with stats.stage("release", rows):
                            entry = item = None
                    # unfinished_tasks accounting backs Topo.wait_idle()
                    self.inq.task_done()
                    t_mark = stats.cycle_end()
        finally:
            self.on_close()

    def _dispatch(self, item: Any, from_name: Optional[str] = None) -> None:
        if self._align and from_name is not None:
            # exactly-once alignment in progress: items — INCLUDING later
            # checkpoints' barriers — from an edge whose barrier already
            # arrived are held back until all edges align
            # (barrier_handler.go BarrierAligner), preserving per-edge order
            for cid, st in list(self._align.items()):
                if from_name in st["blocked"]:
                    st["buffer"].append((item, from_name))
                    if len(st["buffer"]) > self.ALIGN_BUFFER_CAP:
                        # a peer edge's barrier was lost (drop-oldest
                        # backpressure or a dead upstream): force-complete —
                        # degrade this checkpoint to at-least-once instead of
                        # stalling the edge and growing the buffer forever
                        logger.warning(
                            "%s: alignment %s overflowed, degrading to "
                            "at-least-once", self.name, cid)
                        del self._align[cid]
                        self._mark_align_done(cid)
                        self.on_barrier(Barrier(checkpoint_id=cid, qos=1))
                        for it, fn in st["buffer"]:
                            self._dispatch(it, fn)
                    return
        if isinstance(item, Barrier):
            self._handle_barrier(item, from_name)
            return
        span = self._span_begin(item)
        self._tracing_now = span is not None
        ing = _item_stamp(item)
        if ing is not None:
            # keep the LAST seen provenance (not reset on control events):
            # window emissions fire on trigger dispatches, where the freshest
            # contributing batch's ingest time is exactly the right stamp
            self._cur_ingest_ms = ing
        self._cur_boundary_ns = _item_stamp(item, "boundary_ns")
        self.stats.inc_in()
        self.stats.process_begin()
        try:
            if isinstance(item, Watermark):
                self.on_watermark(item)
            elif isinstance(item, EOF):
                self.on_eof(item)
            elif isinstance(item, Trigger):
                self.on_trigger(item)
            elif isinstance(item, PreTrigger):
                self.on_pre_trigger(item)
            else:
                self.process(item)
        except Exception as exc:  # per-item containment: skip poisoned items
            self.stats.inc_exception(str(exc))
            logger.warning("%s error: %s", self.name, exc)
            self.on_error(exc, item)
        finally:
            self.stats.process_end()
            if span is not None:
                self._tracing_now = False
                self._span_end(span)

    def _span_begin(self, item: Any, kind: Optional[str] = None):
        """Open a span of this node around `item`'s handling when the rule
        is traced (one attribute check when no rule is): child of the span
        that emitted `item`, else of the span open on this thread, else a
        root (a source's input, a timer's Trigger). `kind` names what was
        handled where no item says it."""
        tracer = Tracer._instance
        if not (tracer is not None and tracer.any_enabled
                and self._topo is not None
                and tracer.is_enabled(getattr(self._topo, "rule_id", ""))):
            return None
        rows = 0
        if kind is None:
            kind, rows = item_stats(item)
        return tracer.begin(self._topo.rule_id, self.name, kind, rows,
                            ctx=tracer.lookup(item))

    def _span_end(self, span) -> None:
        attrs, self._span_attrs = self._span_attrs, None
        span.end(attrs)

    # ------------------------------------------------------------- overridables
    def on_open(self) -> None:
        """Synchronous setup on the opener's thread (subscriptions, timers).
        Must be fast — Topo.open() blocks on it. Slow work (jit warmup)
        belongs in on_worker_start."""

    def on_worker_start(self) -> None:
        """First action on the worker thread, before the dispatch loop —
        e.g. warmup compiles that must not block Topo.open()."""

    def on_close(self) -> None:
        pass

    def process(self, item: Any) -> None:
        """Data item (ColumnBatch / collection / row)."""
        self.emit(item)

    def _handle_barrier(self, barrier: Barrier, from_name: Optional[str]) -> None:
        """Fan-in-correct barrier handling (barrier_handler.go:23-88).

        qos<=1 (at-least-once) BarrierTracker: snapshot + forward on the
        FIRST arrival of a checkpoint id, swallow the rest — no duplicate
        barriers downstream, no multi-snapshot.

        qos==2 (exactly-once) BarrierAligner: after the first arrival, hold
        back items from edges whose barrier already came, snapshot only when
        every input edge's barrier arrived (a consistent cut), then replay
        the held-back items.
        """
        cid = barrier.checkpoint_id
        n = max(len(self._input_names), 1)
        if barrier.qos >= 2 and n > 1:
            if cid in self._align_done:
                # a peer's late barrier for a checkpoint that already
                # completed (alignment overflow degraded it) — swallow it,
                # re-opening alignment would stall that edge forever
                return
            st = self._align.get(cid)
            if st is None:
                st = {"blocked": set(), "buffer": []}
                self._align[cid] = st
            st["blocked"].add(from_name)
            if len(st["blocked"]) >= n:
                del self._align[cid]
                self._mark_align_done(cid)
                self.on_barrier(barrier)
                for item, fn in st["buffer"]:
                    self._dispatch(item, fn)
            return
        seen = self._barrier_seen.get(cid, 0)
        if seen == 0:
            self.on_barrier(barrier)
        if seen + 1 >= n:
            self._barrier_seen.pop(cid, None)
        else:
            self._barrier_seen[cid] = seen + 1
            if len(self._barrier_seen) > 64:
                # stale ids (a peer edge lost its barrier to backpressure):
                # drop the oldest bookkeeping, the checkpoint already fired
                oldest = min(self._barrier_seen)
                del self._barrier_seen[oldest]

    #: held-back items per in-flight alignment before it force-completes
    ALIGN_BUFFER_CAP = 10_000

    def _mark_align_done(self, cid: int) -> None:
        self._align_done[cid] = True
        while len(self._align_done) > 16:
            del self._align_done[next(iter(self._align_done))]

    def on_barrier(self, barrier: Barrier) -> None:
        """Snapshot own state, ack the coordinator, forward downstream.
        Called exactly once per checkpoint id (see _handle_barrier).

        A snapshot failure (e.g. the fused node's bounded async-emit drain
        timing out on a wedged device fetch) must fail THIS CHECKPOINT, not
        the rule: skip the ack — the checkpoint never completes and a later
        one retries — but still forward the barrier so downstream aligners
        never stall, and keep the worker thread alive."""
        if self._topo is not None:
            try:
                state = self.snapshot_state()
            except Exception as exc:
                logger.error(
                    "%s: snapshot for checkpoint %d failed (%s) — skipping "
                    "ack; this checkpoint will not commit, a later one "
                    "retries", self.name, barrier.checkpoint_id, exc)
                # surface in /rules metrics: a PERSISTENTLY failing snapshot
                # silently pins recovery to an old checkpoint otherwise
                self.stats.inc_exception(f"snapshot failed: {exc}")
            else:
                self._topo.checkpoint_ack(self.name, barrier, state)
        self.broadcast(barrier)

    def on_watermark(self, wm: Watermark) -> None:
        self.broadcast(wm)

    def on_eof(self, eof: EOF) -> None:
        self.broadcast(eof)

    def on_trigger(self, trig: Trigger) -> None:
        pass

    def on_pre_trigger(self, pre: PreTrigger) -> None:
        pass

    def on_error(self, exc: Exception, item: Any) -> None:
        """Per-item error: forwarded downstream as data when send_error."""

    def extra_pending(self) -> int:
        """Work in flight OUTSIDE the input queue (e.g. the source's decode
        ring) — Topo.wait_idle counts it so 'idle' still means no data
        anywhere in the DAG."""
        return 0

    # ------------------------------------------------------------------ output
    def emit(self, item: Any, count: int = 1) -> None:
        tracer = Tracer._instance
        if tracer is not None and tracer.any_enabled:
            tracer.tag(item)  # the open span's context follows the item
        ing = getattr(_emit_ctx, "ingest_ms", _NO_OVERRIDE)
        if ing is _NO_OVERRIDE:
            ing = self._cur_ingest_ms
        if ing is not None:
            _stamp_item(item, ing)  # provenance follows the item too
        if self._cur_boundary_ns is not None:
            _stamp_item(item, self._cur_boundary_ns, "boundary_ns")
        self.stats.inc_out(count)
        self.broadcast(item)

    # ------------------------------------------------------------------- state
    def snapshot_state(self) -> Optional[dict]:
        return None

    def restore_state(self, state: dict) -> None:
        pass
