"""Per-rule span tracing with a local, queryable span store (analogue of
pkg/tracer/manager.go:36-171 and the /trace REST routes).

Tracing is enabled per rule (with an optional strategy: "always" records
every span, "head" samples the first N spans per second). A span is opened
BEFORE the work it times and closed after it: its start is wall-clock
nanoseconds (`time.time_ns()`, the clock the jax profiler's host plane
uses), its duration comes from `perf_counter_ns`. Two kinds of span exist:

- a DISPATCH span, opened by the node fabric around one item's dispatch
  (runtime/node.py) — its parent is the span during which the item was
  emitted (the (trace id, span id) pair rides the item across the queue
  hop via tag()/lookup()); a source's dispatch and a timer-caused
  Trigger's dispatch have no causing span and are the roots of a trace;
- a STAGE span, opened by `StatManager.stage()/span()` (utils/metrics.py)
  around one named piece of work — its parent is whatever span is open on
  its thread: the dispatch span, or the enclosing stage for a sub-stage.

The open span of a thread is thread-local context; work handed to another
thread (decode pool, async emit worker) carries the context with it and
installs it there with `set_current`. A dispatch span's self time
(duration minus its stage children) is the node's untimed Python.

The store is a bounded in-memory ring per rule (the reference's local span
storage with remote-collector export gated out — zero egress here)."""
from __future__ import annotations

import itertools
import threading
import time as _time
import weakref
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Tuple

from ..utils import timex

_local = threading.local()

#: rule label of nodes owned by a shared source subtopo (runtime/subtopo.py):
#: they serve every attached rule, so they are traced while ANY rule is
SHARED_RULE = "__shared__"

#: (trace id, span id) — what rides an item across a queue hop and what a
#: thread holds while a span is open on it
Ctx = Tuple[str, str]


class Span:
    __slots__ = ("trace_id", "span_id", "parent_id", "rule_id", "op",
                 "start_ns", "duration_us", "kind", "rows", "attrs",
                 "stage", "_t0", "_prev", "_tracer")

    def __init__(self, trace_id, span_id, parent_id, rule_id, op, start_ns,
                 duration_us, kind, rows, attrs=None, stage="") -> None:
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.rule_id = rule_id
        self.op = op
        self.start_ns = start_ns  # wall clock, taken before the work
        self.duration_us = duration_us
        self.kind = kind
        self.rows = rows
        # extra key→value span attributes (e.g. the sink's e2e_ms latency);
        # None for the common attribute-less span
        self.attrs = attrs
        self.stage = stage  # "" for a dispatch span

    def end(self, attrs: Optional[dict] = None) -> None:
        """Close a span `Tracer.begin` opened: take its duration, hand the
        thread back to the span that was open before it, and admit it to
        the rule's ring (and the OTLP tee)."""
        self.duration_us = (_time.perf_counter_ns() - self._t0) // 1000
        _local.ctx = self._prev
        if attrs:
            self.attrs = attrs
        self._tracer._admit(self)

    def to_dict(self) -> Dict[str, Any]:
        out = {
            "traceId": self.trace_id, "spanId": self.span_id,
            "parentSpanId": self.parent_id, "rule": self.rule_id,
            "op": self.op, "startTimeMs": self.start_ns // 1_000_000,
            "startTimeUnixNano": self.start_ns,
            "durationUs": self.duration_us, "kind": self.kind,
            "rows": self.rows,
        }
        if self.stage:
            out["stage"] = self.stage
        if self.attrs:
            out["attributes"] = dict(self.attrs)
        return out


class Tracer:
    _instance: Optional["Tracer"] = None

    def __init__(self, max_spans_per_rule: int = 2048) -> None:
        self._enabled: Dict[str, str] = {}  # rule_id -> strategy
        self._spans: Dict[str, deque] = {}
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self.max_spans = max_spans_per_rule
        self.any_enabled = False  # hot-path fast check, no lock
        self._head_window: Dict[str, tuple] = {}  # head sampling buckets
        # trace propagation across queue hops: emitted items are tagged with
        # the emitting span's context, keyed by id() with a weakref
        # cleanup (many item types — dataclasses with eq — are unhashable,
        # so WeakKeyDictionary can't hold them)
        self._item_traces: Dict[int, tuple] = {}
        # non-weakref-able items (plain lists/dicts — e.g. multi-row project
        # output) can't register a cleanup callback, so they live in a
        # BOUNDED insertion-ordered map with explicit oldest-first eviction.
        # id() reuse after gc can mis-associate a stale entry with a new
        # object; the map is small and short-lived, and a wrong trace id on
        # one span is a telemetry blemish, not a correctness issue.
        self._fallback_traces: "OrderedDict[int, Ctx]" = OrderedDict()
        # optional remote tee (observability/otlp.py) — every span the local
        # store admits is also handed to the exporter, mirroring the
        # reference's dual local+OTLP export (pkg/tracer/manager.go:62-76)
        self.exporter = None

    @classmethod
    def global_instance(cls) -> "Tracer":
        if cls._instance is None:
            cls._instance = Tracer()
        return cls._instance

    # ------------------------------------------------------------- management
    #: "head" sampling records at most this many spans per rule per second
    HEAD_SPANS_PER_SEC = 32

    def enable(self, rule_id: str, strategy: str = "always") -> None:
        if strategy not in ("always", "head"):
            from ..utils.infra import EngineError

            raise EngineError(
                f"unknown trace strategy {strategy!r} (want always|head)")
        with self._lock:
            self._enabled[rule_id] = strategy
            for rid in (rule_id, SHARED_RULE):
                self._spans.setdefault(rid, deque(maxlen=self.max_spans))
            self.any_enabled = True

    def disable(self, rule_id: str) -> None:
        with self._lock:
            self._enabled.pop(rule_id, None)
            self.any_enabled = bool(self._enabled)

    def is_enabled(self, rule_id: str) -> bool:
        return rule_id in self._enabled or (
            rule_id == SHARED_RULE and self.any_enabled)

    def set_exporter(self, exporter) -> None:
        """Install (or clear, with None) the remote OTLP tee."""
        old, self.exporter = self.exporter, exporter
        if old is not None:
            old.close()

    # ------------------------------------------------------------- recording
    @staticmethod
    def current() -> Optional[Ctx]:
        """Context of the span open on this thread, if any."""
        return getattr(_local, "ctx", None)

    @staticmethod
    def set_current(ctx: Optional[Ctx]) -> None:
        """Install a carried context on this thread (a worker picking up
        work another thread's span handed over), or clear it with None."""
        _local.ctx = ctx

    def begin(self, rule_id: str, op: str, kind: str, rows: int = 0,
              ctx: Optional[Ctx] = None, stage: str = "") -> Span:
        """Open a span NOW and make it this thread's current one, until its
        `end()`. Its parent is `ctx` (a context carried by the item), else the span
        already open on this thread, else nothing: a new trace's root."""
        prev = getattr(_local, "ctx", None)
        if ctx is None:
            ctx = prev
        if ctx is None:
            trace_id, parent_id = f"t{next(self._ids):08x}", ""
        else:
            trace_id, parent_id = ctx
        # kuiperlint: ignore[clock-discipline]: a span's start must sit on the profiler's host clock (wall ns) so the rule's trace and the device trace line up; the mock clock has 1 ms steps
        start_ns = _time.time_ns()
        span = Span(trace_id, f"s{next(self._ids):08x}", parent_id, rule_id,
                    op, start_ns, 0, kind, rows, stage=stage)
        span._prev = prev
        span._tracer = self
        _local.ctx = (trace_id, span.span_id)
        span._t0 = _time.perf_counter_ns()
        return span

    def _admit(self, span: Span) -> None:
        rule_id = span.rule_id
        # ENGINE-clock seconds for head sampling: mock-clock tests see
        # deterministic sampling windows (advance() moves the bucket
        # boundary). Read BEFORE self._lock — a mock advance fires timer
        # callbacks holding the clock lock, and those can reach tag()
        # (which takes self._lock), so reading the clock under our lock
        # would invert the clock-first order utils/lockcheck.py polices
        sec = timex.now_ms() // 1000
        with self._lock:
            if self._enabled.get(rule_id) == "head":
                wsec, n = self._head_window.get(rule_id, (sec, 0))
                if wsec != sec:
                    wsec, n = sec, 0
                if n >= self.HEAD_SPANS_PER_SEC:
                    self._head_window[rule_id] = (wsec, n)
                    return
                self._head_window[rule_id] = (wsec, n + 1)
            ring = self._spans.get(rule_id)
            if ring is not None:
                ring.append(span)
        if ring is not None and self.exporter is not None:
            self.exporter.on_span(span)

    #: bounded size of the non-weakref-able item→trace fallback map
    FALLBACK_CAP = 4096

    def tag(self, item: Any) -> None:
        """Remember that `item` was emitted under this thread's open span,
        so the dispatch that receives it can name its parent."""
        ctx = self.current()
        if ctx is None:
            return
        key = id(item)
        try:
            ref = weakref.ref(
                item, lambda _r, k=key: self._item_traces.pop(k, None))
        except TypeError:
            # not weakref-able (plain list/dict): bounded fallback map so
            # the trace still survives the queue hop to the next node
            with self._lock:
                self._fallback_traces[key] = ctx
                self._fallback_traces.move_to_end(key)
                while len(self._fallback_traces) > self.FALLBACK_CAP:
                    self._fallback_traces.popitem(last=False)
            return
        self._item_traces[key] = (ref, ctx)

    def lookup(self, item: Any) -> Optional[Ctx]:
        got = self._item_traces.get(id(item))
        if got is not None and got[0]() is item:
            return got[1]
        return self._fallback_traces.get(id(item))

    # --------------------------------------------------------------- queries
    def rule_traces(self, rule_id: str, limit: int = 50) -> List[str]:
        """Most recent trace ids of a rule (reference /trace/rule/{id})."""
        with self._lock:
            ring = self._spans.get(rule_id)
            if not ring:
                return []
            seen: List[str] = []
            for span in reversed(ring):
                if span.trace_id not in seen:
                    seen.append(span.trace_id)
                if len(seen) >= limit:
                    break
            return seen

    def trace(self, trace_id: str) -> List[Dict[str, Any]]:
        """All spans of one trace (reference /trace/{id})."""
        with self._lock:
            out = []
            for ring in self._spans.values():
                out.extend(s.to_dict() for s in ring if s.trace_id == trace_id)
            out.sort(key=lambda s: s["startTimeUnixNano"])
            return out

    def rule_spans(self, rule_id: str, limit: int = 200) -> List[Dict[str, Any]]:
        with self._lock:
            ring = self._spans.get(rule_id)
            if not ring:
                return []
            return [s.to_dict() for s in list(ring)[-limit:]]


def item_stats(item: Any) -> tuple:
    """(kind, row count) of a dispatched item for span annotation."""
    kind = type(item).__name__
    n = getattr(item, "n", None)
    if n is None:
        if isinstance(item, list):
            n = len(item)
        else:
            n = 1
    return kind, int(n)
