"""Host-path window operators — analogue of eKuiper's WindowOperator v1/v2
(internal/topo/node/window_op.go:235 execProcessingWindow,
event_window_trigger.go:112 execEventWindow) and WatermarkOp
(watermark_op.go:33-170).

These buffer rows and emit WindowTuples at triggers. They serve the window
types / options the fused device kernel doesn't take (sliding, session,
state, event-time, trigger conditions); the aggregation over their output
is still batch-vectorized downstream where possible.
"""
from __future__ import annotations

import numpy as np

from typing import Any, List, Optional

from ..data.batch import ColumnBatch
from ..data.rows import Row, Tuple, WindowRange, WindowTuples
from ..sql import ast
from ..sql.eval import Evaluator
from ..utils import timex
from .events import EOF, Trigger, Watermark
from .node import Node


class WatermarkNode(Node):
    """Generates watermarks from event timestamps, drops late events
    (reference: watermark_op.go — lateTolerance drop + ordered release)."""

    def __init__(self, name: str, late_tolerance_ms: int = 0, **kw) -> None:
        super().__init__(name, op_type="op", **kw)
        self.late_tolerance = late_tolerance_ms
        self.max_ts = 0
        self.dropped = 0

    def process(self, item: Any) -> None:
        if isinstance(item, ColumnBatch):
            # columnar path: late-drop by mask, order by timestamp, forward
            # the batch WITHOUT exploding to rows (the columnar spine
            # continues into the window operator)
            ts = item.timestamps
            if ts is None:
                ts = np.zeros(item.n, dtype=np.int64)
            wm = self.max_ts - self.late_tolerance
            keep = ts >= wm
            n_late = int(item.n - keep.sum())
            if n_late:
                self.dropped += n_late
                self.stats.inc_dropped("stale_watermark", n=n_late)
                idx = np.nonzero(keep)[0]
                item = item.take(idx)
                ts = ts[idx]
            if item.n:
                self.max_ts = max(self.max_ts, int(ts.max()))
                order = np.argsort(ts, kind="stable")
                if not np.array_equal(order, np.arange(item.n)):
                    item = item.take(order)
                self.emit(item, count=item.n)
        elif isinstance(item, Row):
            if item.timestamp < self.max_ts - self.late_tolerance:
                self.dropped += 1
                self.stats.inc_dropped("stale_watermark")
            else:
                self.max_ts = max(self.max_ts, item.timestamp)
                self.emit(item)
        else:
            self.emit(item)
            return
        new_wm = self.max_ts - self.late_tolerance
        if new_wm > 0:
            self.broadcast(Watermark(ts=new_wm))

    def watermark_ts(self) -> Optional[int]:
        """Current watermark (None until one is established) — the health
        plane's watermark-lag probe (observability/health.py) reads this
        per tick; lag = engine clock − watermark. Mirrors the broadcast
        guard in `_on`: a tolerance-adjusted value ≤ 0 was never emitted
        downstream and must not read as a (wildly lagging) watermark."""
        wm = self.max_ts - self.late_tolerance
        if wm <= 0:
            return None
        return wm

    def snapshot_state(self) -> Optional[dict]:
        return {"max_ts": self.max_ts}

    def restore_state(self, state: dict) -> None:
        self.max_ts = state.get("max_ts", 0)


class WindowNode(Node):
    """Buffering window operator, all types, processing- or event-time."""

    def __init__(
        self,
        name: str,
        window: ast.Window,
        is_event_time: bool = False,
        rule_id: str = "",
        **kw,
    ) -> None:
        super().__init__(name, op_type="op", **kw)
        self.window = window
        self.is_event_time = is_event_time
        self.ev = Evaluator(rule_id=rule_id)
        self.buffer: List[Row] = []
        self.length_ms = window.length_ms()
        self.interval_ms = window.interval_ms()
        self.delay_ms = window.delay_ms()
        self.wt = window.window_type
        # count window
        self.count_len = window.length or 0
        self.count_interval = window.interval or self.count_len
        self._rows_since_emit = 0
        # session
        self._session_start: Optional[int] = None
        self._session_timer = None
        self._session_cap_timer = None
        # state window
        self._state_open = False
        # event-time bookkeeping
        self._next_emit_end: Optional[int] = None
        self._timer = None
        # event-time sliding: rows that already triggered their window
        # (id-keyed — mutating data objects leaked state, VERDICT weak#7)
        self._slid_ids: set = set()
        # columnar spine: tumbling/hopping buffer ColumnBatches whole and
        # explode to rows only at emit, only for selected rows. A window
        # FILTER rides along when it compiles to a vectorized host closure;
        # otherwise the row path below handles everything.
        self._vfilter = None
        self._use_bbuf = self.wt in (
            ast.WindowType.TUMBLING_WINDOW, ast.WindowType.HOPPING_WINDOW)
        if window.filter is not None and self._use_bbuf:
            from ..sql.compiler import try_compile

            self._vfilter = try_compile(window.filter, mode="host")
            if self._vfilter is None:
                self._use_bbuf = False
        self.bbuf: List[ColumnBatch] = []

    # ----------------------------------------------------------------- open
    def on_open(self) -> None:
        if self.is_event_time:
            return
        if self.wt in (ast.WindowType.TUMBLING_WINDOW, ast.WindowType.HOPPING_WINDOW):
            self._schedule_next_tick()

    def on_close(self) -> None:
        for t in (self._timer, self._session_timer, self._session_cap_timer):
            if t is not None:
                t.stop()

    def _tick_interval(self) -> int:
        if self.wt == ast.WindowType.TUMBLING_WINDOW:
            return self.length_ms
        return self.interval_ms or self.length_ms

    def _schedule_next_tick(self) -> None:
        now = timex.now_ms()
        interval = self._tick_interval()
        # epoch-aligned boundaries like the reference's getAlignedWindowEndTime
        next_end = timex.align_to_window(now + 1, interval)
        # the trigger carries the scheduled boundary: a real clock calls
        # back with the time it woke at, which lies past the grid
        self._timer = timex.after(
            next_end - now,
            lambda ts, end=next_end: self.put_control(Trigger(ts=end)))

    # --------------------------------------------------------------- ingest
    def process(self, item: Any) -> None:
        if isinstance(item, ColumnBatch):
            if self._use_bbuf:
                self._ingest_batch(item)
                return
            rows: List[Row] = item.to_tuples()
        elif isinstance(item, Row):
            # single rows (incl. JoinTuples from lookup joins) keep the row
            # buffer; trigger paths merge it with the columnar buffer
            rows = [item]
        else:
            self.emit(item)
            return
        if self.window.filter is not None:
            rows = [r for r in rows if self.ev.eval_condition(self.window.filter, r)]
        for r in rows:
            self._ingest_row(r)

    # ------------------------------------------------------- columnar buffer
    def _ingest_batch(self, batch: ColumnBatch) -> None:
        """Tumbling/hopping: batches buffer WHOLE; no per-row work at
        ingest. Selection/eviction happen on the timestamp arrays at
        trigger time, and rows materialize only when a window emits."""
        if self._vfilter is not None and batch.n:
            try:
                mask = np.broadcast_to(np.asarray(
                    self._vfilter(batch.columns), dtype=np.bool_),
                    (batch.n,)).copy()
                for c in self._vfilter.columns:
                    # null filter columns exclude the row, matching the
                    # row evaluator and FilterNode (nodes_ops.py)
                    mask &= batch.is_valid(c)
            except Exception:
                mask = np.array([
                    self.ev.eval_condition(self.window.filter, r)
                    for r in batch.to_tuples()], dtype=np.bool_)
            if not mask.all():
                batch = batch.take(np.nonzero(mask)[0])
        if batch.n:
            self.bbuf.append(batch)

    def _bts(self, batch: ColumnBatch):
        if batch.timestamps is None:
            return np.zeros(batch.n, dtype=np.int64)
        return batch.timestamps

    def _bbuf_select(self, start: int, end: int) -> List[Row]:
        """Materialize rows with start <= ts < end (ts-ordered batches)."""
        out: List[Row] = []
        for batch in self.bbuf:
            ts = self._bts(batch)
            mask = (ts >= start) & (ts < end)
            if mask.all():
                out.extend(batch.to_tuples())
            elif mask.any():
                out.extend(batch.take(np.nonzero(mask)[0]).to_tuples())
        return out

    def _bbuf_evict_before(self, cutoff: int) -> None:
        kept: List[ColumnBatch] = []
        for batch in self.bbuf:
            ts = self._bts(batch)
            mask = ts >= cutoff
            if mask.all():
                kept.append(batch)
            elif mask.any():
                kept.append(batch.take(np.nonzero(mask)[0]))
        self.bbuf = kept

    def _bbuf_all_rows(self) -> List[Row]:
        out: List[Row] = []
        for batch in self.bbuf:
            out.extend(batch.to_tuples())
        return out

    def _ingest_row(self, r: Row) -> None:
        wt = self.wt
        if wt == ast.WindowType.COUNT_WINDOW:
            self.buffer.append(r)
            if len(self.buffer) > self.count_len:
                del self.buffer[: len(self.buffer) - self.count_len]
            self._rows_since_emit += 1
            if self._rows_since_emit >= self.count_interval:
                self._rows_since_emit = 0
                self._emit_window(list(self.buffer), WindowRange(0, timex.now_ms()))
            return
        if wt == ast.WindowType.STATE_WINDOW:
            if not self._state_open:
                if self.ev.eval_condition(self.window.begin_condition, r):
                    self._state_open = True
                    self.buffer = [r]
                return
            self.buffer.append(r)
            if self.ev.eval_condition(self.window.emit_condition, r):
                self._emit_window(self.buffer, WindowRange(0, timex.now_ms()))
                self.buffer = []
                self._state_open = False
            return
        if wt == ast.WindowType.SESSION_WINDOW and not self.is_event_time:
            now = timex.now_ms()
            if not self.buffer:
                self._session_start = now
                if self.length_ms > 0:
                    self._session_cap_timer = timex.after(
                        self.length_ms, lambda ts: self.put_control(Trigger(ts=ts, tag="cap"))
                    )
            self.buffer.append(r)
            if self._session_timer is not None:
                self._session_timer.stop()
            timeout = self.interval_ms or self.length_ms
            self._session_timer = timex.after(
                timeout, lambda ts: self.put_control(Trigger(ts=ts, tag="gap"))
            )
            return
        if wt == ast.WindowType.SLIDING_WINDOW and not self.is_event_time:
            now = timex.now_ms()
            self.buffer.append(r)
            self._evict_before(now - self.length_ms - self.delay_ms)
            should = True
            if self.window.trigger_condition is not None:
                should = self.ev.eval_condition(self.window.trigger_condition, r)
            if should:
                if self.delay_ms > 0:
                    t0 = now
                    timex.after(
                        self.delay_ms,
                        lambda ts: self.put_control(Trigger(ts=ts, tag=("delayed", t0))),
                    )
                else:
                    self._emit_window(
                        [x for x in self.buffer if x.timestamp > now - self.length_ms],
                        WindowRange(now - self.length_ms, now),
                    )
            return
        # tumbling/hopping (processing or event time), event-time session/sliding
        self.buffer.append(r)
        if self.is_event_time:
            return

    # -------------------------------------------------------------- triggers
    def on_trigger(self, trig: Trigger) -> None:
        wt = self.wt
        if wt in (ast.WindowType.TUMBLING_WINDOW, ast.WindowType.HOPPING_WINDOW):
            end = trig.ts
            start = end - self.length_ms
            if wt == ast.WindowType.TUMBLING_WINDOW:
                rows = self._bbuf_all_rows() + self.buffer
                self.bbuf = []
                self.buffer = []
            else:
                # windows are [start, end); the upper bound matters — a row
                # landing in the same ms as the tick must count once (in the
                # next window), not in both
                rows = self._bbuf_select(start, end) + [
                    r for r in self.buffer if start <= r.timestamp < end]
                cutoff = end - self.length_ms + (self.interval_ms or 0)
                self._bbuf_evict_before(cutoff)
                self._evict_before(cutoff)
            self._emit_window(rows, WindowRange(start, end))
            self._schedule_next_tick()
            return
        if wt == ast.WindowType.SESSION_WINDOW:
            if trig.tag == "gap" or trig.tag == "cap":
                if self.buffer:
                    self._emit_window(
                        self.buffer,
                        WindowRange(self._session_start or 0, trig.ts),
                    )
                    self.buffer = []
                if self._session_cap_timer is not None:
                    self._session_cap_timer.stop()
            return
        if wt == ast.WindowType.SLIDING_WINDOW and isinstance(trig.tag, tuple):
            _, t0 = trig.tag
            start = t0 - self.length_ms
            end = t0 + self.delay_ms
            rows = [x for x in self.buffer if start < x.timestamp <= end]
            self._emit_window(rows, WindowRange(start, end))
            self._evict_before(timex.now_ms() - self.length_ms - self.delay_ms)
            return

    def on_watermark(self, wm: Watermark) -> None:
        """Event-time triggering (event_window_trigger.go:30-112)."""
        if not self.is_event_time:
            self.broadcast(wm)
            return
        wt = self.wt
        if wt in (ast.WindowType.TUMBLING_WINDOW, ast.WindowType.HOPPING_WINDOW):
            interval = self._tick_interval()
            if self._next_emit_end is None:
                # first window end at the next aligned boundary past the
                # earliest buffered event
                candidates = [int(self._bts(b).min())
                              for b in self.bbuf if b.n]
                candidates += [r.timestamp for r in self.buffer]
                if not candidates:
                    self.broadcast(wm)
                    return
                self._next_emit_end = timex.align_to_window(
                    min(candidates) + 1, interval)
            while self._next_emit_end is not None and wm.ts >= self._next_emit_end:
                end = self._next_emit_end
                start = end - self.length_ms
                # [start, end): row at exactly `end` opens the next window
                rows = self._bbuf_select(start, end) + [
                    r for r in self.buffer if start <= r.timestamp < end]
                cutoff = (end if wt == ast.WindowType.TUMBLING_WINDOW
                          else end - self.length_ms + interval)
                self._bbuf_evict_before(cutoff)
                self._evict_before(cutoff)
                self._emit_window(rows, WindowRange(start, end))
                self._next_emit_end = end + interval
        elif wt == ast.WindowType.SLIDING_WINDOW:
            # trigger one window per event whose (ts + delay) has passed;
            # already-triggered rows tracked by identity, not by mutating
            # the data objects
            ready = [r for r in self.buffer if r.timestamp + self.delay_ms <= wm.ts
                     and id(r) not in self._slid_ids]
            for r in ready:
                t0 = r.timestamp
                rows = [
                    x for x in self.buffer
                    if t0 - self.length_ms < x.timestamp <= t0 + self.delay_ms
                ]
                if self.window.trigger_condition is None or self.ev.eval_condition(
                    self.window.trigger_condition, r
                ):
                    self._emit_window(
                        rows, WindowRange(t0 - self.length_ms, t0 + self.delay_ms)
                    )
                self._slid_ids.add(id(r))
            self._evict_before(wm.ts - self.length_ms - self.delay_ms)
            self._slid_ids &= {id(r) for r in self.buffer}
        elif wt == ast.WindowType.SESSION_WINDOW:
            timeout = self.interval_ms or self.length_ms
            self.buffer.sort(key=lambda r: r.timestamp)
            while self.buffer:
                # find a complete session fully below the watermark
                session: List[Row] = [self.buffer[0]]
                for r in self.buffer[1:]:
                    if r.timestamp - session[-1].timestamp > timeout:
                        break
                    session.append(r)
                last = session[-1].timestamp
                if last + timeout <= wm.ts:
                    self._emit_window(
                        session,
                        WindowRange(session[0].timestamp, last + timeout),
                    )
                    self.buffer = self.buffer[len(session):]
                else:
                    break
        self.broadcast(wm)

    def on_eof(self, eof: EOF) -> None:
        # flush whatever is buffered (trial/bounded runs)
        rows = list(self.buffer) + self._bbuf_all_rows()
        if rows:
            now = timex.now_ms()
            self._emit_window(rows, WindowRange(now - self.length_ms, now))
            self.buffer = []
            self.bbuf = []
        self.broadcast(eof)

    def occupancy_rows(self) -> int:
        """Rows buffered awaiting a trigger (row + columnar buffers) —
        the host window path's analogue of pane-ring occupancy, sampled
        by the health evaluator."""
        return len(self.buffer) + sum(b.n for b in self.bbuf)

    # ----------------------------------------------------------------- emit
    def _emit_window(self, rows: List[Row], wr: WindowRange) -> None:
        self.emit(WindowTuples(content=list(rows), window_range=wr))

    def _evict_before(self, ts: int) -> None:
        """Drop rows strictly before ts (rows at ts can still belong to a
        [ts, ...) window)."""
        if ts <= 0:
            return
        self.buffer = [r for r in self.buffer if r.timestamp >= ts]

    # ----------------------------------------------------------------- state
    def snapshot_state(self) -> Optional[dict]:
        rows = [r for r in self.buffer if isinstance(r, Tuple)]
        rows += [r for r in self._bbuf_all_rows() if isinstance(r, Tuple)]
        return {
            "buffer": [
                {"message": r.message, "timestamp": r.timestamp,
                 "emitter": r.emitter,
                 # __analytic_* overlays are computed upstream of the
                 # window; losing them on restore would make the evaluator
                 # re-run the analytic (double-advancing its state)
                 "cal_cols": dict(r.cal_cols),
                 # sliding windows: already-triggered rows must not
                 # re-trigger (and duplicate their window) after a restore
                 "slid": id(r) in self._slid_ids}
                for r in rows
            ],
            "rows_since_emit": self._rows_since_emit,
            "state_open": self._state_open,
            "next_emit_end": self._next_emit_end,
        }

    def restore_state(self, state: dict) -> None:
        restored = []
        self._slid_ids = set()
        for d in state.get("buffer", []):
            r = Tuple(emitter=d.get("emitter", ""), message=d["message"],
                      timestamp=d["timestamp"],
                      cal_cols=dict(d.get("cal_cols", {})))
            restored.append(r)
            if d.get("slid"):
                self._slid_ids.add(id(r))
        # columnarizing drops cal-col overlays; rows carrying __analytic_*
        # state stay in the row buffer after a restore
        if (self._use_bbuf and restored
                and not any(r.cal_cols for r in restored)):
            from ..data.batch import from_tuples

            # one batch per emitter: joins match rows by emitter, and a
            # single batch can only stamp one
            by_emitter: dict = {}
            for r in restored:
                by_emitter.setdefault(r.emitter, []).append(r)
            self.bbuf = [from_tuples(rows, emitter=em)
                         for em, rows in by_emitter.items()]
            self.buffer = []
        else:
            self.buffer = restored
        self._rows_since_emit = state.get("rows_since_emit", 0)
        self._state_open = state.get("state_open", False)
        self._next_emit_end = state.get("next_emit_end")
