"""Fused window→GROUP BY→aggregate device node — the TPU-native replacement
for the reference's WindowIncAggOperator (window_inc_agg_op.go) and the
window+aggregate+project interpreter chain of the hot path (SURVEY §3.2).

Handles processing-time TUMBLING and HOPPING windows and non-overlapping
COUNT windows whose aggregates all compile to the device kernel
(ops/aggspec.py eligibility). Per micro-batch: encode GROUP BY keys to slots
(host dictionary), fold columns into device partials (one jitted XLA program);
per trigger: finalize on device, one transfer, emit GroupedTuplesSet whose
groups carry precomputed agg_values — downstream HAVING/ORDER/PROJECT read
them without recomputation.
"""
from __future__ import annotations

import math
import time
from typing import Any, Dict, List, Optional

import numpy as np

from ..data.batch import ColumnBatch
from ..data.rows import GroupedTuples, GroupedTuplesSet, Tuple, WindowRange
from ..ops.aggspec import (
    F32_EXACT,
    HH_COL_PREFIX,
    HLL_COL_PREFIX,
    I64_COL_PREFIX,
    KernelPlan,
    ValueDict,
    _call_key,
    _hll_encode_numeric,
    hash_column_for_hll,
)
from ..ops.groupby import _NO_STAGE, DeviceGroupBy
from ..ops.keytable import KeyTable
from ..sql import ast
from ..observability.tracer import Tracer
from ..utils import timex
from ..utils.infra import logger
from .events import EOF, PreTrigger, Trigger
from .ingest import key_encode_stage
from .node import _NO_OVERRIDE, Node, _emit_ctx, _stamp_item


def _host_mask(ce, columns: Dict[str, np.ndarray], n: int) -> np.ndarray:
    """Vectorized host condition -> per-row bool mask. A batch missing the
    referenced column (or with uncoercible types) evaluates to all-false —
    null semantics, matching the host row evaluator."""
    try:
        return np.broadcast_to(np.asarray(ce(columns), dtype=np.bool_), (n,))
    except Exception:
        return np.zeros(n, dtype=np.bool_)


def _int64_column(col, vm, n: int):
    """(int64 column, validity) of a BIGINT column for the exact partials:
    an integer column as it is, a float one truncated, an object one's
    ints kept; a missing value, NaN or non-number is invalid (NULL)."""
    if col is None:
        return np.zeros(n, dtype=np.int64), np.zeros(n, dtype=np.bool_)
    if np.issubdtype(col.dtype, np.integer):
        return col.astype(np.int64, copy=False), vm
    if col.dtype == np.object_:
        ok = np.fromiter((not isinstance(v, bool) and (
            isinstance(v, int) or (isinstance(v, float) and math.isfinite(v)))
            for v in col), np.bool_, n)
        vals = np.fromiter((int(v) if o else 0 for v, o in zip(col, ok)),
                           np.int64, n)
    else:
        ok = np.isfinite(col)
        vals = np.where(ok, col, 0).astype(np.int64)
    return vals, ok if vm is None else np.logical_and(ok, vm)


def _enc_arr(a: np.ndarray) -> dict:
    """Compact checkpoint encoding for a numpy array: raw bytes + dtype."""
    import base64

    a = np.ascontiguousarray(a)
    return {"d": str(a.dtype),
            "b": base64.b64encode(a.tobytes()).decode("ascii")}


def _dec_arr(v) -> np.ndarray:
    import base64

    if isinstance(v, dict) and "b" in v:
        return np.frombuffer(base64.b64decode(v["b"]),
                             dtype=np.dtype(v["d"])).copy()
    return np.asarray(v)  # legacy list-encoded checkpoints


class FusedWindowAggNode(Node):
    def __init__(
        self,
        name: str,
        window: ast.Window,
        plan: KernelPlan,
        dims: List[ast.FieldRef],
        capacity: int = 16384,
        micro_batch: int = 4096,
        rule_id: str = "",
        direct_emit=None,  # ops.emit.DirectEmitPlan — vectorized tail
        mesh=None,  # jax.sharding.Mesh — run the kernel sharded (parallel/)
        prefinalize_lead_ms: int = 250,  # latency-hiding emit (prefinalize.py)
        emit_columnar: bool = False,  # window result stays a ColumnBatch
        is_event_time: bool = False,  # watermark-driven panes (see below)
        late_tolerance_ms: int = 0,
        dev_ring_budget_mb: int = 256,  # sliding device-state HBM cap
        sliding_impl: str = "daba",  # "daba" rings | "refold" legacy path
        ring_layout=None,  # ops.slidingring.RingLayout chosen at plan time
        tier_budget_mb: float = 0.0,  # tiered key state HBM budget (0=off)
        tier_scan_ms: int = 0,  # tier placement cadence (0=window-derived)
        **kw,
    ) -> None:
        super().__init__(name, op_type="op", **kw)
        self.window = window
        self.plan = plan
        self.dims = dims
        self.direct_emit = direct_emit
        self.emit_columnar = emit_columnar
        self.wt = window.window_type
        self.length_ms = window.length_ms()
        self.interval_ms = window.interval_ms()
        self.is_event_time = is_event_time
        if is_event_time and self.wt in (ast.WindowType.SESSION_WINDOW,
                                         ast.WindowType.COUNT_WINDOW,
                                         ast.WindowType.STATE_WINDOW):
            # event-time sessions/counts/state windows: one pane (sessions
            # fold one complete session at a time, counts/state fold the
            # open span into pane 0 and reset per emission); the
            # bucket/pane routing below is tumbling/hopping machinery
            self.n_panes = 1
            self._next_emit_bucket: Optional[int] = None
            self._max_bucket: Optional[int] = None
            self._dirty: set = set()
        elif is_event_time:
            # event-time tumbling/hopping on device: each row routes to the
            # pane of its time bucket (bucket = ts // bucket_ms, pane =
            # bucket % P) and watermarks drive emission — pane count covers
            # every bucket that can be live at once (window span + late
            # tolerance + slack), so recycled panes are always emitted+reset
            # before reuse
            self.bucket_ms = (self.interval_ms
                              if self.wt == ast.WindowType.HOPPING_WINDOW
                              and self.interval_ms else self.length_ms)
            if self.length_ms % max(self.bucket_ms, 1) != 0:
                # pane decomposition needs bucket | length; flooring the
                # span would silently aggregate less than the declared
                # window (the planner routes such shapes to the exact host
                # path — direct construction fails loudly instead)
                raise ValueError(
                    f"event-time window length {self.length_ms}ms is not a "
                    f"multiple of the pane bucket {self.bucket_ms}ms")
            span = max(self.length_ms // max(self.bucket_ms, 1), 1)
            slack = -(-max(late_tolerance_ms, 0) // max(self.bucket_ms, 1))
            self.n_panes = max(span + slack + 2, 4)
            if self.n_panes > 255:
                # pane ids ship as uint8; the planner routes such shapes to
                # the host path (device_path_eligible) — direct construction
                # fails loudly rather than corrupting pane routing
                raise ValueError(
                    f"event-time window needs {self.n_panes} panes "
                    "(max 255): widen the hop interval or reduce "
                    "lateTolerance")
            self.window_span = span
            self._next_emit_bucket: Optional[int] = None
            self._max_bucket: Optional[int] = None
            # buckets holding unexpired data — empty windows skip their
            # device round trip entirely, and time gaps fast-forward in
            # O(1) instead of emitting per empty bucket
            self._dirty: set = set()
        elif self.wt == ast.WindowType.HOPPING_WINDOW:
            iv = max(self.interval_ms, 1)
            self.n_panes = max((self.length_ms + iv - 1) // iv, 1)
        elif self.wt == ast.WindowType.SLIDING_WINDOW:
            # Device-path sliding windows (reference:
            # internal/topo/node/window_op.go:741 row-triggered semantics,
            # EXACT): rows fold into fine time panes by row timestamp; a
            # trigger row t emits window (t-L, t+delay] as
            #   merge(panes fully inside) ⊕ scratch-refold of the two
            #   partial edge buckets from a host-side columnar row ring.
            # Positive refolds only — every agg kind stays exact (no
            # subtraction), min/max/hll included.
            self.delay_ms = window.delay_ms()
            # ring geometry is a PLAN-time decision (the planner passes the
            # layout it chose; direct construction derives the same one):
            # finer buckets shrink the per-trigger edge corrections,
            # bounded by the uint8 pane budget AND by HBM — see
            # ops/slidingring.py plan_ring_layout
            from ..ops.slidingring import ring_layout_for

            if ring_layout is None:
                ring_layout = ring_layout_for(
                    window, plan, capacity=capacity,
                    budget_mb=dev_ring_budget_mb)
            self._ring_layout = ring_layout
            self.bucket_ms = ring_layout.bucket_ms
            self.n_ring_panes = ring_layout.n_ring_panes
            self.n_panes = ring_layout.n_panes
            self._scratch_pane = ring_layout.scratch_pane
            if sliding_impl not in ("daba", "refold"):
                raise ValueError(
                    f"slidingImpl must be 'daba' or 'refold', "
                    f"got {sliding_impl!r}")
            self._pane_bucket: Dict[int, int] = {}  # pane -> bucket held
            self._ring: Dict[int, list] = {}  # bucket -> [(cols,valid,slots,ts)]
            # device-side cache of the SAME segments (pre-padded fold
            # inputs kept alive on device): the trigger-time edge refold
            # then uploads one (mb,) bool mask per segment instead of
            # re-uploading the rows — the r04 paced 407ms p50 was mostly
            # this re-upload + its device folds. Entries align 1:1 with
            # _ring lists (None = no device copy, e.g. after restore).
            self._dev_ring: Dict[int, list] = {}
            # HBM budget for the cache: each qualifying batch pins
            # mb-padded float32 buffers per column for the whole ring
            # retention window, which at high batch rates on long windows
            # is GBs — past the cap the OLDEST entries drop to None and
            # their refolds fall back to the exact host path
            self.dev_ring_budget_bytes = int(dev_ring_budget_mb) << 20
            self._dev_ring_bytes = 0
            from collections import deque as _deque

            self._dev_ring_fifo = _deque()  # (bucket, idx, nbytes) in age order
            self._bucket_max_ts: Dict[int, int] = {}
            self._ring_max_bucket = -1
            self._pending_slides: Dict[int, int] = {}  # t -> fire_at_ms
            self._trigger_host = None
            if window.trigger_condition is not None:
                from ..sql.compiler import try_compile as _try_compile

                self._trigger_host = _try_compile(
                    window.trigger_condition, mode="host")
                if self._trigger_host is None:
                    raise ValueError(
                        "sliding device path needs a vectorizable OVER "
                        "(WHEN ...) trigger condition")
            else:
                raise ValueError(
                    "sliding device path requires a trigger condition: "
                    "per-row emission at device batch rates must be gated "
                    "(the exact host path handles unconditional sliding)")
        else:
            self.n_panes = 1
        if self.wt == ast.WindowType.STATE_WINDOW:
            # Condition-bounded windows on the device (reference: host
            # WindowNode STATE semantics — a begin-condition row opens the
            # window, rows fold until an emit-condition row closes it,
            # inclusive). Conditions evaluate VECTORIZED on the host
            # columns; only the open spans upload and fold.
            from ..sql.compiler import try_compile as _try_compile

            self._begin_host = _try_compile(window.begin_condition,
                                            mode="host")
            self._emitc_host = _try_compile(window.emit_condition,
                                            mode="host")
            if self._begin_host is None or self._emitc_host is None:
                raise ValueError(
                    "state device path needs vectorizable begin/emit "
                    "conditions (the host path handles the rest)")
            self._state_open = False
        if self.wt == ast.WindowType.SESSION_WINDOW:
            # Processing-time SESSION windows on the device (reference
            # semantics window_op.go: session is per-STREAM — any row
            # extends the session; gap silence or the length cap closes
            # it): rows fold into the single pane exactly like tumbling,
            # and the gap/cap timers drive emission + reset.
            # EVENT-time sessions buffer columnar batches and resolve the
            # session structure at each watermark with vectorized numpy
            # timestamp logic (argsort + diff > gap), then fold each
            # complete session on device and finalize — exact parity with
            # the host path's sort/scan (nodes_window.py on_watermark),
            # with the aggregation on the device instead of Python rows
            # (ref window_inc_agg_op.go:616).
            self.gap_ms = self.interval_ms or self.length_ms
            self._session_open = False
            self._session_start = 0
            self._last_row_ms = 0
            # stale-trigger guard: gap/cap triggers carry the session id
            # they were armed for; a trigger for a dead session is ignored
            self._session_id = 0
            self._gap_timer = None
            self._gap_gen = 0  # arm generation: one live gap check at a time
            self._cap_timer = None
            self._evs_batches: List[ColumnBatch] = []  # event-time buffer
        # heavy_hitters: per-column reversible dictionaries (codes -> values)
        # + the spec index -> raw column map for emit-time decoding. The hh
        # component is wide (sketches.HH_SIZE floats/key), so start small and
        # grow on demand instead of allocating the full default capacity.
        self._hh_cols: Dict[int, str] = {
            i: next(iter(s.arg.columns))[len(HH_COL_PREFIX):]
            for i, s in enumerate(plan.specs)
            if s.kind == "heavy_hitters"
        }
        self._hh_dicts: Dict[str, ValueDict] = {}
        self._hh_overflow_warned: set = set()
        if self._hh_cols and capacity > 2048:
            capacity = 2048
        # tiered key state (ops/tierstore.py, docs/TIERED_STATE.md):
        # geometry chosen here at plan/construction time from the HBM
        # budget and the actual pane count, like the sliding ring layout.
        # Eligible shapes: tumbling/hopping (processing or event time —
        # spilled per-pane partials stay exact across demotion windows)
        # and sliding (quiescent-only demotion). heavy_hitters plans,
        # exact BIGINT partials (the cold tier packs float32 rows) and mesh
        # kernels keep the untiered path.
        self.tier = None
        self._tier_layout = None
        if tier_budget_mb and mesh is None and not self._hh_cols and \
                not getattr(plan, "has_exact", False) and \
                not getattr(plan, "has_orderstat", False) and \
                self.wt in (ast.WindowType.TUMBLING_WINDOW,
                            ast.WindowType.HOPPING_WINDOW,
                            ast.WindowType.SLIDING_WINDOW):
            from ..ops.tierstore import plan_tier_layout

            self._tier_layout = plan_tier_layout(
                plan, int(self.n_panes), capacity, float(tier_budget_mb),
                scan_interval_ms=int(tier_scan_ms),
                window_ms=self.interval_ms or self.length_ms)
            if self._tier_layout is not None:
                # the cold tier pins resident keys at the hot target, so
                # every per-capacity allocation (group-by state, sliding
                # rings — what lets a wide-hll rule keep DABA inside
                # slidingDevRingMb) builds at the capped capacity;
                # growth past it stays possible but becomes the last
                # resort the recycler works to avoid
                capacity = min(capacity,
                               self._tier_layout.hot_capacity())
        self.gb = self._make_gb(plan, capacity, micro_batch, mesh)
        # sliding implementation: DABA rings by default (constant-time
        # trigger emission, ops/slidingring.py), the legacy refold path as
        # the parity/escape-hatch fallback (`slidingImpl` rule option)
        self.ring = None
        self._ring_dev = None
        self.sliding_impl: Optional[str] = None
        if self.wt == ast.WindowType.SLIDING_WINDOW:
            self.sliding_impl = self._choose_sliding_impl(sliding_impl)
        # sharded path may round capacity up for even shard division
        self.kt = KeyTable(self.gb.capacity)
        if self._tier_layout is not None and \
                getattr(self.gb, "track_touch", False):
            from ..ops.tierstore import TierManager

            key_name = (dims[0].name if len(dims) == 1
                        and getattr(dims[0], "name", None) else None)
            sliding = self.wt == ast.WindowType.SLIDING_WINDOW
            self.tier = TierManager(
                self.gb, self.kt, self._tier_layout,
                rule_id=rule_id, key_name=key_name,
                submit=self._tier_submit,
                # sliding demotes only quiescent keys: idle past the whole
                # ring/row retention, so no pane, ring partial, or host
                # ring row still references the recycled slot
                quiescent_only=sliding,
                min_idle_ms=((self.n_ring_panes + 10) * self.bucket_ms
                             if sliding else 0),
                on_tier_event=self._on_tier_event)
        else:
            self._tier_layout = None  # kernel form ineligible (multirule)
        # shared-source fan-out slot reuse: None = undecided, True = our kt
        # mirrors the subtopo's neutral table, False = self-encode forever.
        # Tiered slot recycling breaks the neutral table's dense
        # insertion-order contract, so tiered rules always self-encode.
        self._shared_slots_ok = None if self.tier is None else False
        self._shared_nkt = None  # the neutral table our slots come from
        self._prep_registered = False  # upload spec handed to the prep ctx
        self.state = None
        self.cur_pane = 0
        self._timer = None
        # count window
        self.count_len = window.length or 0
        self._rows_in_window = 0
        self._spec_keys = [_call_key(s.call) for s in plan.specs]
        self._dtypes_seen = False
        # exact BIGINT aggregates (ops/aggspec.py EXACT_INT_AGGS): rows
        # folded through the exact partials (kuiper_fold_int_exact_rows_
        # total), and integer rows past 2^24 that reached a float32
        # sum/avg/min/max instead, by the plan's reason
        # (kuiper_agg_int_fallback_total); one writer, the folding thread
        self._exact = bool(getattr(plan, "has_exact", False))
        self.fold_int_exact_rows = 0
        self.agg_int_fallback: Dict[str, int] = {}
        self._float_int_cols = sorted({
            (s.float_reason, next(iter(s.arg.columns)))
            for s in plan.specs
            if s.float_reason and len(s.arg.columns) == 1})
        # rows the pre-issues' host shadows mirrored over the slots a
        # micro-batch touches, not over their whole capacity (ops/
        # prefinalize.py HostShadow.fold; kuiper_shadow_fold_compact_rows_
        # total): once a shadow, as stage shadow_fold counts its rows
        self.shadow_fold_compact_rows = 0
        # exact order statistics (ops/orderstat.py): the rows the buffers
        # held in each pane as it closed, as the device's selection counted
        # them (kuiper_orderstat_rows_total; the emit worker writes it),
        # rung climbs (kuiper_orderstat_grow_total), and each pane's fill
        # as the host knows it without a sync — the fold appends every row
        # of a micro-batch, so a pane's fill is the rows folded into it
        # since its reset (the folding thread writes these two)
        self._orderstat = bool(getattr(plan, "has_orderstat", False))
        self.orderstat_rows = 0
        self.orderstat_grows = 0
        self._os_fill = [0] * int(self.n_panes)
        # latency-hiding emit: pre-issued device finalize + host tail shadow.
        # Only for timer-driven windows (boundary known in advance), plans
        # whose expressions have numpy twins, and non-collective kernels.
        # _pipeline holds up to 2 (PendingFinalize, HostShadow) pairs: a
        # fresher pre-issue is stacked when an earlier fetch is still in
        # flight at the next pre-trigger, and the boundary uses the newest
        # READY one.
        self._pipeline = []
        self._pre_timers = []
        self.prefinalize_lead_ms = int(prefinalize_lead_ms)
        self._prefinalize_ok = (
            not is_event_time  # watermark boundaries aren't clock-known
            and self.prefinalize_lead_ms > 0
            and self.gb.supports_prefinalize
            and plan.host_foldable
            # hh boundaries use the compact device-recovery finalize — the
            # pre-issue would ship the raw HH_SIZE-wide sketch instead
            and not self._hh_cols
            and self.wt in (ast.WindowType.TUMBLING_WINDOW,
                            ast.WindowType.HOPPING_WINDOW)
            and self.prefinalize_lead_ms < self._tick_interval()
        )
        # After a pre-issue takes its snapshot, tail rows keep folding into
        # the device state AND into that pre-issue's host shadow. The
        # emitted window = snapshot ⊕ shadow counts each row exactly once
        # (the snapshot excludes tail rows, the shadow holds exactly them);
        # the device state stays COMPLETE at all times, so checkpoints need
        # no flush-back and hopping panes retain tail rows for later windows.
        # COUNT-window async emission: the boundary dispatches the device
        # finalize on an immutable state snapshot, resets, and keeps folding;
        # a worker thread fetches + emits when the result lands. Emission
        # latency (one device round trip) stops stalling ingest — essential
        # at 1M-key cardinality where the finalize fetch is MBs. Barriers
        # and EOF drain the queue first, so ordering contracts hold.
        self._async_count = (
            self.wt == ast.WindowType.COUNT_WINDOW
            and self.gb.supports_prefinalize
            and not self._hh_cols
            and prefinalize_lead_ms > 0
        )
        # heavy_hitters timer boundaries also emit asynchronously: the
        # compact _hh_fin result is dispatched on the pre-reset snapshot
        # and delivered by the worker — the boundary never stalls a
        # sync fetch (2-3 link round trips) in the fold stream
        self._async_hh = (
            bool(self._hh_cols)
            and self.wt in (ast.WindowType.TUMBLING_WINDOW,
                            ast.WindowType.HOPPING_WINDOW)
            and not is_event_time
            and self.gb.supports_prefinalize
            and prefinalize_lead_ms > 0
        )
        # order-statistic boundaries too (planner.py orderstat_host_reason
        # admits processing-time tumbling and hopping windows only): the
        # device selection is dispatched on the pre-reset snapshot, the
        # worker waits for it (stage orderstat_select) and finishes the
        # ranks on the host
        self._async_os = self._orderstat
        # vmapped rule-group boundaries (MultiRuleFusedNode) also emit
        # asynchronously: one (R, S+1, keys) transfer per family is MBs,
        # and a sync fetch at the boundary stalls every rider's fold stream
        self._async_mr = False  # set by MultiRuleFusedNode
        # deferred boundary emission: when no pre-issue has landed at a
        # tumbling/hopping boundary, the merge wait moves to the emit
        # worker instead of stalling folds — crucial for wide sketch
        # finalizes (hll components are KBs/key)
        self._emit_late_async = (
            self.wt in (ast.WindowType.TUMBLING_WINDOW,
                        ast.WindowType.HOPPING_WINDOW)
            and not is_event_time
            and self.gb.supports_prefinalize
            and not self._hh_cols
        )
        self._emit_q = None
        self._emit_worker = None
        # worker-installed slot->key decode pin for deferred deliveries
        # (tiered slot recycling; see _keys_snapshot)
        self._kt_keys_override = None
        # per-boundary record: {"source": "device"|"sync"|"device-async"|
        #  "device-async-late"|"device-ring",
        #  "fetch_ms": issue→landed ms of the chosen fetch (-1 in flight)}
        self.last_emit_info: Optional[dict] = None
        # cumulative twin of last_emit_info["source"], one count per
        # emitted window — how a boundary was answered stays visible after
        # the next boundary overwrites the record above (surfaced in
        # /rules/{id}/status)
        self.emit_sources: Dict[str, int] = {}
        # sliding (DABA) triggers by the path that served the window body:
        # fast (one combine of the ring's running partials), flip (the
        # partials rebuilt from the panes first), dyn (traced-mask pane
        # merge, the exact fallback), edge (nothing on the device: every
        # row in the host edge shadow) — kuiper_sliding_triggers_total
        self.sliding_triggers: Dict[str, int] = {}
        # ... and by where the trigger was finished: device (fast and
        # flip: the tail program, a compact fetch), host (dyn and edge:
        # the shadow, the merge and the final values in numpy) —
        # kuiper_sliding_tail_total
        self.sliding_tails: Dict[str, int] = {}

    def _make_gb(self, plan, capacity: int, micro_batch: int, mesh):
        """Build the group-by kernel; subclasses override (MultiRuleFusedNode
        builds a BatchedGroupBy with the already-computed self.n_panes)."""
        if mesh is not None:
            from ..parallel.sharded import ShardedGroupBy

            return ShardedGroupBy(
                plan, mesh, capacity=capacity, n_panes=int(self.n_panes),
                micro_batch=micro_batch,
            )
        return DeviceGroupBy(
            plan, capacity=capacity, n_panes=int(self.n_panes),
            micro_batch=micro_batch,
            track_touch=getattr(self, "_tier_layout", None) is not None,
        )

    # --------------------------------------------------------------- lifecycle
    def on_open(self) -> None:
        if self.state is None:  # keep checkpoint-restored partials
            self.state = self.gb.init_state()
        # HBM accounting (observability/memwatch.py): the three pools this
        # node owns — group-by partial state, the sliding device batch
        # cache, and the host key table — become kuiper_device_bytes rows
        from ..observability import memwatch

        rule = getattr(self._topo, "rule_id", "") if self._topo else ""
        memwatch.register(
            "groupby_state", self,
            lambda n: sum(int(getattr(a, "nbytes", 0) or 0)
                          for a in (n.state or {}).values()),
            rule=rule)
        memwatch.register("key_table", self,
                          lambda n: n.kt.approx_bytes(), rule=rule)
        if self.wt == ast.WindowType.SLIDING_WINDOW:
            memwatch.register("dev_ring", self,
                              lambda n: n._dev_ring_bytes, rule=rule)
            if self.sliding_impl == "daba":
                # the DABA partials replace the _dev_ring batch cache in
                # HBM — they get their own kuiper_device_bytes row so
                # /diagnostics/memory sees the ring state, not a silently
                # double-budgeted dev_ring
                memwatch.register("sliding_ring", self,
                                  lambda n: n.ring_dev_bytes(), rule=rule)
        # register the trigger timer BEFORE the (slow) warmup compile so the
        # first window boundary is anchored at open time, not compile-end
        if not self.is_event_time and self.wt in (
            ast.WindowType.TUMBLING_WINDOW, ast.WindowType.HOPPING_WINDOW
        ):
            self._schedule_next_tick()

    def on_worker_start(self) -> None:
        self._warmup()

    def _warmup(self) -> None:
        """Probe the AOT executable cache for every jit site this node
        will exercise, on a THROWAWAY state, before data arrives. Against
        a warm disk cache (runtime/aotcache.py) this is a deserialization
        sweep — tens of ms, zero traces; against a cold one it is the
        build (1-40s of jit latency the first window would otherwise
        pay). Runs inside aotcache.building() so the builds it triggers
        are accounted as deliberate, not serve-time misses. Must never
        touch self.state — it may hold partials restored from a
        checkpoint."""
        from . import aotcache

        self._warmup_stage = "init"
        try:
            with aotcache.building():
                self._warmup_probe()
        except Exception as exc:
            # a swallowed warmup failure is a guaranteed serve-time
            # compile stall on the first real window — count it
            # (kuiper_warmup_failures_total), leave a flight event, and
            # say which stage died so it bisects
            stage = getattr(self, "_warmup_stage", "?")
            rule = getattr(self._topo, "rule_id", "") if self._topo else ""
            logger.warning(
                "fused warmup failed at stage %r (rule %s will pay "
                "serve-time compiles): %s", stage, rule or "?", exc)
            aotcache.note_warmup_failure(rule, stage, exc)

    def _warmup_probe(self) -> None:
        # no valid masks: matches the common typed-schema batch pytree so
        # the compiled executable is the one real folds will hit
        # (dtype-correct per column — expression-IR derived columns
        # are int32, ops/groupby.py col_np_dtype)
        from ..ops.groupby import warmup_cols

        self._warmup_stage = "fold"
        cols = warmup_cols(self.plan)
        slots = np.zeros(1, dtype=np.int32)
        dummy = self.gb.init_state()
        if self.is_event_time or self.wt == ast.WindowType.SLIDING_WINDOW:
            # event-time and sliding folds ship per-row pane VECTORS
            # for multi-bucket batches and the SCALAR pane for
            # single-bucket ones (the in-order common case) — warm both
            # executables, and the traced-mask finalize
            dummy = self.gb.fold(dummy, cols, slots,
                                 pane_idx=np.zeros(1, dtype=np.int64))
            dummy = self.gb.fold(dummy, cols, slots, pane_idx=0)
            self._warmup_stage = "finalize"
            self.gb.finalize(dummy, 1, panes=[0])
            if self.wt == ast.WindowType.SLIDING_WINDOW:
                # implementation-aware trigger-path warmup: the DABA
                # rounds warm the ring kernels, the refold rounds warm
                # fold_masked — never a dead kernel's executable
                if self.sliding_impl == "daba":
                    self._warmup_stage = "ring"
                    self._warmup_ring(dummy)
                else:
                    # compile the mask-only edge refold (fold_masked)
                    # with the exact runtime pytree: pre-padded device
                    # inputs + (mb,) bool mask — a first real trigger
                    # must not pay a 20-40s jit stall mid-stream.
                    # force=True bypasses the small-batch HBM guard,
                    # which would silently reject this 1-row batch and
                    # skip the compile
                    dev = self._upload_sliding_inputs(
                        warmup_cols(self.plan),
                        {}, np.zeros(1, dtype=np.int32), force=True)
                    self._warmup_stage = "fold_masked"
                    if dev is not None:
                        mask = np.zeros(self.gb.micro_batch,
                                        dtype=np.bool_)
                        dummy = self.gb.fold_masked(
                            dummy, dev[3], dev[2], mask,
                            self.n_ring_panes)
        else:
            dummy = self.gb.fold(dummy, cols, slots,
                                 pane_idx=self.cur_pane)
            self._warmup_stage = "finalize"
            self.gb.finalize(dummy, 1)
        if self._prefinalize_ok:
            self._warmup_stage = "prefinalize"
            pending = self.gb.prefinalize_begin(dummy)
            self.gb.prefinalize_merge(pending, None, 1)
        if self.tier is not None:
            self._warmup_stage = "tier"
            # compile the demote/promote sites so the first boundary
            # with a plan doesn't pay the jit stall
            dummy, pk = self.tier.ts.demote(
                dummy, np.zeros(1, dtype=np.int32))
            dummy = self.tier.ts.promote(
                dummy, np.asarray(pk)[:1], np.zeros(1, dtype=np.int32))
        self._warmup_stage = "reset_pane"
        self.gb.reset_pane(dummy, self.cur_pane)

    def _warmup_ring(self, dummy) -> None:
        """Probe/compile the DABA trigger path (advance/flip/query/tail +
        the traced-mask components fallback) on throwaway state."""
        from ..ops.slidingring import QUERY_ADJ

        if self._ring_dev is None:  # follow a checkpoint-restored capacity
            self.ring.capacity = int(self.gb.capacity)
        ring = self.ring.init_state()
        ring = self.ring.advance(ring, dummy, 0, True, 0, False)
        ring = self.ring.flip(ring, dummy, 0,
                              np.zeros(self.n_ring_panes, dtype=np.bool_))
        body = self.ring.query(
            ring, dummy, body_on=False, f_on=False, f_slot=0,
            adj_slots=np.zeros(QUERY_ADJ, dtype=np.int32),
            adj_weights=np.zeros(QUERY_ADJ, dtype=np.float32),
            adj_mm=np.zeros(QUERY_ADJ, dtype=np.bool_))
        # the tail at its one static edge shape, no row in it
        # kuiperlint: ignore[host-sync]: warm-up on throwaway state, before the first row
        np.asarray(self.ring.tail_begin(body, self.ring.edge_buffers([])))
        self.gb.components_begin_dyn(
            dummy, np.zeros(self.gb.n_panes, dtype=np.bool_)).get()

    def on_close(self) -> None:
        if self._timer is not None:
            self._timer.stop()
        for t in self._pre_timers:
            t.stop()
        if self.wt == ast.WindowType.SESSION_WINDOW:
            for t in (self._gap_timer, self._cap_timer):
                if t is not None:
                    t.stop()
        self._drain_async_emits()
        if self._emit_q is not None and self._emit_worker is not None \
                and self._emit_worker.is_alive():
            self._emit_q.put(None)
            self._emit_worker.join(timeout=5)

    def _tick_interval(self) -> int:
        if self.wt == ast.WindowType.TUMBLING_WINDOW:
            return self.length_ms
        return self.interval_ms or self.length_ms

    def _schedule_next_tick(self) -> None:
        now = timex.now_ms()
        interval = self._tick_interval()
        next_end = timex.align_to_window(now + 1, interval)
        # the trigger carries the SCHEDULED boundary, not the fire time: a
        # real clock calls back with the time it woke at, and window_end()
        # would then lie off the grid by the timer's lateness
        self._timer = timex.after(
            next_end - now,
            lambda ts, end=next_end: self.put_control(Trigger(ts=end)))
        if self._prefinalize_ok:
            # two chances per boundary: a pre-issue at 2x lead, and one at
            # 1x lead that on_pre_trigger skips when the first has landed
            self._pre_timers = []
            lead = self.prefinalize_lead_ms
            for k in (2, 1):
                if next_end - now > k * lead:
                    self._pre_timers.append(timex.after(
                        next_end - now - k * lead,
                        lambda ts, end=next_end: self.put_control(PreTrigger(ts=end)),
                    ))

    # ------------------------------------------------------------------- data
    def process(self, item: Any) -> None:
        if not isinstance(item, ColumnBatch):
            if isinstance(item, Tuple):
                # stray row path: wrap into a single-row batch
                from ..data.batch import from_tuples

                item = from_tuples([item], emitter=item.emitter)
            else:
                self.emit(item)
                return
        if item.n == 0:
            return
        if self.wt == ast.WindowType.COUNT_WINDOW:
            self._fold_count_window(item)
        elif self.wt == ast.WindowType.SESSION_WINDOW:
            if self.is_event_time:
                # session structure resolves at watermark time: buffer the
                # COLUMNAR batch as-is (no device work yet — folds happen
                # per complete session so pane 0 is always one session)
                self._evs_batches.append(item)
            else:
                self._fold(item)
                self._touch_session()
        elif self.wt == ast.WindowType.STATE_WINDOW:
            self._fold_state_window(item)
        else:
            self._fold(item)

    def _fold(self, batch: ColumnBatch, start: int = 0, end: Optional[int] = None) -> int:
        """Fold rows [start:end) of the batch; returns rows folded."""
        end = batch.n if end is None else end
        if end <= start:
            return 0
        sub = (batch if (start == 0 and end == batch.n)
               else batch.take(np.arange(start, end)))
        if self.is_event_time and self.wt not in (
                ast.WindowType.COUNT_WINDOW, ast.WindowType.STATE_WINDOW):
            # event-time COUNT/STATE fold like processing time: the
            # upstream watermark node already late-dropped and ordered the
            # rows, and their boundaries are row-driven (count / condition
            # toggles), not bucket-driven
            return self._fold_event(sub)
        if self.wt == ast.WindowType.SLIDING_WINDOW:
            return self._fold_sliding(sub)
        return self._fold_rows(sub, self.cur_pane)

    def _shared_encode(self, sub: ColumnBatch) -> Optional[np.ndarray]:
        """Shared-source fan-out: reuse the subtopo's one-per-batch key
        encode (subtopo.py SharedPrepCtx) instead of re-encoding per rule.
        The neutral table's slot ids are dense insertion-ordered, so
        feeding our own table the same key sequence (keys_slice of the
        new tail) yields identical ids — our table stays self-contained
        for emit decode and checkpoints. Returns None (caller self-encodes)
        when no shared ctx rides the batch or our table diverged (e.g.
        restored from a checkpoint predating the shared pipeline)."""
        ctx = getattr(sub, "shared_ctx", None)
        if ctx is None or self._shared_slots_ok is False:
            return None
        key_name = getattr(self.dims[0], "name", None)
        if not key_name:
            self._shared_slots_ok = False
            return None
        try:
            slots, n_keys, nkt = ctx.encode(sub, key_name, self.stats)
        except Exception as exc:
            logger.debug("%s: shared key encode failed (%s) — self-encoding",
                         self.name, exc)
            self._shared_slots_ok = False
            return None
        if self._shared_slots_ok is None:  # one-time compatibility check
            self._shared_slots_ok = self.kt.n_keys == 0 or (
                self.kt.decode_all() == nkt.keys_slice(0, self.kt.n_keys))
            if not self._shared_slots_ok:
                return None
        self._shared_nkt = nkt
        start = self.kt.n_keys
        if start < n_keys:
            # mirror the neutral table's new keys into our own, in order
            new = nkt.keys_slice(start, n_keys)
            with key_encode_stage(self.stats, n_keys - start):
                grew = self.kt.mirror(new)
            if grew:
                self.state = self.gb.grow(self.state, self.kt.capacity)
            if self.kt.keys_slice(start, n_keys) != new:
                # truly diverged (our table numbered the keys otherwise):
                # self-encode from now on. n_keys ABOVE the snapshot is
                # normal with the pipelined upload stage — pool workers
                # may encode batch k+1 before batch k's snapshot is
                # consumed, so our table can legitimately run ahead of an
                # older batch's n_keys; its slot values are all below the
                # snapshot and stay valid.
                self._shared_slots_ok = False
                return None
        return slots

    def keytable_encode_rows(self) -> Dict[str, int]:
        """Rows this node's own key table encoded, by path: every row of
        a rule that encodes for itself, only the mirrored new keys of one
        that rides a shared source's encode."""
        return self.kt.encode_rows

    def pane_occupancy(self) -> "Optional[float]":
        """Event-time pane-ring occupancy (dirty buckets / ring size),
        None on clock-driven paths where the ring has no backlog notion.
        Health-evaluator probe (observability/health.py): occupancy near
        1.0 means the watermark lags far enough that panes risk the
        counted `pane_recycle` loss mode. Session/count/state windows
        fold into ONE pane but track dirtiness per absolute time bucket
        — a dirty-count/1 ratio is not a recycle-risk fraction, so they
        report None like the clock-driven paths."""
        dirty = getattr(self, "_dirty", None)
        if dirty is None:
            return None
        if self.wt in (ast.WindowType.SESSION_WINDOW,
                       ast.WindowType.COUNT_WINDOW,
                       ast.WindowType.STATE_WINDOW):
            return None
        return len(dirty) / max(self.n_panes, 1)

    def prep_spec(self):
        """(key_name, kernel columns, micro_batch, derived, sharding,
        mesh_tag) for the ingest prep's upload stage — the ONE definition
        of what precompute() should build for this node (the planner
        registers it at plan time, the first _shared_device_inputs call
        covers un-plumbed paths). `derived` is (expr_tag, DerivedCol
        tuple): the expression IR's host-derived columns, pre-encoded and
        pre-uploaded by the pool under share keys that include the IR
        hash — plans whose expressions differ can never alias. Sharded
        kernels add their row sharding + mesh tag: the pool then places
        each padded column/slot vector ACROSS the mesh (per-shard H2D)
        under tag-suffixed share keys, so a sharded and an unsharded
        consumer of one stream can never alias an upload."""
        from ..sql.expr_ir import is_derived_expr_col

        key_name = (self.dims[0].name
                    if len(self.dims) == 1
                    and getattr(self.dims[0], "name", None) else None)
        # mesh placement only when the kernel actually CONSUMES device
        # inputs: a multi-process mesh can't device_put onto
        # non-addressable devices (ShardedGroupBy uses its own
        # local-slice _put and opts out of device inputs) — registering
        # its sharding would make every precompute() raise per batch
        shard_ok = (getattr(self.gb, "mesh_tag", "")
                    and getattr(self.gb, "accepts_device_inputs", False))
        return (key_name,
                [n for n in self.plan.columns
                 if not n.startswith(HLL_COL_PREFIX)
                 and not n.startswith(HH_COL_PREFIX)
                 and not is_derived_expr_col(n)],
                self.gb.micro_batch,
                ((self.plan.expr_tag, self.plan.derived)
                 if self.plan.derived else None),
                self.gb.batch_sharding if shard_ok else None,
                self.gb.mesh_tag if shard_ok else "")

    def _shared_device_inputs(self, sub: ColumnBatch, cols, valid, slots):
        """One device upload per column/slot vector for ALL fan-out
        consumers of this batch: pad to the static micro-batch shape once,
        device_put once, and let every rider fold from the same HBM
        buffers. Only plain numeric columns share (hll/hh derivations are
        node-specific); only single-chunk batches qualify (n <= micro_batch
        — guaranteed by micro-batch-aligned source flushes). Returns
        (dev_cols, dev_valid, dev_slots|None) or None."""
        ctx = getattr(sub, "shared_ctx", None)
        mb = self.gb.micro_batch
        if ctx is None or sub.n > mb or \
                not getattr(self.gb, "accepts_device_inputs", False):
            return None
        if not self._prep_registered:
            # hand the upload spec to the prep ctx once: from then on the
            # decode pool's upload stage pre-builds these device inputs and
            # every share() below is a cache hit off the fused worker
            self._prep_registered = True
            reg = getattr(ctx, "register_upload", None)
            if reg is not None:
                reg(*self.prep_spec())
        # canonical builders + key scheme shared with the prep ctx's
        # pool-side pre-upload (runtime/ingest.py): same keys, same bytes
        from ..sql.expr_ir import is_derived_expr_col
        from .ingest import (pad_col_for_device, pad_slots_for_device,
                             share_key, slot_wire_u16)

        dcols: Dict[str, Any] = {}
        dvalid: Dict[str, Any] = {}
        expr_tag = getattr(self.plan, "expr_tag", "")
        # mesh-aware uploads: a sharded kernel's inputs are placed with
        # its row sharding (per-shard H2D) under tag-suffixed share keys
        # — the replicated single-chip upload and the mesh placement can
        # never serve each other
        mesh_tag = getattr(self.gb, "mesh_tag", "")
        shd = getattr(self.gb, "batch_sharding", None) if mesh_tag else None

        def _key(*parts):
            return share_key(*parts, mesh_tag=mesh_tag)

        for name in self.plan.columns:
            if name.startswith(HLL_COL_PREFIX) or \
                    name.startswith(HH_COL_PREFIX):
                continue
            if is_derived_expr_col(name):
                # expression-IR derived column (already materialized in
                # `cols` by _build_kernel_inputs): share key carries the
                # plan's IR hash — a peer plan with different
                # expressions derives different bytes under a different
                # key, never a false cache hit
                host = cols[name]
                dt = str(host.dtype)
                dv, _ = sub.share(_key("dexpr", expr_tag, name, mb),
                                  lambda h=host, d=dt:
                                  pad_col_for_device(h, None, mb,
                                                     dtype=d,
                                                     sharding=shd))
                dcols[name] = dv
                continue
            src_col = sub.columns.get(name)
            dtype = "float32"
            if name.startswith(I64_COL_PREFIX):
                # the prep uploads an integer column's words under the
                # same key (runtime/ingest.py precompute)
                src_col = sub.columns.get(name[len(I64_COL_PREFIX):])
                dtype = "int64"
            if src_col is None or src_col.dtype == np.object_:
                continue
            host, vm = cols[name], valid.get(name)
            dv, dm = sub.share(_key("dcol", name, mb),
                               lambda h=host, v=vm, d=dtype:
                               pad_col_for_device(h, v, mb, dtype=d,
                                                  sharding=shd))
            dcols[name] = dv
            if dm is not None:
                dvalid[name] = dm
        dslots = None
        if slots is not None and self._shared_slots_ok and \
                len(self.dims) == 1:
            from ..ops.groupby import slot_dtype

            # dtype follows the NEUTRAL table's capacity (the slots' value
            # domain — and what the prep ctx keyed its pre-upload on, so
            # the lookup below hits); our own kt may be pre-sized larger
            # without invalidating a uint16 wire format. Sharded kernels
            # always ship int32 (the certified shard_map wire dtype).
            cap = (self._shared_nkt.capacity
                   if self._shared_nkt is not None else self.kt.capacity)
            u16 = slot_wire_u16(slot_dtype(cap) is np.uint16, mesh_tag)
            dslots = sub.share(
                _key("dslots", self.dims[0].name, mb, u16),
                lambda s=slots, u=u16: pad_slots_for_device(
                    s, mb, u, sharding=shd))
        if not dcols and dslots is None:
            return None
        return dcols, dvalid, dslots

    def _build_kernel_inputs(self, sub: ColumnBatch):
        """Encode group keys + materialize the kernel's numeric columns and
        validity masks for `sub`. Returns (cols, valid, slots)."""
        key_cols = [sub.key_column(d.name) for d in self.dims]
        if key_cols:
            slots = (self._shared_encode(sub)
                     if len(self.dims) == 1 else None)
            if slots is None:
                with key_encode_stage(self.stats, sub.n):
                    slots, grew = self.kt.encode_multi(key_cols)
                if grew:
                    self.state = self.gb.grow(self.state, self.kt.capacity)
        else:
            slots = np.zeros(sub.n, dtype=np.int32)
            if self.kt.n_keys == 0:
                self.kt.encode_column(np.array(["__all__"], dtype=np.object_))
        cols: Dict[str, np.ndarray] = {}
        valid: Dict[str, np.ndarray] = {}
        # expression-IR derived columns (__sd_*/__ts32_*): dictionary
        # codes + rebased event time, host prep with self-describing
        # null sentinels (sql/expr_ir.py) — built once per batch here,
        # shared by the device upload AND the host shadows
        if self.plan.derived:
            from ..sql.expr_ir import materialize_derived

            materialize_derived(self.plan.derived, cols, sub,
                                expr_tag=self.plan.expr_tag)
        for name in self.plan.columns:
            if name in cols:
                continue  # derived expr column, just materialized
            if name.startswith(I64_COL_PREFIX):
                # exact BIGINT partials read the column as int64 (the
                # raw name stays float32 for FILTER / WHERE)
                raw = name[len(I64_COL_PREFIX):]
                cols[name], vm = _int64_column(sub.columns.get(raw),
                                               sub.valid.get(raw), sub.n)
                if vm is not None:
                    valid[name] = vm
                continue
            if name.startswith(HLL_COL_PREFIX):
                # derived hashed copy for hll; raw column stays numeric for
                # any other spec / WHERE / FILTER that shares it
                raw = name[len(HLL_COL_PREFIX):]
                col = sub.columns.get(raw)
                if col is None:
                    cols[name] = np.full(sub.n, np.nan, dtype=np.float32)
                elif col.dtype == np.object_:
                    cols[name] = hash_column_for_hll(col)
                else:
                    cols[name] = _hll_encode_numeric(col)
                v = sub.valid.get(raw)
                if v is not None:
                    valid[name] = v
                continue
            if name.startswith(HH_COL_PREFIX):
                # heavy_hitters: dictionary-encode to dense codes the sketch
                # can bit-recover; the dict decodes them back at emit
                raw = name[len(HH_COL_PREFIX):]
                col = sub.columns.get(raw)
                vd = self._hh_dicts.setdefault(raw, ValueDict())
                if col is None:
                    cols[name] = np.full(sub.n, np.nan, dtype=np.float32)
                else:
                    with self.stats.stage("hh_encode", sub.n,
                                          within="upload"):
                        cols[name], missed = vd.lookup(col)
                        if missed is not None:
                            # rows whose value no table knows yet: none,
                            # once a stream's values have all been seen
                            with self.stats.stage("hh_encode_new",
                                                  len(missed),
                                                  within="hh_encode"):
                                vd.learn(col, cols[name], missed)
                    if vd.overflowed and raw not in self._hh_overflow_warned:
                        self._hh_overflow_warned.add(raw)
                        self.stats.inc_exception(
                            f"heavy_hitters dictionary overflow on '{raw}': "
                            "values past the code budget are no longer "
                            "counted")
                        logger.warning(
                            "heavy_hitters(%s): value dictionary exceeded "
                            "%d distinct values; new values are invisible "
                            "to the sketch", raw,
                            len(vd.snapshot()))
                v = sub.valid.get(raw)
                if v is not None:
                    valid[name] = v
                continue
            col = sub.columns.get(name)
            if col is None:
                cols[name] = np.full(sub.n, np.nan, dtype=np.float32)
                continue
            if col.dtype == np.object_:
                # mixed/object numeric column: coerce, NaN for bad rows
                coerced = np.full(sub.n, np.nan, dtype=np.float32)
                for i, v in enumerate(col):
                    if isinstance(v, (int, float)) and not isinstance(v, bool):
                        coerced[i] = v
                cols[name] = coerced
            else:
                cols[name] = col
            v = sub.valid.get(name)
            if v is not None:
                valid[name] = v
        if not self._dtypes_seen:
            self.gb.observe_dtypes(cols)
            self._dtypes_seen = True
        if self._float_int_cols:
            self._count_int_fallback(cols)
        return cols, valid, slots

    def _count_int_fallback(self, cols: Dict[str, np.ndarray]) -> None:
        """Integer rows float32 cannot hold (|v| >= 2^24) that reach a
        float32 sum/avg/min/max — a column not declared BIGINT (schemaless
        or FLOAT), an expression, a plan with heavy_hitters — counted by
        the plan's reason, once a row: such a result is rounded, and says
        so."""
        for reason, col in self._float_int_cols:
            arr = cols.get(col)
            if arr is None or not np.issubdtype(arr.dtype, np.integer):
                continue
            big = int(np.count_nonzero((arr >= F32_EXACT)
                                       | (arr <= -F32_EXACT)))
            if big:
                self.agg_int_fallback[reason] = (
                    self.agg_int_fallback.get(reason, 0) + big)

    def _fold_h2d(self, rows: int):
        """The stage a fold's host -> device staging runs in (handed to
        `gb.fold`, which opens it once a chunk, inside `fold`)."""
        return self.stats.stage("fold_h2d", rows, within="fold")

    @property
    def fold_transfers(self) -> int:
        """Runtime calls that staging has made
        (kuiper_fold_transfers_total)."""
        return self.gb.transfers_total

    @property
    def fold_resident_args(self) -> int:
        """Arguments that staging took from the kernel's device-resident
        scalar table instead (kuiper_fold_resident_args_total)."""
        return self.gb.resident_total

    def _fold_rows(self, sub: ColumnBatch, pane_arg) -> int:
        """Encode keys + build kernel columns + device fold for `sub`,
        folding into `pane_arg` (scalar pane or per-row pane vector).
        Stage accounting: "upload" covers key encode + kernel-input build +
        shared device puts (the host-side work feeding the link), "fold"
        the whole `gb.fold` — of which "fold_h2d", nested, is the staging
        of what was not pre-uploaded and the rest the jitted dispatch —,
        "shadow_fold" the numpy mirror into the un-merged pre-issues'
        shadows; together with the source's "decode" these expose the
        ingest-pipeline balance per node."""
        with self.stats.stage("upload", sub.n):
            cols, valid, slots = self._build_kernel_inputs(sub)
            if self.gb.capacity < self.kt.capacity:
                # the key table is wider than the state: a restored
                # snapshot taken at a smaller capacity than this node was
                # built with (restore_state keeps the table's own)
                self.state = self.gb.grow(self.state, self.kt.capacity)
            if self.tier is not None:
                # admission point: returning demoted keys (this batch's
                # new-key log) get their spilled partials merged back
                # into their fresh slots before the fold lands
                self.state = self.tier.admit(self.state)
            dev = self._shared_device_inputs(sub, cols, valid, slots)
        with self.stats.stage("fold", sub.n):
            if self._orderstat:
                self._os_reserve(pane_arg, sub.n)
            if dev is not None:
                # shared uploads: device columns/slots computed once
                # serve every fan-out consumer; host copies still feed
                # the shadows
                dcols, dvalid, dslots = dev
                self.state = self.gb.fold(
                    self.state, {**cols, **dcols},
                    dslots if dslots is not None else slots,
                    {**valid, **dvalid}, pane_arg, n_rows=sub.n,
                    h2d=self._fold_h2d)
            else:
                self.state = self.gb.fold(self.state, cols, slots,
                                          valid, pane_arg,
                                          h2d=self._fold_h2d)
        if self._exact:
            self.fold_int_exact_rows += sub.n
        if self._orderstat:
            self._os_fill[pane_arg] += sub.n
        if hasattr(self.gb, "note_rows"):
            # per-shard accounting (kuiper_shard_*): the kernel counts
            # host slot vectors itself; the prep path hands it DEVICE
            # slots, so count off the host copy here — and refresh the
            # key-occupancy hint either way
            if dev is not None and dev[2] is not None:
                self.gb.note_rows(slots, sub.n, n_keys=self.kt.n_keys)
            else:
                self.gb.n_keys_hint = self.kt.n_keys
        if self._pipeline:
            # every un-merged pre-issue's shadow mirrors the fold
            with self.stats.stage("shadow_fold",
                                  sub.n * len(self._pipeline)):
                for _, shadow in self._pipeline:
                    self.shadow_fold_compact_rows += shadow.fold(
                        cols, slots, valid)
        return sub.n

    def _os_reserve(self, pane: int, rows: int) -> None:
        """Climb the order-statistic buffers' rung before `rows` more rows
        into `pane` would not fit (ops/orderstat.py rung_for): a row is
        never dropped. The buffers grow on the device, every pane at once."""
        from ..ops import orderstat

        rung = self.state["osv"].shape[-1]
        need = orderstat.rung_for(self._os_fill[pane], rows, rung,
                                  self.gb.micro_batch)
        if need > rung:
            self.state = orderstat.grow_leaves(self.state, need)
            self.orderstat_grows += 1

    @property
    def orderstat_buffer_rows(self) -> int:
        """The order-statistic buffers' current rung: rows a pane holds
        (kuiper_orderstat_buffer_rows)."""
        if self.state is not None and "osv" in self.state:
            return int(self.state["osv"].shape[-1])
        from ..ops.orderstat import first_rung

        return first_rung(self.gb.micro_batch)

    # ------------------------------------------------------------ event time
    def _fold_event(self, sub: ColumnBatch) -> int:
        """Per-row pane routing for event-time windows: bucket = ts //
        bucket_ms, pane = bucket % P. Rows for already-emitted buckets drop
        (their pane may be recycled). A batch spanning more buckets than the
        pane budget folds IN ORDER: fold what fits, emit the oldest pending
        window to free its pane, continue — so a recycled pane is always
        emitted+reset before new rows land in it."""
        ts = sub.timestamps
        if ts is None:
            ts = np.zeros(sub.n, dtype=np.int64)
        buckets = ts // self.bucket_ms
        if self._next_emit_bucket is None:
            self._next_emit_bucket = int(buckets.min())
        late = buckets < self._next_emit_bucket
        if late.any():
            n_late = int(late.sum())
            self.stats.inc_dropped("stale_watermark", n=n_late,
                                   detail="bucket already emitted")
            keep = np.nonzero(~late)[0]
            if len(keep) == 0:
                return 0
            sub = sub.take(keep)
            buckets = buckets[keep]
        self._max_bucket = max(int(buckets.max()),
                               self._max_bucket
                               if self._max_bucket is not None else -1)
        total = 0
        while sub.n:
            # pane-reuse safety: bucket b is foldable once bucket b-P
            # expired, i.e. b <= next_emit + P - W
            limit = (self._next_emit_bucket
                     + self.n_panes - self.window_span)
            mask = buckets <= limit
            idx = np.nonzero(mask)[0]
            if len(idx):
                seg = buckets[idx]
                ub = np.unique(seg)
                # single-bucket batch (in-order streams, bucket >> batch
                # span — the common case): scalar pane, no per-row pane
                # vector upload, the same fast executable as processing time
                pane_arg = (int(ub[0]) % self.n_panes if len(ub) == 1
                            else (seg % self.n_panes).astype(np.uint8))
                total += self._fold_rows(
                    sub if mask.all() else sub.take(idx), pane_arg)
                self._dirty.update(int(b) for b in ub)
            if mask.all():
                break
            # make room for the rest: emit data windows in order, jump
            # over empty stretches without device round trips. NOTE: rows
            # within late tolerance that arrive AFTER a pane-pressure
            # forced emission drop (counted) — bounded panes trade the
            # host path's unbounded buffering for device residence.
            rest = np.nonzero(~mask)[0]
            sub = sub.take(rest)
            buckets = buckets[rest]
            self._advance_one(int(buckets.min()))
        return total

    def _advance_one(self, needed_bucket: int) -> None:
        """Advance the emission cursor toward making `needed_bucket`
        foldable: emit the next window when it can contain data, otherwise
        JUMP the empty stretch in O(1) (an outlier timestamp must not spin
        one iteration per empty bucket)."""
        nxt = self._next_emit_bucket
        if not self._dirty:
            self._next_emit_bucket = max(
                nxt + 1,
                needed_bucket - (self.n_panes - self.window_span))
            return
        first = min(self._dirty)
        if nxt < first:
            # windows ending before `first` see no data
            self._next_emit_bucket = first
            return
        self._emit_event_bucket(nxt)

    def _emit_event_bucket(self, b: int) -> None:
        """Emit the window ENDING at bucket b's boundary (tumbling: just b;
        hopping: the window spanning buckets [b-W+1 .. b]), then expire the
        oldest pane of that window. Windows with no dirty buckets skip the
        device round trip entirely."""
        W = self.window_span
        window_buckets = range(b - W + 1, b + 1)
        has_data = any(x in self._dirty for x in window_buckets)
        n_keys = self.kt.n_keys
        end_ms = (b + 1) * self.bucket_ms
        wr = WindowRange(end_ms - self.length_ms, end_ms)
        panes = sorted({(x % self.n_panes) for x in window_buckets})
        if has_data and n_keys:
            outs, act = self.gb.finalize(self.state, n_keys, panes=panes)
            self._emit_active(outs, act, wr, counted=False)
        # spilled keys demoted with data in this window's buckets emit
        # host-side (their pane epochs gate validity)
        self._emit_tier_extras(wr, panes=panes)
        expiring = b - W + 1
        if expiring in self._dirty:
            self._dirty.discard(expiring)
            self._reset_pane_tiered(expiring % self.n_panes)
        self._tier_boundary()
        self._next_emit_bucket = b + 1

    def on_watermark(self, wm) -> None:
        if self.is_event_time and self.wt == ast.WindowType.SESSION_WINDOW:
            self._evs_watermark(wm.ts)
            self.broadcast(wm)
            return
        if self.is_event_time and self._next_emit_bucket is not None:
            floor_b = wm.ts // self.bucket_ms - 1  # buckets fully below wm
            while self._next_emit_bucket <= floor_b:
                if not self._dirty:
                    self._next_emit_bucket = floor_b + 1
                    break
                first = min(self._dirty)
                if self._next_emit_bucket < first:
                    # nothing can emit before the first dirty bucket
                    self._next_emit_bucket = min(first, floor_b + 1)
                    continue
                self._emit_event_bucket(self._next_emit_bucket)
        self.broadcast(wm)

    def _fold_count_window(self, batch: ColumnBatch) -> None:
        pos = 0
        while pos < batch.n:
            room = self.count_len - self._rows_in_window
            take = min(room, batch.n - pos)
            self._fold(batch, pos, pos + take)
            self._rows_in_window += take
            pos += take
            if self._rows_in_window >= self.count_len:
                # a count window has no Trigger: its boundary starts at
                # the dispatch of the batch that fills it
                _emit_ctx.boundary_t0 = self.stats.started_perf_ns
                wr = WindowRange(0, timex.now_ms())
                if self._async_count:
                    self._emit_count_async(wr)
                else:
                    self._emit(wr)
                with self.stats.stage("boundary_reset"):
                    self.state = self.gb.reset_pane(self.state, 0)
                self._rows_in_window = 0

    # ---------------------------------------------------------- state window
    def _fold_state_window(self, batch: ColumnBatch) -> None:
        """Walk the batch's begin/emit toggle points (both masks computed
        in one vectorized pass); fold only open spans, emit + reset at
        each emit row (inclusive, mirroring the host row path — which does
        NOT evaluate the emit condition on the row that just opened the
        window)."""
        begin_m = _host_mask(self._begin_host, batch.columns, batch.n)
        emit_m = _host_mask(self._emitc_host, batch.columns, batch.n)
        pos = 0
        while pos < batch.n:
            scan_from = pos
            if not self._state_open:
                opens = np.nonzero(begin_m[pos:])[0]
                if not len(opens):
                    return  # closed and no begin row in the rest
                pos += int(opens[0])
                self._state_open = True
                scan_from = pos + 1  # opening row can't also close it
            closes = np.nonzero(emit_m[scan_from:])[0]
            if not len(closes):
                self._fold(batch, pos, batch.n)
                return  # window stays open across batches
            end = scan_from + int(closes[0]) + 1  # emit row is inclusive
            self._fold(batch, pos, end)
            self._emit(WindowRange(0, timex.now_ms()))
            self.state = self.gb.reset_pane(self.state, 0)
            self._state_open = False
            pos = end

    # ------------------------------------------------- event-time sessions
    def _evs_watermark(self, wm_ts: int) -> None:
        """Emit every COMPLETE leading session below the watermark — the
        vectorized mirror of the host path's sort/scan (nodes_window.py
        on_watermark SESSION branch): sort buffered rows by event time,
        split where consecutive gaps exceed the session gap, and emit a
        session only when last + gap <= wm. Each emitted session folds on
        device into pane 0 and finalizes through the normal emit tail."""
        if not self._evs_batches:
            return
        timeout = self.gap_ms
        big = (self._evs_batches[0] if len(self._evs_batches) == 1
               else ColumnBatch.concat(self._evs_batches))
        ts = big.timestamps
        if ts is None:
            ts = np.zeros(big.n, dtype=np.int64)
        order = np.argsort(ts, kind="stable")
        ts_sorted = ts[order]
        # session boundaries: index i ends a session when the next row is
        # more than `timeout` later
        bounds = np.nonzero(np.diff(ts_sorted) > timeout)[0]
        start = 0
        for end in [*(bounds + 1).tolist(), len(ts_sorted)]:
            last = int(ts_sorted[end - 1])
            if last + timeout > wm_ts:
                break  # leading incomplete session: stop, like the host
            sub = big.take(order[start:end])
            self._fold_rows(sub, 0)
            self._emit(WindowRange(int(ts_sorted[start]), last + timeout))
            self.state = self.gb.reset_pane(self.state, 0)
            start = end
        if start == 0:
            self._evs_batches = [big]  # compacted, nothing emitted
        elif start >= len(ts_sorted):
            self._evs_batches = []
        else:
            self._evs_batches = [big.take(np.sort(order[start:]))]

    def _evs_flush(self) -> None:
        """EOF flush: all buffered rows as ONE window [now-L, now) — host
        path parity (nodes_window.py on_eof)."""
        if not self._evs_batches:
            return
        big = (self._evs_batches[0] if len(self._evs_batches) == 1
               else ColumnBatch.concat(self._evs_batches))
        self._evs_batches = []
        now = timex.now_ms()
        self._fold_rows(big, 0)
        self._emit(WindowRange(now - self.length_ms, now))
        self.state = self.gb.reset_pane(self.state, 0)

    # ---------------------------------------------------------- session time
    def _touch_session(self) -> None:
        """A batch arrived: open the session if closed (arming the length
        cap) and record the last-row time. ONE inactivity-check timer per
        gap window — it re-arms itself against `_last_row_ms` instead of a
        timer per batch (a timer thread per batch would accumulate
        batch_rate x gap_seconds sleepers on the hot path)."""
        now = timex.now_ms()
        if not self._session_open:
            self._session_open = True
            self._session_start = now
            self._session_id += 1
            if self.length_ms > 0:
                sid = self._session_id
                self._cap_timer = timex.after(
                    self.length_ms,
                    lambda ts, _s=sid: self.put_control(
                        Trigger(ts=ts, tag=("session_cap", _s))))
        self._last_row_ms = now
        if (self._gap_timer is None or self._gap_timer.fired
                or self._gap_timer.stopped):
            self._arm_gap_check(self.gap_ms)

    def _arm_gap_check(self, delay_ms: int) -> None:
        # a fired-but-undrained previous check may still deliver its trigger;
        # the generation tag makes that stale trigger a no-op, so re-arming
        # here can never leave two live gap checks for one session
        if self._gap_timer is not None:
            self._gap_timer.stop()
        self._gap_gen += 1
        sid, gen = self._session_id, self._gap_gen
        self._gap_timer = timex.after(
            max(delay_ms, 1),
            lambda ts, _s=sid, _g=gen: self.put_control(
                Trigger(ts=ts, tag=("session_gap", _s, _g))))

    def _on_session_trigger(self, trig: Trigger) -> None:
        kind, sid = trig.tag[0], trig.tag[1]
        if not self._session_open or sid != self._session_id:
            return  # stale trigger for a session that already closed
        if kind == "session_cap":
            self._close_session(trig.ts)
            return
        if trig.tag[2] != self._gap_gen:
            return  # superseded gap check — a newer one is armed
        # gap check: close only if the session has truly been idle for a
        # full gap; otherwise re-arm for the remaining quiet time (a row
        # may have arrived after this timer fired but before it drained)
        idle = timex.now_ms() - self._last_row_ms
        if idle >= self.gap_ms:
            self._close_session(self._last_row_ms + self.gap_ms)
        else:
            self._arm_gap_check(self.gap_ms - idle)

    def _touch_session_timers_only(self) -> None:
        """Arm gap (+ remaining cap) timers for an already-open session
        (checkpoint restore)."""
        now = timex.now_ms()
        self._last_row_ms = now
        self._session_id += 1
        if self.length_ms > 0:
            remaining = max(self._session_start + self.length_ms - now, 1)
            sid = self._session_id
            self._cap_timer = timex.after(
                remaining,
                lambda ts, _s=sid: self.put_control(
                    Trigger(ts=ts, tag=("session_cap", _s))))
        self._arm_gap_check(self.gap_ms)

    def _close_session(self, end_ts: int) -> None:
        self._emit(WindowRange(self._session_start, end_ts))
        self.state = self.gb.reset_pane(self.state, 0)
        self._session_open = False
        for t in (self._gap_timer, self._cap_timer):
            if t is not None:
                t.stop()
        self._gap_timer = self._cap_timer = None

    # ------------------------------------------------- async count emission
    def _emit_count_async(self, wr: WindowRange) -> None:
        """Dispatch the device finalize on the (immutable) current state and
        hand the fetch+emit to the worker thread; the fold stream continues
        without waiting a device round trip."""
        if self.kt.n_keys == 0:
            self.last_emit_info = None
            return
        self._emit_async(
            "count", lambda: self.gb._finalize(
                self.state, (True,) * self.gb.n_panes), wr)

    def _emit_hh_async(self, wr: WindowRange) -> None:
        """Heavy-hitters boundary: dispatch the compact device recovery on
        the immutable state and hand delivery to the worker."""
        if self.kt.n_keys == 0:
            self.last_emit_info = None
            return
        self._emit_async(
            "hh", lambda: self.gb._hh_fin(
                self.state, np.ones(self.gb.n_panes, dtype=np.bool_)), wr)

    def _emit_os_async(self, wr: WindowRange) -> None:
        """Order-statistic boundary: dispatch the device selection on the
        immutable state — it also counts the rows the closing pane's
        buffers hold — and hand delivery to the worker."""
        if self.kt.n_keys == 0:
            self.last_emit_info = None
            return
        self._emit_async(
            "os", lambda: self.gb._os_sel(
                self.state, np.ones(self.gb.n_panes, dtype=np.bool_),
                self.gb._pane_arg(self.cur_pane)), wr)

    def _keys_snapshot(self):
        """Slot->key decode snapshot for a DEFERRED delivery: tiered
        rules retire/recycle slots at boundaries (ops/tierstore.py), so
        a worker delivery decoding the LIVE table could attribute the
        window to a slot's next tenant. Untiered tables are append-only
        — no snapshot needed. Sliding stays live too: it demotes only
        quiescent keys (act 0 in every pane — never in a delivery's
        active set), and a per-trigger million-entry copy would be real
        overhead."""
        if self.tier is None or self.wt == ast.WindowType.SLIDING_WINDOW:
            return None
        return self.kt.decode_all()

    def _emit_async(self, kind: str, dispatch, wr: WindowRange) -> None:
        """Shared async-emit protocol: `dispatch()` the finalize program,
        start the device→host copy, enqueue for the worker. The dispatched
        program sees an immutable snapshot, so the caller is free to reset
        panes immediately after."""
        with self.stats.stage("emit"):
            t_issue = time.perf_counter()  # issue→landed starts here
            with self.stats.span("finalize"):
                stacked_dev = dispatch()
                stacked_dev.copy_to_host_async()
            self._emit_submit(kind, stacked_dev, self.kt.n_keys, wr,
                              self._keys_snapshot(), t_issue)

    def _emit_submit(self, kind: str, payload, n_keys: int, wr,
                     keys_snap=None, t_issue: Optional[float] = None) -> None:
        """Enqueue one deferred delivery for the emit worker with what it
        needs from THIS (the dispatch) thread, captured at issue: the
        ingest provenance (the worker must not read the live
        _cur_ingest_ms, which keeps advancing with post-boundary folds),
        the boundary's start and the trace context. `t_issue` is the perf
        clock before the dispatch, where the caller made one; now, else."""
        self._ensure_emit_worker()
        self._emit_q.put((kind, payload, n_keys, wr,
                          t_issue or time.perf_counter(),
                          self._cur_ingest_ms, keys_snap,
                          getattr(_emit_ctx, "boundary_t0", None),
                          Tracer.current()))

    def _ensure_emit_worker(self) -> None:
        import queue
        import threading

        if self._emit_q is None:
            self._emit_q = queue.Queue()
        if self._emit_worker is None or not self._emit_worker.is_alive():
            self._emit_worker = threading.Thread(
                target=self._emit_worker_loop, name=f"{self.name}-emit",
                daemon=True)
            self._emit_worker.start()

    def _emit_worker_loop(self) -> None:
        while True:
            item = self._emit_q.get()
            if item is None:
                break
            (kind, payload, n_keys, wr, t_issue, issue_ing, keys_snap,
             boundary_t0, ctx) = item
            # install the issue-time provenance for every emit() this
            # delivery makes (node.py reads it ahead of _cur_ingest_ms;
            # issue_ing=None means "stamp nothing", not "read live");
            # keys_snap pins the slot->key decode to dispatch time so a
            # tiered boundary's slot retire/recycle between dispatch and
            # delivery cannot misattribute the window. The boundary's start
            # and the trace context of the issuing dispatch come along, so
            # the delivery's phases and spans belong to that boundary.
            _emit_ctx.ingest_ms = issue_ing
            _emit_ctx.boundary_t0 = boundary_t0
            Tracer.set_current(ctx)
            self._kt_keys_override = keys_snap
            try:
                if kind == "tier":
                    # tiered-state maintenance (ops/tierstore.py): harvest
                    # a landed demote block / run the placement scan —
                    # off the fold thread, by design
                    self.tier.worker_task(payload)
                    continue
                # `emit` accrues off the node's worker here, as the
                # pool's `decode` does off the source's
                with self.stats.stage("emit") as st:
                    st.rows = self._deliver_async(kind, payload, n_keys, wr,
                                                  t_issue)
            except Exception as exc:
                logger.error("async %s emit failed on %s: %s",
                             kind, self.name, exc)
                # count it: a window dropped here must show in /rules
                # metrics, not just a log line (the sync path raised into
                # the node's normal exception accounting)
                self.stats.inc_exception(f"async {kind} emit failed: {exc}")
            finally:
                _emit_ctx.ingest_ms = _NO_OVERRIDE
                _emit_ctx.boundary_t0 = None
                Tracer.set_current(None)
                self._kt_keys_override = None
                self._emit_q.task_done()

    def _deliver_async(self, kind: str, payload, n_keys: int, wr,
                       t_issue: float) -> int:
        """One deferred delivery on the emit worker: wait for the device→
        host copy (`fetch`), assemble the window (`merge`), hand it down;
        returns the groups emitted."""
        if kind == "pf":
            return self._deliver_pf(*payload, n_keys, wr)
        if kind == "ring":
            # sliding DABA trigger. Device tail (no shadow): the compact
            # final values land, the window's key slots are cut from them.
            # Host tail: the O(1) body combine's components land, the host
            # edge shadow is merged, final values in numpy — the same
            # component tail as the prefinalize emit. Dispatch -> landed
            # of the ring's programs and what is left on the host are
            # stages of their own inside `emit` (one call a trigger each:
            # counters a reader can take); the dyn fallback's fetch is
            # another program's and stays a `fetch` span
            pending, shadow, path = payload
            if shadow is None:
                with self.stats.stage("slide_query", n_keys, within="emit",
                                      since_ns=int(t_issue * 1e9)):
                    # kuiperlint: ignore[host-sync]: emit worker thread — THE intended sync point; the fold thread already dispatched and moved on
                    arr = np.asarray(pending)
                fetch_ms = (time.perf_counter() - t_issue) * 1000.0
                with self.stats.stage("slide_merge", n_keys, within="emit"):
                    outs, act = self._outs_from_stacked(arr, n_keys)
            else:
                if pending is not None:
                    with self.stats.span("fetch"):
                        pending.get()
                fetch_ms = (pending.fetch_ms() if pending is not None else
                            (time.perf_counter() - t_issue) * 1000.0)
                with self.stats.stage("slide_merge", n_keys, within="emit"):
                    outs, act = self.gb.prefinalize_merge(pending, shadow,
                                                          n_keys)
            self.last_emit_info = {"source": "device-ring",
                                   "fetch_ms": fetch_ms}
            return self._emit_active(outs, act, wr)
        if kind == "os":
            # the selection's dispatch → landed is a stage of its own
            # inside `emit` (one call a boundary); the ranks, midpoints
            # and interpolations on the host are the `merge` span
            with self.stats.stage("orderstat_select", n_keys, within="emit",
                                  since_ns=int(t_issue * 1e9)):
                # kuiperlint: ignore[host-sync]: emit worker thread — THE intended sync point; the fold thread already dispatched and moved on
                arr = np.asarray(payload)
            self.last_emit_info = {
                "source": "device-async",
                "fetch_ms": (time.perf_counter() - t_issue) * 1000.0,
            }
            self.orderstat_rows += self.gb.os_closed_rows(arr)
            with self.stats.span("merge"):
                outs, act = self.gb.os_assemble(arr, n_keys)
            return self._emit_active(outs, act, wr)
        # heavy hitters: dispatch → landed and the host tail below are
        # stages of their own inside `emit` (counters a reader can take:
        # one call a boundary), not sub-stage spans
        with (self.stats.stage("hh_finalize", n_keys, within="emit",
                               since_ns=int(t_issue * 1e9))
              if kind == "hh" else self.stats.span("fetch")):
            # kuiperlint: ignore[host-sync]: emit worker thread — THE intended sync point; the fold thread already dispatched and moved on
            arr = np.asarray(payload)
        self.last_emit_info = {
            "source": "device-async",
            "fetch_ms": (time.perf_counter() - t_issue) * 1000.0,
        }
        if kind == "mr":
            self._deliver_mr(arr, n_keys, wr)
            self._count_emit_source()
            return n_keys
        if kind == "hh":
            with self.stats.stage("hh_assemble", n_keys, within="emit"):
                outs, act = self.gb.hh_assemble(arr, n_keys, self._hh_items)
            return self._emit_active(outs, act, wr, hh_decoded=True)
        with self.stats.span("merge"):
            outs, act = self._outs_from_stacked(arr, n_keys)
        return self._emit_active(outs, act, wr)

    def _outs_from_stacked(self, arr: np.ndarray, n_keys: int):
        """(per-spec values, act) over the window's key slots from a
        landed finalize result (`gb.outs_from_finalized`)."""
        with self._int_combine(n_keys):
            return self.gb.outs_from_finalized(arr, n_keys)

    def _int_combine(self, n_keys: int):
        """The stage an exact plan's host tail runs in, nested in `emit`
        on whichever thread runs it: limbs -> int64, the shadow merge, the
        integer mean and the int64 columns the sink is handed. Nothing for
        a float plan."""
        if not self._exact:
            return _NO_STAGE
        return self.stats.stage("int_combine", n_keys, within="emit")

    def _fetch_and_merge(self, pending, shadow, n_keys: int):
        """Complete a pre-issued finalize on this thread: wait for its
        fetch to land (`fetch` sub-stage), then merge the tail shadow and
        compute the final values (`merge`)."""
        with self.stats.span("fetch"):
            pending.get()
        with self.stats.span("merge"), self._int_combine(n_keys):
            return self.gb.prefinalize_merge(pending, shadow, n_keys)

    def _emit_active(self, outs, act, wr: WindowRange,
                     counted: bool = True, hh_decoded: bool = False) -> int:
        """Build the output of the window's active groups, if any, along
        the path the plan chose (the tail of the `merge` sub-stage) and
        hand it downstream; returns the number of active groups, 0 for an
        empty window. `counted` bumps the per-source window count first;
        `hh_decoded` says the heavy-hitters codes are values already."""
        active = np.nonzero(act > 0)[0]
        if len(active) == 0:
            return 0
        if counted:
            self._count_emit_source()
        with self.stats.span("merge", len(active)):
            if not hh_decoded:
                outs = self._decode_hh(outs)
            built = (self._build_direct(outs, active, wr)
                     if self.direct_emit is not None
                     else self._build_grouped(outs, active, wr))
        if built is not None:
            self._hand_down(*built)
        return len(active)

    def _hand_down(self, item: Any, count: int = 1) -> None:
        """Hand a window's result downstream: here the boundary's `emit`
        phase ends (dispatch of what closed the window -> now) and its
        `sink` phase begins — the stamp rides the item to the sink."""
        t0 = getattr(_emit_ctx, "boundary_t0", None)
        if t0 is not None and self._topo is not None:
            now = time.perf_counter_ns()
            self._topo.observe_boundary("emit", (now - t0) / 1000.0)
            _stamp_item(item, now, "boundary_ns")
        self.emit(item, count)

    # bounded drain deadline; tests shrink it to exercise the abort path
    drain_deadline_s: float = 30.0

    def _drain_async_emits(self, deadline_s: Optional[float] = None,
                           must_complete: bool = False) -> None:
        """Block until in-flight async emissions have been delivered —
        called before checkpoints, EOF flush, and close so ordering and
        snapshot contracts hold. Bounded: a wedged device fetch must not
        hang checkpoints/EOF/close forever. On
        timeout: the snapshot path (must_complete=True) RAISES so the
        checkpoint fails and a later one retries — committing now would
        advance source offsets past rows whose window output exists only
        in this process's queue (a crash would lose it). EOF/close paths
        log and proceed: the worker is still alive and delivers whenever
        the fetch unwedges."""
        q = self._emit_q
        if q is None:
            return
        if deadline_s is None:
            deadline_s = self.drain_deadline_s
        deadline = time.perf_counter() + deadline_s
        with q.all_tasks_done:
            while q.unfinished_tasks:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    if must_complete:
                        raise RuntimeError(
                            f"{self.name}: async emit drain timed out after "
                            f"{deadline_s:.0f}s with {q.unfinished_tasks} "
                            "emission(s) in flight — aborting this "
                            "checkpoint (a later one will retry)")
                    logger.error(
                        "%s: async emit drain timed out after %.0fs with %d "
                        "emission(s) still in flight; proceeding without "
                        "waiting (the emit worker delivers them when the "
                        "device fetch unwedges)",
                        self.name, deadline_s, q.unfinished_tasks)
                    return
                q.all_tasks_done.wait(remaining)

    # -------------------------------------------------------- tiered state
    def _tier_submit(self, payload: tuple) -> None:
        """Hand a tier task (demote harvest / policy scan) to the
        prefinalize/emit worker — the policy and the packed-row fetch
        never run on the fold thread."""
        self._emit_submit("tier", payload, 0, None)

    def _on_tier_event(self, kind: str, n: int = 0) -> None:
        """Tier transition hook: demotions/promotions invalidate the
        sliding ring's running partials (the panes stay the truth — the
        next trigger rebuilds via flip or the components_dyn fallback),
        and demotions leave a flight-recorder breadcrumb."""
        if self.wt == ast.WindowType.SLIDING_WINDOW and \
                self.sliding_impl == "daba":
            self._rg_dirty = True
        if kind == "demote":
            from .events import recorder

            recorder().record(
                "tier_demote", rule=self.stats.rule_id, severity="info",
                component="tier_store", node=self.name, keys=n)

    def _reset_pane_tiered(self, pane: int) -> None:
        """reset_pane + the tier epoch bump: spilled rows remember the
        per-pane epoch they were packed under, so a reset here marks
        their slice of that pane stale (ops/tierstore.py)."""
        self.state = self.gb.reset_pane(self.state, pane)
        if self.tier is not None:
            self.tier.note_pane_reset(pane)
        if self._orderstat:  # the device fill went back to 0 too
            self._os_fill[pane] = 0

    def _tier_boundary(self) -> None:
        """Pane-boundary tier hook (fold thread): apply the worker's
        pending demote plan and dispatch the next touch scan."""
        if self.tier is not None:
            self.state = self.tier.on_boundary(self.state)

    def _emit_tier_extras(self, wr: WindowRange,
                          panes: Optional[List[int]] = None) -> None:
        """Emit the spilled (cold-tier) keys' contribution to a closing
        window: their still-valid per-pane partials finalize host-side
        (the prefinalize numpy tail) and ride the same emit tail as the
        device groups — as a second message for the window, after (or
        concurrent with) the device groups."""
        if self.tier is None:
            return
        res = self.tier.window_groups(self.plan, panes)
        if res is None:
            return
        keys, outs, _act = res
        if self.direct_emit is not None:
            dim_names = [d.name for d in self.dims]
            dim_cols: Dict[str, np.ndarray] = {}
            if dim_names:
                if len(dim_names) == 1:
                    col = np.empty(len(keys), dtype=np.object_)
                    col[:] = keys
                    dim_cols[dim_names[0]] = col
                else:
                    for i, dn in enumerate(dim_names):
                        col = np.empty(len(keys), dtype=np.object_)
                        col[:] = [k[i] for k in keys]
                        dim_cols[dn] = col
            if self.emit_columnar:
                cb = self.direct_emit.run_columnar(
                    dim_cols, outs, wr.window_start, wr.window_end)
                if cb is not None and cb.n:
                    self.emit(cb, count=cb.n)
            else:
                msgs = self.direct_emit.run(
                    dim_cols, outs, wr.window_start, wr.window_end)
                if msgs:
                    self.emit(msgs, count=len(msgs))
            return
        out_lists = []
        for col in outs:
            sel = col
            if np.issubdtype(sel.dtype, np.floating):
                sel = np.where(np.isnan(sel), None, sel.astype(object))
            out_lists.append(sel.tolist())
        groups: List[GroupedTuples] = []
        dim_names = [d.name for d in self.dims]
        single_dim = dim_names[0] if len(dim_names) == 1 else None
        spec_keys = self._spec_keys
        ts = wr.window_end
        for j, key in enumerate(keys):
            if single_dim is not None:
                msg = {single_dim: key}
            elif dim_names:
                msg = dict(zip(dim_names, key))
            else:
                msg = {}
            agg_values = {spec_keys[i]: out_lists[i][j]
                          for i in range(len(spec_keys))}
            groups.append(GroupedTuples(
                content=[Tuple(emitter="", message=msg, timestamp=ts)],
                group_key=str(key), window_range=wr,
                agg_values=agg_values))
        self.emit(GroupedTuplesSet(groups=groups, window_range=wr))

    # ------------------------------------------------------------- sliding
    def _choose_sliding_impl(self, requested: str) -> str:
        """Resolve the sliding implementation at construction: DABA rings
        when the kernel supports the component-merge tail (plain
        DeviceGroupBy — sharded folds and heavy_hitters finalizes keep the
        exact refold path) and the ring's static HBM footprint fits the
        sliding_dev_ring_mb budget; the refold path otherwise."""
        if requested != "daba":
            return "refold"
        if getattr(self.gb, "watch_prefix", "") != "groupby" or \
                not getattr(self.gb, "supports_prefinalize", False) or \
                getattr(self.gb, "_host_finalize_only", False):
            # structured + attributable (ISSUE 15 satellite): the silent
            # auto-fallback hid that a sharded rule's sliding triggers
            # still refold — the flight event names the reason, and the
            # explain "sliding" section mirrors it at plan time
            reason = ("sharded_kernel"
                      if getattr(self.gb, "watch_prefix", "") == "sharded"
                      else "heavy_hitters"
                      if getattr(self.gb, "_host_finalize_only", False)
                      else "kernel_form")
            from .events import recorder

            recorder().record(
                "sliding_impl_fallback", rule=self.stats.rule_id,
                severity="info", component="sliding_ring", node=self.name,
                requested="daba", action="refold", reason=reason)
            logger.info(
                "%s: sliding ring unavailable for this kernel form "
                "(%s) — using the refold path (mesh DABA ring is future "
                "work)", self.name, reason)
            return "refold"
        from ..ops.slidingring import SlidingRing

        try:
            ring = SlidingRing(self.gb, self._ring_layout)
        except ValueError as exc:
            logger.warning("%s: sliding ring rejected (%s) — using the "
                           "refold path", self.name, exc)
            return "refold"
        est = ring.estimate_bytes(self.gb.capacity)
        if est > self.dev_ring_budget_bytes:
            # structured flight event either way: a wide-hll rule that
            # still exceeds slidingDevRingMb after bucket coarsening
            # either got its capacity capped by the cold tier (tiered
            # construction shrinks it to the hot target, so this branch
            # means even THAT didn't fit) or silently refolding would
            # hide the regression class PR 11 left open
            from .events import recorder

            recorder().record(
                "sliding_ring_budget", rule=self.stats.rule_id,
                severity="warn", component="sliding_ring", node=self.name,
                estimate_bytes=int(est),
                budget_bytes=int(self.dev_ring_budget_bytes),
                tiered=self._tier_layout is not None, action="refold")
            logger.warning(
                "%s: sliding ring needs %.1fMB > slidingDevRingMb=%.0fMB "
                "budget — using the refold path (raise the budget, "
                "coarsen the window, or tighten the tier hot target)",
                self.name, est / 2**20, self.dev_ring_budget_bytes / 2**20)
            return "refold"
        if self._tier_layout is not None:
            # DABA accepted at the tier-capped capacity: record that the
            # cold tier (not refolding) is what absorbs excess
            # cardinality for this rule
            from .events import recorder

            recorder().record(
                "sliding_tier_demote", rule=self.stats.rule_id,
                severity="info", component="sliding_ring", node=self.name,
                estimate_bytes=int(est),
                budget_bytes=int(self.dev_ring_budget_bytes),
                hot_slots=int(self._tier_layout.hot_slots),
                action="daba_tiered")
        self.ring = ring
        self._ring_reset_tracking()
        # the running total retains one spare bucket beyond the window
        # span: eviction must subtract a pane BEFORE its slot can be
        # recycled by bucket b+R in the same fold call (R = span + 3)
        self._span_tot = self._ring_layout.span_buckets + 1
        return "daba"

    def _ring_reset_tracking(self) -> None:
        """Host-side ring bookkeeping to a cold (dirty) state: the next
        trigger rebuilds the device partials from the panes in one flip."""
        from collections import deque as _deque

        self._rg_head = -1       # newest bucket any row has folded into
        self._rg_closed = -1     # last bucket absorbed into the partials
        self._rg_dirty = True    # cache needs a flip before serving
        self._rg_flip_lo = -1    # front-stack span [flip_lo, flip_hi]
        self._rg_flip_hi = -1
        self._rg_closes = 0      # advance count (drift re-anchor cadence)
        self._rg_anchor = 0
        self._rg_tot = _deque()  # (bucket, slot, absorbed) in the total

    def _ring_state_now(self):
        """The live device ring state, lazily allocated and kept at the
        kernel's (possibly grown) key capacity."""
        if self._ring_dev is None:
            self.ring.capacity = int(self.gb.capacity)
            self._ring_dev = self.ring.init_state()
        elif self.ring.capacity < self.gb.capacity:
            self._ring_dev = self.ring.grow(self._ring_dev,
                                            self.gb.capacity)
        return self._ring_dev

    def ring_dev_bytes(self) -> int:
        """memwatch probe: live HBM bytes of the DABA ring partials."""
        if self._ring_dev is None:
            return 0
        from ..ops.slidingring import SlidingRing

        return SlidingRing.state_nbytes(self._ring_dev)

    def _ring_advance_buckets(self, buckets: np.ndarray) -> None:
        """Bucket-close maintenance after a fold: absorb newly closed
        panes into the running partials (O(1) device work per bucket,
        ~1/bucket_ms per second — off the trigger path). Late rows into
        already-absorbed buckets and time gaps mark the cache dirty; the
        next trigger heals it with one flip (the panes stay the truth)."""
        ubs = np.unique(buckets).tolist()
        nh = int(ubs[-1])
        if self._rg_closed >= 0 and int(ubs[0]) <= self._rg_closed:
            self._rg_dirty = True
        if nh <= self._rg_head:
            return
        if self._rg_head < 0 or nh - self._rg_head > 8:
            # cold start or a time gap: skip per-bucket advances and let
            # the next trigger rebuild everything in one flip
            self._rg_dirty = True
            self._rg_tot.clear()
            self._rg_head = nh
            self._rg_closed = nh - 1
            return
        for b in range(self._rg_head, nh):
            self._ring_close_bucket(b)
        self._rg_head = nh

    def _ring_close_bucket(self, b: int) -> None:
        slot = b % self.n_ring_panes
        on = self._pane_bucket.get(slot) == b
        ev_slot, ev_on = 0, False
        self._rg_tot.append((b, slot, on))
        if len(self._rg_tot) > self._span_tot:
            ob, oslot, oon = self._rg_tot.popleft()
            if oon and self._pane_bucket.get(oslot) != ob:
                # the evicted bucket's pane was already recycled (burst
                # batch) — its contribution cannot be subtracted; rebuild
                # from the panes at the next trigger instead
                self._rg_dirty = True
            else:
                ev_slot, ev_on = oslot, bool(oon)
        if not self._rg_dirty:
            with self.stats.stage("slide_advance", flip=False):
                self._ring_dev = self.ring.advance(
                    self._ring_state_now(), self.state, slot, bool(on),
                    ev_slot, ev_on)
        self._rg_closes += 1
        self._rg_closed = b

    def _ring_admit(self, sub: ColumnBatch, ts, buckets):
        """What a micro-batch's buckets do to the ring before its rows are
        folded: the late guard, pane recycling, expiry of retained rows.
        Returns (sub, ts, buckets) without the rows that came too late, or
        None when none is left."""
        # late guard: drop a row ONLY when its pane has been recycled
        # past its bucket (folding it would corrupt newer live data).
        # Rows merely out of order — pane still holds their bucket, or
        # an older one the recycle loop will reset — fold exactly like
        # the host path.
        if self._ring_max_bucket >= 0:
            drop_buckets = []
            for b in np.unique(buckets).tolist():
                held = self._pane_bucket.get(int(b) % self.n_ring_panes)
                if held is not None and held > int(b):
                    drop_buckets.append(int(b))
            if drop_buckets:
                late = np.isin(buckets, drop_buckets)
                n_late = int(late.sum())
                self.stats.inc_dropped(
                    "pane_recycle", n=n_late,
                    detail="sliding pane retention")
                keep = np.nonzero(~late)[0]
                if len(keep) == 0:
                    return None
                sub = sub.take(keep)
                ts = ts[keep]
                buckets = buckets[keep]
        # recycle panes: reset any pane about to receive a newer
        # bucket. The recycled bucket's ROWS stay in the ring a while
        # longer — a trigger whose window still needs that bucket
        # detects the recycled pane and refolds the whole window from
        # the ring (exact fallback)
        for b in np.unique(buckets).tolist():
            pane = int(b) % self.n_ring_panes
            held = self._pane_bucket.get(pane)
            if held is not None and held != int(b):
                self._reset_pane_tiered(pane)
            self._pane_bucket[pane] = int(b)
        self._ring_max_bucket = max(self._ring_max_bucket,
                                    int(buckets.max()))
        # ring outlives panes by a margin so the stale-window fallback
        # can always reconstruct; beyond that the window is
        # unrecoverable anyway
        floor_b = self._ring_max_bucket - self.n_ring_panes - 8
        expired = [b for b in self._ring if b < floor_b]
        for b in expired:
            del self._ring[b]
            dropped = self._dev_ring.pop(b, None)
            if dropped:
                self._dev_ring_bytes -= sum(
                    self._dev_entry_nbytes(e) for e in dropped)
            self._bucket_max_ts.pop(b, None)
        if expired:
            # purge the expired buckets' fifo bookkeeping too: the
            # evict loop only drains it when OVER budget, so an
            # under-budget rule would otherwise grow the deque for the
            # life of the stream
            self._dev_ring_fifo = type(self._dev_ring_fifo)(
                t for t in self._dev_ring_fifo if t[0] >= floor_b)
        return sub, ts, buckets

    def _fold_sliding(self, sub: ColumnBatch) -> int:
        """Sliding device path: fold rows into time panes keyed by row
        timestamp, mirror them into the host ring (for edge-bucket refolds
        at emission), and fire trigger rows."""
        # `slide_ring`: what a micro-batch costs this thread for the window
        # being a ring of panes and retained rows, outside `upload`, `fold`,
        # `slide_advance` and `slide_edge` — here the stamps' buckets, the
        # guard, the recycle and the expiry, below the append and the
        # trigger mask
        with self.stats.stage("slide_ring"):
            ts = sub.timestamps
            if ts is None:
                now = timex.now_ms()
                ts = np.full(sub.n, now, dtype=np.int64)
            buckets = ts // self.bucket_ms
            aliased = int(buckets.max() - buckets.min()) >= self.n_ring_panes
            kept = None if aliased else self._ring_admit(sub, ts, buckets)
        # a single batch spanning >= n_ring_panes buckets would alias two
        # buckets onto one pane WITHIN one fold call (replay/backfill
        # bursts); split into alias-free chunks folded in bucket order so
        # each recycle lands before its pane receives new rows
        if aliased:
            order = np.argsort(buckets, kind="stable")
            sorted_b = buckets[order]
            start = 0
            base = int(sorted_b[0])
            for i in range(1, len(order) + 1):
                if i == len(order) or int(sorted_b[i]) - base >= self.n_ring_panes:
                    self._fold_sliding(sub.take(order[start:i]))
                    if i < len(order):
                        base = int(sorted_b[i])
                        start = i
            return sub.n
        if kept is None:
            return 0
        sub, ts, buckets = kept
        daba = self.sliding_impl == "daba"
        with self.stats.stage("upload", sub.n):
            cols, valid, slots = self._build_kernel_inputs(sub)
            if self.tier is not None:
                self.state = self.tier.admit(self.state)
            # the DABA path needs no device batch cache: triggers combine
            # running partials, edges fold on host from the row ring
            dev = (None if daba
                   else self._upload_sliding_inputs(cols, valid, slots))
            pane_vec = (buckets % self.n_ring_panes).astype(np.uint8)
            fold_cols, fold_valid, fold_slots, n_rows = (
                (dev[0], dev[1], dev[2], sub.n) if dev is not None
                else (cols, valid, slots, None))
        with self.stats.stage("fold", sub.n):
            # single-bucket batch: scalar-pane fast path (the common case —
            # a batch spans far less time than one pane)
            pane_arg = (int(pane_vec[0]) if len(np.unique(pane_vec)) == 1
                        else pane_vec)
            self.state = self.gb.fold(self.state, fold_cols, fold_slots,
                                      fold_valid, pane_arg, n_rows=n_rows,
                                      h2d=self._fold_h2d)
        if hasattr(self.gb, "note_rows"):
            self.gb.n_keys_hint = self.kt.n_keys  # fold counted host slots
        with self.stats.stage("slide_ring", sub.n):
            for b in np.unique(buckets).tolist():
                m = buckets == b
                sel = np.nonzero(m)[0]
                seg = (
                    {k: v[sel] for k, v in cols.items()},
                    {k: v[sel] for k, v in valid.items()},
                    slots[sel], ts[sel],
                ) if not m.all() else (cols, valid, slots, ts)
                self._ring.setdefault(int(b), []).append(seg)
                if not daba:
                    # aligned device entry: whole-batch refs + this
                    # bucket's row mask (the refold ANDs the window time
                    # cut into it)
                    entry = (None if dev is None
                             else (dev[3], dev[2], m, ts))
                    lst = self._dev_ring.setdefault(int(b), [])
                    lst.append(entry)
                    if entry is not None:
                        nb = self._dev_entry_nbytes(entry)
                        self._dev_ring_bytes += nb
                        self._dev_ring_fifo.append(
                            (int(b), len(lst) - 1, nb))
                        self._dev_ring_evict()
                bmax = int(ts[sel].max())
                if bmax > self._bucket_max_ts.get(int(b), -1):
                    self._bucket_max_ts[int(b)] = bmax
            # trigger rows: vectorized OVER(WHEN ...) on the raw batch columns
            trig_mask = _host_mask(self._trigger_host, sub.columns, sub.n)
        if daba:
            self._ring_advance_buckets(buckets)
        # tier maintenance at bucket granularity (sliding's pane
        # boundary): throttled by the scan cadence inside
        self._tier_boundary()
        for i in np.nonzero(trig_mask)[0].tolist():
            t = int(ts[i])
            if self.delay_ms > 0:
                self._schedule_sliding(t, timex.now_ms() + self.delay_ms)
            else:
                self._emit_sliding(t)
        return sub.n

    def _upload_sliding_inputs(self, cols, valid, slots, force: bool = False):
        """Pre-pad + upload one batch's fold inputs, so (a) the fold uses
        them without its own upload and (b) the ring keeps the device refs
        for mask-only edge refolds. Returns (dev_cols, dev_valid, s_dev,
        dev_all) or None when the batch can't ship as one chunk.
        dev_all is the combined {col, __valid_col} dict fold_masked takes.
        `force` bypasses the small-batch HBM guard — the warmup uses it so
        fold_masked actually compiles (a 1-row warmup batch would otherwise
        be rejected and the first real trigger would pay the jit stall)."""
        mb = self.gb.micro_batch
        n = len(slots)
        if n > mb or not getattr(self.gb, "accepts_device_inputs", False) \
                or getattr(self.gb, "mesh_tag", ""):
            # sharded sliding keeps the host-path edge refold: fold_masked
            # is uncertified for the sharded kernel and the _dev_ring
            # would pin replicated (unsharded) copies across the mesh
            return None
        if n < mb // 4 and not force:
            # small batches would pin a full mb-padded device buffer each
            # for the whole ring retention window — HBM cost out of all
            # proportion; their edge refolds are cheap host uploads anyway
            return None
        import jax.numpy as jnp

        from ..ops.aggspec import materialize_hll_columns

        from ..ops.groupby import col_np_dtype

        cols = materialize_hll_columns(self.plan.columns, cols, n)
        pad = mb - n
        dev_cols, dev_valid, dev_all = {}, {}, {}
        for name in self.plan.columns:
            arr = np.asarray(cols[name], dtype=col_np_dtype(self.plan, name))
            if pad:
                arr = np.pad(arr, (0, pad))
            d = jnp.asarray(arr)
            dev_cols[name] = d
            dev_all[name] = d
            vm = valid.get(name)
            if vm is not None:
                vm = np.pad(vm, (0, pad)) if pad else vm
                vm = jnp.asarray(vm)
                dev_valid[name] = vm
            dev_all["__valid_" + name] = vm
        s = slots
        if pad:
            s = np.pad(s, (0, pad))
        from ..ops.groupby import slot_dtype

        # capacity here is post-grow for this batch (_build_kernel_inputs
        # ran first), so a mid-stream doubling past 65,535 switches NEW
        # cached entries to int32; earlier uint16 entries in _dev_ring stay
        # valid — their slot values predate the grow (fold_masked casts)
        s_dev = jnp.asarray(s.astype(slot_dtype(self.gb.capacity),
                                     copy=False))
        return dev_cols, dev_valid, s_dev, dev_all

    @staticmethod
    def _dev_entry_nbytes(entry) -> int:
        """Device footprint of one _dev_ring entry. Multi-bucket batches
        share the same whole-batch buffers across their entries, so this
        over-counts them — the budget errs toward evicting early, never
        toward exceeding HBM."""
        if entry is None:
            return 0
        dev_all, s_dev = entry[0], entry[1]

        def nb(a):
            if a is None:
                return 0
            v = getattr(a, "nbytes", None)
            return int(v) if v is not None else int(
                a.size * a.dtype.itemsize)

        return sum(nb(a) for a in dev_all.values()) + nb(s_dev)

    def _dev_ring_evict(self) -> None:
        """Drop the oldest cached device entries until the cache fits the
        HBM budget; their refolds fall back to the exact host path (the
        aligned _ring rows are always retained)."""
        freed = evicted = 0
        while (self._dev_ring_bytes > self.dev_ring_budget_bytes
               and self._dev_ring_fifo):
            b, idx, nbytes = self._dev_ring_fifo.popleft()
            lst = self._dev_ring.get(b)
            if lst is None or idx >= len(lst) or lst[idx] is None:
                continue  # already gone (bucket expired past the ring floor)
            lst[idx] = None
            self._dev_ring_bytes -= nbytes
            freed += nbytes
            evicted += 1
        if evicted:
            # flight-recorder breadcrumb: budget pressure is why refolds
            # slowed down (host-path fallback), worth a line in a bundle
            from .events import recorder

            recorder().record(
                "memory_evict", rule=self.stats.rule_id, severity="warn",
                component="dev_ring", node=self.name, entries=evicted,
                bytes_freed=freed, bytes_now=self._dev_ring_bytes,
                budget_bytes=self.dev_ring_budget_bytes)

    def _schedule_sliding(self, t: int, fire_at: int) -> None:
        """Register a delayed sliding emission; tracked in _pending_slides
        so a checkpoint/restore re-arms it instead of dropping the window."""
        self._pending_slides[t] = fire_at
        delay = max(fire_at - timex.now_ms(), 0)
        timex.after(delay, lambda _ts, t0=t: self.put_control(
            Trigger(ts=t0, tag=("sliding", t0))))

    def _emit_sliding(self, t: int) -> None:
        """Emit the exact window (t-L, t+delay] for trigger time t."""
        if self.sliding_impl == "daba":
            return self._emit_sliding_ring(t)
        n_keys = self.kt.n_keys
        if n_keys == 0:
            return
        lo = t - self.length_ms  # exclusive
        hi = t + self.delay_ms  # inclusive
        b_lo, b_hi = lo // self.bucket_ms, hi // self.bucket_ms
        full = []
        stale = False
        for b in range(b_lo + 1, b_hi):
            if self._pane_bucket.get(b % self.n_ring_panes) == b:
                full.append(b)
            elif b in self._ring:
                stale = True  # pane recycled but ring rows still present
        scratch_rows = []

        def ring_rows(b, lo_excl=None, hi_incl=None):
            devs = self._dev_ring.get(b, [])
            for i, (cols, valid, slots, ts) in enumerate(self._ring.get(b, [])):
                dev = devs[i] if i < len(devs) else None
                if dev is not None:
                    # mask-only refold: AND the window time cut into the
                    # bucket mask over the cached whole-batch device input
                    dev_all, s_dev, bmask, full_ts = dev
                    m = bmask.copy()
                    if lo_excl is not None:
                        m &= full_ts > lo_excl
                    if hi_incl is not None:
                        m &= full_ts <= hi_incl
                    if m.any():
                        mb = self.gb.micro_batch
                        if len(m) < mb:
                            m = np.pad(m, (0, mb - len(m)))
                        scratch_rows.append(("dev", dev_all, s_dev, m))
                    continue
                m = np.ones(len(ts), dtype=np.bool_)
                if lo_excl is not None:
                    m &= ts > lo_excl
                if hi_incl is not None:
                    m &= ts <= hi_incl
                if m.any():
                    sel = np.nonzero(m)[0]
                    scratch_rows.append(("host",
                        {k: v[sel] for k, v in cols.items()},
                        {k: v[sel] for k, v in valid.items()},
                        slots[sel]))

        if stale:
            # fallback: a needed pane was recycled under emission backlog —
            # refold the WHOLE window from the ring (exact, just slower)
            full = []
            for b in range(b_lo, b_hi + 1):
                ring_rows(b, lo_excl=lo, hi_incl=hi)
            self.stats.inc_exception("sliding pane recycled; ring refold")
        else:
            if b_lo == b_hi:
                ring_rows(b_lo, lo_excl=lo, hi_incl=hi)
            else:
                ring_rows(b_lo, lo_excl=lo)
                # high edge served straight from its PANE when exact: the
                # pane holds precisely bucket b_hi's rows folded so far,
                # which equals (b_hi*B, hi] when no received row exceeds hi
                # and the pane's span clears the window's low cut
                if (self._pane_bucket.get(b_hi % self.n_ring_panes) == b_hi
                        and b_hi * self.bucket_ms > lo
                        and self._bucket_max_ts.get(b_hi, hi + 1) <= hi):
                    full.append(b_hi)
                else:
                    ring_rows(b_hi, hi_incl=hi)
        used_scratch = False
        for entry in scratch_rows:
            if entry[0] == "dev":
                _, dev_all, s_dev, m = entry
                self.state = self.gb.fold_masked(
                    self.state, dev_all, s_dev, m, self._scratch_pane)
            else:
                _, cols, valid, slots = entry
                with self.stats.stage("fold", len(slots)):
                    self.state = self.gb.fold(
                        self.state, cols, slots, valid, self._scratch_pane,
                        h2d=self._fold_h2d)
            used_scratch = True
        panes = sorted({b % self.n_ring_panes for b in full})
        if used_scratch:
            panes.append(self._scratch_pane)
        if panes and getattr(self.gb, "_host_finalize_only", False):
            # host-only components: keep the exact synchronous path
            outs, act = self.gb.finalize(self.state, n_keys, panes=panes)
            self._emit_active(outs, act, WindowRange(lo, hi), counted=False)
        elif panes:
            # dispatch-and-defer: the finalize launches here, IN ORDER on
            # the device stream (after the scratch folds, before the
            # scratch reset below), and the emit worker fetches+delivers —
            # a sync fetch would stall the fold stream ~1+ RTT per trigger
            # (the r03-recorded 0.3-1s sliding emit latencies were exactly
            # these blocking fetches). The traced (runtime) pane mask keeps
            # one compiled executable no matter which panes are live.
            pane_mask = np.zeros(self.gb.n_panes, dtype=np.bool_)
            pane_mask[panes] = True
            self._emit_async(
                "count",
                lambda: self.gb._finalize_dyn(self.state, pane_mask),
                WindowRange(lo, hi))
        if used_scratch:
            self._reset_pane_tiered(self._scratch_pane)

    # ---------------------------------------------------- sliding (DABA)
    def _emit_sliding_ring(self, t: int) -> None:
        """DABA-ring emission for trigger time t: the full-pane window
        body is ONE device combine of the ring's running partials (plus at
        most QUERY_ADJ pane slices). Where that program served the body
        (paths fast and flip) the trigger is finished on the device: the
        rows of the partial edge buckets, cut by stamp from the row ring,
        go up in one fixed-shape buffer and the tail program scatters
        them, combines body and edges and returns final values — no
        per-trigger shadow, no fetch of the sketch. Every off-discipline
        shape (a head that moved on, a window inside one bucket, an empty
        body, delay, recycled panes, restores) keeps the exact host tail:
        a HostShadow of the edge rows merged by the emit worker with
        whatever the traced-mask pane merge returns. The panes remain the
        ground truth on both."""
        from ..ops.prefinalize import HostShadow

        n_keys = self.kt.n_keys
        if n_keys == 0:
            return
        lo = t - self.length_ms  # exclusive
        hi = t + self.delay_ms  # inclusive
        b_lo, b_hi = lo // self.bucket_ms, hi // self.bucket_ms
        include_head = False
        if b_lo == b_hi:
            # window inside one bucket: the edge rows ARE the window
            cuts = [(b_lo, lo, hi)]
            body = None
        else:
            cuts = [(b_lo, lo, None)]
            body = (b_lo + 1, b_hi - 1)
            # high edge served straight from the live PANE when exact:
            # it holds precisely bucket b_hi's rows folded so far, which
            # equals (b_hi*B, hi] when no received row exceeds hi
            if (self._pane_bucket.get(b_hi % self.n_ring_panes) == b_hi
                    and self._bucket_max_ts.get(b_hi, hi + 1) <= hi):
                include_head = True
            else:
                cuts.append((b_hi, None, hi))
        # the path first, then the edge rows once: for the device where
        # the ring's program took the body, for the shadow where not
        t_issue = time.perf_counter()  # slide_query: dispatch -> landed
        body_dev, path = self._ring_body_query(body, include_head, b_hi)
        pending = shadow = None
        # the trigger's edge rows on this (the fused) thread — a stage of
        # its own, inside neither `fold` nor `emit`
        with self.stats.stage("slide_edge") as st:
            if body_dev is not None:
                buffers = self.ring.edge_buffers(
                    [seg for cut in cuts for seg in self._ring_rows_cut(*cut)])
                st.rows = sum(n for _c, _v, _s, n in buffers)
                pending = self.ring.tail_begin(body_dev, buffers)
            else:
                # as many rows as key slots are in use: the window has no
                # other, and for a wide sketch the allocation is most of
                # the stage (4 KB a row at 1,024 bins)
                shadow = HostShadow(self.plan, self.gb.comp_specs, n_keys)
                for cut in cuts:
                    self._shadow_ring_rows(shadow, *cut)
                st.rows = shadow.n_rows
        if path == "dyn":
            pending = self._ring_query_dyn(*body, include_head,
                                           b_hi % self.n_ring_panes, shadow)
            if pending is None:
                path = "edge"
        tail = "host" if shadow is not None else "device"
        self.sliding_triggers[path] = self.sliding_triggers.get(path, 0) + 1
        self.sliding_tails[tail] = self.sliding_tails.get(tail, 0) + 1
        self._emit_submit("ring", (pending, shadow, path), n_keys,
                          WindowRange(lo, hi), t_issue=t_issue)

    def _ring_rows_cut(self, b: int, lo_excl: Optional[int] = None,
                       hi_incl: Optional[int] = None):
        """Bucket b's retained rows inside a time cut, segment by segment:
        yields (cols, valid, slots, sel) — the segment's kernel inputs
        and the rows of it that count (an index array; None: all of
        them). Bounded by ONE bucket of rows, not the window history."""
        for cols, valid, slots, ts in self._ring.get(b, []):
            m = np.ones(len(ts), dtype=np.bool_)
            if lo_excl is not None:
                m &= ts > lo_excl
            if hi_incl is not None:
                m &= ts <= hi_incl
            if m.any():
                yield cols, valid, slots, (None if m.all()
                                           else np.nonzero(m)[0])

    def _shadow_ring_rows(self, shadow, b: int, lo_excl: Optional[int] = None,
                          hi_incl: Optional[int] = None) -> None:
        """Numpy-fold bucket b's retained rows (optionally time-cut) into
        the trigger's HostShadow."""
        for cols, valid, slots, sel in self._ring_rows_cut(b, lo_excl,
                                                           hi_incl):
            if sel is None:
                shadow.fold(cols, slots, valid)
            else:
                shadow.fold({k: v[sel] for k, v in cols.items()},
                            slots[sel],
                            {k: v[sel] for k, v in valid.items()})

    def _ring_body_query(self, body, include_head: bool, b_hi: int):
        """Resolve the path that serves one trigger's window body and,
        where it is the ring's own program, dispatch it: the O(1) ring
        query when the running partials cover the body (fast), after a
        one-off flip (rebuild from panes) when they don't (flip). Returns
        the query's components on the device and the path; (None, "dyn")
        for shapes outside the in-order discipline (delayed emissions,
        recycled panes), whose traced-mask pane merge the caller
        dispatches once the shadow is there, and (None, "edge") for an
        empty body."""
        from ..ops.slidingring import QUERY_ADJ

        head_slot = b_hi % self.n_ring_panes
        if body is None:
            return None, "edge"
        j, e = body
        if j > e:
            if not include_head:
                return None, "edge"
            adj_slots = np.zeros(QUERY_ADJ, dtype=np.int32)
            adj_w = np.zeros(QUERY_ADJ, dtype=np.float32)
            adj_mm = np.zeros(QUERY_ADJ, dtype=np.bool_)
            adj_slots[0] = head_slot
            adj_w[0] = 1.0
            adj_mm[0] = True
            return self.ring.query(
                self._ring_state_now(), self.state, body_on=False,
                f_on=False, f_slot=0, adj_slots=adj_slots,
                adj_weights=adj_w, adj_mm=adj_mm), "fast"
        if self._rg_closed == e and self._rg_head == b_hi:
            path = "fast"
            ok = not self._rg_dirty and self._ring_fast_ok(j)
            if not ok:
                path = "flip"
                self._ring_flip(j, e)
                ok = not self._rg_dirty and self._ring_fast_ok(j)
            if ok:
                return self._ring_query_fast(j, include_head,
                                             head_slot), path
        return None, "dyn"

    def _ring_fast_ok(self, j: int) -> bool:
        """Can the running partials serve a body starting at bucket j?"""
        from ..ops.slidingring import QUERY_ADJ

        if self._rg_closes - self._rg_anchor > 4 * self._span_tot:
            # periodic re-anchor: rebuild the float totals from the panes
            # before subtract-on-evict drift can accumulate
            return False
        if self.ring.mm_comps:
            if self._rg_flip_lo < 0 or j < self._rg_flip_lo \
                    or j > self._rg_flip_hi + 1:
                return False
        if not self._rg_tot or self._rg_tot[0][0] > j:
            return False  # the total no longer covers the window start
        n_sub = sum(1 for (b, _s, on) in self._rg_tot if b < j and on)
        return n_sub <= QUERY_ADJ - 1

    def _ring_flip(self, j: int, e: int) -> None:
        """Rebuild every running partial from the live panes over [j, e]
        (one fused device scan — the amortized DABA flip). A bucket whose
        pane was recycled while its rows are still retained cannot flip
        (the pane is gone); the caller then takes the dyn fallback."""
        from collections import deque as _deque

        valid = np.zeros(self.n_ring_panes, dtype=np.bool_)
        tot_entries = []
        for b in range(j, e + 1):
            s = b % self.n_ring_panes
            live = self._pane_bucket.get(s) == b
            if not live and b in self._ring:
                return  # rows exist but the pane is gone — dyn fallback
            valid[b - j] = live
            tot_entries.append((b, s, live))
        with self.stats.stage("slide_advance", flip=True):
            self._ring_dev = self.ring.flip(
                self._ring_state_now(), self.state, j % self.n_ring_panes,
                valid)
        self._rg_tot = _deque(tot_entries)
        self._rg_flip_lo, self._rg_flip_hi = j, e
        self._rg_anchor = self._rg_closes
        self._rg_dirty = False

    def _ring_query_fast(self, j: int, include_head: bool,
                         head_slot: int):
        """The constant-time trigger: combine(front[j], back) for the
        two-stack components, the running total ± at most two trailing
        pane slices for the additive ones, plus the live head pane."""
        from ..ops.slidingring import QUERY_ADJ

        adj_slots = np.zeros(QUERY_ADJ, dtype=np.int32)
        adj_w = np.zeros(QUERY_ADJ, dtype=np.float32)
        adj_mm = np.zeros(QUERY_ADJ, dtype=np.bool_)
        k = 0
        for b, s, on in self._rg_tot:
            if b < j and on:
                adj_slots[k] = s
                adj_w[k] = -1.0
                k += 1
        if include_head:
            adj_slots[k] = head_slot
            adj_w[k] = 1.0
            adj_mm[k] = True
        f_on = bool(self.ring.mm_comps) and j <= self._rg_flip_hi
        return self.ring.query(
            self._ring_state_now(), self.state, body_on=True, f_on=f_on,
            f_slot=j % self.n_ring_panes, adj_slots=adj_slots,
            adj_weights=adj_w, adj_mm=adj_mm)

    def _ring_query_dyn(self, j: int, e: int, include_head: bool,
                        head_slot: int, shadow):
        """Exact fallback body: merge the window's live panes under a
        traced mask (one executable, O(window span) reads — only for
        off-discipline triggers); buckets whose pane was recycled refold
        their retained rows on host into the trigger's shadow."""
        pane_mask = np.zeros(self.gb.n_panes, dtype=np.bool_)
        missing = 0
        for b in range(j, e + 1):
            s = b % self.n_ring_panes
            if self._pane_bucket.get(s) == b:
                pane_mask[s] = True
            elif b in self._ring:
                self._shadow_ring_rows(shadow, b)
                missing += 1
        if missing:
            self.stats.inc_exception("sliding pane recycled; ring refold")
        if include_head:
            pane_mask[head_slot] = True
        if not pane_mask.any():
            return None
        return self.gb.components_begin_dyn(self.state, pane_mask)

    # ---------------------------------------------------------------- trigger
    def on_pre_trigger(self, pre: PreTrigger) -> None:
        """Ahead of the window boundary: dispatch finalize on the state
        snapshot (jax immutability = free double buffer) and start shadowing
        tail rows on host. If an earlier pre-issue for this boundary has
        already landed, this refresh is unnecessary and skipped; if it's
        still in flight, stack a fresher one. See ops/prefinalize.py."""
        if not self._prefinalize_ok or self.kt.n_keys == 0:
            return
        from ..ops.prefinalize import HostShadow

        # a landed fetch serves the boundary — no refresh needed
        if self._pipeline and self._pipeline[-1][0].ready():
            return
        # at most 2 un-landed device fetches: each is a full components
        # download, and stacking more behind a slow one compounds the
        # backlog until fetches lag the stream by whole windows
        if len(self._pipeline) >= 2:
            return
        with self.stats.stage("emit"), self.stats.span("finalize"):
            self._pipeline.append((
                self.gb.prefinalize_begin(self.state),
                HostShadow(self.plan, self.gb.comp_specs, self.kt.capacity),
            ))

    def on_trigger(self, trig: Trigger) -> None:
        # the boundary begins with this dispatch; how late it is against
        # the tick's own time is timer lateness plus the queue behind folds
        _emit_ctx.boundary_t0 = self.stats.started_perf_ns
        if self._topo is not None:
            self._topo.observe_boundary(
                "trigger_delay", (timex.now_ms() - trig.ts) * 1000.0)
        if self.wt == ast.WindowType.SLIDING_WINDOW:
            # delayed sliding emission scheduled at trigger-row time + delay
            if isinstance(trig.tag, tuple) and trig.tag[0] == "sliding":
                self._pending_slides.pop(trig.tag[1], None)
                self._emit_sliding(trig.tag[1])
            return
        if self.wt == ast.WindowType.SESSION_WINDOW:
            if isinstance(trig.tag, tuple) and trig.tag[0] in (
                    "session_gap", "session_cap"):
                self._on_session_trigger(trig)
            return
        end = trig.ts
        wr = WindowRange(end - self.length_ms, end)
        if self._async_hh:
            self._emit_hh_async(wr)
        elif self._async_os:
            self._emit_os_async(wr)
        elif self._async_mr:
            self._emit_mr_async(wr)
        else:
            self._boundary_emit(wr)
        # spilled (cold-tier) keys with live pane data contribute to this
        # window host-side, BEFORE the pane expiry marks them stale
        self._emit_tier_extras(wr)
        # what the boundary leaves this thread to do once the window is on
        # its way: the pane reset's dispatch, tier upkeep, the next tick's
        # timers (a thread each)
        with self.stats.stage("boundary_reset"):
            if self.wt == ast.WindowType.TUMBLING_WINDOW:
                self._reset_pane_tiered(0)
            else:
                # advance to the next pane; expire it (it held the oldest
                # slice)
                self.cur_pane = (self.cur_pane + 1) % self.n_panes
                self._reset_pane_tiered(self.cur_pane)
            self._tier_boundary()
            self._schedule_next_tick()

    def on_eof(self, eof: EOF) -> None:
        if self.is_event_time and self.wt == ast.WindowType.SESSION_WINDOW:
            self._drain_async_emits()
            self._evs_flush()
            self.broadcast(eof)
            return
        if self.is_event_time and self.wt not in (
                ast.WindowType.COUNT_WINDOW, ast.WindowType.STATE_WINDOW):
            # flush every window that can still contain data (bounded
            # runs / trials) — iterate the dirty set, never bucket-by-bucket
            # across gaps. COUNT/STATE fold into pane 0 like processing
            # time and flush through the shared path below (their _dirty
            # set is never populated — returning here would silently drop
            # the open span)
            while self._dirty:
                first = min(self._dirty)
                nxt = self._next_emit_bucket
                self._next_emit_bucket = first if nxt is None else max(nxt,
                                                                       first)
                self._emit_event_bucket(self._next_emit_bucket)
            self.broadcast(eof)
            return
        if self.wt == ast.WindowType.SLIDING_WINDOW:
            # sliding emits only on trigger rows; nothing to flush
            self.broadcast(eof)
            return
        now = timex.now_ms()
        self._drain_async_emits()  # deliver queued count windows in order
        if self.wt == ast.WindowType.SESSION_WINDOW:
            if self._session_open:
                self._close_session(now)
            self.broadcast(eof)
            return
        wr_eof = WindowRange(now - self.length_ms, now)
        self._emit(wr_eof)
        self._emit_tier_extras(wr_eof)
        if self.wt == ast.WindowType.TUMBLING_WINDOW:
            self._reset_pane_tiered(0)
        self.broadcast(eof)

    # ------------------------------------------------------------------- emit
    def _boundary_emit(self, wr: WindowRange) -> None:
        """Window-boundary emission that never blocks the fold stream.

        If some pre-issue is ready (a landed device fetch), emit
        synchronously — the fast path. Otherwise the merge would WAIT on
        an un-landed fetch (a wide sketch finalize is tens of MB — the
        reference's window trigger emits inline and has the same stall,
        window_op.go:235), so hand the wait to the emit worker and keep
        folding: the pre-issue snapshot is immutable,
        and the boundary's pane reset cannot disturb it. A worker backlog
        also defers, so windows always deliver in order."""
        if not self._emit_late_async:
            return self._emit(wr)
        q = self._emit_q
        backlog = q is not None and q.unfinished_tasks > 0
        ready_any = any(p.ready() for p, _ in self._pipeline)
        if not backlog and (ready_any or not self.kt.n_keys):
            return self._emit(wr)
        n_keys = self.kt.n_keys
        pipeline, self._pipeline = self._pipeline, []
        if pipeline:
            with self.stats.stage("emit"):
                # backup finalize dispatched NOW, before on_trigger's
                # reset_pane donates the state buffers: if the deferred
                # merge later fails (wedged fetch), the worker recovers
                # from this snapshot — a device launch whose transfer
                # happens only on that fallback
                with self.stats.span("finalize"):
                    backup = self.gb._finalize(
                        self.state, (True,) * self.gb.n_panes)
                self._emit_submit("pf", (pipeline, backup), n_keys,
                                  wr, self._keys_snapshot())
        else:
            # no pre-issue in flight: dispatch the finalize on the
            # immutable state and let the worker fetch + deliver
            self._emit_async(
                "count", lambda: self.gb._finalize(
                    self.state, (True,) * self.gb.n_panes), wr)

    def _deliver_pf(self, pipeline, backup, n_keys: int,
                    wr: WindowRange) -> int:
        """Emit-worker delivery of a deferred boundary: wait for the best
        pre-issue to land, merge, emit. Runs off the fold thread; touches
        only the immutable pre-issue snapshots and the closed window's
        shadow, never self.state. `backup` is a full finalize dispatched
        on the pre-reset snapshot — the recovery path when the merge
        fails, mirroring the sync path's finalize fallback."""
        chosen = next(
            ((p, s) for p, s in reversed(pipeline) if p.ready()),
            pipeline[0])
        try:
            outs, act = self._fetch_and_merge(chosen[0], chosen[1], n_keys)
        except Exception as exc:
            logger.warning("%s: deferred boundary merge failed (%s) — "
                           "recovering from the backup finalize", self.name,
                           exc)
            try:
                # kuiperlint: ignore[host-sync]: recovery path on the emit worker — fetching the backup finalize IS the point
                outs, act = self._outs_from_stacked(np.asarray(backup),
                                                    n_keys)
            except Exception as exc2:
                logger.error(
                    "%s: backup finalize also failed (%s) — window [%s, %s) "
                    "lost to the sink", self.name, exc2, wr.window_start,
                    wr.window_end)
                self.stats.inc_exception(f"deferred emit failed: {exc2}")
                return 0
        self.last_emit_info = {
            "source": "device-async-late",
            "fetch_ms": chosen[0].fetch_ms(),
        }
        return self._emit_active(outs, act, wr)

    def _count_emit_source(self) -> None:
        """Bump the cumulative per-source window count from the record
        the delivering path just wrote."""
        src = self.last_emit_info["source"]
        self.emit_sources[src] = self.emit_sources.get(src, 0) + 1

    def _emit(self, wr: WindowRange) -> None:
        """Synchronous emission on the calling (fold) thread: the `emit`
        stage with its `finalize`/`fetch`/`merge` sub-stages."""
        pipeline, self._pipeline = self._pipeline, []
        n_keys = self.kt.n_keys
        if n_keys == 0:
            self.last_emit_info = None  # no stale record for empty windows
            return
        with self.stats.stage("emit") as st:
            if pipeline:
                outs, act = self._merge_pipeline(pipeline, n_keys)
            else:
                outs, act = self._finalize_sync(n_keys)
                self.last_emit_info = {"source": "sync", "fetch_ms": 0.0}
            st.rows = self._emit_active(outs, act, wr)
            if not st.rows:
                self.last_emit_info = None  # nothing emitted this boundary

    def _finalize_sync(self, n_keys: int):
        """Dispatch the finalize and wait for it here: `finalize`."""
        with self.stats.span("finalize"), self._int_combine(n_keys):
            return self.gb.finalize(self.state, n_keys)

    def _merge_pipeline(self, pipeline, n_keys: int):
        """Serve a boundary from its pre-issued finalizes."""
        # newest READY pre-issue wins; if nothing is ready, wait on the
        # oldest (its fetch was registered first, it completes first)
        chosen = next(
            ((p, s) for p, s in reversed(pipeline) if p.ready()),
            pipeline[0])
        source = "device"
        try:
            outs, act = self._fetch_and_merge(chosen[0], chosen[1], n_keys)
        except Exception as exc:
            logger.warning("prefinalize merge failed, sync fallback: %s",
                           exc)
            outs, act = self._finalize_sync(n_keys)
            source = "sync"
        # read after the merge: the fetch may have been waited for
        self.last_emit_info = {"source": source,
                               "fetch_ms": chosen[0].fetch_ms()}
        return outs, act

    def _hh_items(self, i: int, codes: np.ndarray, counts: list) -> list:
        """The emitted form of spec `i`'s kept heavy-hitters candidates, all
        keys' at once: codes back to the original values in one array
        lookup, one dict a candidate."""
        vd = self._hh_dicts.get(self._hh_cols[i])
        if vd is None:
            return [{"value": None, "count": n} for n in counts]
        return [{"value": v, "count": n}
                for v, n in zip(vd.decode_array(codes).tolist(), counts)]

    def _decode_hh(self, outs):
        """Map heavy_hitters (code, count) pairs back to original values."""
        from ..ops.prefinalize import hh_split

        if not self._hh_cols:
            return outs
        outs = list(outs)
        for i in self._hh_cols:
            col = outs[i]
            pairs = [p for row in col for p in row]
            outs[i] = hh_split(
                self._hh_items(
                    i, np.array([c for c, _ in pairs], dtype=np.int64),
                    [n for _, n in pairs]),
                np.fromiter(map(len, col), dtype=np.int64, count=len(col)))
        return outs

    def _build_grouped(self, outs, active: np.ndarray, wr: WindowRange):
        """Row-path emit tail: build the GroupedTuplesSet for downstream
        HAVING/ORDER/PROJECT nodes; returns (item, count)."""
        # bulk-convert once (C speed) instead of per-slot numpy scalar access —
        # emit latency is dominated by this host loop at 10k+ groups
        active_list = active.tolist()
        out_lists = []
        for col in outs:
            sel = col[active]
            if np.issubdtype(sel.dtype, np.floating):
                sel = np.where(np.isnan(sel), None, sel.astype(object))
            out_lists.append(sel.tolist())
        groups: List[GroupedTuples] = []
        dim_names = [d.name for d in self.dims]
        single_dim = dim_names[0] if len(dim_names) == 1 else None
        spec_keys = self._spec_keys
        snap = self._kt_keys_override
        decode = snap.__getitem__ if snap is not None else self.kt.decode
        ts = wr.window_end
        for j, slot in enumerate(active_list):
            key = decode(slot)
            if single_dim is not None:
                msg = {single_dim: key}
            elif dim_names:
                msg = dict(zip(dim_names, key))
            else:
                msg = {}
            agg_values = {
                spec_keys[i]: out_lists[i][j] for i in range(len(spec_keys))
            }
            groups.append(
                GroupedTuples(
                    content=[Tuple(emitter="", message=msg, timestamp=ts)],
                    group_key=str(key), window_range=wr, agg_values=agg_values,
                )
            )
        return GroupedTuplesSet(groups=groups, window_range=wr), 1

    def _build_direct(self, outs, active: np.ndarray, wr: WindowRange):
        """Vectorized tail: HAVING/ORDER/LIMIT/projection computed over the
        finalize arrays into the final output messages; returns (item,
        count), or None when HAVING left nothing."""
        dim_names = [d.name for d in self.dims]
        dim_cols: Dict[str, np.ndarray] = {}
        if dim_names:
            keys = (self._kt_keys_override
                    if self._kt_keys_override is not None
                    else self.kt.decode_all())
            if len(dim_names) == 1:
                col = np.empty(len(active), dtype=np.object_)
                col[:] = [keys[s] for s in active.tolist()]
                dim_cols[dim_names[0]] = col
            else:
                sel = [keys[s] for s in active.tolist()]
                for i, dn in enumerate(dim_names):
                    col = np.empty(len(active), dtype=np.object_)
                    col[:] = [k[i] for k in sel]
                    dim_cols[dn] = col
        agg_cols = [col[active] for col in outs]
        if self.emit_columnar:
            cb = self.direct_emit.run_columnar(
                dim_cols, agg_cols, wr.window_start, wr.window_end
            )
            return (cb, cb.n) if cb is not None and cb.n else None
        msgs = self.direct_emit.run(
            dim_cols, agg_cols, wr.window_start, wr.window_end
        )
        # Fused direct-emit contract: always a list of message dicts,
        # never a bare dict, so consumers of this path see one shape per
        # mode (list here, ColumnBatch when emit_columnar) — ref
        # internal/xsql/collection.go:70, WindowTuples is one type.
        return (msgs, len(msgs)) if msgs else None

    # ------------------------------------------------------------------ state
    def snapshot_state(self) -> Optional[dict]:
        self._drain_async_emits(must_complete=True)
        # the pre-issues' shadows are not part of a snapshot: drop them, so
        # the open window's boundary finalizes the (complete) device state
        self._pipeline = []
        host = self.gb.state_to_host(self.state)
        snap = {
            "keys": self.kt.decode_all(),
            "partials": {k: v.tolist() for k, v in host.items()},
            "cur_pane": self.cur_pane,
            "rows_in_window": self._rows_in_window,
        }
        if self._hh_dicts:
            # code order indexes the saved sketch counters — must persist
            snap["hh_dicts"] = {
                c: vd.snapshot() for c, vd in self._hh_dicts.items()
            }
        if self.tier is not None:
            # both tiers persist: the device partials above already carry
            # the hot tier (keys list encodes retired slots as None
            # holes); this is the cold tier — spilled rows + epochs, so
            # a key demoted at kill time comes back queryable
            snap["tier"] = self.tier.snapshot()
        if self.wt == ast.WindowType.SESSION_WINDOW:
            snap["session_open"] = self._session_open
            snap["session_start"] = self._session_start
        if self.wt == ast.WindowType.STATE_WINDOW:
            snap["state_open"] = self._state_open
        if self.is_event_time:
            snap["next_emit_bucket"] = self._next_emit_bucket
            snap["max_bucket"] = self._max_bucket
            snap["dirty_buckets"] = sorted(self._dirty)
        if self.wt == ast.WindowType.SESSION_WINDOW and self.is_event_time \
                and self._evs_batches:
            snap["evs"] = [
                {"cols": {k: v.tolist() for k, v in b.columns.items()},
                 "valid": {k: v.tolist() for k, v in b.valid.items()},
                 "ts": (b.timestamps.tolist()
                        if b.timestamps is not None else None),
                 "emitter": b.emitter, "n": b.n}
                for b in self._evs_batches
            ]
        if self.wt == ast.WindowType.SLIDING_WINDOW:
            snap["pane_bucket"] = dict(self._pane_bucket)
            snap["ring_max_bucket"] = self._ring_max_bucket
            snap["pending_slides"] = dict(self._pending_slides)
            # the ring is a window's worth of raw rows (same magnitude as
            # the host path's buffer snapshot) — base64 of the raw array
            # bytes keeps serialization at memcpy speed instead of building
            # millions of Python objects via tolist()
            snap["ring"] = {
                str(b): [
                    {"cols": {k: _enc_arr(v) for k, v in cols.items()},
                     "valid": {k: _enc_arr(v) for k, v in valid.items()},
                     "slots": _enc_arr(slots), "ts": _enc_arr(ts)}
                    for cols, valid, slots, ts in segs
                ]
                for b, segs in self._ring.items()
            }
        return snap

    def restore_state(self, state: dict) -> None:
        keys = state.get("keys", [])
        self.kt.restore([tuple(k) if isinstance(k, list) else k for k in keys])
        partials = state.get("partials")
        if partials:
            host, cap = self.gb.host_from_partials(partials)
            self.gb.capacity = cap
            # a sharded kernel may round the restored capacity UP for
            # even shard division (mesh-size-change tolerance: an 8-shard
            # restore of a 1-chip snapshot, or vice versa) — state_from_
            # host owns that decision, the key table follows it
            self.state = self.gb.state_from_host(host)
            self.kt.capacity = max(self.kt.capacity, self.gb.capacity)
        if self.tier is not None and state.get("tier"):
            self.tier.restore(state["tier"])
        self.cur_pane = state.get("cur_pane", 0)
        self._rows_in_window = state.get("rows_in_window", 0)
        for c, values in state.get("hh_dicts", {}).items():
            vd = ValueDict()
            vd.restore(values)
            self._hh_dicts[c] = vd
        if self.wt == ast.WindowType.STATE_WINDOW:
            self._state_open = bool(state.get("state_open", False))
        if self.wt == ast.WindowType.SESSION_WINDOW \
                and state.get("session_open"):
            # re-open with fresh timers: a restored session's rows count,
            # and the gap restarts from the restore instant
            self._session_open = True
            self._session_start = int(state.get("session_start", 0))
            self._touch_session_timers_only()
        if self.is_event_time:
            self._next_emit_bucket = state.get("next_emit_bucket")
            self._max_bucket = state.get("max_bucket")
            self._dirty = set(state.get("dirty_buckets", []))
        if self.wt == ast.WindowType.SESSION_WINDOW and self.is_event_time:
            self._evs_batches = []
            for d in state.get("evs", []):
                cols = {}
                for k, v in d["cols"].items():
                    arr = np.asarray(v)
                    if arr.dtype.kind in ("U", "O"):  # strings stay object
                        arr = np.array(v, dtype=np.object_)
                    cols[k] = arr
                self._evs_batches.append(ColumnBatch(
                    n=int(d["n"]), columns=cols,
                    valid={k: np.asarray(v, dtype=np.bool_)
                           for k, v in d.get("valid", {}).items()},
                    timestamps=(np.asarray(d["ts"], dtype=np.int64)
                                if d.get("ts") is not None else None),
                    emitter=d.get("emitter", "")))
        if self.wt == ast.WindowType.SLIDING_WINDOW:
            self._pane_bucket = {int(k): v for k, v in
                                 state.get("pane_bucket", {}).items()}
            self._ring_max_bucket = state.get("ring_max_bucket", -1)
            self._bucket_max_ts = {}
            self._ring = {
                int(b): [
                    ({k: _dec_arr(v) for k, v in seg["cols"].items()},
                     {k: _dec_arr(v) for k, v in seg["valid"].items()},
                     _dec_arr(seg["slots"]), _dec_arr(seg["ts"]))
                    for seg in segs
                ]
                for b, segs in state.get("ring", {}).items()
            }
            # device input cache + max-ts tracking don't survive a restore:
            # refolds fall back to host uploads (exact), pane-serving stays
            # off for pre-restore buckets (missing max-ts fails the check).
            # Pad with None placeholders so post-restore appends stay
            # 1:1-aligned with the restored _ring segment lists — this must
            # run AFTER the ring is rebuilt (building it from the
            # pre-restore ring left restored segments unpadded, so the
            # first post-restore append landed at device index 0 while its
            # rows sat at ring index k: refolds then served the wrong
            # segment from the cache)
            self._dev_ring = {b: [None] * len(segs)
                              for b, segs in self._ring.items()}
            self._dev_ring_bytes = 0
            self._dev_ring_fifo.clear()
            if self.sliding_impl == "daba":
                # the ring partials are caches of the pane state — never
                # checkpointed; a restore starts dirty and the first
                # trigger rebuilds them from the restored panes in one flip
                self._ring_dev = None
                self._ring_reset_tracking()
                self._rg_head = self._ring_max_bucket
                self._rg_closed = (self._rg_head - 1
                                   if self._rg_head >= 0 else -1)
            # re-arm delayed emissions that were pending at the checkpoint
            # (past-due ones fire immediately) — without this, windows for
            # triggers inside the restart gap would silently never emit
            self._pending_slides = {}
            for t, fire_at in state.get("pending_slides", {}).items():
                self._schedule_sliding(int(t), int(fire_at))
