"""Shared pane-fold node — one device fold serving N correlated rules.

The subtopo pool (runtime/subtopo.py) already shares the source, decode,
key encode and device upload across rules of one stream; the expensive
part — the ops/groupby.py device fold — still ran once per rule. This
node closes that gap for rules the planner proves correlated
(planner/sharing.py: identical GROUP BY key set + WHERE, unionable
aggregate specs, window length/interval integer multiples of a common
pane): every batch folds ONCE into a shared pane ring (ops/panestore.py),
and each member rule gets a lightweight emit hop that combines the panes
spanning its window and runs its own vectorized tail into its own sink
chain.

Topology: the store rides the shared subtopo as ONE rider (rider id
"__fold__:<key>"), so the pool's refcounting, prep-ctx forwarding and
copy-on-write fan-out all apply unchanged:

    SrcSubTopo tail ─► [WatermarkNode]? ─► SharedFoldNode ─► rule A emit hop ─► A's sinks
                                                          └► rule B emit hop ─► B's sinks

Attach/detach are refcounted per member rule: a late-joining rule warms
from the LIVE panes (its first window may cover rows folded before it
attached — documented warmup semantics, docs/SHARING.md) without
restarting peers; the last detach tears the store down and releases the
subtopo rider. Shared folds serve qos=0 rules only (same restriction as
the subtopo pool — rule-scoped barriers cannot flow through a shared
pipeline); snapshot/restore still exists at node level (per-rule emit
cursors + pane partials) for save/restore tooling and tests.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from ..data.batch import ColumnBatch
from ..data.rows import Tuple as Row, WindowRange
from ..ops.aggspec import HH_COL_PREFIX, HLL_COL_PREFIX, KernelPlan
from ..ops.panestore import PaneStore, build_value_columns, spec_map_into
from ..utils import timex
from ..utils.infra import logger
from .events import EOF, Trigger, Watermark
from .ingest import key_encode_stage
from .node import Node


@dataclass
class MemberSpec:
    """Everything the store needs to emit one rule's windows."""

    rule_id: str
    length_ms: int
    interval_ms: int  # == length_ms for tumbling
    plan: KernelPlan  # the rule's OWN plan (spec order = direct_emit order)
    direct_emit: Any  # ops/emit.py DirectEmitPlan
    dims: List[str] = field(default_factory=list)
    emit_columnar: bool = True
    #: predicate lifting (ops/aggspec.py lift_predicate): index (into
    #: `plan.specs`) of the synthetic `count(*) FILTER(WHERE <pred>)`
    #: activity spec this member's group existence reads from; None =
    #: the store's global `act` (member folds every row)
    act_idx: Any = None


class _Member:
    __slots__ = ("spec", "entry", "topo", "span", "spec_map", "last_end_ms",
                 "attach_bucket")

    def __init__(self, spec: MemberSpec, entry: Node, topo: Any,
                 span: int, spec_map: List[int],
                 last_end_ms: Optional[int], attach_bucket: int) -> None:
        self.spec = spec
        self.entry = entry
        self.topo = topo
        self.span = span
        self.spec_map = spec_map
        self.last_end_ms = last_end_ms  # event-time emit cursor
        self.attach_bucket = attach_bucket


class SharedEmitNode(Node):
    """Per-rule emit hop behind a shared fold: gives the rule its own
    queue (backpressure isolation — one slow sink chain cannot stall the
    shared fold or its peers) and its own stats. Window results arrive
    fully combined; HAVING/ORDER/projection already ran in the member's
    vectorized tail inside the store."""

    def __init__(self, name: str, **kw) -> None:
        super().__init__(name, op_type="op", **kw)

    def process(self, item: Any) -> None:
        self.emit(item)


class _StoreShim:
    """Stands in as `_topo` for the store + its watermark node: errors fan
    out to every member rule's topo; log records route to the __shared__
    file (same contract as subtopo._FanoutTopoShim)."""

    rule_id = "__shared__"

    def __init__(self, store: "SharedFoldNode") -> None:
        self._store = store

    def drain_error(self, err: BaseException, origin: str = "") -> None:
        for topo in self._store.member_topos():
            topo.drain_error(err, f"sharedfold:{origin}")

    def checkpoint_ack(self, node_name, barrier, state) -> None:
        pass  # shared folds serve qos=0 rules only; no barriers flow here


class SharedFoldNode(Node):
    def __init__(
        self,
        key: str,
        name: str,
        plan: KernelPlan,
        pane_ms: int,
        n_panes: int,
        subtopo_ref=None,  # runtime/subtopo.py SubTopoRef; None = standalone
        capacity: int = 16384,
        micro_batch: int = 4096,
        is_event_time: bool = False,
        late_tolerance_ms: int = 0,
        buffer_length: int = 1024,
        mesh_cfg=None,
    ) -> None:
        super().__init__(name, op_type="op", buffer_length=buffer_length)
        self.key = key
        self.rider_id = "__fold__:" + key
        self.plan = plan
        self.pane_ms = int(pane_ms)
        self.n_panes = int(n_panes)
        self.is_event_time = bool(is_event_time)
        self.late_tolerance_ms = int(late_tolerance_ms)
        # key-range-sharded store (ISSUE 15): same-mesh members pool a
        # pane ring partitioned over the mesh's "keys" axis; an
        # unavailable mesh degrades to the single-chip store with a log
        # (the store key's mesh facet kept mismatched peers apart)
        mesh = None
        if mesh_cfg:
            from ..parallel.mesh import mesh_from_options, resolve_auto_cfg

            try:
                resolved = resolve_auto_cfg(dict(mesh_cfg))
                mesh = (mesh_from_options(resolved)
                        if resolved is not None else None)
            except Exception as exc:
                logger.warning(
                    "%s: shared pane store mesh %s unavailable (%s) — "
                    "single-chip store", name, mesh_cfg, exc)
        self.store = PaneStore(plan, pane_ms, n_panes, capacity=capacity,
                               micro_batch=micro_batch, mesh=mesh)
        self.dims: List[str] = []  # set by first attach (compat-keyed)
        self._members: Dict[str, _Member] = {}
        self._mlock = threading.RLock()
        self._subtopo = None
        self._subtopo_ref = subtopo_ref
        self._wm_node = None
        if is_event_time:
            from .nodes_window import WatermarkNode

            self._wm_node = WatermarkNode(
                f"{name}_wm", late_tolerance_ms=late_tolerance_ms,
                buffer_length=buffer_length)
            self._wm_node.connect(self)
        self._topo = _StoreShim(self)
        # shared store nodes are emitted under rule="__shared__" in the
        # scrape; their flight events (pane_recycle bursts) carry the
        # same label so /diagnostics/events?rule= filtering lines up
        self.stats.rule_id = "__shared__"
        if self._wm_node is not None:
            self._wm_node._topo = self._topo
            self._wm_node.stats.rule_id = "__shared__"
        self._opened = False
        self._closed = False
        self._tick_timer = None
        # pane bookkeeping: bucket = (time or event ts) // pane_ms,
        # pane = bucket % n_panes
        self._cur_bucket = timex.now_ms() // self.pane_ms
        self._pane_bucket: Dict[int, int] = {}
        self._dirty: set = set()
        self._floor_bucket: Optional[int] = None  # event time: emitted floor
        # cursors restored ahead of member re-attach (restore_state)
        self._restored_cursors: Dict[str, int] = {}
        # shared-source fan-out key encode (mirrors nodes_fused.py
        # _shared_encode): None = undecided, False = self-encode forever.
        # A live tier (ops/tierstore.py) recycles slots, which breaks the
        # neutral table's dense insertion-order contract — self-encode.
        self._shared_slots_ok: Optional[bool] = (
            None if self.store.tier is None else False)
        self._shared_nkt = None
        self.prep_ctx = None  # set by SrcSubTopo.attach
        self.prep_specs: List[tuple] = [self._prep_spec()]
        # fold-dedup telemetry: would = folds N private rules would have
        # run for the folded batches, did = folds this store actually ran
        self.folds_did = 0
        self.folds_would = 0
        self.windows_emitted = 0

    # ------------------------------------------------------------- accessors
    def member_count(self) -> int:
        return len(self._members)

    def member_topos(self) -> List[Any]:
        return [m.topo for m in self._members.values()]

    def pipeline_nodes(self) -> List[Node]:
        nodes: List[Node] = []
        if self._subtopo is not None:
            nodes.extend(self._subtopo.nodes)
        if self._wm_node is not None:
            nodes.append(self._wm_node)
        nodes.append(self)
        return nodes

    @property
    def source(self) -> Optional[Node]:
        return self._subtopo.source if self._subtopo is not None else None

    def keytable_encode_rows(self) -> Dict[str, int]:
        """Rows the store's own key table encoded, by path."""
        return self.store.kt.encode_rows

    def fold_dedup_ratio(self) -> float:
        """1 - actual folds / folds N private rules would have run."""
        if self.folds_would <= 0:
            return 0.0
        return 1.0 - self.folds_did / self.folds_would

    def pane_occupancy(self) -> float:
        """Fraction of the pane ring held by unexpired (dirty) buckets —
        occupancy approaching 1.0 under event time means the watermark
        lags far enough that panes risk recycling before emission (the
        counted `pane_recycle` loss mode). Health-evaluator probe."""
        return len(self._dirty) / max(self.n_panes, 1)

    def member_cursor_ms(self, rule_id: str) -> Optional[int]:
        """One member rule's event-time emit cursor (last emitted window
        end). Watermark lag is a PER-RULE fact even though the pane store
        is shared — each member advances its own cursor."""
        m = self._members.get(rule_id)
        return m.last_end_ms if m is not None else None

    def _prep_spec(self):
        """(key_name, kernel columns, micro_batch, derived, sharding,
        mesh_tag) for the shared ingest prep's upload stage — the union
        plan's one declaration of what precompute() should pre-upload
        for this store (incl. the members' predicate-lift derived
        columns, keyed by the union's expression-IR hash; sharded stores
        add their row sharding + mesh tag, nodes_fused.py prep_spec)."""
        from ..sql.expr_ir import is_derived_expr_col

        key_name = self.dims[0] if len(self.dims) == 1 else None
        # same gate as nodes_fused.prep_spec: never register a mesh
        # placement the kernel won't consume (multi-process meshes)
        shard_ok = (getattr(self.store.gb, "mesh_tag", "")
                    and getattr(self.store.gb, "accepts_device_inputs",
                                False))
        return (key_name,
                [n for n in self.plan.columns
                 if not n.startswith(HLL_COL_PREFIX)
                 and not n.startswith(HH_COL_PREFIX)
                 and not is_derived_expr_col(n)],
                self.store.gb.micro_batch,
                ((self.plan.expr_tag, self.plan.derived)
                 if getattr(self.plan, "derived", ()) else None),
                self.store.gb.batch_sharding if shard_ok else None,
                self.store.gb.mesh_tag if shard_ok else "")

    # --------------------------------------------------------- attach/detach
    def attach_rule(self, spec: MemberSpec, entry: Node, topo: Any) -> bool:
        """Join a rule to the shared fold. Returns False when this store
        already closed (caller resolves a fresh one from the pool); raises
        on geometry/spec mismatch — the planner declines such rules, so a
        mismatch here is a plan/open race and must fail loudly."""
        with self._mlock:
            if self._closed:
                return False
            if spec.rule_id in self._members:
                raise ValueError(
                    f"rule {spec.rule_id} already attached to {self.name}")
            if spec.length_ms % self.pane_ms or \
                    spec.interval_ms % self.pane_ms:
                raise RuntimeError(
                    f"{self.name}: rule {spec.rule_id} window "
                    f"({spec.length_ms}/{spec.interval_ms}ms) is not a "
                    f"multiple of the live {self.pane_ms}ms pane — replan")
            span = spec.length_ms // self.pane_ms
            if span > self.n_panes - 1:
                raise RuntimeError(
                    f"{self.name}: rule {spec.rule_id} spans {span} panes, "
                    f"store holds {self.n_panes} — replan")
            spec_map = spec_map_into(self.plan, spec.plan)
            if not self._members:
                self.dims = list(spec.dims)
                self.prep_specs = [self._prep_spec()]
            elif list(spec.dims) != self.dims:
                raise RuntimeError(
                    f"{self.name}: rule {spec.rule_id} GROUP BY "
                    f"{spec.dims} != store key set {self.dims} — replan")
            m = _Member(spec, entry, topo, span, spec_map,
                        self._restored_cursors.get(spec.rule_id),
                        self._cur_bucket)
            members = dict(self._members)
            members[spec.rule_id] = m
            self._members = members  # copy-on-write (concurrent boundary)
            # control events (EOF, watermarks) reach the rule's chain
            self.outputs = self.outputs + [entry]
            if not self._opened:
                self._open_pipeline()
                self._opened = True
            logger.debug("%s: rule %s attached (%d member(s), warm from "
                         "live panes)", self.name, spec.rule_id,
                         len(members))
            from .events import recorder

            recorder().record("shared_fold_attach", rule=spec.rule_id,
                              store=self.name, members=len(members))
            return True

    def detach_rule(self, rule_id: str) -> None:
        close_now = False
        with self._mlock:
            m = self._members.get(rule_id)
            if m is None:
                return
            members = dict(self._members)
            del members[rule_id]
            self._members = members
            from .events import recorder

            recorder().record("shared_fold_detach", rule=rule_id,
                              store=self.name, members=len(members))
            self.outputs = [o for o in self.outputs if o is not m.entry]
            if not members and self._opened:
                self._closed = True
                close_now = True
                _pool_remove(self.key, self)
        if close_now:
            if self._tick_timer is not None:
                self._tick_timer.stop()
            if self._subtopo is not None:
                self._subtopo.detach(self.rider_id)
            for n in ([self._wm_node] if self._wm_node is not None else []):
                n.close()
            self.close()
            for n in ([self._wm_node] if self._wm_node else []) + [self]:
                n.join(timeout=2.0)
            logger.debug("shared fold %s closed (last rule detached)",
                         self.name)

    def _open_pipeline(self) -> None:
        """Start this node (+ watermark hop) and ride the shared subtopo
        as one rider. Standalone mode (no subtopo_ref — benches/tests
        driving process()/on_trigger directly) skips both."""
        if self._subtopo_ref is None:
            return
        head = self._wm_node if self._wm_node is not None else self
        # prep specs stashed on whichever node attaches reach the shared
        # ingest ctx through SrcSubTopo.attach's forwarding
        head.prep_specs = self.prep_specs
        self.open()
        if self._wm_node is not None:
            self._wm_node.open()
        self._subtopo = self._subtopo_ref.resolve_and_attach(
            self.rider_id, head, self._topo)
        if self.prep_ctx is None:
            self.prep_ctx = getattr(head, "prep_ctx", None)

    def status(self) -> Dict[str, Any]:
        out = ({} if self._subtopo is None
               else dict(self._subtopo.status()))
        if self._wm_node is not None:
            out[self._wm_node.name] = self._wm_node.stats
        out[self.name] = self.stats
        return out

    # -------------------------------------------------------------- lifecycle
    def on_open(self) -> None:
        self._cur_bucket = timex.now_ms() // self.pane_ms
        if not self.is_event_time:
            self._schedule_tick()

    def on_worker_start(self) -> None:
        self.store.warmup()

    def on_close(self) -> None:
        if self._tick_timer is not None:
            self._tick_timer.stop()

    def _schedule_tick(self) -> None:
        """Arm the next pane-boundary trigger. Re-arms from the timer
        callback itself (not the worker) so a burst of elapsed panes
        enqueues one trigger per boundary in order — the worker then
        advances bucket state strictly by queue order, exactly like the
        private fused node's cur_pane."""
        now = timex.now_ms()
        end = timex.align_to_window(now + 1, self.pane_ms)

        def fire(ts: int, end=end) -> None:
            if self._closed or self._stop.is_set():
                return
            # carry the SCHEDULED boundary, not the fire time: the real
            # clock invokes callbacks with the actual (sleep-overshot)
            # time, and an off-grid ts would fail every member's
            # `end % interval == 0` emission gate forever
            self.put_control(Trigger(ts=end))
            self._schedule_tick()

        self._tick_timer = timex.after(end - now, fire)

    # ------------------------------------------------------------------- data
    def process(self, item: Any) -> None:
        if not isinstance(item, ColumnBatch):
            if isinstance(item, Row):
                from ..data.batch import from_tuples

                item = from_tuples([item], emitter=item.emitter)
            else:
                self.broadcast(item)
                return
        if item.n == 0 or not item.covers(None):
            # (a micro-batch the shared source decoded for narrower riders
            # before this store attached: the store reads every column)
            return
        if item.shared_ctx is None and self.prep_ctx is not None:
            item.ensure_share_state()
            item.shared_ctx = self.prep_ctx
        self._fold(item)

    def _fold(self, sub: ColumnBatch) -> None:
        with self.stats.stage("upload", sub.n) as st:
            slots = self._encode(sub)
            cols, valid = build_value_columns(self.plan, sub)
            if self.is_event_time:
                sub, cols, valid, slots, pane_arg = self._event_panes(
                    sub, cols, valid, slots)
                if sub is None:
                    st.counted = False  # nothing left to upload:
                    return  # every row was late (pane recycled)
            else:
                b = self._cur_bucket
                pane = b % self.n_panes
                held = self._pane_bucket.get(pane)
                if held is not None and held != b:
                    # safety net — rotation resets ahead of reuse normally
                    self.store.reset_pane(pane)
                    self._dirty.discard(held)
                self._pane_bucket[pane] = b
                self._dirty.add(b)
                pane_arg = pane
            dev = self._device_inputs(sub, cols, valid, slots)
            st.rows = sub.n
        with self.stats.stage("fold", sub.n):
            if dev is not None:
                dcols, dvalid, dslots = dev
                self.store.fold({**cols, **dcols},
                                {**valid, **dvalid},
                                dslots if dslots is not None else slots,
                                pane_arg, n_rows=sub.n)
            else:
                self.store.fold(cols, valid, slots, pane_arg)
        if hasattr(self.store.gb, "note_rows"):
            # per-shard accounting (kuiper_shard_*): the kernel counts
            # host slot vectors itself; the prep path hands it DEVICE
            # slots, so count off the host copy here (nodes_fused twin)
            if dev is not None and dev[2] is not None:
                self.store.gb.note_rows(slots, sub.n,
                                        n_keys=self.store.kt.n_keys)
            else:
                self.store.gb.n_keys_hint = self.store.kt.n_keys
        self.folds_did += 1
        self.folds_would += max(len(self._members), 1)

    def _event_panes(self, sub, cols, valid, slots):
        """Event-time pane routing: bucket = ts // pane_ms. Rows whose
        pane was recycled past their bucket drop (counted); panes are
        claimed/reset per new bucket."""
        ts = sub.timestamps
        if ts is None:
            ts = np.zeros(sub.n, dtype=np.int64)
        buckets = ts // self.pane_ms
        if self._floor_bucket is None:
            self._floor_bucket = int(buckets.min())
        # drop (a) rows below the emitted floor — including rows a single
        # wide batch would alias onto a newer bucket's pane (in-batch
        # spread >= n_panes) — and (b) rows whose pane a NEWER bucket
        # already claimed: folding either would add old rows into the
        # newer window's aggregates. Bounded panes trade the host path's
        # unbounded buffering for device residence; every drop is counted
        # (same contract as the fused event path).
        lo = max(self._floor_bucket,
                 int(buckets.max()) - self.n_panes + 1)
        drop = buckets < lo
        for b in np.unique(buckets).tolist():
            held = self._pane_bucket.get(int(b) % self.n_panes)
            if held is not None and held > int(b):
                drop |= buckets == b
        if drop.any():
            self.stats.inc_dropped(
                "pane_recycle", n=int(drop.sum()),
                detail="late event (pane emitted/recycled)")
            keep = np.nonzero(~drop)[0]
            if len(keep) == 0:
                return None, None, None, None, None
            sub = sub.take(keep)
            cols = {k: v[keep] for k, v in cols.items()}
            valid = {k: v[keep] for k, v in valid.items()}
            slots = slots[keep]
            buckets = buckets[keep]
        for b in np.unique(buckets).tolist():
            b = int(b)
            pane = b % self.n_panes
            held = self._pane_bucket.get(pane)
            if held is not None and held != b:
                # held < b here (newer buckets were dropped above): the
                # older bucket's partials are discarded. If its windows had
                # not emitted yet (watermark lagging past the pane budget)
                # that is COUNTED data loss, never corruption.
                if held in self._dirty:
                    self.stats.inc_dropped(
                        "pane_recycle",
                        detail="recycled before emission (watermark lag)")
                self.store.reset_pane(pane)
                self._dirty.discard(held)
            self._pane_bucket[pane] = b
            self._dirty.add(b)
        ub = np.unique(buckets)
        pane_arg = (int(ub[0]) % self.n_panes if len(ub) == 1
                    else (buckets % self.n_panes).astype(np.uint8))
        self._cur_bucket = max(self._cur_bucket, int(buckets.max()))
        return sub, cols, valid, slots, pane_arg

    # ------------------------------------------------------------- key encode
    def _encode(self, sub: ColumnBatch) -> np.ndarray:
        kt = self.store.kt
        if not self.dims:
            if kt.n_keys == 0:
                kt.encode_column(np.array(["__all__"], dtype=np.object_))
            return np.zeros(sub.n, dtype=np.int32)
        if len(self.dims) == 1:
            slots = self._shared_encode(sub)
            if slots is not None:
                return slots
        key_cols = [sub.key_column(name) for name in self.dims]
        with key_encode_stage(self.stats, sub.n):
            slots, _ = kt.encode_multi(key_cols)
        return slots

    def _shared_encode(self, sub: ColumnBatch) -> Optional[np.ndarray]:
        """Ride the subtopo's one-per-batch key encode (same contract as
        nodes_fused.py _shared_encode: the neutral table's dense
        insertion-ordered ids match what feeding our own table the same
        sequence yields, so our table stays self-contained for emit
        decode and snapshots)."""
        ctx = getattr(sub, "shared_ctx", None)
        if ctx is None or self._shared_slots_ok is False:
            return None
        kt = self.store.kt
        try:
            slots, n_keys, nkt = ctx.encode(sub, self.dims[0], self.stats)
        except Exception as exc:
            logger.debug("%s: shared key encode failed (%s) — self-encoding",
                         self.name, exc)
            self._shared_slots_ok = False
            return None
        if self._shared_slots_ok is None:
            self._shared_slots_ok = kt.n_keys == 0 or (
                kt.decode_all() == nkt.keys_slice(0, kt.n_keys))
            if not self._shared_slots_ok:
                return None
        self._shared_nkt = nkt
        start = kt.n_keys
        if start < n_keys:
            new = nkt.keys_slice(start, n_keys)
            with key_encode_stage(self.stats, n_keys - start):
                kt.mirror(new)
            if kt.keys_slice(start, n_keys) != new:
                self._shared_slots_ok = False  # diverged: self-encode from now
                return None
        return slots

    def _device_inputs(self, sub, cols, valid, slots):
        """One device upload per column/slot vector for every consumer of
        this batch — same share keys + canonical builders as
        nodes_fused.py _shared_device_inputs, so a batch pre-uploaded by
        the ingest prep stage is a cache hit here."""
        ctx = getattr(sub, "shared_ctx", None)
        mb = self.store.gb.micro_batch
        if ctx is None or sub.n > mb or \
                not getattr(self.store.gb, "accepts_device_inputs", False):
            return None
        from ..sql.expr_ir import is_derived_expr_col
        from .ingest import (pad_col_for_device, pad_slots_for_device,
                             share_key, slot_wire_u16)

        dcols: Dict[str, Any] = {}
        dvalid: Dict[str, Any] = {}
        expr_tag = getattr(self.plan, "expr_tag", "")
        # mesh-aware uploads: tag-suffixed keys + row-sharded placement
        # for sharded stores (mirror of nodes_fused._shared_device_inputs)
        mesh_tag = getattr(self.store.gb, "mesh_tag", "")
        shd = (getattr(self.store.gb, "batch_sharding", None)
               if mesh_tag else None)

        def _key(*parts):
            return share_key(*parts, mesh_tag=mesh_tag)

        for name in self.plan.columns:
            if name.startswith(HLL_COL_PREFIX) or \
                    name.startswith(HH_COL_PREFIX):
                continue
            if is_derived_expr_col(name):
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
            if src_col is None or src_col.dtype == np.object_:
                continue
            host, vm = cols[name], valid.get(name)
            dv, dm = sub.share(_key("dcol", name, mb),
                               lambda h=host, v=vm:
                               pad_col_for_device(h, v, mb,
                                                  sharding=shd))
            dcols[name] = dv
            if dm is not None:
                dvalid[name] = dm
        dslots = None
        if self._shared_slots_ok and len(self.dims) == 1:
            from ..ops.groupby import slot_dtype

            cap = (self._shared_nkt.capacity
                   if self._shared_nkt is not None else self.store.kt.capacity)
            u16 = slot_wire_u16(slot_dtype(cap) is np.uint16, mesh_tag)
            dslots = sub.share(
                _key("dslots", self.dims[0], mb, u16),
                lambda s=slots, u=u16: pad_slots_for_device(
                    s, mb, u, sharding=shd))
        if not dcols and dslots is None:
            return None
        return dcols, dvalid, dslots

    # ---------------------------------------------------------------- trigger
    def on_trigger(self, trig: Trigger) -> None:
        """Processing-time pane boundary: emit every member whose window
        ends here, then rotate the ring (reset the pane the NEXT bucket
        will claim — it held bucket now-P, no longer spanned by any
        member window since P > max span)."""
        if self.is_event_time:
            return
        end_ms = trig.ts
        cache: Dict[Any, Any] = {}  # members sharing a pane set combine once
        for m in list(self._members.values()):
            if end_ms % m.spec.interval_ms == 0:
                self._emit_member(m, end_ms, cache=cache)
                m.last_end_ms = end_ms
        nb = end_ms // self.pane_ms
        pane = nb % self.n_panes
        held = self._pane_bucket.get(pane)
        if held is not None and held != nb:
            self.store.reset_pane(pane)
            self._dirty.discard(held)
            self._pane_bucket.pop(pane)
        self._cur_bucket = nb

    def on_watermark(self, wm: Watermark) -> None:
        """Event-time emission: each member's cursor advances through every
        window end at or below the watermark; panes wholly below every
        member's next window are released."""
        if not self.is_event_time:
            self.broadcast(wm)
            return
        members = list(self._members.values())
        cache: Dict[Any, Any] = {}  # no folds land mid-dispatch: one
        for m in members:           # combine per distinct live pane set
            iv = m.spec.interval_ms
            if m.last_end_ms is None:
                if self._floor_bucket is None:
                    continue  # no data yet: nothing to anchor the grid
                first_ts = self._floor_bucket * self.pane_ms
                m.last_end_ms = (first_ts // iv) * iv
            while m.last_end_ms + iv <= wm.ts:
                end = m.last_end_ms + iv
                self._emit_member(m, end, cache=cache)
                m.last_end_ms = end
        # release panes no member's NEXT window can span
        starts = [m.last_end_ms + m.spec.interval_ms - m.spec.length_ms
                  for m in members if m.last_end_ms is not None]
        if starts and len(starts) == len(members):
            floor_b = min(starts) // self.pane_ms
            for b in [b for b in self._dirty if b < floor_b]:
                pane = b % self.n_panes
                if self._pane_bucket.get(pane) == b:
                    self.store.reset_pane(pane)
                    self._pane_bucket.pop(pane)
                self._dirty.discard(b)
            self._floor_bucket = max(self._floor_bucket or 0, floor_b)
        self.broadcast(wm)

    def on_eof(self, eof: EOF) -> None:
        """Flush: each member's current partial window (bounded runs).
        Tumbling members flush the buckets since their last boundary;
        hopping members their trailing span (finer panes may include a
        partial leading bucket — see docs/SHARING.md)."""
        now = timex.now_ms()
        for m in list(self._members.values()):
            if self.is_event_time:
                if not self._dirty:
                    continue
                iv = m.spec.interval_ms
                hi = (max(self._dirty) + 1) * self.pane_ms
                end = -(-hi // iv) * iv  # align up
                last = m.last_end_ms
                if last is None or end > last:
                    self._emit_member(m, end)
                    m.last_end_ms = end
                continue
            b_hi = max((now - 1) // self.pane_ms, self._cur_bucket)
            b_lo = b_hi - m.span + 1
            if m.spec.interval_ms == m.spec.length_ms:  # tumbling
                anchor = (m.last_end_ms // self.pane_ms
                          if m.last_end_ms is not None else m.attach_bucket)
                b_lo = max(b_lo, anchor)
            self._emit_member(m, now, b_lo=b_lo, b_hi=b_hi)
        self.broadcast(eof)

    # ------------------------------------------------------------------- emit
    def _emit_member(self, m: _Member, end_ms: int,
                     b_lo: Optional[int] = None,
                     b_hi: Optional[int] = None,
                     cache: Optional[Dict[Any, Any]] = None) -> None:
        """Combine the panes spanning one member's window ending at
        `end_ms` and run the member's vectorized tail into its emit hop —
        the emit-combine overhead the planner's cost model weighs against
        the saved per-rule folds. `cache` scopes ONE boundary dispatch (no
        folds land in between, state is unchanged): members sharing a live
        pane set reuse one finalize+transfer, and the key table decodes
        once per dispatch instead of once per member."""
        n_keys = self.store.kt.n_keys
        if b_hi is None:
            b_hi = (end_ms - 1) // self.pane_ms
        if b_lo is None:
            b_lo = b_hi - m.span + 1
        # combine ONLY panes still owned by a dirty bucket of this window:
        # a pane recycled forward (event-time backlog) holds a NEWER
        # bucket's partials — merging it would fold future rows into this
        # window (the recycled bucket's loss was already counted at
        # recycle time)
        live = [b for b in range(b_lo, b_hi + 1)
                if b in self._dirty
                and self._pane_bucket.get(b % self.n_panes) == b]
        if n_keys == 0 or not live:
            return  # empty window: no device round trip, no emission
        # per-rule emit-combine latency, attributed under rule="__shared__"
        # (this node renders there) with the member in the stage label
        with self.stats.stage(f"emit[{m.spec.rule_id}]") as st:
            panes = sorted({b % self.n_panes for b in live})
            ckey = ("combine", tuple(panes), n_keys)
            if cache is not None and ckey in cache:
                outs, act = cache[ckey]
            else:
                outs, act = self.store.combine(panes, n_keys)
                if cache is not None:
                    cache[ckey] = (outs, act)
            if m.spec.act_idx is not None:
                # predicate-lifted member: group existence is this member's
                # own `count(*) FILTER(WHERE <pred>)` column — a key whose
                # rows all failed the member's predicate must not emit a
                # group (byte parity with the private plan's post-WHERE act)
                # kuiperlint: ignore[host-sync]: `outs` are HOST numpy arrays (store.combine already fetched+sliced them) — no device value in reach
                act = np.asarray(outs[m.spec_map[int(m.spec.act_idx)]])
            active = np.nonzero(act > 0)[0]
            n_groups = len(active)
            if n_groups:
                wr = WindowRange(end_ms - m.spec.length_ms, end_ms)
                dim_cols: Dict[str, np.ndarray] = {}
                if self.dims:
                    if cache is not None:
                        keys = cache.get("__keys__")
                        if keys is None:
                            keys = cache["__keys__"] = \
                                self.store.kt.decode_all()
                    else:
                        keys = self.store.kt.decode_all()
                    if len(self.dims) == 1:
                        col = np.empty(n_groups, dtype=np.object_)
                        col[:] = [keys[s] for s in active.tolist()]
                        dim_cols[self.dims[0]] = col
                    else:
                        sel = [keys[s] for s in active.tolist()]
                        for i, dn in enumerate(self.dims):
                            col = np.empty(n_groups, dtype=np.object_)
                            col[:] = [k[i] for k in sel]
                            dim_cols[dn] = col
                agg_cols = [outs[u][active] for u in m.spec_map]
                if m.spec.emit_columnar:
                    payload = m.spec.direct_emit.run_columnar(
                        dim_cols, agg_cols, wr.window_start, wr.window_end)
                    count = payload.n if payload is not None else 0
                else:
                    payload = m.spec.direct_emit.run(
                        dim_cols, agg_cols, wr.window_start, wr.window_end)
                    count = len(payload) if payload else 0
                if count:
                    # ingest→emit provenance (the PR 3 SLO layer): stamp the
                    # freshest contributing batch's ingest time, exactly what
                    # Node.emit() would do — send_to alone doesn't stamp, and
                    # an unstamped window never records an e2e sample at the
                    # member's sink
                    from .node import _stamp_item

                    if self._cur_ingest_ms is not None:
                        _stamp_item(payload, self._cur_ingest_ms)
                    self.stats.inc_out(count)
                    self.send_to(m.entry, payload)
                self.windows_emitted += 1
            st.rows = n_groups

    # ------------------------------------------------------------------ state
    def snapshot_state(self) -> Optional[dict]:
        snap = self.store.snapshot()
        snap.update({
            "cur_bucket": self._cur_bucket,
            "pane_bucket": {str(p): b for p, b in self._pane_bucket.items()},
            "dirty": sorted(self._dirty),
            "floor_bucket": self._floor_bucket,
            "cursors": {rid: m.last_end_ms
                        for rid, m in self._members.items()
                        if m.last_end_ms is not None},
        })
        return snap

    def restore_state(self, state: dict) -> None:
        self.store.restore(state)
        self._cur_bucket = int(state.get("cur_bucket", self._cur_bucket))
        self._pane_bucket = {int(p): int(b) for p, b in
                             state.get("pane_bucket", {}).items()}
        self._dirty = set(state.get("dirty", []))
        self._floor_bucket = state.get("floor_bucket")
        self._restored_cursors = {
            rid: int(v) for rid, v in state.get("cursors", {}).items()}
        # already-attached members pick their cursor up immediately
        for rid, m in self._members.items():
            if rid in self._restored_cursors:
                m.last_end_ms = self._restored_cursors[rid]


class SharedFoldRider:
    """What a member rule's Topo holds while riding a shared fold — the
    same surface Topo expects from a SrcSubTopo (nodes/status/detach), so
    topo.open/close/wait_idle/status and the Prometheus __shared__ dedup
    all work unchanged."""

    def __init__(self, node: SharedFoldNode) -> None:
        self._node = node

    @property
    def nodes(self) -> List[Node]:
        return self._node.pipeline_nodes()

    @property
    def source(self):
        return self._node.source

    def detach(self, rule_id: str) -> None:
        self._node.detach_rule(rule_id)

    def ref_count(self) -> int:
        return self._node.member_count()

    def status(self) -> Dict[str, Any]:
        return self._node.status()


class SharedFoldRef:
    """Plan-time handle: the live store resolves at Topo.open (a pooled
    instance may have closed between planning and opening), mirroring
    subtopo.SubTopoRef."""

    def __init__(self, key: str, member_spec: MemberSpec, builder) -> None:
        self.key = key
        self.member_spec = member_spec
        self.builder = builder

    def resolve_and_attach(self, rule_id: str, entry: Node,
                           topo: Any) -> SharedFoldRider:
        for _ in range(8):
            node = get_or_create(self.key, self.builder)
            try:
                ok = node.attach_rule(self.member_spec, entry, topo)
            except Exception:
                # geometry/spec mismatch (plan/open race): a never-opened
                # memberless store must not linger in the pool — the
                # rule's restart replans against reality (private fold)
                if node.member_count() == 0 and not node._opened:
                    _pool_remove(self.key, node)
                raise
            if ok:
                return SharedFoldRider(node)
        raise RuntimeError(f"cannot attach to shared fold {self.key}")


# ------------------------------------------------------------------- pool
_stores: Dict[str, SharedFoldNode] = {}
_pool_lock = threading.Lock()


def get_or_create(key: str, builder) -> SharedFoldNode:
    with _pool_lock:
        node = _stores.get(key)
    if node is not None:
        return node
    candidate = builder()  # outside the lock: builds device state
    with _pool_lock:
        node = _stores.get(key)
        if node is None:
            _stores[key] = candidate
            return candidate
    return node  # lost the race; unopened candidate is garbage-collected


def get_store(key: str) -> Optional[SharedFoldNode]:
    with _pool_lock:
        return _stores.get(key)


def _pool_remove(key: str, node: SharedFoldNode) -> None:
    with _pool_lock:
        if _stores.get(key) is node:
            del _stores[key]


def live_stores() -> List[SharedFoldNode]:
    with _pool_lock:
        return list(_stores.values())


def pool_size() -> int:
    with _pool_lock:
        return len(_stores)


def reset() -> None:
    """Test hook: close and drop every pooled store."""
    with _pool_lock:
        stores = list(_stores.values())
        _stores.clear()
    for node in stores:
        node._closed = True
        if node._tick_timer is not None:
            node._tick_timer.stop()
        if node._wm_node is not None:
            node._wm_node.close()
        node.close()
