"""Per-rule topology — analogue of eKuiper's Topo (internal/topo/topo.go:46-318):
owns the node DAG, opens sinks→ops→sources, drains errors, coordinates
checkpoints, and persists/restores state through the rule's KV store.
"""
from __future__ import annotations

import queue
import threading
from typing import Any, Dict, List, Optional

from ..observability.histogram import LatencyHistogram
from ..store import kv
from ..utils import timex
from ..utils.infra import logger
from ..utils.metrics import flatten_status
from .events import Barrier
from .node import Node


#: phases of a window boundary, in the order they happen
BOUNDARY_PHASES = ("trigger_delay", "emit", "sink")


class Topo:
    def __init__(self, rule_id: str, qos: int = 0, checkpoint_interval_ms: int = 300_000) -> None:
        self.rule_id = rule_id
        self.qos = qos
        self.checkpoint_interval_ms = checkpoint_interval_ms
        self.sources: List[Node] = []
        self.ops: List[Node] = []
        self.sinks: List[Node] = []
        # (SubTopoRef, entry node) pairs — shared sources this rule rides;
        # the live SrcSubTopo instances are resolved at open() time
        self.shared: List = []
        self._live_shared: List = []
        self.errq: "queue.Queue[BaseException]" = queue.Queue(maxsize=8)
        self._open = False
        self._ckpt_timer = None
        self._ckpt_id = 0
        self._ckpt_lock = threading.Lock()
        self._ckpt_pending: Dict[int, Dict[str, Optional[dict]]] = {}
        self._store = None
        # rule-level ingest→emit latency distribution (ms): sinks record a
        # sample per delivered emission (nodes_sink.py _observe_e2e); the
        # Prometheus layer exports it as the kuiper_rule_e2e_latency_ms
        # histogram, the status JSON as a p50/p90/p99/max summary
        self.e2e_hist = LatencyHistogram()
        # the engine's side of a window boundary, by phase (µs; rendered as
        # the kuiper_boundary_ms histogram and as p50/p95 in the status):
        # trigger_delay — the Trigger's ts → its dispatch on the window
        # node; emit — that dispatch → the window's result handed
        # downstream; sink — handed downstream → the sink's collect
        # returned. A count window has no Trigger and records the last two.
        self.boundary_hists = {p: LatencyHistogram() for p in BOUNDARY_PHASES}

    # ------------------------------------------------------------------ wiring
    def add_source(self, node: Node) -> Node:
        node._topo = self
        node.stats.rule_id = self.rule_id
        self.sources.append(node)
        return node

    def add_op(self, node: Node) -> Node:
        node._topo = self
        node.stats.rule_id = self.rule_id
        self.ops.append(node)
        return node

    def add_sink(self, node: Node) -> Node:
        node._topo = self
        node.stats.rule_id = self.rule_id
        self.sinks.append(node)
        return node

    def add_shared_source(self, ref, entry: Node) -> Node:
        """Ride a pooled shared source (runtime/subtopo.py SubTopoRef);
        `entry` is this rule's pass-through attach point (must also be
        add_op'd). The live instance is resolved when the topo opens."""
        self.shared.append((ref, entry))
        return entry

    def all_nodes(self) -> List[Node]:
        return self.sources + self.ops + self.sinks

    def live_shared(self) -> List:
        """(SrcSubTopo, entry node) pairs this rule currently rides — the
        public accessor for observability layers (scrapes must not reach
        into the private open()/close()-managed list)."""
        return list(self._live_shared)

    def entry_nodes(self) -> List[Node]:
        """This rule's first OWN nodes on the data path: the attach
        points of shared sources plus every direct consumer of a private
        source. The QoS control plane installs per-rule shed gates here —
        upstream of them sits shared (multi-rule) or connector-owned
        work, downstream is all this rule's private pipeline, so a gate
        at the entry sheds exactly one rule's input."""
        out: List[Node] = []
        seen: set = set()
        for _ref, entry in self.shared:
            if id(entry) not in seen:
                seen.add(id(entry))
                out.append(entry)
        for src in self.sources:
            for n in src.outputs:
                if id(n) not in seen:
                    seen.add(id(n))
                    out.append(n)
        return out

    def set_shed(self, fraction: float) -> None:
        """Install (or clear, fraction=0) the rule-scoped shed gate on
        every entry node (runtime/control.py SLO-driven shedding)."""
        for node in self.entry_nodes():
            node.set_shed_fraction(fraction)

    def shed_fraction(self) -> float:
        """The currently installed shed fraction (max across entries)."""
        return max((n._shed_frac for n in self.entry_nodes()),
                   default=0.0)

    def shed_rows(self) -> int:
        """Rows discarded by the shed gate so far (reason="shed_qos"
        across entry nodes) — the control plane's per-rule counter."""
        return sum(n.stats.dropped.get("shed_qos", 0)
                   for n in self.entry_nodes())

    def observe_e2e(self, lat_ms: int) -> None:
        """One ingest→emit latency sample (ms), recorded by sink nodes."""
        self.e2e_hist.record(lat_ms)

    def observe_boundary(self, phase: str, us: float) -> None:
        """One boundary's time in `phase` (µs), recorded by the window
        node (trigger_delay, emit) and the sink (sink)."""
        self.boundary_hists[phase].record(us)

    # --------------------------------------------------------------- lifecycle
    def open(self) -> None:
        """Start sinks → ops → sources (reference order, topo.go:275-318),
        restore checkpointed state, then activate checkpointing if QoS>0."""
        if self.qos > 0:
            self._store = kv.get_store().kv(f"checkpoint:{self.rule_id}")
            self._restore()
        if self.qos >= 2:
            # exactly-once: data items carry their sender so fan-in nodes
            # can hold back barriered edges (node.py _handle_barrier)
            for node in self.all_nodes():
                node._tag_data = True
        for node in self.sinks + self.ops + self.sources:
            node.open()
        self._live_shared = [
            (ref.resolve_and_attach(self.rule_id, entry, self), entry)
            for ref, entry in self.shared
        ]
        self._open = True
        if self.qos > 0:
            self._schedule_checkpoint()

    def close(self) -> None:
        self._open = False
        if self._ckpt_timer is not None:
            self._ckpt_timer.stop()
        for subtopo, _ in self._live_shared:
            subtopo.detach(self.rule_id)
        self._live_shared = []
        for node in self.sources + self.ops + self.sinks:
            node.close()
        for node in self.all_nodes():
            node.join(timeout=2.0)

    def wait_idle(self, timeout: float = 10.0) -> bool:
        """Block until every node's input queue is drained AND no node is
        mid-dispatch (queue.unfinished_tasks == 0). Emissions happen while the
        emitting node's task is still unfinished, so a snapshot where all
        counts are zero means no data is in flight anywhere in the DAG.
        Deterministic replacement for sleep()-based settling in tests."""
        import time as _time

        deadline = _time.perf_counter() + timeout
        # shared-subtopo nodes (the physical source + its decode ring) count
        # too: data sitting there is still in flight toward this rule
        nodes = self.all_nodes() + [
            n for st, _ in self._live_shared for n in st.nodes]
        while _time.perf_counter() < deadline:
            if all(n.inq.unfinished_tasks == 0 and n.extra_pending() == 0
                   for n in nodes):
                return True
            # kuiperlint: ignore[clock-discipline]: real-thread poll — worker queues drain in wall time even when the engine clock is mocked
            _time.sleep(0.002)
        return False

    def drain_error(self, err: BaseException, origin: str = "") -> None:
        logger.error("rule %s node %s failed: %s", self.rule_id, origin, err)
        try:
            self.errq.put_nowait(err)
        except queue.Full:
            pass

    def wait_error(self, timeout: Optional[float] = None) -> Optional[BaseException]:
        try:
            return self.errq.get(timeout=timeout)
        except queue.Empty:
            return None

    # ------------------------------------------------------------- status JSON
    def status(self) -> Dict[str, Any]:
        stats = {n.name: n.stats for n in self.all_nodes()}
        for subtopo, _ in self._live_shared:
            # shared ingest pipelines serve this rule too; surface their
            # metrics under the rule status like the reference does for
            # shared source instances
            for name, sm in subtopo.status().items():
                stats.setdefault(name, sm)
        out = flatten_status(stats)
        # which path answered each emitted window, cumulatively (fused
        # window nodes): device fetch / sync finalize
        for n in self.all_nodes():
            srcs = getattr(n, "emit_sources", None)
            if srcs:
                out[f"{n.stats.op_type}_{n.name}_{n.stats.instance}"
                    "_emit_sources"] = dict(srcs)
        # the columns each source decodes now (a shared source: the union
        # of what its riders read)
        sources = list(self.sources) + [
            st.source for st, _ in self._live_shared
            if getattr(st, "source", None) is not None]
        for n in sources:
            cols = getattr(n, "decoded_columns", None)
            if cols is not None:
                out[f"{n.stats.op_type}_{n.name}_{n.stats.instance}"
                    "_decoded_columns"] = cols()
        # rows each node's key tables encoded, by path (ops/keytable.py)
        for n in sources + self.ops:
            fn = getattr(n, "keytable_encode_rows", None)
            enc = fn() if fn is not None else None
            if enc is not None:
                out[f"{n.stats.op_type}_{n.name}_{n.stats.instance}"
                    "_keytable_encode_rows"] = dict(enc)
        # runtime calls the staging of a window node's folds has made,
        # arguments it took from the device-resident scalar table instead,
        # and rows its host shadows mirrored over the touched slots only
        for n in self.ops:
            for key in ("fold_transfers", "fold_resident_args",
                        "shadow_fold_compact_rows"):
                count = getattr(n, key, None)
                if count is not None:
                    out[f"{n.stats.op_type}_{n.name}_{n.stats.instance}"
                        f"_{key}"] = count
            # exact BIGINT aggregates: rows through the int64 partials,
            # integer rows past 2^24 that reached a float32 one instead
            if getattr(n, "_exact", False):
                out[f"{n.stats.op_type}_{n.name}_{n.stats.instance}"
                    "_fold_int_exact_rows"] = n.fold_int_exact_rows
            # exact order statistics: rows buffered, the rung, its climbs
            if getattr(n, "_orderstat", False):
                for key in ("orderstat_rows", "orderstat_buffer_rows",
                            "orderstat_grows"):
                    out[f"{n.stats.op_type}_{n.name}_{n.stats.instance}"
                        f"_{key}"] = getattr(n, key)
            if getattr(n, "agg_int_fallback", None):
                out[f"{n.stats.op_type}_{n.name}_{n.stats.instance}"
                    "_agg_int_fallback"] = dict(n.agg_int_fallback)
        # rule-level SLO summary: the ingest→emit distribution percentiles
        out["e2e_latency_ms"] = self.e2e_hist.snapshot()
        # ... and its engine-side phases per window boundary, ms
        out["boundary_ms"] = {}
        for phase, hist in self.boundary_hists.items():
            if hist.count:
                p50, p95 = hist.percentiles([50, 95])
                out["boundary_ms"][phase] = {
                    "count": hist.count, "p50": p50 / 1000.0,
                    "p95": p95 / 1000.0}
        # engine-health views (observability/devwatch.py): per-op XLA
        # trace-vs-cache-hit counts — a steady-state rule should show
        # compiles flat while cache_hits climb; anything else is paying
        # compile latency per batch
        from ..observability import devwatch

        xla = devwatch.registry().rule_status(self.rule_id)
        if xla:
            out["xla_compile"] = xla
        # device-time split (observability/kernwatch.py): the rule's
        # sampled host-dispatch vs device-compute time and per-kernel
        # roofline utilization — the device-side twin of the host stage
        # timings above
        from ..observability import kernwatch

        kern = kernwatch.rule_status(self.rule_id)
        if kern:
            out["device_time"] = kern
        # health-plane verdict (observability/health.py), when the
        # evaluator has one — last verdict only, a status call must not
        # pay evaluation cost
        from ..observability import health

        verdict = health.rule_verdict(self.rule_id)
        if verdict is not None:
            out["health"] = verdict
        return out

    def topo_json(self) -> Dict[str, Any]:
        edges: Dict[str, List[str]] = {}
        for n in self.all_nodes():
            edges[n.name] = [o.name for o in n.outputs]
        return {
            "sources": [n.name for n in self.sources],
            "edges": edges,
        }

    # -------------------------------------------------------------- checkpoint
    def _schedule_checkpoint(self) -> None:
        def fire(ts: int) -> None:
            if not self._open:
                return
            self.trigger_checkpoint()
            self._schedule_checkpoint()

        self._ckpt_timer = timex.after(self.checkpoint_interval_ms, fire)

    def trigger_checkpoint(self) -> int:
        """Inject barriers at sources (coordinator.go:236-324)."""
        with self._ckpt_lock:
            self._ckpt_id += 1
            cid = self._ckpt_id
            self._ckpt_pending[cid] = {}
        barrier = Barrier(checkpoint_id=cid, qos=self.qos)
        for src in self.sources:
            src.put(barrier)
        return cid

    def checkpoint_ack(self, node_name: str, barrier: Barrier, state: Optional[dict]) -> None:
        """Task snapshot ack; completes the checkpoint when all stateful
        nodes have answered (coordinator.go:93-171)."""
        with self._ckpt_lock:
            pend = self._ckpt_pending.get(barrier.checkpoint_id)
            if pend is None:
                return
            pend[node_name] = state
            expected = {n.name for n in self.all_nodes()}
            if set(pend.keys()) >= expected:
                states = {k: v for k, v in pend.items() if v is not None}
                del self._ckpt_pending[barrier.checkpoint_id]
                if self._store is not None:
                    self._store.set("latest", {
                        "checkpoint_id": barrier.checkpoint_id,
                        "states": states,
                    })
                logger.debug(
                    "rule %s checkpoint %d complete (%d stateful nodes)",
                    self.rule_id, barrier.checkpoint_id, len(states),
                )

    def _restore(self) -> None:
        snap, ok = self._store.get_ok("latest")
        if not ok or not snap:
            return
        states = snap.get("states", {})
        by_name = {n.name: n for n in self.all_nodes()}
        for name, state in states.items():
            node = by_name.get(name)
            if node is not None:
                node.restore_state(state)
        self._ckpt_id = snap.get("checkpoint_id", 0)

    def save_state_now(self) -> None:
        """Force-save without barriers (EnableSaveStateBeforeStop,
        topo.go:113-120) — used on graceful stop."""
        if self._store is None:
            return
        states = {}
        for node in self.all_nodes():
            try:
                s = node.snapshot_state()
            except Exception as exc:
                # one wedged node (e.g. bounded async-emit drain timeout)
                # must not discard every OTHER node's state — notably a
                # memory-only CacheNode whose pending at-least-once sink
                # payloads persist only through this snapshot
                logger.error("%s: stop-time snapshot failed (%s) — saving "
                             "the other nodes' state", node.name, exc)
                node.stats.inc_exception(f"stop snapshot failed: {exc}")
                continue
            if s is not None:
                states[node.name] = s
        with self._ckpt_lock:
            self._ckpt_id += 1
            self._store.set("latest", {
                "checkpoint_id": self._ckpt_id, "states": states,
            })
