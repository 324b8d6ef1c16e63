"""Shared-source subtopology — one source + decode pipeline serving N rules.

The reference refcounts a SrcSubTopo per source so 300 rules over one MQTT
stream subscribe once and fan out in-process (reference:
internal/topo/subtopo.go:38-60, subtopo_pool.go:34). Here the shared unit is
the SourceNode (ingest → decode → schema coercion → micro-batch), whose tail
broadcasts ColumnBatches to each attached rule's entry node. Attach/detach
are refcounted; the pipeline opens on the first attach and closes when the
last rule detaches.

The source decodes the UNION of the columns its attached riders read: each
rider hands over its pruning set on attach (`project_columns`; None = every
column, e.g. `SELECT *` or a rider that does not say), the subtopo keeps the
union and gives it to its SourceNode, which takes it up at its next
hand-over (one micro-batch, one column set). A rider that widens the union
is handed only micro-batches decoded with its columns (`ColumnBatch.covers`);
a detach narrows the union to what the riders that stay read.

Sharing is restricted to qos=0 rules (the planner enforces it): checkpoint
barriers are injected at sources, and a shared source cannot carry
rule-private barriers. This matches the reference's default deployments —
its fan-out benchmark rules are all at-most-once.

Thread-safety: broadcast iterates the tail's `outputs` list, so attach and
detach REPLACE the list instead of mutating it (copy-on-write) — a broadcast
running concurrently keeps iterating its own snapshot.
"""
from __future__ import annotations

import json
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..utils.infra import logger
from .node import Node


class _FanoutTopoShim:
    """Stands in as `_topo` for nodes owned by a subtopo: errors fan out to
    every attached rule's topo (each supervisor decides restart policy).
    Shared pipelines serve many rules at once, so their log records route
    to one __shared__ file rather than a single rule's (utils/rulelog)."""

    rule_id = "__shared__"

    def __init__(self, subtopo: "SrcSubTopo") -> None:
        self._subtopo = subtopo

    def drain_error(self, err: BaseException, origin: str = "") -> None:
        for topo in self._subtopo.attached_topos():
            topo.drain_error(err, f"shared:{origin}")

    def checkpoint_ack(self, node_name, barrier, state) -> None:
        # shared subtopos serve qos=0 rules only; no barriers flow here
        pass


class SubTopoRef:
    """Plan-time handle: the subtopo instance is resolved at Topo.open, not
    at plan time — a pooled instance may have closed (last rule detached)
    between planning and opening, and a fresh one must be built then."""

    def __init__(self, key: str, builder: Callable[[], List[Node]]) -> None:
        self.key = key
        self.builder = builder

    def resolve_and_attach(self, rule_id: str, entry: Node, topo: Any) -> "SrcSubTopo":
        # retry: get_or_create may return an instance that loses its last
        # rule and closes before our attach lands; closed instances refuse
        # the attach and are already evicted, so the next lookup builds fresh
        for _ in range(8):
            st = get_or_create(self.key, self.builder)
            if st.attach(rule_id, entry, topo):
                return st
        raise RuntimeError(f"cannot attach to subtopo {self.key}")


# Per-subtopo shared ingest prep — one key encode + one device upload per
# batch for every fan-out consumer. The implementation moved to
# runtime/ingest.py (IngestPrepCtx) when the decode pool gained the
# pipelined upload stage; this name stays for the subtopo-facing role.
from .ingest import IngestPrepCtx as SharedPrepCtx  # noqa: E402


class SrcSubTopo:
    def __init__(self, key: str, nodes: List[Node]) -> None:
        self.key = key
        self.nodes = nodes  # [source, *chain]; tail broadcasts to entries
        self._shim = _FanoutTopoShim(self)
        for n in nodes:
            n._topo = self._shim
            # shared nodes never pass through Topo.add_*: stamp the same
            # rule label the Prometheus exposition uses, so their
            # drop-burst flight events filter consistently
            n.stats.rule_id = "__shared__"
        self._lock = threading.RLock()
        self._attached: Dict[str, Tuple[Node, Any]] = {}
        # rider id -> the columns it reads (None = all): what the source
        # decodes is their union
        self._reads: Dict[str, Optional[frozenset]] = {}
        self._opened = False
        self._closed = False
        # adopt the source's prep ctx when it has one (prep-enabled source:
        # its decode pool precomputes into the SAME ctx the entries attach
        # to batches), else create the subtopo-local one as before
        self.prep_ctx = (getattr(self.source, "prep_ctx", None)
                         or SharedPrepCtx())

    @property
    def tail(self) -> Node:
        return self.nodes[-1]

    @property
    def source(self) -> Node:
        return self.nodes[0]

    def attached_topos(self) -> List[Any]:
        with self._lock:
            return [t for _, t in self._attached.values()]

    def ref_count(self) -> int:
        with self._lock:
            return len(self._attached)

    def read_union(self) -> Optional[frozenset]:
        """The union of what the attached riders read (None = every
        column): what the source is told to decode."""
        with self._lock:
            reads = list(self._reads.values())
        if any(r is None for r in reads):
            return None
        return frozenset().union(*reads)

    def _decode_union(self) -> None:
        """Hand the riders' union to the source (under self._lock)."""
        set_columns = getattr(self.source, "set_decode_columns", None)
        if set_columns is not None and self._reads:
            set_columns(self.read_union())

    def attach(self, rule_id: str, entry: Node, topo: Any) -> bool:
        """Returns False when this instance already closed (caller resolves
        a fresh one from the pool)."""
        with self._lock:
            if self._closed:
                return False
            if rule_id in self._attached:
                raise ValueError(f"rule {rule_id} already attached to {self.key}")
            self._attached[rule_id] = (entry, topo)
            # widen the decode BEFORE the entry joins the fan-out: the
            # micro-batches still in flight are narrower and the entry
            # turns them away (SharedEntryNode.process)
            reads = getattr(entry, "project_columns", None)
            self._reads[rule_id] = (None if reads is None
                                    else frozenset(reads))
            self._decode_union()
            entry.prep_ctx = self.prep_ctx  # shared fan-out ingest prep
            # plan-time upload specs stashed on the entry reach the shared
            # ctx here (the subtopo instance resolves only at open)
            reg = getattr(self.prep_ctx, "register_upload", None)
            if reg is not None:
                for spec in getattr(entry, "prep_specs", ()):
                    reg(*spec)
            self.tail.outputs = self.tail.outputs + [entry]  # copy-on-write
            if not self._opened:
                # chain first, source last, so the first payload finds the
                # downstream queues live (same order Topo.open uses)
                for n in reversed(self.nodes):
                    n.open()
                self._opened = True
                logger.debug("subtopo %s opened", self.key)
            return True

    def detach(self, rule_id: str) -> None:
        close_now = False
        with self._lock:
            got = self._attached.pop(rule_id, None)
            if got is None:
                return
            entry, _ = got
            self.tail.outputs = [o for o in self.tail.outputs if o is not entry]
            self._reads.pop(rule_id, None)
            self._decode_union()
            if not self._attached and self._opened:
                # mark closed + evict BEFORE releasing the lock: a concurrent
                # attach on this instance now returns False, and a concurrent
                # get_or_create builds a fresh instance
                self._closed = True
                close_now = True
                _pool_remove(self.key, self)
        if close_now:
            for n in self.nodes:
                n.close()
            for n in self.nodes:
                n.join(timeout=2.0)
            logger.debug("subtopo %s closed (last rule detached)", self.key)

    def status(self) -> Dict[str, Any]:
        return {n.name: n.stats for n in self.nodes}


class SharedEntryNode(Node):
    """Per-rule entry behind a shared source: a pass-through hop that gives
    the rule its own queue (backpressure isolation — one slow rule drops its
    own oldest items, reference subtopo semantics) and its own stats.

    The pooled source decodes the union of its riders' columns; each rule
    prunes its own copy of the stream down to its own set HERE
    (planner/optimizer.py), and turns away a micro-batch decoded before it
    attached with fewer columns than it reads."""

    def __init__(self, name: str, project_columns=None, **kw) -> None:
        super().__init__(name, op_type="op", **kw)
        self.project_columns = (set(project_columns)
                                if project_columns is not None else None)
        self.prep_ctx = None  # set by SrcSubTopo.attach
        self.prep_specs: List[tuple] = []  # plan-time upload specs

    def register_prep_spec(self, spec) -> None:
        """Stash a plan-time upload spec; SrcSubTopo.attach forwards it to
        the shared prep ctx once this entry joins a live subtopo."""
        self.prep_specs.append(spec)

    def process(self, item: Any) -> None:
        cols = self.project_columns
        from ..data.batch import ColumnBatch

        if isinstance(item, ColumnBatch):
            if not item.covers(cols):
                return  # rows from before this rider's attach
            if item.shared_ctx is None:
                item.ensure_share_state()  # BEFORE any pruned copy forks it
                item.shared_ctx = self.prep_ctx
        if cols is not None:
            from ..data.rows import Tuple as Row

            if isinstance(item, ColumnBatch) and not (
                set(item.columns) <= cols
            ):
                # pruned COPY rides the same share cache: the original
                # column objects are identical, so slots/device uploads
                # computed by one rider serve every other rider too
                item = ColumnBatch(
                    n=item.n,
                    columns={k: v for k, v in item.columns.items()
                             if k in cols},
                    valid={k: v for k, v in item.valid.items() if k in cols},
                    timestamps=item.timestamps, emitter=item.emitter,
                    shared_ctx=item.shared_ctx,
                    share_state=item.share_state,
                    ingest_ms=item.ingest_ms,
                    decoded=item.decoded,
                )
            elif isinstance(item, Row) and not (
                set(item.message) <= cols
            ):
                # COPY, never mutate: the shared tail broadcasts the same
                # object to every rider, each with its own pruning set
                item = Row(
                    emitter=item.emitter,
                    message={k: v for k, v in item.message.items()
                             if k in cols},
                    timestamp=item.timestamp,
                    metadata=getattr(item, "metadata", None) or {},
                )
        self.emit(item)


# ------------------------------------------------------------------- pool
_pool: Dict[str, SrcSubTopo] = {}
_pool_lock = threading.Lock()


def subtopo_key(stream_name: str, props: Dict[str, Any]) -> str:
    """Stable identity of a shareable source pipeline: the stream plus every
    config knob that changes what the pipeline emits."""
    return stream_name + ":" + json.dumps(props, sort_keys=True, default=str)


def get_or_create(key: str, builder: Callable[[], List[Node]]) -> SrcSubTopo:
    with _pool_lock:
        st = _pool.get(key)
    if st is not None:
        return st
    # build OUTSIDE the lock: connector construction/configure may do I/O,
    # and one slow source must not stall planning of unrelated rules
    candidate = SrcSubTopo(key, builder())
    with _pool_lock:
        st = _pool.get(key)
        if st is None:
            _pool[key] = candidate
            return candidate
    return st  # lost the race; unopened candidate is garbage-collected


def peek(key: str) -> Optional[SrcSubTopo]:
    """The live pooled pipeline under `key`, or None (never builds one)."""
    with _pool_lock:
        return _pool.get(key)


def _pool_remove(key: str, subtopo: SrcSubTopo) -> None:
    with _pool_lock:
        if _pool.get(key) is subtopo:
            del _pool[key]


def pool_size() -> int:
    with _pool_lock:
        return len(_pool)


def reset() -> None:
    """Test hook: close and drop every pooled subtopo."""
    with _pool_lock:
        topos = list(_pool.values())
        _pool.clear()
    for st in topos:
        for n in st.nodes:
            n.close()
