"""Sink node — analogue of eKuiper's sink chain (planner_sink.go:36-253:
transform → batch → encode → cache → sink node) with SinkNode retry
(sink_node.go:197-255) folded in.

Transforms supported: field picking, dataTemplate (a pragmatic subset of Go
templates: {{.field}} substitution), sendSingle splitting, omitIfEmpty.
"""
from __future__ import annotations

import re
import time as _time
from typing import Any, Dict, List, Optional

from ..data.batch import ColumnBatch
from ..data.rows import GroupedTuplesSet, Row, Tuple, WindowTuples
from ..utils import timex
from ..utils.infra import logger
from .node import Node, _item_stamp

_TMPL_RE = re.compile(r"\{\{\s*\.(\w+)\s*\}\}")


def apply_transform(msg: Dict[str, Any], fields=None, exclude_fields=None,
                    data_template: str = "") -> Any:
    """Field projection + dataTemplate rendering (transform_op.go)."""
    if fields:
        msg = {k: msg.get(k) for k in fields}
    if exclude_fields:
        msg = {k: v for k, v in msg.items() if k not in exclude_fields}
    if data_template:
        return _TMPL_RE.sub(lambda m: str(msg.get(m.group(1), "")), data_template)
    return msg


def transform_messages(msgs: List[Dict[str, Any]], fields=None,
                       exclude_fields=None, data_template: str = "") -> List[Any]:
    """`apply_transform` over one item's messages — decided once per item:
    with nothing configured the list goes through as it is."""
    if not (fields or exclude_fields or data_template):
        return msgs
    return [apply_transform(m, fields, exclude_fields, data_template)
            for m in msgs]


def to_messages(item: Any) -> List[Dict[str, Any]]:
    """Normalize any runtime data item to a list of plain message dicts
    (shared by SinkNode and the sink-chain EncodeNode)."""
    if isinstance(item, list):
        out: List[Dict[str, Any]] = []
        for x in item:
            out.extend(to_messages(x))
        return out
    if isinstance(item, Tuple):
        return [item.all_values()]
    if isinstance(item, GroupedTuplesSet):
        return [g.all_values() for g in item.groups]
    if isinstance(item, (WindowTuples,)):
        return [r.all_values() for r in item.rows()]
    if isinstance(item, ColumnBatch):
        return item.to_messages()
    if isinstance(item, dict):
        return [item]
    if isinstance(item, Row):
        return [item.all_values()]
    return []


class SinkNode(Node):
    def __init__(
        self,
        name: str,
        sink,  # io.Sink
        send_single: bool = False,
        fields: Optional[List[str]] = None,
        exclude_fields: Optional[List[str]] = None,
        data_template: str = "",
        omit_if_empty: bool = False,
        retry_count: int = 0,
        retry_interval_ms: int = 1000,
        cache_node=None,  # upstream CacheNode for at-least-once nack feedback
        **kw,
    ) -> None:
        super().__init__(name, op_type="sink", **kw)
        self.cache_node = cache_node
        self.sink = sink
        self.send_single = send_single
        self.fields = fields
        self.exclude_fields = exclude_fields
        self.data_template = data_template
        self.omit_if_empty = omit_if_empty
        self.retry_count = retry_count
        self.retry_interval_ms = retry_interval_ms
        self._current: Any = None  # item being processed (cache ack/nack key)
        self._retries = 0  # failed collects of the item being delivered
        self.results: List[Any] = []  # test/trial access

    def on_open(self) -> None:
        self.sink.connect()

    def on_close(self) -> None:
        try:
            self.sink.close()
        except Exception as exc:
            logger.debug("sink %s close error: %s", self.name, exc)

    # ------------------------------------------------------------------ data
    def process(self, item: Any) -> None:
        self._observe_e2e(item)
        # ack/nack to the cache always reference the PRE-transform item the
        # cache emitted, so its in-flight tracking matches on resends
        self._current = item
        with self.stats.stage("sink") as st:
            st.rows = self._deliver(item)
        handed = self._cur_boundary_ns
        if handed is not None and self._topo is not None:
            # the boundary's last phase: handed downstream by the window
            # node -> collect returned (queue wait, convert, deliver)
            self._topo.observe_boundary(
                "sink", (_time.perf_counter_ns() - handed) / 1000.0)

    def _deliver(self, item: Any) -> int:
        """Convert (`convert` sub-stage) and deliver (`deliver`) one item;
        returns the messages delivered."""
        single = False
        if (isinstance(item, ColumnBatch) and item.n
                and getattr(self.sink, "accepts_batches", False)
                and not (self.send_single or self.fields
                         or self.exclude_fields or self.data_template)):
            # columnar fast path: a batch-capable sink takes the window
            # emission as-is — no per-row dict materialization (at 250+
            # rules x thousands of keys per boundary that conversion is
            # seconds of host time)
            payload, n = item, item.n
        elif isinstance(item, (bytes, bytearray, str)):
            # opaque payloads: post-encode/compress bytes, rendered template
            # strings — pass through untransformed
            # (reference: bytes-collector sink variant, sink_node.go:197)
            payload = (bytes(item) if isinstance(item, (bytes, bytearray))
                       else item)
            n = 1
        else:
            with self.stats.span("convert") as sp:
                msgs = transform_messages(
                    to_messages(item), self.fields, self.exclude_fields,
                    self.data_template)
                sp.rows = n = len(msgs)
            if not msgs and self.omit_if_empty:
                return 0
            single = self.send_single
            payload = msgs if single or n != 1 else msgs[0]
        with self.stats.span("deliver", n) as sp:
            self._retries = 0
            if single:
                # the cache tracks the PRE-split item: ack only after every
                # message lands, and stop on the first nack so the whole
                # item is parked exactly once (resend replays it from the
                # start)
                if all(self._collect(m, ack=False) for m in payload) \
                        and self.cache_node is not None:
                    self.cache_node.ack(self._current)
            else:
                self._collect(payload)
            if self._retries:
                sp.attrs = {"retries": self._retries}
        return n

    def _observe_e2e(self, item: Any) -> None:
        """Record the ingest→emit latency sample for items carrying their
        source ingest stamp (runtime/node.py provenance propagation) into
        the rule's end-to-end histogram — the paper's SLO (p99 emit < 50ms)
        measured where the result actually leaves the engine."""
        ing = _item_stamp(item)
        if ing is None:
            return
        lat_ms = max(timex.now_ms() - ing, 0)
        topo = self._topo
        observe = getattr(topo, "observe_e2e", None)
        if observe is not None:
            observe(lat_ms)
        if self._tracing_now:
            self._span_attrs = {"e2e_ms": lat_ms}

    def _collect(self, payload: Any, ack: bool = True) -> bool:
        attempts = 0
        delay = self.retry_interval_ms
        while True:
            try:
                self.sink.collect(payload)
                if ack and self.cache_node is not None:
                    self.cache_node.ack(self._current)  # drop spilled copy
                self.results.append(payload)
                if len(self.results) > 10000:
                    del self.results[:5000]
                return True
            except Exception as exc:
                attempts += 1
                self._retries += 1
                self.stats.inc_exception(str(exc))
                if attempts > self.retry_count:
                    if self.cache_node is not None:
                        # at-least-once: park the item in the sink cache; its
                        # resend loop re-delivers when the sink recovers
                        self.cache_node.nack(self._current)
                        return False
                    raise
                timex.sleep(delay)
                delay = min(delay * 2, 30_000)
