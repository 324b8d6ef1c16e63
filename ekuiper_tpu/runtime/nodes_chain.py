"""Sink/source chain operators — analogues of the reference's per-edge nodes
(SURVEY §2.3):

  BatchNode        size+linger batching pre-sink (batch_op.go:29-38)
  EncodeNode       rows -> bytes via a converter (encode_op.go)
  CompressNode /   wrap utils.codecs compressors (compress_op.go)
  DecompressNode
  EncryptNode /    aes gcm/cfb (encrypt_op.go)
  DecryptNode
  CacheNode        at-least-once sink buffering: memory page + KV-store disk
                   spill, resend loop with backoff
                   (cache_op.go, cache/sync_cache.go:107-378)
  RateLimitNode    per-interval latest-message throttle (rate_limit.go:36-67)
  DedupTriggerNode interval dedup w/ expiring state (dedup_trigger_op.go:32-302)

All are ordinary Nodes on the threaded DAG; they pass through Barrier /
Watermark / EOF control events via the Node defaults.
"""
from __future__ import annotations

import base64
import json
import pickle
import threading
from typing import Any, Dict, List, Optional, Tuple

from ..utils import timex
from ..utils.codecs import get_compressor, get_encryptor
from ..utils.infra import logger
from .events import EOF
from .node import Node


def _dumps(item: Any) -> str:
    """KV-safe serialization for spilled payloads (KV backends store JSON)."""
    return base64.b64encode(pickle.dumps(item)).decode("ascii")


def _loads(raw: Any) -> Any:
    return pickle.loads(base64.b64decode(raw))


class BatchNode(Node):
    """Accumulate messages; emit a list when size or linger expires
    (batch_op.go:29-38 — sendInterval/batchSize)."""

    def __init__(self, name: str, size: int = 0, linger_ms: int = 0, **kw) -> None:
        super().__init__(name, **kw)
        if size <= 0 and linger_ms <= 0:
            raise ValueError("batch needs batchSize or lingerInterval")
        self.size = size
        self.linger_ms = linger_ms
        self._buf: List[Any] = []
        self._mu = threading.Lock()
        self._timer = None

    def on_open(self) -> None:
        if self.linger_ms > 0:
            self._arm()

    def _arm(self) -> None:
        self._timer = timex.get_clock().after(self.linger_ms, lambda _now: self._fire())

    def _fire(self) -> None:
        self._flush()
        if not self._stop.is_set():
            self._arm()

    def _flush(self) -> None:
        with self._mu:
            buf, self._buf = self._buf, []
        if buf:
            self.emit(buf, count=len(buf))

    def process(self, item: Any) -> None:
        items = item if isinstance(item, list) else [item]
        full = False
        with self._mu:
            self._buf.extend(items)
            full = self.size > 0 and len(self._buf) >= self.size
        if full:
            self._flush()

    def on_eof(self, eof: EOF) -> None:
        self._flush()
        self.broadcast(eof)

    def on_close(self) -> None:
        if self._timer is not None:
            self._timer.stop()
        self._flush()


class TransformNode(Node):
    """Sink-side transform as a standalone stage (transform_op.go): applied
    BEFORE encode/compress/encrypt so those stages see the projected payload.
    When present, the terminal SinkNode's own transform is disabled."""

    def __init__(self, name: str, send_single: bool = False,
                 fields: Optional[List[str]] = None,
                 exclude_fields: Optional[List[str]] = None,
                 data_template: str = "", omit_if_empty: bool = False,
                 **kw) -> None:
        super().__init__(name, **kw)
        self.send_single = send_single
        self.fields = fields
        self.exclude_fields = exclude_fields
        self.data_template = data_template
        self.omit_if_empty = omit_if_empty

    def process(self, item: Any) -> None:
        from .nodes_sink import to_messages, transform_messages

        msgs = to_messages(item)
        if not msgs and self.omit_if_empty:
            return
        msgs = transform_messages(msgs, self.fields, self.exclude_fields,
                                  self.data_template)
        if self.send_single:
            for m in msgs:
                self.emit(m)
        else:
            self.emit(msgs if len(msgs) != 1 else msgs[0])


class EncodeNode(Node):
    """Rows -> bytes via the sink's FORMAT converter (encode_op.go)."""

    def __init__(self, name: str, converter, **kw) -> None:
        super().__init__(name, **kw)
        self.converter = converter

    def process(self, item: Any) -> None:
        from .nodes_sink import to_messages

        if isinstance(item, (bytes, bytearray)):
            self.emit(bytes(item))  # already encoded upstream
            return
        if isinstance(item, str):
            # rendered dataTemplate output is the final wire payload
            self.emit(item.encode())
            return
        msgs = to_messages(item)
        payload = msgs[0] if len(msgs) == 1 else msgs
        self.emit(self.converter.encode(payload))


class CompressNode(Node):
    def __init__(self, name: str, algorithm: str, **kw) -> None:
        super().__init__(name, **kw)
        self._compress, _ = get_compressor(algorithm)

    def process(self, item: Any) -> None:
        if not isinstance(item, (bytes, bytearray)):
            item = json.dumps(item, default=str).encode()
        self.emit(self._compress(bytes(item)))


class DecompressNode(Node):
    def __init__(self, name: str, algorithm: str, **kw) -> None:
        super().__init__(name, **kw)
        _, self._decompress = get_compressor(algorithm)

    def process(self, item: Any) -> None:
        self.emit(self._decompress(bytes(item)))


class EncryptNode(Node):
    def __init__(self, name: str, algorithm: str, props: Dict[str, Any], **kw) -> None:
        super().__init__(name, **kw)
        self._enc = get_encryptor(algorithm, props)

    def process(self, item: Any) -> None:
        if not isinstance(item, (bytes, bytearray)):
            item = json.dumps(item, default=str).encode()
        self.emit(self._enc.encrypt(bytes(item)))


class DecryptNode(Node):
    def __init__(self, name: str, algorithm: str, props: Dict[str, Any], **kw) -> None:
        super().__init__(name, **kw)
        self._enc = get_encryptor(algorithm, props)

    def process(self, item: Any) -> None:
        self.emit(self._enc.decrypt(bytes(item)))


class CacheNode(Node):
    """At-least-once sink buffer (sync_cache.go:107-378).

    Pass-through while the downstream sink is healthy. The SinkNode reports
    failures back via `nack(payload)`; nacked payloads go to the memory page,
    spilling to the rule's KV store beyond `memory_threshold`. A resend timer
    retries oldest-first, preserving order, with `resend_interval_ms` pacing.
    """

    def __init__(
        self,
        name: str,
        store_kv=None,  # KV namespace for disk spill (None = memory only)
        memory_threshold: int = 1024,
        max_disk_cache: int = 1024 * 1024,
        resend_interval_ms: int = 100,
        **kw,
    ) -> None:
        super().__init__(name, **kw)
        self.kv = store_kv
        self.memory_threshold = memory_threshold
        self.max_disk_cache = max_disk_cache
        self.resend_interval_ms = resend_interval_ms
        self._mem: List[Any] = []
        self._disk_head = 0  # next key to resend
        self._disk_tail = 0  # next key to write
        self._mu = threading.Lock()
        self._timer = None
        self._armed = False  # resend timer reserved (see _reserve_arm_locked)
        self._closed = False
        self._inflight = None  # ("mem"|"disk", item) awaiting sink ack/nack
        # (disk_key, item) for a mem in-flight delivery whose payload a
        # barrier spilled to disk while the sink ack was still outstanding;
        # the late ack must delete that record or the resend timer would
        # redeliver an already-delivered item (duplicate sink output)
        self._spilled_inflight = None
        if self.kv is not None:  # restore spill bounds from a previous run
            keys = []
            for k in self.kv.keys():
                try:
                    keys.append(int(k))  # close-spill prepends: can be < 0
                except (TypeError, ValueError):
                    continue
            if keys:
                keys.sort()
                self._disk_head, self._disk_tail = keys[0], keys[-1] + 1

    def on_open(self) -> None:
        # a restart with spilled backlog must resend WITHOUT waiting for new
        # traffic (a fully-consumed rewindable source may never push again)
        with self._mu:
            arm = ((self._mem or self._disk_head != self._disk_tail)
                   and self._reserve_arm_locked())
        if arm:
            self._register_arm()

    # pass-through; SinkNode acks successes / nacks failures back to us
    def process(self, item: Any) -> None:
        with self._mu:
            pending = (bool(self._mem) or self._disk_head != self._disk_tail
                       or self._inflight is not None)
        if pending:
            self._enqueue(item)  # keep order: new items go behind the backlog
        else:
            self.emit(item)

    def ack(self, item: Any) -> None:
        """Downstream delivery confirmed — only now drop the spilled copy
        (sync_cache deletes a disk record only after a successful send)."""
        arm = False
        with self._mu:
            fl = self._inflight
            if fl is None or fl[1] is not item and fl[1] != item:
                sp = self._spilled_inflight
                if sp is not None and (sp[1] is item or sp[1] == item):
                    # late ack for a delivery whose payload a barrier moved
                    # to disk — drop the spilled record so it isn't resent
                    self._spilled_inflight = None
                    self.kv.delete(str(sp[0]))
                    if sp[0] == self._disk_head:
                        self._disk_head += 1
                    if bool(self._mem) or self._disk_head != self._disk_tail:
                        arm = self._reserve_arm_locked()
                # else: ack for a pass-through item — nothing tracked
            else:
                kind = fl[0]
                self._inflight = None
                if kind == "disk":
                    self.kv.delete(str(self._disk_head))
                    self._disk_head += 1
                if bool(self._mem) or self._disk_head != self._disk_tail:
                    arm = self._reserve_arm_locked()
        if arm:
            self._register_arm()

    def nack(self, item: Any) -> None:
        """Called by the downstream SinkNode when collect ultimately fails."""
        arm = False
        tracked = False
        with self._mu:
            fl = self._inflight
            sp = self._spilled_inflight
            if fl is not None and (fl[1] is item or fl[1] == item):
                self._inflight = None
                if fl[0] == "mem":
                    self._mem.insert(0, item)
                # a disk record was never deleted — it will be re-read
                tracked = True
                arm = self._reserve_arm_locked()
            elif sp is not None and (sp[1] is item or sp[1] == item):
                # failed delivery whose payload a barrier spilled: the disk
                # record IS the retry copy — re-enqueueing would duplicate
                self._spilled_inflight = None
                tracked = True
                arm = self._reserve_arm_locked()
        if arm:
            self._register_arm()
        if not tracked:
            self._enqueue(item, front=True)

    def _enqueue(self, item: Any, front: bool = False) -> None:
        dropped = 0
        with self._mu:
            if front:
                self._mem.insert(0, item)
            elif self.kv is not None and (
                len(self._mem) >= self.memory_threshold
                or self._disk_head != self._disk_tail  # FIFO: go behind spill
            ):
                if self._disk_tail - self._disk_head < self.max_disk_cache:
                    self.kv.set(str(self._disk_tail), _dumps(item))
                    self._disk_tail += 1
                else:
                    dropped = 1  # stat recorded below, outside _mu
            else:
                self._mem.append(item)
            arm = self._reserve_arm_locked()
        if dropped:
            # outside _mu: inc_exception reads the engine clock, and the
            # mock clock fires _resend -> _mu while holding the clock
            # lock (clock orders before _mu — utils/lockcheck.py)
            self.stats.inc_exception("disk cache full, dropped")
        if arm:
            self._register_arm()

    def _arm(self) -> None:
        with self._mu:
            arm = self._reserve_arm_locked()
        if arm:
            self._register_arm()

    def _reserve_arm_locked(self) -> bool:
        """Reserve the resend timer. Caller holds self._mu and, when this
        returns True, MUST call _register_arm() AFTER releasing it: timer
        registration takes the engine clock lock, and the mock clock
        fires callbacks (-> _resend -> self._mu) while holding it —
        arming under self._mu was the clock/cache ABBA
        utils/lockcheck.py caught on day one (clock orders before _mu)."""
        if self._armed or self._closed:
            return False
        self._armed = True
        return True

    def _register_arm(self) -> None:
        # outside self._mu by contract (see _reserve_arm_locked)
        self._timer = timex.get_clock().after(
            self.resend_interval_ms, lambda _now: self._resend())

    def _resend(self) -> None:
        arm = False
        item = None
        with self._mu:
            self._timer = None
            self._armed = False
            if self._closed:
                return
            if self._inflight is not None or self._spilled_inflight is not None:
                # previous delivery still unconfirmed — wait for ack/nack
                # (a spilled in-flight is still a live downstream delivery;
                # resending its disk record now would duplicate it)
                arm = self._reserve_arm_locked()
            elif self._mem:
                item = self._mem.pop(0)
                self._inflight = ("mem", item)
            elif self.kv is not None and self._disk_head != self._disk_tail:
                raw = self.kv.get(str(self._disk_head))
                if raw is None:  # lost record — skip the slot
                    self._disk_head += 1
                    arm = self._reserve_arm_locked()
                else:
                    item = _loads(raw)
                    self._inflight = ("disk", item)  # deleted only on ack
        if arm:
            self._register_arm()
        if item is not None:
            self.emit(item)

    def pending(self) -> int:
        with self._mu:
            n = len(self._mem) + (self._disk_tail - self._disk_head)
            if self._inflight is not None and self._inflight[0] == "mem":
                n += 1
            return n

    def _spill_page_locked(self) -> Tuple[int, int]:
        """Move the memory page (queue FRONT — oldest pending) plus any
        unconfirmed in-flight delivery INTO the spill KV, prepending BEFORE
        the disk head (keys may go negative) so replay order stays
        oldest-first. Enforces max_disk_cache like _enqueue: the OLDEST
        items keep their slots, the newest overflow drops. Caller holds
        self._mu and returns (moved, dropped); the caller records the
        drop stat AFTER releasing _mu (inc_exception reads the engine
        clock — clock orders before _mu, utils/lockcheck.py)."""
        items = list(self._mem)
        inflight_item = None
        if self._inflight is not None and self._inflight[0] == "mem":
            inflight_item = self._inflight[1]
            items.insert(0, inflight_item)
            self._inflight = None
        n_drop = 0
        room = self.max_disk_cache - (self._disk_tail - self._disk_head)
        if len(items) > max(room, 0):
            n_drop = len(items) - max(room, 0)
            items = items[:max(room, 0)]
        for item in reversed(items):
            self._disk_head -= 1
            self.kv.set(str(self._disk_head), _dumps(item))
        if inflight_item is not None and items:
            # items[0] (the in-flight delivery) landed at the new disk head;
            # remember the key so its still-outstanding ack can delete it
            self._spilled_inflight = (self._disk_head, inflight_item)
        self._mem.clear()
        return len(items), n_drop

    def snapshot_state(self) -> Optional[dict]:
        # The spill KV is the ONE durable store for pending payloads: at a
        # barrier the memory page moves into it (immediately durable even
        # if the checkpoint never completes), and the JSON checkpoint
        # carries only bookkeeping — no payload double-persist between the
        # checkpoint and the close-time spill. Memory-only caches (no KV)
        # still encode the page into the checkpoint itself.
        out = None
        dropped = 0
        with self._mu:
            if self.kv is not None:
                n, dropped = self._spill_page_locked()
                out = {"spilled": n}
            else:
                items = list(self._mem)
                if self._inflight is not None and self._inflight[0] == "mem":
                    items.insert(0, self._inflight[1])
        if dropped:
            self.stats.inc_exception("disk cache full, dropped", n=dropped)
        if out is not None:
            return out
        return {"mem_enc": [_dumps(i) for i in items]}

    def restore_state(self, state: dict) -> None:
        with self._mu:
            if "mem_enc" in state:
                self._mem = [_loads(r) for r in state["mem_enc"]]
            elif "mem" in state:  # legacy raw-list snapshots
                self._mem = list(state.get("mem", []))
            # KV-backed pages were spilled at snapshot time; __init__
            # already recovered the disk bounds

    def on_close(self) -> None:
        with self._mu:
            # closed gate: an arm reserved but not yet registered by a
            # racing thread may still create a timer, but its _resend
            # no-ops once closed is set — nothing re-emits after close
            self._closed = True
            timer, self._timer = self._timer, None
        if timer is not None:
            timer.stop()
        # spill whatever is still in memory (items nacked after the last
        # barrier) so nothing is lost across restarts; a disk-sourced
        # in-flight record was never deleted, so it replays by itself
        if self.kv is not None:
            with self._mu:
                _, dropped = self._spill_page_locked()
            if dropped:
                self.stats.inc_exception("disk cache full, dropped",
                                         n=dropped)


class RateLimitNode(Node):
    """Keep only the most recent message per interval (rate_limit.go:36-67,
    default 'latest' strategy; mergeField frame-merge is host-path only)."""

    def __init__(self, name: str, interval_ms: int, **kw) -> None:
        super().__init__(name, **kw)
        if interval_ms < 1:
            raise ValueError("interval should be larger than 1ms")
        self.interval_ms = interval_ms
        self._latest: Any = None
        self._has = False
        self._mu = threading.Lock()
        self._timer = None

    def on_open(self) -> None:
        self._arm()

    def _arm(self) -> None:
        self._timer = timex.get_clock().after(self.interval_ms, lambda _now: self._fire())

    def _fire(self) -> None:
        with self._mu:
            item, self._has = (self._latest, False) if self._has else (None, False)
            self._latest = None
        if item is not None:
            self.emit(item)
        if not self._stop.is_set():
            self._arm()

    def process(self, item: Any) -> None:
        with self._mu:
            self._latest = item
            self._has = True

    def on_close(self) -> None:
        if self._timer is not None:
            self._timer.stop()


class DedupTriggerNode(Node):
    """Interval-overlap dedup for trigger events (dedup_trigger_op.go:32-302).

    Rows carry start/end(/now) fields; already-seen [start,end) sub-ranges are
    suppressed, novel sub-ranges emit as {alias: [[start,end],...]} merged into
    the row. Seen state expires after `expire_ms`.
    """

    def __init__(
        self,
        name: str,
        alias: str = "dedup_trigger",
        start_field: str = "start",
        end_field: str = "end",
        now_field: str = "",
        expire_ms: int = 3_600_000,
        **kw,
    ) -> None:
        super().__init__(name, **kw)
        self.alias = alias
        self.start_field = start_field
        self.end_field = end_field
        self.now_field = now_field
        self.expire_ms = expire_ms
        self._seen: List[List[int]] = []  # sorted non-overlapping [start,end)

    def process(self, item: Any) -> None:
        from ..data.rows import Row

        msg = item.all_values() if isinstance(item, Row) else dict(item)
        start = int(msg.get(self.start_field, 0))
        end = int(msg.get(self.end_field, 0))
        now = int(msg.get(self.now_field, end)) if self.now_field else end
        if end <= start:
            raise ValueError(f"dedup trigger: end {end} <= start {start}")
        # expire old state
        horizon = now - self.expire_ms
        self._seen = [iv for iv in self._seen if iv[1] > horizon]
        novel = self._subtract(start, end)
        if not novel:
            return  # fully duplicate
        self._insert(start, end)
        msg = dict(msg)
        msg[self.alias] = novel
        self.emit(msg)

    def _subtract(self, start: int, end: int) -> List[List[int]]:
        """[start,end) minus seen ranges -> novel sub-ranges."""
        out: List[List[int]] = []
        cur = start
        for s, e in sorted(self._seen):
            if e <= cur:
                continue
            if s >= end:
                break
            if s > cur:
                out.append([cur, min(s, end)])
            cur = max(cur, e)
            if cur >= end:
                break
        if cur < end:
            out.append([cur, end])
        return out

    def _insert(self, start: int, end: int) -> None:
        merged: List[List[int]] = []
        for s, e in sorted(self._seen + [[start, end]]):
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        self._seen = merged

    def snapshot_state(self) -> Optional[dict]:
        return {"seen": [list(iv) for iv in self._seen]}

    def restore_state(self, state: dict) -> None:
        self._seen = [list(iv) for iv in state.get("seen", [])]
