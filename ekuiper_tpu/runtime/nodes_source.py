"""Source pipeline node — the fused analogue of the reference's source split
(connector → rate-limit → decode → preprocessor, planner_source.go:35-197).

A SourceNode owns a connector (io registry), decodes payloads via the
converter, coerces to the stream schema (preprocessor semantics incl.
event-time extraction from the TIMESTAMP option), accumulates rows into
columnar micro-batches (size/linger bounded), and emits ColumnBatch — the
TPU-native ingest form. Micro-batching here is what turns the reference's
per-tuple goroutine hops into whole-batch device work.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, NamedTuple, Optional

from ..data import cast
from ..data.batch import ColumnBatch, from_tuples
from ..data.rows import Tuple
from ..data.types import Schema
from ..utils import timex
from ..utils.infra import logger
from .events import EOF
from .node import Node


class _DecodePlan(NamedTuple):
    """What one micro-batch is decoded with: the column set, the declared
    schema cut to it, and the native decoder's field spec (None: the
    stream is not natively decodable). A hand-over reads the node's
    current plan under the pending lock and every job carries it, so a
    micro-batch is never decoded with two plans."""

    project: Optional[frozenset]  # None = every declared column
    schema: Optional[Schema]
    fast_spec: Optional[tuple]


class SourceNode(Node):
    def __init__(
        self,
        name: str,
        connector,  # io.Source instance
        schema: Optional[Schema] = None,
        timestamp_field: str = "",
        strict_validation: bool = False,
        micro_batch_rows: int = 4096,
        linger_ms: int = 10,
        buffer_length: int = 1024,
        emit_batches: bool = True,
        converter=None,  # io.converters.Converter for bytes payloads
        project_columns=None,  # column-pruning set (planner/optimizer.py)
        decode_pool_size: int = 0,  # 0 = decode inline (no pool threads)
        decode_shards: int = 0,  # native parse shards; 0 = auto
        ring_depth: int = 2,  # decoded-batch ring depth (pool backpressure)
        prep_upload: bool = True,  # pool workers pre-encode keys + device_put
    ) -> None:
        super().__init__(name, op_type="source", buffer_length=buffer_length)
        self.connector = connector
        self.converter = converter
        self.timestamp_field = timestamp_field
        self.strict = cast.STRICT if strict_validation else cast.CONVERT_ALL
        self.micro_batch_rows = micro_batch_rows
        self.linger_ms = linger_ms
        self.emit_batches = emit_batches
        # batch mode buffers RAW decoded messages; schema coercion +
        # event-time extraction run COLUMNAR at flush (data/batch.py
        # from_messages) instead of per-row — the row path (emit_batches=
        # False) keeps the per-tuple preprocessor
        self._pending_msgs: List[Dict[str, Any]] = []
        self._pending_ts: List[int] = []
        # native fast path: JSON bytes payloads for a fully-scalar typed
        # schema buffer RAW and decode straight to columns in C at flush
        # (io/fastjson.py over native/jsoncol.cpp). Eligibility is the
        # DECLARED schema's; which of its columns a micro-batch decodes
        # is the plan's (set_decode_columns)
        self._declared_schema = schema
        self._declared_spec = None
        self._pending_raw: List[bytes] = []
        self._pending_raw_ts: List[int] = []
        if converter is not None and schema is not None:
            from ..io.converters import JsonConverter
            from ..io.fastjson import ensure_native, schema_field_spec

            if type(converter) is JsonConverter and \
                    self.strict != cast.STRICT:
                # STRICT streams keep the python cast path — the C decoder
                # hard-codes CONVERT_ALL coercion
                spec = schema_field_spec(schema)
                if spec is not None and timestamp_field:
                    # event-time via the fast path needs an exact int64
                    # column; other shapes keep the python extractor
                    ftypes = {f.name: f.type for f in schema.fields}
                    from ..data.types import DataType

                    if ftypes.get(timestamp_field) != DataType.BIGINT:
                        spec = None
                self._declared_spec = spec
                if spec is not None:
                    ensure_native()
        # the native decoder's own counts, added once a micro-batch:
        # object members decoded into a column / stepped over, payload bytes
        self.decode_tally: Dict[str, int] = {
            "kept": 0, "skipped": 0, "bytes": 0}
        self._plan = self._make_plan(project_columns)
        self._pending_lock = threading.Lock()
        self._linger_timer = None
        # sharded ingest pipeline (runtime/ingest.py): flush-time decode
        # runs on pool workers, shard-parallel inside the native parse,
        # handed to the fused node through a bounded ordered ring. Pool-
        # less sources (decode_pool_size=0) decode inline exactly as
        # before. The pool itself starts LAZILY at first use: planned-but-
        # never-opened topos (rule validation plans then closes without
        # open()) must not leak worker threads.
        self.decode_pool_size = (int(decode_pool_size) if emit_batches
                                 else 0)
        self.ring_depth = int(ring_depth)
        self._decode_shards = (int(decode_shards) if decode_shards
                               else max(self.decode_pool_size, 1))
        self._pool = None
        # pipelined upload stage (runtime/ingest.py IngestPrepCtx): pool
        # workers key-slot-encode each decoded batch and pre-pad +
        # device_put its kernel inputs, so the fused worker receives
        # device-resident refs instead of raw host columns. Only with the
        # pool on — the decode_pool_size=0 default path stays bit-for-bit
        # the pre-pool inline pipeline (mock-clock determinism).
        self.prep_ctx = None
        if self.decode_pool_size > 0 and prep_upload:
            from .ingest import IngestPrepCtx

            self.prep_ctx = IngestPrepCtx()

    # ------------------------------------------------------- decoded columns
    def _make_plan(self, project_columns) -> _DecodePlan:
        project = (None if project_columns is None
                   else frozenset(project_columns))
        schema, spec = self._declared_schema, self._declared_spec
        if project is not None:
            if schema is not None and not schema.schemaless:
                # restrict the declared schema too: from_tuples
                # materializes a column per schema field, so pruning must
                # reach it or typed streams would re-grow zero-filled
                # columns at batch build
                schema = Schema(fields=[f for f in schema.fields
                                        if f.name in project])
            if spec is not None:
                spec = tuple(f for f in spec if f[0] in project)
        return _DecodePlan(project, schema, spec)

    def set_decode_columns(self, project_columns) -> None:
        """Decode `project_columns` (None: every declared column) from the
        next micro-batch handed over on: pending rows are still raw, so
        swapping the plan under the pending lock is a flush edge. A shared
        subtopo calls this with the union of what its riders read
        (runtime/subtopo.py); a private source keeps its rule's set."""
        plan = self._make_plan(project_columns)
        with self._pending_lock:
            self._plan = plan

    @property
    def schema(self) -> Optional[Schema]:
        return self._plan.schema

    @property
    def project_columns(self) -> Optional[frozenset]:
        return self._plan.project

    @property
    def _fast_spec(self) -> Optional[tuple]:
        return self._plan.fast_spec

    def keytable_encode_rows(self) -> Optional[Dict[str, int]]:
        """Rows the ingest prep's key tables encoded, by path
        (ops/keytable.py ENCODE_PATHS); None without the prep stage."""
        if self.prep_ctx is None:
            return None
        out: Dict[str, int] = {}
        for kt in list(self.prep_ctx.key_tables.values()):
            for path, n in kt.encode_rows.items():
                out[path] = out.get(path, 0) + n
        return out

    def decoded_columns(self):
        """The columns a micro-batch is decoded with now, for the status
        and /explain: sorted names, or "*" (everything the payload bears)."""
        plan = self._plan
        if plan.project is None:
            if plan.schema is None or plan.schema.schemaless:
                return "*"
            return sorted(f.name for f in plan.schema.fields)
        return sorted(plan.project)

    # ------------------------------------------------------------------ ingest
    def on_open(self) -> None:
        self.connector.open(self.ingest)

    def on_close(self) -> None:
        try:
            self.connector.close()
        except Exception as exc:
            logger.debug("source %s close error: %s", self.name, exc)
        self._flush()
        if self._pool is not None:
            self._pool.close()

    def ingest(self, payload: Any, metadata: Optional[Dict[str, Any]] = None) -> None:
        """Connector callback: raw bytes (decoded here via the stream's
        FORMAT converter), a LIST of raw bytes payloads (a broker drain —
        batch-decoded), dict, list of dicts, or Tuple. It runs on the
        connector's thread, outside the node's dispatch loop, so under a
        traced rule it opens the source's own span: the root of the trace
        its rows travel in."""
        span = self._span_begin(payload)
        try:
            self._ingest(payload, metadata)
        finally:
            if span is not None:
                self._span_end(span)

    def _ingest(self, payload: Any, metadata: Optional[Dict[str, Any]]) -> None:
        now = timex.now_ms()
        if self._fast_spec is not None and self.emit_batches:
            raws = None
            if isinstance(payload, (bytes, bytearray)):
                raws = [bytes(payload)]
            elif (isinstance(payload, list) and payload
                  and all(isinstance(p, (bytes, bytearray))
                          for p in payload)):
                raws = [bytes(p) for p in payload]
            if raws is not None:
                self.stats.inc_in(len(raws))
                self._buffer("raw", raws, [now] * len(raws))
                return
        if isinstance(payload, (bytes, bytearray)):
            if self.converter is None:
                self.stats.inc_exception("bytes payload but no converter")
                return
            try:
                payload = self.converter.decode(bytes(payload))
            except Exception as exc:
                self.stats.inc_exception(f"decode error: {exc}")
                self.stats.inc_dropped("decode_error")
                return
        msgs: List[Dict[str, Any]] = []
        if isinstance(payload, Tuple):
            self.stats.inc_in(1)
            if not self.emit_batches:
                t = self._preprocess(payload)
                if t is not None:
                    t.ingest_ms = now
                    self.emit(t)
                return
            # preserve the tuple's own (replay/historical) timestamp
            self._buffer("msgs", [payload.message], [payload.timestamp or now])
            return
        elif isinstance(payload, dict):
            msgs = [payload]
        elif isinstance(payload, list):
            if payload and isinstance(payload[0], (bytes, bytearray)):
                msgs = self._decode_many(payload)
                if msgs is None:
                    return
            else:
                msgs = [m for m in payload if isinstance(m, dict)]
        elif payload is None:
            return
        else:
            self.stats.inc_exception(f"unsupported payload {type(payload)}")
            return
        if not msgs:
            return
        self.stats.inc_in(len(msgs))
        if not self.emit_batches:
            for m in msgs:
                t = self._preprocess(Tuple(
                    emitter=self.name, message=m, timestamp=now,
                    metadata=metadata or {}))
                if t is not None:
                    t.ingest_ms = now
                    self.emit(t)
            return
        self._buffer("msgs", msgs, [now] * len(msgs))

    def _buffer(self, kind: str, new_items: list, new_ts: list) -> None:
        """Append to a pending buffer under the lock, then flush at the
        micro-batch threshold or arm the linger timer — the single place
        holding the batching policy for all three ingest shapes. The
        target list is resolved INSIDE the lock: a caller-bound reference
        could be swapped out by a concurrent flush between the attribute
        read and the lock, silently losing the whole append."""
        with self._pending_lock:
            if kind == "raw":
                self._pending_raw.extend(new_items)
                self._pending_raw_ts.extend(new_ts)
            else:
                self._pending_msgs.extend(new_items)
                self._pending_ts.extend(new_ts)
            full = (len(self._pending_msgs) + len(self._pending_raw)
                    >= self.micro_batch_rows)
        if full:
            self._flush(final=False)
            with self._pending_lock:
                leftover = bool(self._pending_msgs or self._pending_raw)
            if not leftover:
                return
            # a micro-batch-aligned flush kept a remainder: make sure a
            # linger timer is live so it cannot stall if ingest pauses
        self._arm_linger()

    def _arm_linger(self) -> None:
        if self._linger_timer is None or self._linger_timer.fired \
                or self._linger_timer.stopped:
            self._linger_timer = timex.after(
                self.linger_ms, lambda ts: self._linger_flush())

    def _linger_flush(self) -> None:
        """Timer-driven flush: stays micro-batch-aligned under sustained
        ingest (a large pending still emits exact micro_batch slices; only
        a sub-micro-batch tail flushes whole) and re-arms while a
        remainder is pending so it drains within another linger period.
        No item caused it, so under a traced rule it is a root span."""
        span = self._span_begin(None, kind="LingerTimer")
        try:
            self._flush(final=False)
        finally:
            if span is not None:
                self._span_end(span)
        with self._pending_lock:
            leftover = bool(self._pending_msgs or self._pending_raw)
        if leftover:
            self._arm_linger()

    def _decode_many(self, payloads: List[bytes]) -> Optional[List[Dict[str, Any]]]:
        """Batch-decode a run of raw payloads. For JSON this splices the
        payloads into ONE array and parses once — one C-level json.loads
        instead of thousands (≈4x per-object) — falling back to per-payload
        decode when any payload is itself an array or malformed."""
        from ..io.converters import JsonConverter

        if self.converter is None:
            self.stats.inc_exception("bytes payload but no converter")
            return None
        if isinstance(self.converter, JsonConverter) and all(
                isinstance(p, (bytes, bytearray)) for p in payloads):
            try:
                spliced = b"[" + b",".join(bytes(p) for p in payloads) + b"]"
                out = self.converter.decode(spliced)
                if all(isinstance(m, dict) for m in out):
                    return out
            except Exception:
                pass  # fall through: per-payload decode isolates bad ones
        msgs: List[Dict[str, Any]] = []
        for p in payloads:
            if isinstance(p, dict):  # mixed drains: dicts pass through
                msgs.append(p)
                continue
            try:
                m = self.converter.decode(bytes(p))
            except Exception as exc:
                self.stats.inc_exception(f"decode error: {exc}")
                self.stats.inc_dropped("decode_error")
                continue
            if isinstance(m, dict):
                msgs.append(m)
            elif isinstance(m, list):
                msgs.extend(x for x in m if isinstance(x, dict))
        return msgs

    def _preprocess(self, t: Tuple) -> Optional[Tuple]:
        """Schema validation/coercion + event-time extraction
        (reference: internal/topo/operator/preprocessor.go)."""
        plan = self._plan  # one plan a tuple, whatever a rider does meanwhile
        if plan.schema is not None and not plan.schema.schemaless:
            msg = {}
            for f in plan.schema.fields:
                if f.name in t.message:
                    try:
                        msg[f.name] = cast.to_typed(t.message[f.name], f, self.strict)
                    except cast.CastError as exc:
                        self.stats.inc_exception(str(exc))
                        return None
            t.message = msg
        if self.timestamp_field:
            v = t.message.get(self.timestamp_field)
            if v is None:
                self.stats.inc_exception(
                    f"missing timestamp field {self.timestamp_field}"
                )
                return None
            try:
                t.timestamp = cast.to_datetime_ms(v)
            except cast.CastError as exc:
                self.stats.inc_exception(str(exc))
                return None
        if plan.project is not None:
            # column pruning (planner/optimizer.py): drop unreferenced
            # fields before batching — smaller batches, tuples, uploads
            t.message = {k: v for k, v in t.message.items()
                         if k in plan.project}
        return t

    # ------------------------------------------------------------------ state
    def snapshot_state(self):
        """Rewindable sources (io/contract.py) checkpoint their offset so a
        restored rule resumes the stream where the snapshot cut it."""
        get_off = getattr(self.connector, "get_offset", None)
        if get_off is None:
            return None
        try:
            return {"offset": get_off()}
        except Exception:
            return None

    def restore_state(self, state: dict) -> None:
        rew = getattr(self.connector, "rewind", None)
        if rew is not None and state and "offset" in state:
            try:
                rew(state["offset"])
            except Exception as exc:
                self.stats.inc_exception(f"rewind failed: {exc}")

    def _flush(self, final: bool = True) -> bool:
        """Flush pending buffers; a final flush also drains the decode
        ring so callers can safely broadcast EOF/barriers after it.
        Returns False when that drain timed out (rows may still be
        decoding) — the barrier path fails its checkpoint on that. The
        drain runs OUTSIDE the pending lock: appending new rows needs
        nothing from the ring, and a held lock would stall every
        connector callback for the drain's duration.

        Cutting the micro-batch and handing it to the decode pool is the
        `ingest` stage: the engine's work on the CALLER's thread (a
        publisher's, for the memory bus; the linger timer's). The appends
        between two flushes (a list extend each) are not timed: a stage
        per connector call would cost more than the append it times."""
        with self.stats.stage("ingest") as st:
            inline, st.rows = self._hand_over(final)
        for job in inline:
            self._emit_decoded(self._decode_job(job))
        if final and self._pool is not None:
            if not self._pool.drain():
                logger.error(
                    "source %s: decode ring drain timed out on a final "
                    "flush; decoded batches may trail stream-end events",
                    self.name)
                return False
        return True

    def _hand_over(self, final: bool) -> tuple:
        """Take the pending rows as decode jobs and submit them to the
        decode pool (which blocks while its ring is full: the backpressure
        toward the connector). Returns (the jobs the caller must decode
        inline — all of them without a pool — AFTER its `ingest` stage has
        closed, so `decode` never nests inside it; the rows taken)."""
        jobs = []
        with self._pending_lock:
            if self._pending_msgs or self._pending_raw:
                msgs, self._pending_msgs = self._pending_msgs, []
                tss, self._pending_ts = self._pending_ts, []
                raws, self._pending_raw = self._pending_raw, []
                rtss, self._pending_raw_ts = self._pending_raw_ts, []
                if not final and len(raws) > self.micro_batch_rows:
                    # emit micro_batch-aligned slices and keep the
                    # remainder pending: the fused kernel pads every chunk
                    # to a static micro_batch shape, so a 1024-row tail
                    # would upload a full chunk's worth of padding — on a
                    # bandwidth-limited link that nearly halves ingest for
                    # misaligned flushes
                    cut = (len(raws) // self.micro_batch_rows
                           ) * self.micro_batch_rows
                    self._pending_raw = raws[cut:]
                    self._pending_raw_ts = rtss[cut:]
                    raws, rtss = raws[:cut], rtss[:cut]
                if msgs:
                    jobs.append(("msgs", msgs, tss, self._plan))
                if raws:
                    jobs.append(("raw", raws, rtss, self._plan))
        n_rows = sum(len(job[1]) for job in jobs)
        if self.decode_pool_size <= 0:
            return jobs, n_rows
        # BOTH job kinds go through the ring when the pool is on, so a msg
        # batch can never overtake an earlier raw batch still decoding
        for i, job in enumerate(jobs):
            try:
                self._ensure_pool().submit(job)
            except RuntimeError:
                return jobs[i:], n_rows  # pool closed (shutdown race)
        return [], n_rows

    def _ensure_pool(self):
        from .ingest import DecodePool

        with self._pending_lock:
            if self._pool is None:
                self._pool = DecodePool(
                    self.decode_pool_size, self.ring_depth,
                    decode_fn=self._decode_job,
                    emit_fn=self._emit_decoded,
                    name=self.name,
                    prepare_fn=(self._prep_upload
                                if self.prep_ctx is not None else None),
                    stats=self.stats)
            return self._pool

    def _prep_upload(self, batch: ColumnBatch) -> None:
        """Upload stage (pool worker thread): precompute key slots + padded
        device inputs for the batch so the fused node's upload collapses to
        share-cache hits. Accrues to THIS node's `upload` stage — together
        with the fused node's (now residual) `upload` timing the pipeline
        balance stays observable per node."""
        with self.stats.stage("upload", batch.n) as st:
            # nothing registered to build: no row of the stage for it
            st.counted = bool(self.prep_ctx.precompute(batch, self.stats))

    def pool_depths(self):
        """(ring occupancy, decode queue depth) for the Prometheus gauges;
        None when no pool has started."""
        pool = self._pool
        if pool is None:
            return None
        return pool.in_flight, pool.queue_depth

    def resize_ingest(self, pool_size=None, ring_depth=None):
        """QoS auto-sizing hook (runtime/control.py): adjust the decode
        pool and/or ring of an already-pooled source. Returns the applied
        {pool_size, ring_depth}, or None for an inline source — the
        control plane never converts a decode_pool_size=0 source to
        pooled (that path is bit-for-bit deterministic by contract)."""
        if self.decode_pool_size <= 0:
            return None
        if pool_size is not None:
            self.decode_pool_size = max(1, int(pool_size))
            if self._pool is not None:
                self.decode_pool_size = self._pool.resize(
                    self.decode_pool_size)
        if ring_depth is not None:
            self.ring_depth = max(1, int(ring_depth))
            if self._pool is not None:
                self.ring_depth = self._pool.set_ring_depth(self.ring_depth)
        return {"pool_size": self.decode_pool_size,
                "ring_depth": self.ring_depth}

    def register_prep_spec(self, spec) -> None:
        """Plan-time upload-spec registration: (key_name, columns,
        micro_batch) from the planner, so the pool's upload stage serves
        from the FIRST batch instead of after the fused node's first fold
        (which also registers, covering un-plumbed paths)."""
        if self.prep_ctx is not None:
            self.prep_ctx.register_upload(*spec)

    def register_tier_prefetch(self, fn) -> None:
        """Tiered key state (ops/tierstore.py): wire the fused consumer's
        cold-tier prefetch into the pool's ordered upload stage."""
        if self.prep_ctx is not None:
            self.prep_ctx.register_tier_prefetch(fn)

    def _emit_decoded(self, batch: Optional[ColumnBatch]) -> None:
        if batch is not None and batch.n:
            self.emit(batch, count=batch.n)

    def _decode_job(self, job) -> Optional[ColumnBatch]:
        """One decode unit: ("raw", payloads, tss, plan) | ("msgs", msgs,
        tss, plan) -> ColumnBatch | None, decoded with the plan the
        hand-over took. Runs on pool workers — touches only immutable
        config, the converter, and the (locked) StatManager."""
        kind, items, tss, plan = job
        with self.stats.stage("decode", len(items)):
            if kind == "raw":
                batch = self._decode_raw_to_batch(items, tss, plan)
            else:
                batch = self._messages_to_batch(items, tss, plan)
        if batch is not None:
            batch.decoded = plan.project
        if batch is not None and batch.ingest_ms is None and tss:
            # e2e provenance: the batch speaks for its OLDEST row (arrival
            # order == tss order), so micro-batch linger and every later
            # pipeline stage count toward the recorded ingest→emit latency
            batch.ingest_ms = int(tss[0])
        if batch is not None and self.prep_ctx is not None \
                and batch.shared_ctx is None:
            # ride the prep ctx on the batch so downstream fused nodes
            # consume the shared encode/upload instead of redoing them
            batch.ensure_share_state()
            batch.shared_ctx = self.prep_ctx
        return batch

    def _messages_to_batch(self, msgs, tss,
                           plan: _DecodePlan) -> Optional[ColumnBatch]:
        from ..data.batch import from_messages

        batch, n_drop = from_messages(
            msgs, tss, schema=plan.schema, emitter=self.name,
            strict=self.strict, timestamp_field=self.timestamp_field,
            on_error=self.stats.inc_exception, project=plan.project)
        if n_drop:
            logger.debug("source %s dropped %d rows at columnarize",
                         self.name, n_drop)
        return batch

    def _decode_raw_to_batch(self, raws: List[bytes], rtss: List[int],
                             plan: _DecodePlan) -> Optional[ColumnBatch]:
        """Native columnar decode of buffered raw JSON payloads
        (io/fastjson.py); python fallback preserves row↔timestamp pairing."""
        import numpy as np

        from ..io.fastjson import decode_columns

        tally: Dict[str, int] = {}
        out = decode_columns(raws, plan.fast_spec,
                             shards=self._decode_shards, tally=tally)
        if tally:
            with self._pending_lock:
                for k, v in tally.items():
                    self.decode_tally[k] += v
        if out is None:
            msgs: List[Dict[str, Any]] = []
            tss: List[int] = []
            for p, t in zip(raws, rtss):
                try:
                    m = self.converter.decode(p)
                except Exception as exc:
                    self.stats.inc_exception(f"decode error: {exc}")
                    self.stats.inc_dropped("decode_error")
                    continue
                if isinstance(m, dict):
                    msgs.append(m)
                    tss.append(t)
                elif isinstance(m, list):
                    for x in m:
                        if isinstance(x, dict):
                            msgs.append(x)
                            tss.append(t)
            if not msgs:
                return None
            return self._messages_to_batch(msgs, tss, plan)
        cols, valid, bad = out
        keep = ~np.asarray(bad, dtype=np.bool_)
        n_bad = len(raws) - int(keep.sum())
        if n_bad:
            self.stats.inc_exception(
                "undecodable or uncastable payload", n=n_bad)
            self.stats.inc_dropped("decode_error", n=n_bad)
        ts = np.asarray(rtss, dtype=np.int64)
        if self.timestamp_field:
            vm = valid[self.timestamp_field]
            missing = keep & ~vm
            n_missing = int(missing.sum())
            if n_missing:
                self.stats.inc_exception(
                    f"missing timestamp field {self.timestamp_field}",
                    n=n_missing)
                keep &= vm
            ts = cols[self.timestamp_field]
        if not keep.any():
            return None
        all_keep = keep.all()
        columns = {k: (v if all_keep else v[keep]) for k, v in cols.items()}
        vout = {}
        for k, vm in valid.items():
            vs = vm if all_keep else vm[keep]
            if not vs.all():
                vout[k] = vs
        return ColumnBatch(
            n=int(keep.sum()), columns=columns, valid=vout,
            timestamps=(ts if all_keep else ts[keep]), emitter=self.name)

    def on_eof(self, eof: EOF) -> None:
        self._flush()
        self.broadcast(eof)

    def extra_pending(self) -> int:
        return self._pool.in_flight if self._pool is not None else 0

    def on_barrier(self, barrier) -> None:
        """Checkpoint barrier: flush pending rows and drain the decode
        ring BEFORE snapshotting the connector offset and forwarding. The
        offset already covers every ingested row, so any row still
        buffered here when the barrier passes would be downstream of the
        checkpoint cut yet behind the offset — lost on restore. A drain
        timeout therefore FAILS this checkpoint (no ack — a later barrier
        retries) while still forwarding the barrier so downstream
        aligners never stall, mirroring Node.on_barrier's snapshot-error
        path."""
        if not self._flush(final=True):
            self.stats.inc_exception(
                "decode ring drain timed out; checkpoint skipped")
            self.broadcast(barrier)
            return
        super().on_barrier(barrier)

    # source node's queue is only used for barriers/EOF injection
    def process(self, item: Any) -> None:
        self.ingest(item)
