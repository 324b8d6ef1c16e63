"""Columnar micro-batch — the TPU-native data representation.

The reference's experimental SliceTuple (internal/xsql/slice_tuple.go:25,
planner index assignment planner.go:88-165) replaces map rows with
index-addressed slices; this module completes that direction: runs of events
become a struct-of-arrays ColumnBatch whose numeric columns upload to device
HBM as jnp arrays, so window/aggregate kernels run vectorized on the VPU/MXU
instead of per-row interpreter walks (the hot loop at internal/xsql/valuer.go:289).

String columns stay host-side; GROUP BY keys are dictionary-encoded to int32
slot ids by the key table (ops/keytable.py) before device upload.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

import threading as _threading

from .rows import Tuple
from .types import DataType, Schema, np_dtype


@dataclass
class ColumnBatch:
    """Struct-of-arrays batch. All columns have equal length `n`.

    - numeric columns: np.float32 / np.int64 / np.bool_
    - host columns (strings, arrays, structs, schemaless): dtype=object
    - `valid[name]`: optional bool mask (absent = all valid)
    - `timestamps`: int64 ms (event time when configured, else ingest time)
    """

    n: int
    columns: Dict[str, np.ndarray] = field(default_factory=dict)
    valid: Dict[str, np.ndarray] = field(default_factory=dict)
    timestamps: Optional[np.ndarray] = None
    emitter: str = ""
    # shared-source fan-out: N consumers of the SAME batch share one key
    # encode and one device upload per column (see runtime/subtopo.py
    # SharedPrepCtx). `share()` memoizes per-batch; pruned copies made by
    # SharedEntryNode carry these references so all riders hit one cache.
    shared_ctx: Any = None
    share_state: Optional[Dict[Any, Any]] = None
    # ingest wall time (engine clock, ms) of the batch's oldest row —
    # stamped at the source, carried through every hop so emit/sink nodes
    # can record true ingest→emit latency (observability/histogram.py)
    ingest_ms: Optional[int] = None
    # the column set the source decoded this batch with (None: every
    # column the payload bore). A shared source decodes the union of what
    # its riders read (runtime/subtopo.py); a rider that joined after this
    # batch was decoded, and reads more, must not take it for its own
    decoded: Optional[frozenset] = None

    # unannotated -> a plain class attribute, not a dataclass field
    _SHARE_INIT_LOCK = _threading.Lock()

    def ensure_share_state(self) -> Dict[Any, Any]:
        state = self.share_state
        if state is None:
            with ColumnBatch._SHARE_INIT_LOCK:
                state = self.share_state
                if state is None:
                    state = self.share_state = {
                        "__lock__": _threading.RLock()}
        return state

    def __getstate__(self) -> dict:
        # the share cache (lock + device arrays) and subtopo ctx are
        # per-process ephemera — drop them so batches stay picklable
        # (sink-cache disk spill pickles parked items)
        state = self.__dict__.copy()
        state["shared_ctx"] = None
        state["share_state"] = None
        return state

    def share(self, key: Any, factory) -> Any:
        """Memoize `factory()` under `key` for every consumer of this batch
        (and its pruned copies). First caller computes; the per-batch lock
        makes concurrent consumers wait instead of duplicating the work."""
        state = self.ensure_share_state()
        with state["__lock__"]:
            if key not in state:
                state[key] = factory()
            return state[key]

    def __len__(self) -> int:
        return self.n

    def covers(self, columns) -> bool:
        """Whether the source decoded at least `columns` (None: all of
        them) for this batch."""
        return self.decoded is None or (
            columns is not None and columns <= self.decoded)

    def names(self) -> List[str]:
        return list(self.columns.keys())

    def column(self, name: str) -> np.ndarray:
        return self.columns[name]

    def key_column(self, name: str) -> np.ndarray:
        """Column `name` as a group key: an absent column is all null, and
        a typed column's null cells (valid mask false) read None — never
        the zero the decoder left in their place, which is a key of its
        own (bidder 0 is not "no bidder")."""
        col = self.columns.get(name)
        if col is None:
            return np.full(self.n, None, dtype=np.object_)
        vm = self.valid.get(name)
        if vm is not None and col.dtype != np.object_ and not vm.all():
            col = col.astype(np.object_)
            col[~vm] = None
        return col

    def is_valid(self, name: str) -> np.ndarray:
        v = self.valid.get(name)
        if v is None:
            return np.ones(self.n, dtype=np.bool_)
        return v

    def numeric_names(self) -> List[str]:
        return [k for k, v in self.columns.items() if v.dtype != np.object_]

    def select(self, mask: np.ndarray) -> "ColumnBatch":
        idx = np.nonzero(mask)[0]
        return self.take(idx)

    def take(self, idx: np.ndarray) -> "ColumnBatch":
        return ColumnBatch(
            n=len(idx),
            columns={k: v[idx] for k, v in self.columns.items()},
            valid={k: v[idx] for k, v in self.valid.items()},
            timestamps=None if self.timestamps is None else self.timestamps[idx],
            emitter=self.emitter,
            ingest_ms=self.ingest_ms,
            decoded=self.decoded,
        )

    def to_messages(self) -> List[Dict[str, Any]]:
        """One plain dict per row, keys in `names()` order — the repo's one
        ColumnBatch → Python conversion, done per COLUMN: `tolist()` yields
        the values `.item()` would per element, rows come from `zip(*cols)`.
        A cell whose `valid` mask is false has its key omitted from the row.
        Reads no timestamps and builds no Tuple."""
        names = self.names()
        if not names:
            return [{} for _ in range(self.n)]
        cols = []
        for name in names:
            col = self.columns[name]
            vals = col.tolist()
            if col.dtype == np.object_ and any(
                    issubclass(t, np.generic) for t in set(map(type, vals))):
                # tolist() hands an object column's elements back as they are
                vals = [v.item() if isinstance(v, np.generic) else v
                        for v in vals]
            cols.append(vals)
        out = [dict(zip(names, vals)) for vals in zip(*cols)]
        for name, v in self.valid.items():
            if name in self.columns and not v.all():
                for i in np.nonzero(~v)[0].tolist():
                    del out[i][name]
        return out

    def to_tuples(self) -> List[Tuple]:
        """Back to row objects (interpreter path)."""
        emitter = self.emitter
        if self.timestamps is None:
            tss = [0] * self.n
        else:
            tss = np.asarray(self.timestamps, dtype=np.int64).tolist()
        return [Tuple(emitter=emitter, message=m, timestamp=t)
                for m, t in zip(self.to_messages(), tss)]

    @staticmethod
    def concat(batches: Sequence["ColumnBatch"]) -> "ColumnBatch":
        batches = [b for b in batches if b.n > 0]
        if not batches:
            return ColumnBatch(n=0)
        if len(batches) == 1:
            return batches[0]
        names: List[str] = []
        for b in batches:
            for k in b.columns:
                if k not in names:
                    names.append(k)
        n_total = sum(b.n for b in batches)
        columns: Dict[str, np.ndarray] = {}
        valid: Dict[str, np.ndarray] = {}
        for name in names:
            parts, vparts, need_valid = [], [], False
            for b in batches:
                col = b.columns.get(name)
                if col is None:
                    dtype = np.object_
                    for ob in batches:
                        if name in ob.columns:
                            dtype = ob.columns[name].dtype
                            break
                    col = np.zeros(b.n, dtype=dtype)
                    vp = np.zeros(b.n, dtype=np.bool_)
                    need_valid = True
                else:
                    vp = b.valid.get(name)
                    if vp is None:
                        vp = np.ones(b.n, dtype=np.bool_)
                    else:
                        need_valid = need_valid or not vp.all()
                parts.append(col)
                vparts.append(vp)
            columns[name] = np.concatenate(parts)
            if need_valid:
                valid[name] = np.concatenate(vparts)
        ts = None
        if all(b.timestamps is not None for b in batches):
            ts = np.concatenate([b.timestamps for b in batches])
        ings = [b.ingest_ms for b in batches if b.ingest_ms is not None]
        return ColumnBatch(
            n=n_total, columns=columns, valid=valid, timestamps=ts,
            emitter=batches[0].emitter,
            ingest_ms=min(ings) if ings else None,
        )


def from_tuples(
    tuples: Sequence[Tuple], schema: Optional[Schema] = None, emitter: str = ""
) -> ColumnBatch:
    """Columnarize a run of rows. With a schema, columns get typed numpy
    dtypes; schemaless columns are inferred from observed python types
    (promoted to object on conflict)."""
    n = len(tuples)
    if n == 0:
        return ColumnBatch(n=0, emitter=emitter)

    names: List[str] = []
    declared: Dict[str, Any] = {}
    if schema is not None and not schema.schemaless:
        for f in schema.fields:
            names.append(f.name)
            declared[f.name] = np_dtype(f.type)
    else:
        seen = set()
        for t in tuples:
            for k in t.message:
                if k not in seen:
                    seen.add(k)
                    names.append(k)

    columns: Dict[str, np.ndarray] = {}
    valid: Dict[str, np.ndarray] = {}
    for name in names:
        raw = [t.message.get(name) for t in tuples]
        mask = np.array([r is not None for r in raw], dtype=np.bool_)
        dtype = declared.get(name)
        if dtype is None:
            dtype = _infer_dtype(raw, mask)
        if dtype == np.object_:
            col = np.empty(n, dtype=np.object_)
            col[:] = raw
        else:
            col = np.zeros(n, dtype=dtype)
            if mask.all():
                try:
                    col[:] = raw
                except (ValueError, TypeError, OverflowError):
                    col = np.empty(n, dtype=np.object_)
                    col[:] = raw
                    dtype = np.object_
            else:
                for i, r in enumerate(raw):
                    if mask[i]:
                        try:
                            col[i] = r
                        except (ValueError, TypeError, OverflowError):
                            mask[i] = False
                if dtype == np.float32:
                    col[~mask] = np.nan
        columns[name] = col
        if not mask.all():
            valid[name] = mask

    ts = np.fromiter((t.timestamp for t in tuples), dtype=np.int64, count=n)
    return ColumnBatch(n=n, columns=columns, valid=valid, timestamps=ts, emitter=emitter)


def from_messages(
    msgs: List[Dict[str, Any]],
    tss: List[int],
    schema: Optional[Schema] = None,
    emitter: str = "",
    strict: str = "convert_all",
    timestamp_field: str = "",
    on_error=None,
    project: Optional[set] = None,
):
    """Columnarize decoded messages DIRECTLY — no per-row Tuple objects, no
    per-row preprocessor. This is the vectorized twin of SourceNode's
    ingest→preprocess→from_tuples chain (reference: per-tuple decode_op +
    preprocessor.Apply, internal/topo/operator/preprocessor.go): schema
    coercion runs per COLUMN (bulk numpy assignment when a C-speed type scan
    proves the payload conforms; per-value cast.to_typed fallback otherwise)
    and event-time extraction is one vectorized pass.

    Returns (ColumnBatch, n_dropped). Rows whose cast or timestamp fails
    drop, mirroring the row-path contract; on_error(msg, n) reports them.
    """
    from . import cast as _cast
    from .types import DataType

    n = len(msgs)
    if n == 0:
        return ColumnBatch(n=0, emitter=emitter), 0
    bad = np.zeros(n, dtype=np.bool_)
    columns: Dict[str, np.ndarray] = {}
    valid: Dict[str, np.ndarray] = {}
    if schema is not None and not schema.schemaless:
        for f in schema.fields:
            raw = [m.get(f.name) for m in msgs]
            mask = np.fromiter(
                (r is not None for r in raw), dtype=np.bool_, count=n)
            col = None
            if f.type == DataType.BIGINT:
                if all(r is None or type(r) is int for r in raw):
                    col = np.zeros(n, dtype=np.int64)
            elif f.type == DataType.FLOAT:
                if all(r is None or type(r) in (int, float) for r in raw):
                    col = np.zeros(n, dtype=np.float32)
            elif f.type == DataType.BOOLEAN:
                if all(r is None or type(r) is bool for r in raw):
                    col = np.zeros(n, dtype=np.bool_)
            elif f.type == DataType.STRING:
                if all(r is None or type(r) is str for r in raw):
                    col = np.empty(n, dtype=np.object_)
                    col[:] = raw
            if col is not None and col.dtype != np.object_:
                try:
                    if mask.all():
                        col[:] = raw
                    else:
                        idx = np.nonzero(mask)[0]
                        col[idx] = [raw[i] for i in idx.tolist()]
                        if col.dtype == np.float32:
                            col[~mask] = np.nan
                except (ValueError, TypeError, OverflowError):
                    col = None  # e.g. ints beyond int64 — cast fallback
            if col is None:
                # non-conforming payload (strings-as-numbers, datetimes,
                # arrays/structs): per-value cast, same rules as the row path
                col = np.empty(n, dtype=np.object_)
                for i, r in enumerate(raw):
                    if r is None:
                        continue
                    try:
                        col[i] = _cast.to_typed(r, f, strict)
                    except _cast.CastError as exc:
                        bad[i] = True
                        if on_error is not None:
                            on_error(str(exc), 1)
                tgt = np_dtype(f.type)
                if tgt != np.object_:
                    # retighten to the declared dtype when every good row
                    # coerced cleanly (device-eligible upload path)
                    good = mask & ~bad
                    tight = np.zeros(n, dtype=tgt)
                    try:
                        idx = np.nonzero(good)[0]
                        tight[idx] = [col[i] for i in idx.tolist()]
                        if tgt == np.float32:
                            tight[~good] = np.nan
                        col = tight
                    except (ValueError, TypeError, OverflowError):
                        pass
            columns[f.name] = col
            if not mask.all():
                valid[f.name] = mask & ~bad
    else:
        names: List[str] = []
        seen = set()
        for m in msgs:
            for k in m:
                if k not in seen:
                    seen.add(k)
                    if project is None or k in project:
                        names.append(k)
        for name in names:
            raw = [m.get(name) for m in msgs]
            mask = np.fromiter(
                (r is not None for r in raw), dtype=np.bool_, count=n)
            dtype = _infer_dtype(raw, mask)
            if dtype == np.object_:
                col = np.empty(n, dtype=np.object_)
                col[:] = raw
            else:
                col = np.zeros(n, dtype=dtype)
                if mask.all():
                    col[:] = raw
                else:
                    idx = np.nonzero(mask)[0]
                    col[idx] = [raw[i] for i in idx.tolist()]
                    if dtype == np.float32:
                        col[~mask] = np.nan
            columns[name] = col
            if not mask.all():
                valid[name] = mask
    ts = np.asarray(tss, dtype=np.int64)
    if timestamp_field:
        raw = columns.get(timestamp_field)
        if raw is not None and raw.dtype == np.int64 \
                and timestamp_field not in valid and not bad.any():
            # int64 column (BIGINT/DATETIME): exact epoch-ms passthrough.
            # Other shapes take the per-value path over the RAW message
            # values (a float32 column can't hold epoch ms exactly).
            ts = raw
        else:
            vm = valid.get(timestamp_field)
            ts = ts.copy()
            for i, m in enumerate(msgs):
                if bad[i]:
                    continue
                r = m.get(timestamp_field)
                if r is None or (vm is not None and not vm[i]):
                    bad[i] = True
                    if on_error is not None:
                        on_error(
                            f"missing timestamp field {timestamp_field}", 1)
                    continue
                try:
                    ts[i] = _cast.to_datetime_ms(r)
                except (_cast.CastError, ValueError, TypeError) as exc:
                    bad[i] = True
                    if on_error is not None:
                        on_error(str(exc), 1)
    n_drop = int(bad.sum())
    cb = ColumnBatch(n=n, columns=columns, valid=valid, timestamps=ts,
                     emitter=emitter)
    if n_drop:
        cb = cb.select(~bad)
    return cb, n_drop


def _infer_dtype(raw: List[Any], mask: np.ndarray):
    saw_float = saw_int = saw_bool = saw_other = False
    for r, ok in zip(raw, mask):
        if not ok:
            continue
        if isinstance(r, bool):
            saw_bool = True
        elif isinstance(r, int):
            saw_int = True
        elif isinstance(r, float):
            saw_float = True
        else:
            saw_other = True
    if saw_other:
        return np.object_
    if saw_bool and (saw_int or saw_float):
        # don't silently coerce True/False into 1/1.0 — keep originals
        return np.object_
    if saw_float:
        return np.float32
    if saw_int:
        return np.int64
    if saw_bool:
        return np.bool_
    return np.object_
