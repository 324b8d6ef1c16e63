"""Aggregate kernel specs — which aggregates of a SELECT can fuse into the
device group-by kernel, and what partial-state components each needs.

The planner extracts AggSpecs from the statement (the incremental-agg rewrite,
reference: planner.go:910-999 rewriteIfIncAggStmt); device-eligible aggregates
fold into (n, s1, s2, mn, mx) partials — the same (count, sum, sum-of-squares,
min, max) triple-plus layout funcs_inc_agg.py uses, so cross-shard merges are
plain adds/mins/maxes.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field, replace as _dc_replace
from typing import Any, Dict, List, Optional, Set, Tuple

import numpy as np

from ..sql import ast, expr_ir
from ..sql.compiler import CompiledExpr
from ..sql.expr_ir import NotVectorizable
from .sketches import HH_MAX_CODES

# aggregate name -> components needed by finalize
DEVICE_AGGS: Dict[str, Set[str]] = {
    "count": {"n"},
    "sum": {"n", "s1"},
    "avg": {"n", "s1"},
    "min": {"mn", "n"},
    "max": {"mx", "n"},
    "stddev": {"n", "s1", "s2"},
    "stddevs": {"n", "s1", "s2"},
    "var": {"n", "s1", "s2"},
    "vars": {"n", "s1", "s2"},
    # inc_ forms share the same partials
    "inc_count": {"n"},
    "inc_sum": {"n", "s1"},
    "inc_avg": {"n", "s1"},
    "inc_min": {"mn", "n"},
    "inc_max": {"mx", "n"},
    "inc_stddev": {"n", "s1", "s2"},
    "inc_stddevs": {"n", "s1", "s2"},
    # sketch aggregates (north-star UDFs) — wide device components
    "hll": {"hll"},
    "distinct_count_approx": {"hll"},
    "percentile_approx": {"hist"},
    "heavy_hitters": {"hh"},
}

# sum/avg/min/max over a column declared BIGINT keep exact int64 partials
# instead of (s1, mn, mx): the sum as four 16-bit limbs in uint32 words
# (carried after every fold, so exact over int64's range and wrapping as
# Go's int64 does), the extremes as a lexicographic (hi int32, lo int32
# with the sign bit flipped) pair — TPU has no native int64
EXACT_INT_AGGS: Dict[str, Set[str]] = {
    "sum": {"n", "i1"},
    "avg": {"n", "i1"},
    "min": {"n", "imn"},
    "max": {"n", "imx"},
}

#: exact order statistics (eKuiper's median / percentile_cont /
#: percentile_disc): no mergeable per-key partial, but a per-pane row
#: buffer of (slot, value) that the boundary sorts on the device
#: (ops/orderstat.py); a spec of these kinds has no component
ORDERSTAT_AGGS = frozenset({"median", "percentile_cont", "percentile_disc"})

ALL_COMPONENTS = ("n", "s1", "s2", "mn", "mx")
#: the exact-integer components, with a word axis (4 for i1, 2 for imn /
#: imx): leading in the device state (groupby.state_shape), trailing in
#: the per-key shape of the stacked components
LIMB_COMPONENTS = {"i1", "imn", "imx"}
# components with a trailing register axis (capacity, k, R)
WIDE_COMPONENTS = {"hll", "hist", "hh"} | LIMB_COMPONENTS

# Derived-column prefix: hll over a bare column reads a dedicated hashed
# copy (strings crc32-hashed, numerics passed through) so the raw column
# stays numeric for every other spec / WHERE / FILTER sharing it.
HLL_COL_PREFIX = "__hll__"

# Derived-column prefix for heavy_hitters: the raw column dictionary-encodes
# to dense integer codes (< sketches.HH_MAX_CODES) that the bit-recovery
# sketch can reconstruct; codes decode back to the original values at emit.
HH_COL_PREFIX = "__hhc__"

# Derived-column prefix for the exact-integer aggregates: the raw BIGINT
# column as int64 on the host (shadow, staging), shipped to the device as
# its two uint32 words — the raw column stays float32 for every FILTER /
# WHERE / float spec sharing it
I64_COL_PREFIX = "__i64__"

#: magnitudes float32 holds exactly: a BIGINT compared with a constant at
#: or past this cannot be evaluated on the float32 device column
F32_EXACT = 2 ** 24


# values below this are exactly representable in float32 and pass through;
# larger integral values hash their decimal repr so the float32 cast cannot
# collapse distinct IDs (e.g. ~1e9-range device ids differing in low bits)
_HLL_SMALL = 2 ** 24


def _hll_encode_value(v) -> float:
    """Distinct-preserving float32 encoding of one value for hll. The SAME
    rule applies whether the value arrives in an object, integer, or float
    batch, so a logical value always folds to the same register."""
    import zlib

    if isinstance(v, bool):
        return float(v)
    if isinstance(v, (int, np.integer)):
        iv = int(v)
        if abs(iv) < _HLL_SMALL:
            return float(iv)
        return float(zlib.crc32(str(iv).encode()))
    if isinstance(v, (float, np.floating)):
        fv = float(v)
        if np.isfinite(fv) and fv.is_integer() and abs(fv) >= _HLL_SMALL:
            return float(zlib.crc32(str(int(fv)).encode()))
        return fv
    return float(zlib.crc32(str(v).encode()))


def hash_column_for_hll(col) -> "np.ndarray":
    """Distinct-preserving stable encoding of a mixed/object column into
    float32 for hll (see _hll_encode_value). crc32 is stable across
    processes so checkpointed registers stay consistent after restore.
    None -> NaN (masked, matching SQL null-skipping aggregates)."""
    out = np.empty(len(col), dtype=np.float32)
    memo: dict = {}
    for i, v in enumerate(col):
        if v is None:
            out[i] = np.nan
            continue
        try:
            h = memo.get(v)
        except TypeError:  # unhashable (dict/list)
            out[i] = _hll_encode_value(v)
            continue
        if h is None:
            h = _hll_encode_value(v)
            memo[v] = h
        out[i] = h
    return out


def _hll_encode_numeric(raw: "np.ndarray") -> "np.ndarray":
    """Vectorized hll encoding of a numeric-dtype column: float32 passthrough
    with the (rare) large integral values deferred to _hll_encode_value so
    the result matches the object-column path exactly."""
    if np.issubdtype(raw.dtype, np.integer):
        arr = raw.astype(np.int64)
        out = arr.astype(np.float32)
        big = np.abs(arr) >= _HLL_SMALL
        for i in np.nonzero(big)[0]:
            out[i] = _hll_encode_value(int(arr[i]))
        return out
    f = np.asarray(raw, dtype=np.float64)
    out = f.astype(np.float32)
    with np.errstate(invalid="ignore"):
        big = np.isfinite(f) & (np.abs(f) >= _HLL_SMALL) & (f == np.floor(f))
    for i in np.nonzero(big)[0]:
        out[i] = _hll_encode_value(float(f[i]))
    return out


# A table of known integer values is indexed by `value - lowest` while that
# takes fewer than this many slots a known value, or fewer than the floor
# (16 KB of float32); wider than that, the sorted values are searched.
_DENSE_SLOTS_PER_VALUE = 8
_DENSE_FLOOR = 4096
_INT64_MIN = -(1 << 63)


def _table_class(dtype) -> Optional[type]:
    """The dtype a column's values are looked up in — every integer but
    uint64 is exact in int64, float16/32 in float64 — or None for a column
    no table serves (strings and objects, uint64, longdouble, complex)."""
    if dtype.kind in "bi" or (dtype.kind == "u" and dtype.itemsize < 8):
        return np.int64
    if dtype.kind == "f" and dtype.itemsize <= 8:
        return np.float64
    return None


class _CodeTable:
    """The values of one dtype class that `ValueDict` has resolved, sorted,
    beside their codes (float32, what the fold uploads). `lookup` answers a
    whole column in a few array calls — each one hands the interpreter lock
    back once, which is what a micro-batch's encode costs on a busy host."""

    __slots__ = ("keys", "codes", "dense", "base")

    def __init__(self, keys: "np.ndarray", codes: "np.ndarray") -> None:
        self.keys = keys
        self.codes = codes
        self.dense = None
        if keys.dtype != np.int64:
            return
        lo, hi = int(keys[0]), int(keys[-1])
        limit = max(_DENSE_FLOOR, _DENSE_SLOTS_PER_VALUE * len(keys))
        # low positive values index the table themselves, which saves the
        # subtraction; otherwise slot 1 is the lowest value
        base = 0 if lo > 0 and hi + 2 <= limit else lo - 1
        if hi - base + 2 <= limit and base >= _INT64_MIN:
            # slot 0 and the last slot stay -1: take(mode="clip") lands
            # every value outside [lo, hi] on one of them
            self.base = base
            self.dense = np.full(hi - base + 2, -1, dtype=np.float32)
            self.dense[keys - base] = codes

    def merged(self, keys: "np.ndarray", codes: "np.ndarray") -> "_CodeTable":
        """This table and the sorted `keys`, none of which it holds."""
        pos = np.searchsorted(self.keys, keys)
        return _CodeTable(np.insert(self.keys, pos, keys),
                          np.insert(self.codes, pos, codes))

    def lookup(self, arr: "np.ndarray") -> "np.ndarray":
        """float32 codes of `arr`; -1 where the table lacks the value."""
        if self.dense is not None:
            return np.take(self.dense, arr - self.base if self.base else arr,
                           mode="clip")
        pos = np.searchsorted(self.keys, arr)
        found = np.take(self.keys, pos, mode="clip") == arr
        return np.where(found, np.take(self.codes, pos, mode="clip"),
                        np.float32(-1))


class ValueDict:
    """Reversible dictionary encoding for a heavy_hitters column: values map
    to dense integer codes (< sketches.HH_MAX_CODES) that fit the sketch's
    bit recovery; codes decode back to the ORIGINAL values (any type,
    strings included) at emit. Codes only grow, so they stay stable across
    the window, across panes, and across checkpoint restore (the fused node
    persists the value list). Values past the code budget encode as NaN
    (masked — invisible to the sketch); heavy hitters by definition appear
    early and often, so they claim low codes long before overflow.

    `_ids`/`_values` are the dictionary. A numeric column is answered from
    `_tables` — per dtype class, the values resolved so far — and Python
    runs only for the rows no table knows (`lookup`, then `learn`)."""

    def __init__(self) -> None:
        self._ids: Dict[Any, int] = {}
        self._values: List[Any] = []
        self._tables: Dict[type, _CodeTable] = {}
        # the values and one None, for `decode_array`; grown when they have
        self._decode_table = np.array([None], dtype=np.object_)
        self.overflowed = False

    def _code(self, v) -> float:
        ids = self._ids
        c = ids.get(v)
        if c is None:
            if len(self._values) >= HH_MAX_CODES:
                self.overflowed = True
                return np.nan
            c = len(self._values)
            ids[v] = c
            self._values.append(v)
        return float(c)

    def encode(self, col: "np.ndarray") -> "np.ndarray":
        """Column -> float32 codes (NaN for None/NaN/overflow)."""
        codes, missed = self.lookup(col)
        if missed is not None:
            self.learn(col, codes, missed)
        return codes

    def lookup(self, col: "np.ndarray"
               ) -> Tuple["np.ndarray", Optional["np.ndarray"]]:
        """The codes of `col` as far as a table knows them, and the indices
        of the rows left for `learn` (NaN so far) — None when there are
        none, which is every micro-batch of a stream whose values have all
        been seen."""
        n = len(col)
        cls = _table_class(col.dtype)
        table = self._tables.get(cls)
        if table is None:
            codes = np.full(n, np.nan, dtype=np.float32)
            missed = np.arange(n)
        else:
            codes = table.lookup(col.astype(cls, copy=False))
            if n == 0 or codes.min() >= 0:
                return codes, None
            missed = np.flatnonzero(codes < 0)
            codes[missed] = np.nan
        if col.dtype.kind in "fc":  # NaN is no value: it stays NaN
            vals = col[missed]
            missed = missed[vals == vals]
        return codes, (missed if len(missed) else None)

    def learn(self, col: "np.ndarray", codes: "np.ndarray",
              missed: "np.ndarray") -> None:
        """Resolve rows `missed` of `col` through the dictionary into
        `codes`: first-seen values take the next codes in sorted order, and
        what was resolved joins the column's table."""
        if col.dtype == np.object_:
            for i, v in enumerate(col.tolist()):
                if v is None:
                    continue
                try:
                    codes[i] = self._code(v)
                except TypeError:  # unhashable (list/dict): stringify
                    codes[i] = self._code(repr(v))
            return
        uniq, inverse = np.unique(col[missed], return_inverse=True)
        ucodes = np.array(
            [self._code(u.item()) for u in uniq], dtype=np.float32
        )
        codes[missed] = ucodes[inverse]
        cls = _table_class(col.dtype)
        coded = ucodes == ucodes  # past the budget: NaN, and in no table
        if cls is None or not coded.any():
            return
        keys, ucodes = uniq[coded].astype(cls, copy=False), ucodes[coded]
        table = self._tables.get(cls)
        self._tables[cls] = (_CodeTable(keys, ucodes) if table is None
                             else table.merged(keys, ucodes))

    def decode(self, code: int):
        return self._values[code] if 0 <= code < len(self._values) else None

    def decode_array(self, codes: "np.ndarray") -> "np.ndarray":
        """`decode` over an integer array at once: the values themselves in
        an object array, None for a code outside the dictionary."""
        table = self._decode_table
        values = self._values
        # read once: the fused worker appends while the emit worker decodes,
        # and codes never change their value, so a table is stale only by
        # being short
        n = len(values)
        if len(table) != n + 1:
            known = len(table) - 1
            table = np.concatenate([table[:known], np.fromiter(
                values[known:n], dtype=np.object_, count=n - known), [None]])
            self._decode_table = table
        return table[np.where((codes >= 0) & (codes < n), codes, n)]

    def snapshot(self) -> List[Any]:
        return list(self._values)

    def restore(self, values: List[Any]) -> None:
        self._values = list(values)
        self._ids = {}
        self._tables = {}  # refilled by the batches that follow
        self._decode_table = np.array([None], dtype=np.object_)
        for i, v in enumerate(self._values):
            try:
                self._ids[v] = i
            except TypeError:
                pass  # unhashable snapshot value (encode stored repr anyway)


def materialize_hll_columns(plan_columns, cols: Dict[str, "np.ndarray"], n: int):
    """Fill in any missing __hll__<col> derived columns from the raw column.
    Returns a new dict when a derivation was needed; callers that already
    materialized them (nodes_fused, with validity masks) pass through."""
    out = None
    for name in plan_columns:
        if not name.startswith(HLL_COL_PREFIX) or name in cols:
            continue
        if out is None:
            out = dict(cols)
        raw = cols.get(name[len(HLL_COL_PREFIX):])
        if raw is None:
            out[name] = np.full(n, np.nan, dtype=np.float32)
        elif getattr(raw, "dtype", None) == np.object_:
            out[name] = hash_column_for_hll(raw)
        else:
            out[name] = _hll_encode_numeric(np.asarray(raw))
    return out if out is not None else cols


@dataclass
class AggSpec:
    """One device-foldable aggregate call."""

    call: ast.Call
    kind: str  # count/sum/avg/min/max/stddev/.../hll/percentile_approx
    components: Set[str]
    arg: Optional[CompiledExpr]  # device closure for the argument (None = count(*))
    filter: Optional[CompiledExpr]  # FILTER(WHERE ...) device closure
    int_input: bool = False  # observed integer input → integer avg/sum results
    #: exact int64 partials (EXACT_INT_AGGS), chosen at plan time from the
    #: stream's declared BIGINT type — fixes the state's dtypes before the
    #: first fold
    exact: bool = False
    #: why a sum/avg/min/max over a number stays on the float32 path (the
    #: `reason` of kuiper_agg_int_fallback_total when integer rows past
    #: 2^24 reach it); "" for every other spec
    float_reason: str = ""
    frac: float = 0.5  # percentile_approx / order-statistic fraction
    topk: int = 3  # heavy_hitters k (2nd literal arg)
    #: order statistics (ORDERSTAT_AGGS): the row buffer this spec reads —
    #: specs of one argument and FILTER share one (KernelPlan.os_bufs);
    #: -1 for every other spec
    os_buf: int = -1
    # numpy twins of arg/filter, used by the latency-hiding tail shadow
    # (ops/prefinalize.py); None when the expr only compiles for device
    arg_host: Optional[CompiledExpr] = None
    filter_host: Optional[CompiledExpr] = None

    @property
    def is_star(self) -> bool:
        return self.arg is None


@dataclass
class KernelPlan:
    """Everything the fused window→aggregate device kernel needs."""

    specs: List[AggSpec]
    filter: Optional[CompiledExpr]  # WHERE clause (device)
    columns: Set[str] = field(default_factory=set)  # numeric columns to upload
    filter_host: Optional[CompiledExpr] = None  # numpy twin of `filter`
    #: per-kernel-column upload dtype ("float32" default; "int32" for the
    #: expression IR's dictionary-code / rebased-ts32 derived columns) —
    #: consumed by the fold upload (ops/groupby.py) and the jitcert fold
    #: derivations (bounded signature families include the dtype)
    col_dtypes: Dict[str, str] = field(default_factory=dict)
    #: expression-IR derived columns (sql/expr_ir.py DerivedCol): host
    #: prep producing the __sd_*/__ts32_* device columns
    derived: Tuple[Any, ...] = ()
    #: stable hash of every compiled expression's IR — part of the
    #: ingest-prep upload share keys (runtime/ingest.py), so two plans
    #: whose expressions differ can never alias a pre-uploaded column
    expr_tag: str = ""
    #: predicate lifting (planner/sharing.py): index of the synthetic
    #: `count(*) FILTER(WHERE <rule predicate>)` activity spec a lifted
    #: member reads its group-existence from (None = the global `act`)
    act_idx: Optional[int] = None
    #: order-statistic row buffers: one per distinct (argument, FILTER)
    #: of the plan's ORDERSTAT_AGGS specs, in first-use order
    os_bufs: Tuple[str, ...] = ()

    @property
    def has_exact(self) -> bool:
        """Some spec keeps exact int64 partials (EXACT_INT_AGGS)."""
        return any(s.exact for s in self.specs)

    @property
    def has_orderstat(self) -> bool:
        """Some spec is an exact order statistic (ORDERSTAT_AGGS)."""
        return bool(self.os_bufs)

    @property
    def host_foldable(self) -> bool:
        """True when every closure has a numpy twin, so a tail of rows can be
        folded on host by the pre-finalize emit pipeline. An order
        statistic never is: its rows live in device buffers the host
        shadow does not mirror."""
        if self.has_orderstat:
            return False
        if self.filter is not None and self.filter_host is None:
            return False
        for s in self.specs:
            if s.arg is not None and s.arg_host is None:
                return False
            if s.filter is not None and s.filter_host is None:
                return False
        return True


_tl = threading.local()


def take_expr_fallbacks() -> List[Dict[str, str]]:
    """Structured NotVectorizable reasons recorded by the LAST
    extract_kernel_plan call on this thread (cleared on read) — the
    planner turns them into `kuiper_expr_host_fallback_total` samples
    and the explain "expressions" section."""
    out = getattr(_tl, "expr_fallbacks", [])
    _tl.expr_fallbacks = []
    return out


def _note_fallback(kind: str, expr: Optional[ast.Expr],
                   exc: NotVectorizable) -> None:
    notes = getattr(_tl, "expr_fallbacks", None)
    if notes is None:
        notes = _tl.expr_fallbacks = []
    notes.append({"kind": kind,
                  "expr": _expr_key(expr) if expr is not None else "",
                  "reason": getattr(exc, "reason", "other"),
                  "detail": str(exc)})


def _compile_device(expr: ast.Expr, want: str, kind: str,
                    anchor_ms: int, str_seed=None
                    ) -> Optional[expr_ir.CompiledIR]:
    """Device-compile one expression via the IR; a failure records the
    structured reason (the whole rule then takes the host path)."""
    try:
        return expr_ir.compile_expr_ir(expr, mode="device", want=want,
                                       anchor_ms=anchor_ms,
                                       str_seed=str_seed)
    except NotVectorizable as exc:
        _note_fallback(kind, expr, exc)
        return None


_COMPARE_OPS = {"=", "!=", "<", "<=", ">", ">="}


def _big_literal(e: ast.Expr) -> bool:
    return (isinstance(e, (ast.IntegerLiteral, ast.NumberLiteral))
            and abs(e.val) >= F32_EXACT)


def _bigint_compare(expr: ast.Expr, int_columns: Set[str]) -> Optional[str]:
    """The first comparison in `expr` of a BIGINT column with a constant
    float32 cannot hold (|c| >= 2^24), or None: on the float32 device
    column such a predicate is not exact."""
    def is_int(e: ast.Expr) -> bool:
        return isinstance(e, ast.FieldRef) and e.name in int_columns

    for node in ast.walk(expr):
        if isinstance(node, ast.BinaryExpr) and node.op in _COMPARE_OPS:
            pairs = [(node.lhs, node.rhs), (node.rhs, node.lhs)]
        elif isinstance(node, ast.BetweenExpr):
            pairs = [(node.value, node.lo), (node.value, node.hi)]
        elif isinstance(node, ast.InExpr):
            pairs = [(node.value, v) for v in node.values]
        else:
            continue
        for col, const in pairs:
            if is_int(col) and _big_literal(const):
                return _expr_key(node)
    return None


def _float_reason(arg: ast.Expr, has_hh: bool) -> str:
    """Why a sum/avg/min/max over `arg` is not given exact partials, or
    an order statistic over it reads float32 values."""
    if has_hh:
        return "heavy_hitters"
    if isinstance(arg, ast.FieldRef):
        return "not_declared_bigint"
    return "expression"


def extract_kernel_plan(
    stmt: ast.SelectStatement, where_on_device: bool = True,
    int_columns: Optional[Set[str]] = None,
) -> Optional[KernelPlan]:
    """Try to build a fully-fused device plan for the statement's aggregates.

    Returns None if any aggregate (or its argument expression) is not
    device-eligible — the planner then uses the host window path.
    `int_columns` names the stream's columns declared BIGINT: sum / avg /
    min / max of a bare such column take exact int64 partials
    (EXACT_INT_AGGS), and a device predicate comparing one with a constant
    past 2^24 declines the plan (reason `bigint_const`).
    """
    _tl.expr_fallbacks = []
    calls = _collect_agg_calls(stmt)
    if not calls:
        return None
    int_columns = set(int_columns or ())
    has_hh = any(c.name == "heavy_hitters" for c in calls)
    if int_columns:
        pieces = [("where", stmt.condition)] if (
            stmt.condition is not None and where_on_device) else []
        for c in calls:
            if c.filter is not None:
                pieces.append((f"agg-filter:{c.name}", c.filter))
            if c.args and not isinstance(c.args[0], ast.Wildcard):
                pieces.append((f"agg-arg:{c.name}", c.args[0]))
        for kind, expr in pieces:
            found = _bigint_compare(expr, int_columns)
            if found is not None:
                _note_fallback(kind, expr, NotVectorizable(
                    f"{found}: a BIGINT compared with a constant past 2^24 "
                    "is not exact on the float32 device column",
                    reason="bigint_const"))
                return None
    # one temporal anchor per plan: every ts32 derivation and rebased
    # literal of this rule shares it (and the IR hashes reflect it)
    anchor_ms = expr_ir.plan_anchor_ms()
    # plan-level string-dictionary seed: union the string constants of
    # every compilable piece, so WHERE + agg args + FILTERs derive ONE
    # __sd_* column per raw column (one host encode, one upload)
    str_seed: Dict[str, Set[str]] = {}
    seed_roots: List[ast.Expr] = []
    if stmt.condition is not None and where_on_device:
        seed_roots.append(stmt.condition)
    for c in calls:
        if c.args and not isinstance(c.args[0], ast.Wildcard):
            seed_roots.append(c.args[0])
        if c.filter is not None:
            seed_roots.append(c.filter)
    for root in seed_roots:
        for col, vals in expr_ir.collect_str_consts(root).items():
            str_seed.setdefault(col, set()).update(vals)
    col_dtypes: Dict[str, str] = {}
    derived: Dict[str, Any] = {}
    ir_keys: List[str] = []

    def absorb(ce: expr_ir.CompiledIR) -> None:
        col_dtypes.update(ce.col_dtypes)
        for d in ce.derived:
            derived[d.name] = d
        ir_keys.append(ce.ir_key)

    specs: List[AggSpec] = []
    columns: Set[str] = set()
    for call in calls:
        kind = call.name[4:] if call.name.startswith("inc_") else call.name
        if call.name not in DEVICE_AGGS and call.name not in ORDERSTAT_AGGS:
            return None
        if call.partition or call.when is not None:
            return None
        if call.name in ORDERSTAT_AGGS and (
                not call.args or isinstance(call.args[0], ast.Wildcard)):
            return None
        frac = 0.5
        topk = 3
        exact = False
        float_reason = ""
        arg_ce: Optional[CompiledExpr] = None
        if call.args and not isinstance(call.args[0], ast.Wildcard):
            if call.name == "heavy_hitters":
                # heavy_hitters(col, k): bare column + literal k only — the
                # column dictionary-encodes through a per-node ValueDict.
                # k is bounded by half the candidate pool (top_k fetches 2k
                # of HH_DEPTH*HH_WIDTH candidates); larger k → exact host path
                from .sketches import HH_DEPTH, HH_WIDTH

                if (
                    len(call.args) != 2
                    or not isinstance(call.args[0], ast.FieldRef)
                    or not isinstance(call.args[1], ast.IntegerLiteral)
                    or not 0 < call.args[1].val <= HH_DEPTH * HH_WIDTH // 2
                ):
                    return None
                topk = int(call.args[1].val)
            elif call.name in ("percentile_approx", "percentile_cont",
                               "percentile_disc"):
                if len(call.args) != 2 or not isinstance(
                    call.args[1], (ast.NumberLiteral, ast.IntegerLiteral)
                ):
                    return None
                frac = float(call.args[1].val)
                if not 0.0 <= frac <= 1.0:
                    # invalid fraction: host path raises the clear error
                    return None
            elif len(call.args) != 1:
                return None
            arg_host: Optional[CompiledExpr] = None
            a0 = call.args[0]
            if kind in EXACT_INT_AGGS:
                exact = (isinstance(a0, ast.FieldRef)
                         and a0.name in int_columns and not has_hh)
                float_reason = "" if exact else _float_reason(a0, has_hh)
            elif kind in ORDERSTAT_AGGS:
                # the row buffers hold float32 (a declared BIGINT argument
                # keeps the rule on the host operator: planner.py
                # orderstat_host_reason)
                float_reason = _float_reason(a0, has_hh)
            if exact:
                icol = I64_COL_PREFIX + a0.name
                arg_ce = CompiledExpr(
                    lambda cols, _c=icol: cols[_c], {icol}, "device"
                )
                arg_host = CompiledExpr(
                    lambda cols, _c=icol: cols[_c], {icol}, "host"
                )
                col_dtypes[icol] = "int64"
            elif kind == "heavy_hitters":
                hcol = HH_COL_PREFIX + call.args[0].name
                arg_ce = CompiledExpr(
                    lambda cols, _h=hcol: cols[_h], {hcol}, "device"
                )
                arg_host = CompiledExpr(
                    lambda cols, _h=hcol: cols[_h], {hcol}, "host"
                )
            elif kind in ("hll", "distinct_count_approx") and isinstance(
                call.args[0], ast.FieldRef
            ):
                hcol = HLL_COL_PREFIX + call.args[0].name
                arg_ce = CompiledExpr(
                    lambda cols, _h=hcol: cols[_h], {hcol}, "device"
                )
                arg_host = CompiledExpr(
                    lambda cols, _h=hcol: cols[_h], {hcol}, "host"
                )
            else:
                arg_ce = _compile_device(call.args[0], "number",
                                         f"agg-arg:{call.name}", anchor_ms,
                                         str_seed=str_seed)
                if arg_ce is None:
                    return None
                absorb(arg_ce)
                arg_host = expr_ir.try_compile_ir(
                    call.args[0], mode="host", want="number",
                    anchor_ms=anchor_ms, str_seed=str_seed)
            columns |= arg_ce.columns
        else:
            arg_host = None
        filter_ce: Optional[CompiledExpr] = None
        filter_host: Optional[CompiledExpr] = None
        if call.filter is not None:
            filter_ce = _compile_device(call.filter, "bool",
                                        f"agg-filter:{call.name}",
                                        anchor_ms, str_seed=str_seed)
            if filter_ce is None:
                return None
            absorb(filter_ce)
            filter_host = expr_ir.try_compile_ir(
                call.filter, mode="host", want="bool", anchor_ms=anchor_ms,
                str_seed=str_seed)
            columns |= filter_ce.columns
        specs.append(
            AggSpec(
                call=call,
                kind="hll" if kind == "distinct_count_approx" else kind,
                components=(set() if call.name in ORDERSTAT_AGGS
                            else set(EXACT_INT_AGGS[kind] if exact
                                     else DEVICE_AGGS[call.name])),
                arg=arg_ce,
                filter=filter_ce,
                int_input=exact,
                frac=frac,
                topk=topk,
                arg_host=arg_host,
                filter_host=filter_host,
                exact=exact,
                float_reason=float_reason,
            )
        )
    where_ce: Optional[CompiledExpr] = None
    where_host: Optional[CompiledExpr] = None
    if stmt.condition is not None and where_on_device:
        where_ce = _compile_device(stmt.condition, "bool", "where",
                                   anchor_ms, str_seed=str_seed)
        if where_ce is None:
            return None  # caller may retry with host-side where
        absorb(where_ce)
        where_host = expr_ir.try_compile_ir(
            stmt.condition, mode="host", want="bool", anchor_ms=anchor_ms,
            str_seed=str_seed)
        columns |= where_ce.columns
    # order statistics of one argument under one FILTER read one buffer
    os_bufs: List[str] = []
    for spec in specs:
        if spec.kind in ORDERSTAT_AGGS:
            key = (_expr_key(spec.call.args[0])
                   + "|f:" + _expr_key(spec.call.filter))
            if key not in os_bufs:
                os_bufs.append(key)
            spec.os_buf = os_bufs.index(key)
    return KernelPlan(
        specs=specs, filter=where_ce, columns=columns,
        filter_host=where_host, col_dtypes=col_dtypes,
        derived=tuple(sorted(derived.values(), key=lambda d: d.name)),
        expr_tag=expr_ir.ir_hash(ir_keys) if ir_keys else "",
        os_bufs=tuple(os_bufs))


def conj(a: Optional[ast.Expr], b: Optional[ast.Expr]) -> Optional[ast.Expr]:
    """AND-conjunction of two optional predicates."""
    if a is None:
        return b
    if b is None:
        return a
    return ast.BinaryExpr("AND", a, b)


def lift_predicate(plan: KernelPlan,
                   condition: Optional[ast.Expr]
                   ) -> Optional[KernelPlan]:
    """Predicate lifting for the shared pane fold (planner/sharing.py,
    per "On the Semantic Overlap of Operators in Stream Processing
    Engines"): the rule-level WHERE moves out of the plan's base filter
    and into every spec's FILTER mask, plus a synthetic
    `count(*) FILTER(WHERE <predicate>)` activity spec the member's emit
    reads its group existence from. Fold output for the original specs
    is byte-identical to the private plan's (the same base∧filter mask
    composition in ops/groupby.py _fold_core), but the plan no longer
    gates the SHARED fold — rules that differ only in predicate can
    union into one pooled fold.

    Spec order is preserved (direct-emit indices stay valid); the
    activity spec appends at the end, its index in `act_idx`.

    Returns None when the conjunction does not device-compile (the
    pieces compiled separately but conflict when conjoined — e.g. a
    column typed temporal by the WHERE and numeric by a FILTER): the
    caller must then keep the fold PRIVATE. An unlifted filtered plan
    must never enter a pooled union — its base filter would gate every
    peer's rows.
    """
    if condition is None:
        # nothing to lift: the plan folds every row, the global `act`
        # is this rule's own activity — share as-is
        return plan
    anchor_ms = expr_ir.plan_anchor_ms()
    # plan-level dictionary seed across WHERE + every FILTER, so the
    # lifted plan derives ONE __sd_* column per raw column (the same
    # one-encode/one-upload invariant extract_kernel_plan keeps)
    str_seed: Dict[str, Set[str]] = {}
    for d in plan.derived:
        # the plan's existing dictionaries (agg args / CASE constants)
        # seed the lift, so the lifted filters resolve to the SAME
        # __sd_* columns the arg closures already reference
        if d.kind == "strdict":
            str_seed.setdefault(d.raw, set()).update(d.values)
    for root in [condition] + [s.call.filter for s in plan.specs
                               if s.call.filter is not None]:
        for col, vals in expr_ir.collect_str_consts(root).items():
            str_seed.setdefault(col, set()).update(vals)
    try:
        new_specs: List[AggSpec] = []
        for spec in plan.specs:
            f_ast = conj(condition, spec.call.filter)
            filter_ce = expr_ir.compile_expr_ir(
                f_ast, mode="device", want="bool", anchor_ms=anchor_ms,
                str_seed=str_seed)
            filter_host = expr_ir.try_compile_ir(
                f_ast, mode="host", want="bool", anchor_ms=anchor_ms,
                str_seed=str_seed)
            new_specs.append(_dc_replace(
                spec, call=_dc_replace(spec.call, filter=f_ast),
                filter=filter_ce, filter_host=filter_host))
        act_filter = expr_ir.compile_expr_ir(
            condition, mode="device", want="bool", anchor_ms=anchor_ms,
            str_seed=str_seed)
        act_host = expr_ir.try_compile_ir(
            condition, mode="host", want="bool", anchor_ms=anchor_ms,
            str_seed=str_seed)
    except NotVectorizable:
        return None
    act_call = ast.Call(name="count", args=[ast.Wildcard()],
                        filter=condition)
    new_specs.append(AggSpec(
        call=act_call, kind="count", components={"n"}, arg=None,
        filter=act_filter, filter_host=act_host))
    col_dtypes = dict(plan.col_dtypes)
    derived = {d.name: d for d in plan.derived}
    columns = set(plan.columns)
    ir_keys = []
    for ce in [s.filter for s in new_specs if s.filter is not None]:
        col_dtypes.update(ce.col_dtypes)
        for d in ce.derived:
            derived[d.name] = d
        columns |= ce.columns
        ir_keys.append(ce.ir_key)
    return KernelPlan(
        specs=new_specs, filter=None, columns=columns, filter_host=None,
        col_dtypes=col_dtypes,
        derived=tuple(sorted(derived.values(), key=lambda d: d.name)),
        expr_tag=expr_ir.ir_hash([plan.expr_tag] + ir_keys),
        act_idx=len(new_specs) - 1)


def explain_expressions(stmt: ast.SelectStatement) -> Dict[str, Any]:
    """The "expressions" section of GET /rules/{id}/explain: per-piece
    device-compilation status with structured NotVectorizable reasons —
    names host expression eval instead of an opaque host-path verdict."""
    anchor_ms = expr_ir.plan_anchor_ms()
    pieces: List[Tuple[str, Optional[ast.Expr], str]] = []
    if stmt.condition is not None:
        pieces.append(("where", stmt.condition, "bool"))
    for call in _collect_agg_calls(stmt):
        if call.args and not isinstance(call.args[0], ast.Wildcard):
            pieces.append((f"agg-arg:{call.name}", call.args[0], "number"))
        if call.filter is not None:
            pieces.append((f"agg-filter:{call.name}", call.filter, "bool"))
    out: List[Dict[str, Any]] = []
    n_host = 0
    for kind, expr, want in pieces:
        entry: Dict[str, Any] = {"kind": kind, "expr": _expr_key(expr)}
        try:
            ce = expr_ir.compile_expr_ir(expr, mode="device", want=want,
                                         anchor_ms=anchor_ms)
            entry["path"] = "device"
            if ce.derived:
                entry["derived"] = [d.name for d in ce.derived]
        except NotVectorizable as exc:
            entry["path"] = "host"
            entry["reason"] = getattr(exc, "reason", "other")
            entry["detail"] = str(exc)
            n_host += 1
        out.append(entry)
    return {"pieces": out, "host_fallbacks": n_host,
            "path": "host" if n_host else "device"}


def _collect_agg_calls(stmt: ast.SelectStatement) -> List[ast.Call]:
    """All aggregate calls in SELECT fields + HAVING, deduplicated by
    (name, arg-tree repr) so avg(x) in both places folds once."""
    from ..functions import registry

    seen: Dict[str, ast.Call] = {}
    roots = [f.expr for f in stmt.fields]
    if stmt.having is not None:
        roots.append(stmt.having)
    for sf in stmt.sorts:
        if sf.expr is not None:
            roots.append(sf.expr)
    for root in roots:
        for node in ast.walk(root):
            if isinstance(node, ast.Call) and registry.is_aggregate(node.name):
                seen.setdefault(_call_key(node), node)
    return list(seen.values())


def _call_key(call: ast.Call) -> str:
    return f"{call.name}({','.join(map(_expr_key, call.args))})" + (
        f"|f:{_expr_key(call.filter)}" if call.filter is not None else ""
    )


def _expr_key(e: Optional[ast.Expr]) -> str:
    if e is None:
        return ""
    if isinstance(e, ast.FieldRef):
        return f"{e.stream}.{e.name}"
    if isinstance(e, ast.Call):
        return _call_key(e)
    if isinstance(e, (ast.IntegerLiteral, ast.NumberLiteral, ast.StringLiteral, ast.BooleanLiteral)):
        return repr(e.val)
    if isinstance(e, ast.BinaryExpr):
        return f"({_expr_key(e.lhs)}{e.op}{_expr_key(e.rhs)})"
    if isinstance(e, ast.UnaryExpr):
        return f"({e.op}{_expr_key(e.expr)})"
    if isinstance(e, ast.BetweenExpr):
        neg = "!" if e.negate else ""
        return (f"({_expr_key(e.value)} {neg}BETWEEN "
                f"{_expr_key(e.lo)},{_expr_key(e.hi)})")
    if isinstance(e, ast.InExpr):
        neg = "!" if e.negate else ""
        return (f"({_expr_key(e.value)} {neg}IN "
                f"[{','.join(_expr_key(v) for v in e.values)}])")
    if isinstance(e, ast.LikeExpr):
        neg = "!" if e.negate else ""
        return f"({_expr_key(e.value)} {neg}LIKE {_expr_key(e.pattern)})"
    if isinstance(e, ast.CaseExpr):
        base = _expr_key(e.value) if e.value is not None else ""
        whens = ";".join(f"{_expr_key(w.cond)}->{_expr_key(w.result)}"
                         for w in e.whens)
        els = _expr_key(e.else_expr) if e.else_expr is not None else ""
        return f"CASE({base};{whens};{els})"
    if isinstance(e, ast.Wildcard):
        return "*"
    return repr(e)
