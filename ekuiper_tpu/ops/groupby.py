"""Fused GROUP BY aggregation kernel — the TPU replacement for the reference's
hot loop (WindowIncAggOperator + AggregateOp + per-group ValuerEval,
reference: internal/topo/node/window_inc_agg_op.go,
internal/topo/operator/aggregate_operator.go:34-74).

Design: per-key partial state lives in dense device arrays of shape
(n_panes, capacity, k) — one column per aggregate spec, one pane per
window sub-interval:

- TUMBLING/COUNT windows: 1 pane, reset after emit.
- HOPPING windows: P = length/interval panes (the "pane/slice" technique from
  sliding-window aggregation literature); each pane is a tumbling sub-window,
  emit merges the live panes, expiry resets one pane.

One jitted `fold` per rule processes a fixed-size micro-batch: WHERE filter,
per-agg argument expressions (compiled device closures), null/validity
masking, and scatter-add/min/max into the partials — all fused by XLA into a
single device program. Micro-batches are padded to a static shape so the
kernel compiles once.

State components per spec: n (count), s1 (sum), s2 (sum of squares),
mn (min), mx (max) — matching funcs_inc_agg.py's accumulators, so shard
merges (parallel/) are elementwise add/min/max.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from .aggspec import AggSpec, KernelPlan

_I32_MAX = (1 << 31) - 1
_I32_MIN = -(1 << 31)

_INIT = {
    "n": 0.0, "s1": 0.0, "s2": 0.0, "mn": np.inf, "mx": -np.inf, "act": 0.0,
    # wide (register-axis) components: HLL registers, log-histogram bins,
    # heavy-hitters group-testing counters
    "hll": 0.0, "hist": 0.0, "hh": 0.0,
    # exact BIGINT partials (aggspec.EXACT_INT_AGGS): the sum's four 16-bit
    # limbs, and an extreme's (hi, lo ^ 2^31) pair — both words at the
    # identity make INT64_MAX (imn) / INT64_MIN (imx)
    "i1": 0, "imn": _I32_MAX, "imx": _I32_MIN,
    # per-slot touch counter (tiered key state, ops/tierstore.py):
    # uint32, shape (capacity,) — NOT pane-scoped, survives pane resets
    "touch": 0,
}

#: device dtype of every state component that is not float32 (the
#: order-statistic buffers' slots and fills among them, ops/orderstat.py)
_COMP_DTYPE = {"i1": np.uint32, "imn": np.int32, "imx": np.int32,
               "touch": np.uint32, "oss": np.int32, "osf": np.int32}

#: rows one scatter may add into a limb before the carry: 16-bit limbs of
#: this many rows and a carried limb stay below 2^32
_LIMB_ROWS = 32768

_WIDE_SIZE = {}  # filled lazily from sketches to avoid import cycle

#: what a fold's staging runs inside when its caller hands no stage opener
_NO_STAGE = contextlib.nullcontext()


def _wide_size(comp: str) -> int:
    if not _WIDE_SIZE:
        from . import sketches

        _WIDE_SIZE["hll"] = sketches.HLL_M
        _WIDE_SIZE["hist"] = sketches.HIST_BINS
        _WIDE_SIZE["hh"] = sketches.HH_SIZE
        _WIDE_SIZE.update(i1=4, imn=2, imx=2)
    return _WIDE_SIZE[comp]


def comp_dtype(comp: str):
    """Device dtype of a state component: float32 but for the exact-integer
    limbs and the touch counter."""
    return np.dtype(_COMP_DTYPE.get(comp, np.float32))


def state_shape(comp: str, n_panes: int, capacity: int, k: int):
    """Device shape of one state component. The exact-integer words lead
    (W, P, capacity, K): each word is then a plain (P, capacity, K) array,
    laid out as the float components are — a trailing word axis made the
    chip's compiler pad intermediates of the carry to 128 lanes (1.3 GB of
    temporaries a fold at 262,144 slots)."""
    from .aggspec import LIMB_COMPONENTS, WIDE_COMPONENTS

    if comp in LIMB_COMPONENTS:
        return (_wide_size(comp), n_panes, capacity, k)
    shape = (n_panes, capacity, k)
    if comp in WIDE_COMPONENTS:
        shape = shape + (_wide_size(comp),)
    return shape


def _lead(comp: str) -> int:
    """Axes before the pane axis: the word axis of an exact component."""
    from .aggspec import LIMB_COMPONENTS

    return 1 if comp in LIMB_COMPONENTS else 0


def _carry(arr):
    """Propagate the 16-bit limbs' carries of a (4, ...) uint32 sum; the
    carry out of the top limb is dropped: int64 arithmetic modulo 2^64,
    as Go's int64 wraps."""
    import jax.numpy as jnp

    limbs = [arr[i] for i in range(4)]
    for i in range(3):
        limbs[i + 1] = limbs[i + 1] + (limbs[i] >> 16)
        limbs[i] = limbs[i] & jnp.uint32(0xFFFF)
    limbs[3] = limbs[3] & jnp.uint32(0xFFFF)
    return jnp.stack(limbs)


def _signed_words(v):
    """(hi, lo) of (mb, 2) uint32 int64 words as int32 that order as the
    int64 does, lexicographically: hi as signed, lo with its sign bit
    flipped."""
    from jax import lax
    import jax.numpy as jnp

    hi = lax.bitcast_convert_type(v[:, 1], jnp.int32)
    lo = lax.bitcast_convert_type(v[:, 0] ^ jnp.uint32(0x80000000),
                                  jnp.int32)
    return hi, lo


def col_np_dtype(plan: KernelPlan, name: str):
    """Upload dtype for one kernel column: float32 unless the plan's
    expression IR declared otherwise (int32 string-dict codes / rebased
    ts32 — KernelPlan.col_dtypes). THE one mapping shared by the fold
    upload, the ingest prep pre-upload, warmups, and the jitcert fold
    derivations."""
    return np.dtype(getattr(plan, "col_dtypes", {}).get(name, "float32"))


def wire_col(arr: np.ndarray, dtype) -> np.ndarray:
    """A host column, already padded, in the form the fold takes: `dtype`
    itself, an int64 column as its two uint32 words a row, (n, 2) = [low,
    high] (jax without x64 has no int64 array)."""
    arr = np.asarray(arr, dtype=dtype)
    if arr.dtype != np.int64:
        return arr
    return np.ascontiguousarray(arr, dtype="<i8").view("<u4").reshape(-1, 2)


def warmup_cols(plan: KernelPlan, n: int = 1) -> Dict[str, np.ndarray]:
    """Dtype-correct zero columns for a warmup fold — the throwaway
    batch must present the same column dtypes real batches will, or the
    warmup compiles an executable no real fold ever hits."""
    return {name: np.zeros(n, dtype=col_np_dtype(plan, name))
            for name in plan.columns}


def slot_dtype(capacity: int):
    """Slot-vector wire dtype for a key capacity — the ONE place holding
    the uint16/int32 boundary. Slots ship as uint16 while every assignable
    slot id (0..capacity-1) fits; past 65,535 they ship int32. Callers that
    cache pre-padded slot arrays (sliding _dev_ring, the ingest prep's
    share cache) key or re-derive on this, so a capacity doubling past the
    boundary switches new uploads to int32 while already-cached uint16
    arrays stay valid — their values predate the grow and still index the
    same dense slots (_fold_core casts to int32 on device)."""
    return np.uint16 if capacity <= 65535 else np.int32


def apply_int_semantics(specs, host: List[np.ndarray]) -> List[np.ndarray]:
    """Integer result types on finalize output: counts are int64; a
    float32-path sum/avg/min/max over integer-typed input, and a
    percentile_disc over it (one of the values, as the host operator
    returns it), is truncated toward zero (eKuiper's int64 division for
    avg) and, where no group is
    NULL, handed on as int64 — exact while values and sums stay below 2^24
    (past it float32 has rounded them: kuiper_agg_int_fallback_total counts
    such rows). Exact specs (declared BIGINT) come out of the int64 tail
    (prefinalize.final_value_np) and pass through. Shared by the
    single-chip and sharded paths so results are identical regardless of
    placement."""
    for i, spec in enumerate(specs):
        if spec.kind in ("count", "hll"):
            host[i] = host[i].astype(np.int64)
        elif (spec.int_input and not spec.exact
              and spec.kind in ("sum", "avg", "min", "max",
                                "percentile_disc")):
            nan = np.isnan(host[i])
            with np.errstate(invalid="ignore"):
                trunc = np.trunc(host[i])
            host[i] = (np.where(nan, np.nan, trunc) if nan.any()
                       else trunc.astype(np.int64))
    return host


def observe_int_inputs(specs, columns: Dict[str, np.ndarray]) -> None:
    """Record integer-typed agg inputs (drives apply_int_semantics)."""
    for spec in specs:
        if spec.arg is not None and len(spec.arg.columns) == 1:
            (col_name,) = spec.arg.columns
            col = columns.get(col_name)
            if col is not None and np.issubdtype(col.dtype, np.integer):
                spec.int_input = True


class DeviceGroupBy:
    """Device-resident group-by aggregation state + jitted fold/finalize."""

    def __init__(
        self,
        plan: KernelPlan,
        capacity: int = 16384,
        n_panes: int = 1,
        micro_batch: int = 4096,
        track_touch: bool = False,
    ) -> None:
        import jax

        self.plan = plan
        # exact BIGINT partials: limb components in the state, and every
        # finalize hands over the stacked components (int32 words) for the
        # host's int64 tail (outs_from_finalized)
        self._exact = bool(getattr(plan, "has_exact", False))
        self.capacity = int(capacity)
        self.n_panes = int(n_panes)
        self.micro_batch = int(micro_batch)
        # tiered key state (ops/tierstore.py): a per-slot uint32 touch
        # counter rides the state pytree and is bumped inside the fold —
        # the placement policy's recency/frequency signal, no host sync
        self.track_touch = bool(track_touch)
        # runtime calls the folds' host -> device staging has made
        # (kuiper_fold_transfers_total) and arguments it took from the
        # scalar table below instead (kuiper_fold_resident_args_total);
        # one writer, the folding thread
        self.transfers_total = 0
        self.resident_total = 0
        # device-resident scalars, int32[] as the executables were lowered
        # with: the pane indices 0 .. n_panes-1, then the full row count
        # `micro_batch`. Each made at its first use and never again; none
        # is ever donated (only argument 0, the state, is), so one array
        # serves every call that needs its value
        self._scalars: List[Any] = [None] * (self.n_panes + 1)
        # component -> ordered spec indices holding a column in that array
        self.comp_specs: Dict[str, List[int]] = {}
        for i, spec in enumerate(plan.specs):
            for comp in spec.components:
                self.comp_specs.setdefault(comp, []).append(i)
        # exact order statistics (ops/orderstat.py): row buffers in the
        # state, one a distinct (argument, FILTER); (buffer, fraction) of
        # each such spec, in spec order, for the boundary's selection
        self.n_os_bufs = len(getattr(plan, "os_bufs", ()))
        self.os_specs = [(s.os_buf, float(s.frac)) for s in plan.specs
                         if s.os_buf >= 0]
        from ..runtime.aotcache import aot_jit

        self._fold = aot_jit(self._fold_impl, op=self._watch_op("fold"),
                                 donate_argnums=(0,))
        # row-masked fold: the sliding edge refold re-folds CACHED device
        # batches under an arbitrary (mb,) bool row mask (window time cut),
        # so trigger emission uploads one 65KB mask instead of the rows
        self._fold_m = aot_jit(self._fold_masked_impl,
                                   op=self._watch_op("fold_masked"),
                                   kind="boundary",
                                   donate_argnums=(0,))
        # pane mask is static: no device upload per emit, one cached
        # executable per live-pane combination (few), and the output is ONE
        # stacked array -> a single device->host transfer per window emit
        self._finalize = aot_jit(self._finalize_impl,
                                     op=self._watch_op("finalize"),
                                     kind="boundary",
                                     static_argnums=(1,))
        # dynamic-mask variant: event-time windows rotate through per-window
        # pane subsets; a static mask would compile one executable per
        # subset (up to n_panes compiles), a traced mask compiles once
        self._finalize_dyn = aot_jit(self._finalize_dyn_impl,
                                         op=self._watch_op("finalize_dyn"),
                                         kind="boundary")
        self._components = aot_jit(self._components_impl,
                                       op=self._watch_op("components"),
                                       kind="boundary",
                                       static_argnums=(1,))
        # traced-pane-mask components twin: the sliding ring's exact
        # fallback (delayed emissions, recycled panes) merges an arbitrary
        # live-pane subset into the SAME stacked components layout with
        # one compiled executable per capacity
        self._components_dyn = aot_jit(self._components_dyn_impl,
                                           op=self._watch_op("components_dyn"),
                                           kind="boundary")
        self._reset_pane = aot_jit(self._reset_pane_impl,
                                       op=self._watch_op("reset_pane"),
                                       kind="boundary",
                                       donate_argnums=(0,))
        # heavy_hitters finalize: candidate recovery + top-k run ON DEVICE
        # (sketches.hh_candidates) so the emit transfer is 2*k2 floats/key,
        # not the HH_SIZE-wide raw sketch; dedupe + value decode finish on
        # host. finalize() routes through _host_finalize for such plans.
        self._host_finalize_only = any(
            s.kind == "heavy_hitters" for s in plan.specs
        )
        if self._host_finalize_only:
            self._hh_fin = aot_jit(self._hh_finalize_impl,
                                       op=self._watch_op("hh_finalize"),
                                       kind="boundary")
        # order statistics: the sort, segment search and rank gathers run
        # ON DEVICE, so a boundary fetches capacity x 6 words a spec, never
        # the buffered rows; finalize() routes through _os_finalize
        if self.n_os_bufs:
            self._os_sel = aot_jit(self._orderstat_select_impl,
                                   op=self._watch_op("orderstat_select"),
                                   kind="boundary")
        # bind this kernel to its compile contract: jitcert derives the
        # closed signature set every site above may be traced with, and
        # the runtime diff (bench rounds, /diagnostics/xla) holds the
        # observed devwatch signatures to it
        from ..observability import jitcert

        jitcert.register_kernel(self)

    #: kuiper_xla_* metric prefix for this kernel's jit sites; subclasses
    #: override (multirule / sharded) so recompiles attribute to the
    #: kernel variant that paid them
    watch_prefix = "groupby"

    def _watch_op(self, site: str) -> str:
        return f"{self.watch_prefix}.{site}"

    #: the latency-hiding emit pipeline (ops/prefinalize.py) works here;
    #: the sharded subclass opts out (its finalize runs collective gathers)
    supports_prefinalize = True
    #: fold() accepts pre-padded device arrays (shared-source fan-out
    #: uploads); the sharded subclass opts out — its fold shards HOST
    #: arrays across the mesh itself
    accepts_device_inputs = True

    # ------------------------------------------------------------------ state
    def init_state(self) -> Dict[str, Any]:
        import jax.numpy as jnp

        state: Dict[str, Any] = {}
        for comp, spec_idxs in self.comp_specs.items():
            shape = state_shape(comp, self.n_panes, self.capacity,
                                len(spec_idxs))
            state[comp] = jnp.full(shape, _INIT[comp],
                                   dtype=comp_dtype(comp))
        # activity: rows per key per pane (post-WHERE), for group existence
        state["act"] = jnp.zeros((self.n_panes, self.capacity), dtype=jnp.float32)
        if self.track_touch:
            state["touch"] = jnp.zeros((self.capacity,), dtype=jnp.uint32)
        if self.n_os_bufs:
            from . import orderstat

            state.update(orderstat.init_leaves(
                self.n_os_bufs, self.n_panes,
                orderstat.first_rung(self.micro_batch)))
        return state

    def grow(self, state: Dict[str, Any], new_capacity: int) -> Dict[str, Any]:
        """Double the key capacity, preserving partials. Runs ON DEVICE
        (jnp.pad) — at 1M-key cardinality a host roundtrip would move GBs
        through the host↔device link per doubling."""
        import jax.numpy as jnp

        from .orderstat import OS_LEAVES

        out: Dict[str, Any] = {}
        for comp, arr in state.items():
            if comp in OS_LEAVES:  # row buffers: no key axis
                out[comp] = arr
                continue
            # the touch column is (capacity,), not pane-scoped — the key
            # axis is axis 0 there, axis 1 everywhere else (2 behind an
            # exact component's word axis)
            key_axis = 0 if comp == "touch" else 1 + _lead(comp)
            if isinstance(arr, np.ndarray):  # host-restored state
                pad_shape = list(arr.shape)
                pad_shape[key_axis] = new_capacity - arr.shape[key_axis]
                pad = np.full(pad_shape, _INIT[comp], dtype=arr.dtype)
                out[comp] = jnp.asarray(
                    np.concatenate([arr, pad], axis=key_axis))
                continue
            pad_width = [(0, 0)] * arr.ndim
            pad_width[key_axis] = (0, new_capacity - arr.shape[key_axis])
            out[comp] = jnp.pad(arr, pad_width,
                                constant_values=_INIT[comp])
        self.capacity = new_capacity
        return out

    # ------------------------------------------------------------------- fold
    def fold(
        self,
        state: Dict[str, Any],
        cols: Dict[str, np.ndarray],
        slots: np.ndarray,
        valid: Optional[Dict[str, np.ndarray]] = None,
        pane_idx=0,
        n_rows: Optional[int] = None,
        h2d: Optional[Callable[[int], Any]] = None,
    ) -> Dict[str, Any]:
        """Fold a host micro-batch into the device partials.

        cols: numeric columns referenced by the kernel plan (numpy).
        slots: int32 key slot per row. valid: optional per-column masks.
        pane_idx: the destination pane — a scalar (processing-time windows)
        or a per-row array (event-time windows route each row to its
        bucket's pane). Rows are chunked/padded to the static micro_batch
        size. h2d: the caller's stage opener (rows -> context manager): a
        chunk's host -> device staging runs inside it, the jitted call
        after it; `transfers_total` counts the staging's runtime calls.
        """
        import jax

        from .aggspec import materialize_hll_columns

        # pre-padded device slots are length mb regardless of real rows, so
        # the true count must come from the caller in that case
        n = n_rows if n_rows is not None else len(slots)
        mb = self.micro_batch
        valid = valid or {}
        cols = materialize_hll_columns(self.plan.columns, cols, n)
        # shared-source fan-out hands PRE-PADDED device arrays (length mb,
        # one upload serving many consumers — nodes_fused.py
        # _shared_device_inputs). Those are single-chunk by contract.
        has_dev = isinstance(slots, jax.Array) or any(
            isinstance(cols[name], jax.Array) for name in self.plan.columns)
        if has_dev:
            assert n <= mb, "pre-uploaded device inputs must be one chunk"
        for start in range(0, max(n, 1), mb):
            end = min(start + mb, n)
            if end <= start:
                break
            with (h2d(end - start) if h2d is not None else _NO_STAGE):
                staged = self._stage_chunk(cols, slots, valid, pane_idx,
                                           start, end)
            state = self._fold(state, *staged)
        return state

    def _resident(self, idx: int):
        """Entry `idx` of the scalar table (the pane `idx`; at `n_panes`
        the full row count), made on the device at its first use."""
        arr = self._scalars[idx]
        if arr is None:
            import jax.numpy as jnp

            value = self.micro_batch if idx == self.n_panes else idx
            arr = self._scalars[idx] = jnp.asarray(value, dtype=jnp.int32)
        return arr

    def _pane_arg(self, pane_idx):
        """A scalar pane as the executables take it: the table's device
        array, or for a pane the table does not hold a host int32 that
        rides the compiled call."""
        if 0 <= pane_idx < self.n_panes:
            return self._resident(int(pane_idx))
        return np.int32(pane_idx)

    def _stage_chunk(self, cols, slots, valid, pane_idx, start: int,
                     end: int):
        """Host -> device staging of rows [start:end). What the device
        holds already is handed over as it is: pre-uploaded columns and
        slots, and from the scalar table a scalar pane and the row count
        of a full micro-batch (`resident_total` counts those two). The
        rest — host columns, masks and slots, a per-row pane vector, the
        row count of a partial chunk — is padded to the static micro-batch,
        cast, and handed on as numpy: it goes up inside the compiled call,
        the one runtime call of a chunk that has anything on the host
        (`transfers_total` counts it; the table's own n_panes + 1 fills
        are once a kernel, not counted). Returns the jitted fold's
        arguments after the state."""
        import jax

        cnt = end - start
        pad = self.micro_batch - cnt
        on_host = bool(pad)  # a partial chunk's row count, below
        dev_cols = {}
        for name in self.plan.columns:
            c = cols[name]
            if isinstance(c, jax.Array):  # pre-padded shared upload
                dev_cols[name] = c
                dev_cols["__valid_" + name] = valid.get(name)
                continue
            on_host = True
            # kuiperlint: ignore[host-sync]: `c` is a HOST column here (device arrays took the pre-padded branch above) — this is H2D staging, not a sync
            arr = np.asarray(c[start:end],
                             dtype=col_np_dtype(self.plan, name))
            if pad:
                arr = np.pad(arr, (0, pad))
            dev_cols[name] = wire_col(arr, arr.dtype)
            vm = valid.get(name)
            if vm is not None:
                vm = vm[start:end]
                if pad:
                    vm = np.pad(vm, (0, pad))
            dev_cols["__valid_" + name] = vm
        # device slots come pre-padded + dtype-chosen by the sharer
        if not isinstance(slots, jax.Array):
            on_host = True
            slots = slots[start:end]
            if pad:
                slots = np.pad(slots, (0, pad))
            # upload-byte diet: slots ship as uint16 when capacity
            # allows (halves the largest upload), and row validity
            # ships as ONE scalar count compared against an iota on
            # device instead of an mb-byte bool mask — HBM/link
            # bandwidth is the bottleneck, not device compute
            slots = slots.astype(slot_dtype(self.capacity), copy=False)
        if isinstance(pane_idx, np.ndarray):
            on_host = True
            pv = pane_idx[start:end]
            if pad:
                pv = np.pad(pv, (0, pad))
            pane_arg = pv.astype(np.uint8)  # n_panes <= 255
        else:
            pane_arg = self._pane_arg(pane_idx)
            if isinstance(pane_arg, jax.Array):
                self.resident_total += 1
            else:
                on_host = True
        if pad:
            n_valid = np.int32(cnt)
        else:
            n_valid = self._resident(self.n_panes)
            self.resident_total += 1
        if on_host:
            self.transfers_total += 1
        return dev_cols, slots, n_valid, pane_arg

    def _fold_impl(self, state, cols, slots, n_valid, pane_idx):
        import jax
        import jax.numpy as jnp

        # kuiper/<kernel>/<section> scopes name the ops in a device trace
        # past the compiler's fusion numbering; the PROGRAM names
        # (jit__fold_impl, ...) are what the benchmark matches, and stay
        with jax.named_scope("kuiper/fold/pad"):
            base = jnp.arange(self.micro_batch, dtype=jnp.int32) < n_valid
        return self._fold_core(state, cols, slots, base, pane_idx, n_valid)

    def _fold_masked_impl(self, state, cols, slots, mask, pane_idx):
        return self._fold_core(state, cols, slots, mask, pane_idx)

    def fold_masked(self, state, dev_cols, slots_dev, mask: np.ndarray,
                    pane_idx: int):
        """Re-fold a cached pre-padded device batch under a host row mask
        (False rows contribute nothing — the mask already ANDs the real-row
        count). Used by the sliding edge refold; see nodes_fused.py."""
        import jax.numpy as jnp

        return self._fold_m(state, dev_cols, slots_dev,
                            jnp.asarray(mask, dtype=jnp.bool_),
                            self._pane_arg(pane_idx))

    def _fold_core(self, state, cols, slots, base, pane_idx, n_valid=None):
        import jax
        import jax.numpy as jnp

        with jax.named_scope("kuiper/fold/slot_prep"):
            slots = slots.astype(jnp.int32)
            pane_idx = pane_idx.astype(jnp.int32)  # scalar or per-row vector
            if self.plan.filter is not None:
                base = jnp.logical_and(base, self.plan.filter(cols))
        # per-column validity composes into per-spec masks below
        with jax.named_scope("kuiper/fold/scatter_act"):
            state["act"] = state["act"].at[pane_idx, slots].add(
                base.astype(jnp.float32)
            )
            if "touch" in state:
                # tier placement signal (ops/tierstore.py): per-slot touched-
                # row count, cumulative — the policy worker diffs successive
                # async fetches for recency/frequency, so the fold itself
                # never syncs
                state["touch"] = state["touch"].at[slots].add(
                    base.astype(jnp.uint32))
        per_spec: List[Tuple[Any, Any]] = []
        with jax.named_scope("kuiper/fold/values"):
            for spec in self.plan.specs:
                if spec.arg is None:
                    v = jnp.ones_like(base, dtype=jnp.float32)
                    m = base
                elif spec.exact:
                    # the column's int64 words, (mb, 2) uint32: no NaN
                    (col,) = spec.arg.columns
                    v = cols[col]
                    m = base
                    vm = cols.get("__valid_" + col)
                    if vm is not None:
                        m = jnp.logical_and(m, vm)
                else:
                    v = spec.arg(cols).astype(jnp.float32)
                    m = base
                    for col in spec.arg.columns:
                        vm = cols.get("__valid_" + col)
                        if vm is not None:
                            m = jnp.logical_and(m, vm)
                    m = jnp.logical_and(m, jnp.logical_not(jnp.isnan(v)))
                if spec.filter is not None:
                    m = jnp.logical_and(m, spec.filter(cols))
                per_spec.append((v, m))
        for comp, spec_idxs in self.comp_specs.items():
            with jax.named_scope(f"kuiper/fold/scatter_{comp}"):
                state[comp] = self._scatter_comp(
                    comp, state[comp], spec_idxs, slots, pane_idx, per_spec)
        if self.n_os_bufs and n_valid is not None:
            from . import orderstat

            if getattr(pane_idx, "ndim", 0):
                raise ValueError("order-statistic buffers take a scalar "
                                 "pane (processing-time windows)")

            # each buffer's rows from its first spec: one argument and one
            # FILTER a buffer, so every spec of it has the same (v, m)
            first = {}
            for i, spec in enumerate(self.plan.specs):
                if spec.os_buf >= 0:
                    first.setdefault(spec.os_buf, i)
            with jax.named_scope("kuiper/fold/orderstat_append"):
                state = orderstat.append(
                    state, [per_spec[first[b]]
                            for b in range(self.n_os_bufs)],
                    slots, pane_idx, n_valid)
        return state

    @staticmethod
    def _scatter_comp(comp, arr, spec_idxs, slots, pane_idx, per_spec):
        """The scatters of one micro-batch into one state component."""
        import jax.numpy as jnp

        if comp == "i1":
            return DeviceGroupBy._scatter_limbs(arr, spec_idxs, slots,
                                                pane_idx, per_spec)
        if comp in ("imn", "imx"):
            return DeviceGroupBy._scatter_extreme(comp, arr, spec_idxs,
                                                  slots, pane_idx, per_spec)
        for k, si in enumerate(spec_idxs):
            v, m = per_spec[si]
            mf = m.astype(jnp.float32)
            if comp == "n":
                arr = arr.at[pane_idx, slots, k].add(mf)
            elif comp == "s1":
                arr = arr.at[pane_idx, slots, k].add(jnp.where(m, v, 0.0))
            elif comp == "s2":
                arr = arr.at[pane_idx, slots, k].add(jnp.where(m, v * v, 0.0))
            elif comp == "mn":
                arr = arr.at[pane_idx, slots, k].min(
                    jnp.where(m, v, jnp.inf)
                )
            elif comp == "mx":
                arr = arr.at[pane_idx, slots, k].max(
                    jnp.where(m, v, -jnp.inf)
                )
            elif comp == "hll":
                from .sketches import hll_parts

                reg, rho = hll_parts(v)
                arr = arr.at[pane_idx, slots, k, reg].max(
                    jnp.where(m, rho, 0.0)
                )
            elif comp == "hist":
                from .sketches import hist_bin

                b = hist_bin(v)
                arr = arr.at[pane_idx, slots, k, b].add(mf)
            elif comp == "hh":
                from .sketches import hh_update_parts

                idx, wts = hh_update_parts(v, mf)  # (mb, J)
                p = (pane_idx[:, None]
                     if getattr(pane_idx, "ndim", 0) == 1 else pane_idx)
                arr = arr.at[p, slots[:, None], k, idx].add(wts)
        return arr

    @staticmethod
    def _scatter_limbs(arr, spec_idxs, slots, pane_idx, per_spec):
        """Exact int64 sums: each row's four 16-bit limbs added into the
        (4, P, cap, K) uint32 words, at most _LIMB_ROWS rows before the
        carry, so no word overflows whatever the keys' skew."""
        import jax.numpy as jnp

        limbs = []
        for si in spec_idxs:
            v, m = per_spec[si]
            lo, hi = v[:, 0], v[:, 1]
            parts = [lo & jnp.uint32(0xFFFF), lo >> 16,
                     hi & jnp.uint32(0xFFFF), hi >> 16]
            limbs.append([jnp.where(m, x, jnp.uint32(0)) for x in parts])
        n = slots.shape[0]
        per_row = getattr(pane_idx, "ndim", 0) == 1
        for a in range(0, n, _LIMB_ROWS):
            b = min(a + _LIMB_ROWS, n)
            p = pane_idx[a:b] if per_row else pane_idx
            words = [arr[i] for i in range(4)]
            for k, parts in enumerate(limbs):
                for i in range(4):
                    words[i] = words[i].at[p, slots[a:b], k].add(
                        parts[i][a:b])
            arr = _carry(jnp.stack(words))
        return arr

    @staticmethod
    def _scatter_extreme(comp, arr, spec_idxs, slots, pane_idx, per_spec):
        """Exact int64 min / max as a lexicographic (hi, lo) pair of int32
        words: the high words' extreme first, then the low words of the
        rows (and the old state) that share it."""
        import jax.numpy as jnp

        ident = _INIT[comp]
        for k, si in enumerate(spec_idxs):
            v, m = per_spec[si]
            hi, lo = _signed_words(v)
            cur_hi, cur_lo = arr[0, :, :, k], arr[1, :, :, k]
            row_hi = jnp.where(m, hi, ident)
            if comp == "imn":
                new_hi = cur_hi.at[pane_idx, slots].min(row_hi)
            else:
                new_hi = cur_hi.at[pane_idx, slots].max(row_hi)
            ties = jnp.logical_and(m, hi == new_hi[pane_idx, slots])
            row_lo = jnp.where(ties, lo, ident)
            keep_lo = jnp.where(new_hi == cur_hi, cur_lo, ident)
            if comp == "imn":
                new_lo = keep_lo.at[pane_idx, slots].min(row_lo)
            else:
                new_lo = keep_lo.at[pane_idx, slots].max(row_lo)
            arr = arr.at[0, :, :, k].set(new_hi).at[1, :, :, k].set(new_lo)
        return arr

    # --------------------------------------------------------------- finalize
    def _merged(self, state, comp: str, pane_mask):
        """Merge panes under a (n_panes,) bool mask."""
        import jax.numpy as jnp

        arr = state[comp]
        pm = pane_mask.reshape(-1, *([1] * (arr.ndim - 1)))
        # an exact component comes out (cap, K, W): the host's layout
        if comp == "i1":  # carried limbs: 255 panes stay below 2^24 a limb
            words = jnp.sum(jnp.where(pm[..., 0][None], arr,
                                      jnp.uint32(0)), axis=1)
            return jnp.moveaxis(words, 0, -1)
        if comp in ("imn", "imx"):
            red = jnp.min if comp == "imn" else jnp.max
            ident = _INIT[comp]
            hi = jnp.where(pm[..., 0], arr[0], ident)
            best_hi = red(hi, axis=0)
            lo = jnp.where(jnp.logical_and(pm[..., 0], hi == best_hi[None]),
                           arr[1], ident)
            return jnp.stack([best_hi, red(lo, axis=0)], axis=-1)
        if comp == "mn":
            return jnp.min(jnp.where(pm, arr, jnp.inf), axis=0)
        if comp in ("mx", "hll"):  # hll registers merge by max
            return jnp.max(jnp.where(pm, arr, -jnp.inf), axis=0)
        return jnp.sum(jnp.where(pm, arr, 0.0), axis=0)

    def _finalize_dyn_impl(self, state, pane_mask):
        if self._exact:
            return self._components_body(state, pane_mask)
        return self._finalize_body(state, pane_mask)

    def _finalize_impl(self, state, pane_mask_tuple):
        import jax.numpy as jnp

        if self._exact:
            return self._components_body(
                state, np.array(pane_mask_tuple, dtype=np.bool_))
        pane_mask = jnp.asarray(np.array(pane_mask_tuple, dtype=np.bool_))
        return self._finalize_body(state, pane_mask)

    def _finalize_body(self, state, pane_mask):
        import jax
        import jax.numpy as jnp

        with jax.named_scope("kuiper/finalize/pane_merge"):
            merged = {
                comp: self._merged(state, comp, pane_mask)
                for comp in self.comp_specs
            }
            act = self._merged(state, "act", pane_mask)
        with jax.named_scope("kuiper/finalize/values"):
            outs = []
            for i, spec in enumerate(self.plan.specs):
                col = {
                    comp: merged[comp][:, self.comp_specs[comp].index(i)]
                    for comp in spec.components
                }
                outs.append(self._final_value(spec, col))
            # one stacked array -> one transfer
            return jnp.stack(outs + [act], axis=0)

    @staticmethod
    def _final_value(spec: AggSpec, c):
        import jax.numpy as jnp

        kind = spec.kind
        if kind == "count":
            return c["n"]
        n = c.get("n")
        if kind == "sum":
            return jnp.where(n > 0, c["s1"], jnp.nan)
        if kind == "avg":
            return jnp.where(n > 0, c["s1"] / jnp.maximum(n, 1.0), jnp.nan)
        if kind == "min":
            return jnp.where(n > 0, c["mn"], jnp.nan)
        if kind == "max":
            return jnp.where(n > 0, c["mx"], jnp.nan)
        if kind in ("stddev", "var"):
            mean = c["s1"] / jnp.maximum(n, 1.0)
            v = jnp.maximum(c["s2"] / jnp.maximum(n, 1.0) - mean * mean, 0.0)
            out = jnp.sqrt(v) if kind == "stddev" else v
            return jnp.where(n > 0, out, jnp.nan)
        if kind in ("stddevs", "vars"):
            mean = c["s1"] / jnp.maximum(n, 1.0)
            v = jnp.maximum(
                (c["s2"] - c["s1"] * mean) / jnp.maximum(n - 1.0, 1.0), 0.0
            )
            out = jnp.sqrt(v) if kind == "stddevs" else v
            return jnp.where(n >= 2, out, jnp.nan)
        if kind == "hll":
            from .sketches import hll_estimate

            # pane merge used -inf for masked panes; clamp back to 0
            regs = jnp.maximum(c["hll"], 0.0)
            return jnp.round(hll_estimate(regs))
        if kind == "percentile_approx":
            from .sketches import hist_quantile

            return hist_quantile(c["hist"], spec.frac)
        raise ValueError(f"unknown device agg kind {kind}")

    def _components_layout(self):
        """(comp, col_start, width, per-key shape) for the stacked
        components array; one flat (capacity, W) f32 array means ONE device
        leaf -> one transfer/wait round trip (per-leaf waits cost about a
        round trip each). An exact plan's array holds int32 words: its
        float components bit for bit, its limbs as they are."""
        from .aggspec import WIDE_COMPONENTS

        layout = []
        col = 0
        for comp in sorted(self.comp_specs):
            shape: Tuple[int, ...] = (len(self.comp_specs[comp]),)
            if comp in WIDE_COMPONENTS:
                shape = shape + (_wide_size(comp),)
            w = int(np.prod(shape))
            layout.append((comp, col, w, shape))
            col += w
        layout.append(("act", col, 1, ()))
        return layout

    def _components_impl(self, state, pane_mask_tuple):
        """Pane-merged raw components (not final values), stacked into one
        (capacity, W) array — the device half of the latency-hiding emit
        (ops/prefinalize.py). Final values are computed on host after the
        tail shadow is merged in."""
        return self._components_body(
            state, np.array(pane_mask_tuple, dtype=np.bool_))

    def _components_dyn_impl(self, state, pane_mask):
        return self._components_body(state, pane_mask)

    def components_begin_dyn(self, state: Dict[str, Any],
                             pane_mask: np.ndarray):
        """Dispatch the traced-mask components merge over an arbitrary
        live-pane subset and start the async copy; returns a
        PendingFinalize sharing prefinalize_merge's host tail. The
        sliding ring's exact fallback route (runtime/nodes_fused.py)."""
        import jax.numpy as jnp

        from .prefinalize import begin_pending

        out = self._components_dyn(
            state, jnp.asarray(pane_mask, dtype=jnp.bool_))
        return begin_pending(out, self.capacity, self._components_layout())

    def _components_body(self, state, pane_mask):
        import jax
        import jax.numpy as jnp

        with jax.named_scope("kuiper/components/pane_merge"):
            parts = []
            for comp in sorted(self.comp_specs):
                m = self._merged(state, comp, pane_mask)
                parts.append(m.reshape(m.shape[0], -1))
            act = self._merged(state, "act", pane_mask)
            parts.append(act.reshape(-1, 1))
        with jax.named_scope("kuiper/components/stack"):
            if self._exact:
                from jax import lax

                parts = [lax.bitcast_convert_type(p, jnp.int32)
                         for p in parts]
            return jnp.concatenate(parts, axis=1)

    def _pane_mask(self, panes: Optional[List[int]]) -> Tuple[bool, ...]:
        pane_mask = np.zeros(self.n_panes, dtype=np.bool_)
        if panes is None:
            pane_mask[:] = True
        else:
            pane_mask[panes] = True
        return tuple(pane_mask.tolist())

    def prefinalize_begin(self, state: Dict[str, Any],
                          panes: Optional[List[int]] = None):
        """Dispatch the components computation and start the async
        device→host copy; returns a PendingFinalize. Non-blocking: the jax
        program sees an immutable snapshot of `state`, so subsequent folds
        don't disturb it."""
        from .prefinalize import begin_pending

        out = self._components(state, self._pane_mask(panes))
        return begin_pending(out, self.capacity, self._components_layout())

    def _final_from_components(
        self, comb: Dict[str, np.ndarray], n_keys: int,
    ) -> Tuple[List[np.ndarray], np.ndarray]:
        """Numpy final values from pane-merged host components."""
        from .prefinalize import final_value_np

        act = comb["act"]
        outs: List[np.ndarray] = []
        for i, spec in enumerate(self.plan.specs):
            c = {
                comp: comb[comp][:, self.comp_specs[comp].index(i)]
                for comp in spec.components
            }
            outs.append(np.asarray(final_value_np(spec, c, act))[:n_keys])
        outs = apply_int_semantics(self.plan.specs, outs)
        return outs, np.asarray(act[:n_keys])

    def prefinalize_merge(
        self, pending, shadow, n_keys: int,
    ) -> Tuple[List[np.ndarray], np.ndarray]:
        """Complete a pre-issued finalize: fetch device components (usually
        already on host), merge the tail shadow, compute final values in
        numpy. Same (outs, act) contract as finalize(). `pending` None:
        nothing on the device belongs to the window, the shadow is all of
        it."""
        from .prefinalize import merge_components

        if pending is None:
            comb = merge_components(shadow.data, None, n_keys)
        else:
            comb = merge_components(pending.get(), shadow, n_keys)
        return self._final_from_components(comb, n_keys)

    def _hh_finalize_impl(self, state, pane_mask):
        """Device finalize for plans containing heavy_hitters: non-hh specs
        produce their final-value row; hh specs produce 2*k2 rows of
        device-recovered candidate (codes, estimates). One small
        (R, capacity) transfer regardless of sketch width."""
        import jax
        import jax.numpy as jnp

        from .sketches import hh_candidates

        with jax.named_scope("kuiper/hh_finalize/pane_merge"):
            merged = {
                comp: self._merged(state, comp, pane_mask)
                for comp in self.comp_specs
            }
            act = self._merged(state, "act", pane_mask)
        rows = []
        for i, spec in enumerate(self.plan.specs):
            if spec.kind == "heavy_hitters":
                with jax.named_scope("kuiper/hh_finalize/candidates"):
                    hhm = merged["hh"][:, self.comp_specs["hh"].index(i)]
                    codes, est = hh_candidates(hhm, 2 * spec.topk)
                    rows.append(codes.T)  # (k2, cap)
                    rows.append(est.T)
            else:
                with jax.named_scope("kuiper/hh_finalize/values"):
                    col = {
                        comp: merged[comp][:, self.comp_specs[comp].index(i)]
                        for comp in spec.components
                    }
                    rows.append(self._final_value(spec, col)[None, :])
        rows.append(act[None, :])
        with jax.named_scope("kuiper/hh_finalize/stack"):
            return jnp.concatenate(rows, axis=0)

    def hh_assemble(
        self, stacked: np.ndarray, n_keys: int,
        items: Optional[Callable[[int, np.ndarray, list], list]] = None,
    ) -> Tuple[List[np.ndarray], np.ndarray]:
        """Host tail of the heavy-hitters finalize: dedupe candidates (a
        code can appear once per depth) and trim to top-k, every key in one
        pass of array operations (`hh_topk_block`); plain specs read their
        final-value row. Shared by the sync finalize route and the async
        emit worker. A heavy-hitters column holds per-key lists of
        `(code, count)`, or of what `items(spec index, kept codes, kept
        counts)` makes of all keys' kept candidates at once (the served
        path: its `{"value", "count"}` dicts)."""
        from .prefinalize import hh_split, hh_topk_block

        outs: List[np.ndarray] = []
        r = 0
        for i, spec in enumerate(self.plan.specs):
            if spec.kind == "heavy_hitters":
                k2 = 2 * spec.topk
                codes, counts, lens = hh_topk_block(
                    stacked[r:r + k2, :n_keys],
                    stacked[r + k2:r + 2 * k2, :n_keys], spec.topk)
                r += 2 * k2
                counts = counts.tolist()
                outs.append(hh_split(
                    list(zip(codes.tolist(), counts)) if items is None
                    else items(i, codes, counts), lens))
            else:
                outs.append(stacked[r, :n_keys].copy())
                r += 1
        act = stacked[-1]
        outs = apply_int_semantics(self.plan.specs, outs)
        return outs, np.asarray(act[:n_keys])

    def _orderstat_select_impl(self, state, pane_mask, closing):
        """Device finalize of a plan with order statistics: each plain spec
        its final-value row, each order statistic the ROWS_PER_SPEC rows
        of its selection (ops/orderstat.py select_rows), then `act`, then
        a row that holds at 0 the rows the buffers hold in pane `closing`
        (orderstat.pane_rows; -1: none); one (R, capacity) int32 array
        (float rows bit-cast), one transfer."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from . import orderstat

        capacity = state["act"].shape[1]
        with jax.named_scope("kuiper/orderstat_select/pane_merge"):
            merged = {
                comp: self._merged(state, comp, pane_mask)
                for comp in self.comp_specs
            }
            act = self._merged(state, "act", pane_mask)
        with jax.named_scope("kuiper/orderstat_select/sort_select"):
            sel = orderstat.select_rows(state, pane_mask, capacity,
                                        self.os_specs)
        rows = []
        pos = 0
        with jax.named_scope("kuiper/orderstat_select/stack"):
            for i, spec in enumerate(self.plan.specs):
                if spec.os_buf >= 0:
                    rows.extend(sel[pos])
                    pos += 1
                    continue
                col = {
                    comp: merged[comp][:, self.comp_specs[comp].index(i)]
                    for comp in spec.components
                }
                rows.append(lax.bitcast_convert_type(
                    self._final_value(spec, col), jnp.int32))
            rows.append(lax.bitcast_convert_type(act, jnp.int32))
            rows.append(jnp.zeros((capacity,), jnp.int32).at[0].set(
                orderstat.pane_rows(state, closing)))
            return jnp.stack(rows, axis=0)

    @staticmethod
    def os_closed_rows(stacked: np.ndarray) -> int:
        """From a landed selection, the rows the buffers held in the pane
        it closed (`_orderstat_select_impl`'s last row)."""
        return int(stacked[-1, 0])

    def os_assemble(self, stacked: np.ndarray, n_keys: int
                    ) -> Tuple[List[np.ndarray], np.ndarray]:
        """Host tail of an order-statistic finalize: per spec its float64
        values over slots [0, n_keys) — the exact rank, midpoint and
        interpolation of `funcs_agg._percentile` for an order statistic,
        the final value for a plain spec — and `act`."""
        from .orderstat import ROWS_PER_SPEC, final_values

        outs: List[np.ndarray] = []
        r = 0
        for spec in self.plan.specs:
            if spec.os_buf >= 0:
                outs.append(final_values(
                    spec.kind, spec.frac,
                    stacked[r:r + ROWS_PER_SPEC, :n_keys]))
                r += ROWS_PER_SPEC
            else:
                outs.append(stacked[r, :n_keys].view(np.float32).copy())
                r += 1
        act = stacked[-2, :n_keys].view(np.float32)
        outs = apply_int_semantics(self.plan.specs, outs)
        return outs, np.asarray(act)

    def _os_finalize(
        self, state: Dict[str, Any], n_keys: int,
        panes: Optional[List[int]],
    ) -> Tuple[List[np.ndarray], np.ndarray]:
        """Finalize route for order-statistic plans: the device selection,
        fetched, then the host tail."""
        pm = np.zeros(self.n_panes, dtype=np.bool_)
        if panes is None:
            pm[:] = True
        else:
            pm[panes] = True
        # kuiperlint: ignore[host-sync]: the synchronous finalize route — fetching its result IS the point
        stacked = np.asarray(self._os_sel(state, pm, self._pane_arg(-1)))
        return self.os_assemble(stacked, n_keys)

    def _host_finalize(
        self, state: Dict[str, Any], n_keys: int,
        panes: Optional[List[int]],
    ) -> Tuple[List[np.ndarray], np.ndarray]:
        """Finalize route for heavy_hitters plans: fetch the compact device
        result, then assemble the top-k lists on host."""
        pm = np.zeros(self.n_panes, dtype=np.bool_)
        if panes is None:
            pm[:] = True
        else:
            pm[panes] = True
        stacked = np.asarray(self._hh_fin(state, pm))
        return self.hh_assemble(stacked, n_keys)

    def outs_from_finalized(
        self, arr: np.ndarray, n_keys: int,
    ) -> Tuple[List[np.ndarray], np.ndarray]:
        """(per-spec values, act) over slots [0, n_keys) from a landed
        `_finalize` result: the (n_specs + 1, capacity) final values, or an
        exact plan's stacked components through the int64 tail."""
        if self._exact:
            from .prefinalize import merge_components, unpack_components

            comb = merge_components(
                unpack_components(arr, self._components_layout()), None,
                n_keys)
            return self._final_from_components(comb, n_keys)
        host = [arr[i][:n_keys] for i in range(len(self.plan.specs))]
        host = apply_int_semantics(self.plan.specs, host)
        return host, np.asarray(arr[-1][:n_keys])

    def finalize(
        self, state: Dict[str, Any], n_keys: int,
        panes: Optional[List[int]] = None,
    ) -> Tuple[List[np.ndarray], np.ndarray]:
        """Emit final aggregate values for slots [0, n_keys).

        Returns (per-spec value arrays, active-row-count array); keys with
        active == 0 did not appear in this window and must not emit a group.
        NaN encodes NULL for empty-group sum/avg/min/max.
        """
        if self._host_finalize_only:
            return self._host_finalize(state, n_keys, panes)
        if self.n_os_bufs:
            return self._os_finalize(state, n_keys, panes)
        pane_mask = np.zeros(self.n_panes, dtype=np.bool_)
        if panes is None:
            pane_mask[:] = True
            stacked = np.asarray(
                self._finalize(state, tuple(pane_mask.tolist())))
        else:
            # subset masks rotate per window (event time): traced mask,
            # single compiled executable
            pane_mask[panes] = True
            stacked = np.asarray(self._finalize_dyn(state, pane_mask))
        return self.outs_from_finalized(stacked, n_keys)

    # ------------------------------------------------------------------ reset
    def _reset_pane_impl(self, state, pane_idx):
        import jax
        import jax.numpy as jnp

        with jax.named_scope("kuiper/reset_pane/fill"):
            for comp in list(state.keys()):
                if comp == "touch":
                    continue  # per-slot recency survives pane expiry
                if comp in ("osv", "oss"):
                    continue  # rows past the fill are dead already
                if comp == "osf":  # a recycled pane's buffer starts empty
                    state[comp] = state[comp].at[pane_idx].set(0)
                    continue
                init = _INIT[comp]
                arr = state[comp]
                if _lead(comp):  # behind an exact component's word axis
                    state[comp] = arr.at[:, pane_idx].set(jnp.full(
                        arr.shape[:1] + arr.shape[2:], init,
                        dtype=arr.dtype))
                    continue
                state[comp] = arr.at[pane_idx].set(
                    jnp.full(arr.shape[1:], init, dtype=arr.dtype))
        return state

    def reset_pane(self, state: Dict[str, Any], pane_idx: int) -> Dict[str, Any]:
        return self._reset_pane(state, self._pane_arg(pane_idx))

    def reset_all(self, state: Dict[str, Any]) -> Dict[str, Any]:
        return self.init_state()

    # ------------------------------------------------------------- dtype note
    def observe_dtypes(self, columns: Dict[str, np.ndarray]) -> None:
        """Record integer-typed agg inputs for reference-exact finalize."""
        observe_int_inputs(self.plan.specs, columns)

    # ---------------------------------------------------------- checkpointing
    def state_to_host(self, state: Dict[str, Any]) -> Dict[str, np.ndarray]:
        return {k: np.asarray(v) for k, v in state.items()}

    def state_from_host(self, host: Dict[str, np.ndarray]) -> Dict[str, Any]:
        import jax.numpy as jnp

        return {k: jnp.asarray(v) for k, v in host.items()}

    def host_from_partials(
        self, partials: Dict[str, Any],
    ) -> Tuple[Dict[str, np.ndarray], int]:
        """Checkpoint partials -> (typed host arrays, capacity): THE one
        place knowing the per-component restore dtypes (float32 except
        the exact-integer limbs and the uint32 touch column, comp_dtype)
        and reconciling the touch leaf against
        this kernel's track_touch (zero-fill a pre-tier checkpoint,
        drop the column for an untiered kernel — the certs here carry
        no touch leaf). Shared by the fused node and the pane store."""
        host = {k: np.asarray(v, dtype=comp_dtype(k))
                for k, v in partials.items()}
        cap = host["act"].shape[1]
        if self.track_touch:
            if "touch" not in host:
                host["touch"] = np.zeros(cap, dtype=np.uint32)
        else:
            host.pop("touch", None)
        return host, cap
