"""Latency-hiding window emit — pre-issued device finalize + host tail shadow.

Why: an emit path that *starts* a device round trip at the window boundary
pays that round trip inside the emit latency; over a slow host↔device link it
can never hit the <50ms p99 target (BASELINE.md north-star row 2). The
reference never faces this (its aggregation state lives in process memory,
internal/topo/node/window_inc_agg_op.go); a TPU-resident design needs an
explicit latency plan.

The plan, exploiting that tumbling/hopping boundaries are known in advance
(timex.align_to_window) and that jax arrays are immutable (a dispatched
program sees a snapshot — no double buffering needed):

  1. One RTT before the boundary, dispatch `components()` on the current
     state and start an async device→host copy (PendingFinalize). The fold
     stream continues uninterrupted.
  2. Rows arriving in the tail window keep folding into the device state
     (so hopping panes / checkpoints stay complete) AND into a HostShadow —
     a numpy mirror of the fold kernel over just those rows (~1-2ms per
     64k-row batch; the tail is a few batches at most).
  3. At the boundary, merge: device components (already on host or in
     flight) ⊕ shadow components, then compute final values in numpy.
     Emit latency = merge + message build, no device round trip.

The shadow folds through the SAME compiled expressions as the device kernel
(host-mode twins from sql/compiler.py) and mirrors its masking rules
(ops/groupby.py _fold_impl), so sync and pre-finalized emits agree to float32
accumulation order.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .aggspec import AggSpec, KernelPlan, LIMB_COMPONENTS, WIDE_COMPONENTS
# identity values / wide register sizes are THE kernel's tables — shared so
# the host shadow can never drift from the device state layout
from .groupby import _INIT, _wide_size, comp_dtype
from .sketches import HIST_BINS, HLL_M, _HIST_HALF, _HIST_HI, _HIST_LO, _LOG_GAMMA, _GAMMA


def _comp_shape(comp: str, spec_idxs: List[int]):
    shape = (len(spec_idxs),)
    if comp in WIDE_COMPONENTS and comp not in LIMB_COMPONENTS:
        shape = shape + (_wide_size(comp),)
    return shape


# ---------------------------------------------------- exact BIGINT partials
#: host identities of the exact components, which the host keeps as int64
_HOST_INIT = {"i1": 0, "imn": np.iinfo(np.int64).max,
              "imx": np.iinfo(np.int64).min}


def _host_comp(comp: str):
    """(identity, dtype) of a component as the host keeps it: the exact
    ones as int64 values, every other one as the device does (float32)."""
    if comp in LIMB_COMPONENTS:
        return _HOST_INIT[comp], np.int64
    return _INIT[comp], np.float32


def limbs_to_int64(comp: str, words: np.ndarray) -> np.ndarray:
    """A fetched exact component as int64 values, one a (key, spec): i1's
    four 16-bit limbs (carried or a few panes' sum of carried ones) added
    modulo 2^64, an extreme's (hi, lo ^ 2^31) int32 pair joined."""
    if comp == "i1":
        w = words.astype(np.uint64)
        with np.errstate(over="ignore"):
            u = (w[..., 0] + (w[..., 1] << np.uint64(16))
                 + (w[..., 2] << np.uint64(32)) + (w[..., 3] << np.uint64(48)))
        return u.view(np.int64)
    hi = words[..., 0].astype(np.int64)
    lo = (words[..., 1].astype(np.int32).view(np.uint32)
          ^ np.uint32(0x80000000)).astype(np.int64)
    return (hi << 32) | lo


#: rows one exact host sum takes: each word's float64 bincount stays below
#: 2^53 (2^21 rows of a 32-bit word)
_I64_SUM_ROWS = 1 << 20


def sum_i64(slots: np.ndarray, v: np.ndarray, cap: int) -> np.ndarray:
    """Per-slot int64 sum of `v`, exact and wrapping modulo 2^64. Where
    every partial sum stays below 2^53 in magnitude (rows x the largest
    |v|: a micro-batch of prices in cents) one float64 bincount holds it
    exactly; else the high (signed) and low (unsigned) 32-bit words are
    summed apart in float64, which holds each exactly, and joined in
    uint64."""
    if len(v) == 0:
        return np.zeros(cap, dtype=np.int64)
    if len(v) * max(abs(int(v.max())), abs(int(v.min()))) < 1 << 53:
        return np.bincount(slots, weights=v.astype(np.float64),
                           minlength=cap)[:cap].astype(np.int64)
    out = np.zeros(cap, dtype=np.uint64)
    for a in range(0, len(v), _I64_SUM_ROWS):
        sl, vv = slots[a:a + _I64_SUM_ROWS], v[a:a + _I64_SUM_ROWS]
        lo = np.bincount(sl, weights=(vv & 0xFFFFFFFF).astype(np.float64),
                         minlength=cap)[:cap]
        hi = np.bincount(sl, weights=(vv >> 32).astype(np.float64),
                         minlength=cap)[:cap]
        with np.errstate(over="ignore"):
            out += ((hi.astype(np.int64).view(np.uint64) << np.uint64(32))
                    + lo.astype(np.uint64))
    return out.view(np.int64)


def trunc_div(s: np.ndarray, d: np.ndarray) -> np.ndarray:
    """int64 s / d rounded toward zero, as Go's int64 division (d > 0)."""
    q = s // d
    return q + ((s - q * d != 0) & (s < 0))


def _int_or_null(vals: np.ndarray, has: np.ndarray) -> np.ndarray:
    """int64 values, or an object column with None where a group holds no
    value (no NaN can stand for NULL in an int64 column)."""
    if has.all():
        return vals
    out = vals.astype(object)
    out[~has] = None
    return out


def _exact_final(spec: AggSpec, c: Dict[str, np.ndarray],
                 act: Optional[np.ndarray]) -> np.ndarray:
    """Final value of an exact spec from int64 components: eKuiper's
    int64 sum / truncating mean / min / max, NULL for a group that holds
    no value — only where the group is in the window (`act`), so a window
    without FILTERed or null values keeps a plain int64 column."""
    n = c["n"]
    has = n > 0 if act is None else (n > 0) | (act <= 0)
    if spec.kind == "sum":
        vals = c["i1"]
    elif spec.kind == "avg":
        vals = trunc_div(c["i1"], np.maximum(n, 1).astype(np.int64))
    else:
        vals = c["imn" if spec.kind == "min" else "imx"]
    return _int_or_null(vals, has)


# ------------------------------------------------------- numpy sketch mirrors
def _splitmix32_np(x: np.ndarray, c1: int, c2: int) -> np.ndarray:
    x = x.astype(np.uint32)
    with np.errstate(over="ignore"):
        x = x ^ (x >> np.uint32(16))
        x = x * np.uint32(c1)
        x = x ^ (x >> np.uint32(15))
        x = x * np.uint32(c2)
        x = x ^ (x >> np.uint32(16))
    return x


def hash_f32_np(v: np.ndarray, salt: int = 0) -> np.ndarray:
    bits = np.ascontiguousarray(np.asarray(v, np.float32)).view(np.uint32)
    bits = bits ^ np.uint32((0x9E3779B9 * (salt + 1)) & 0xFFFFFFFF)
    return _splitmix32_np(bits, 0x7FEB352D, 0x846CA68B)


def hll_parts_np(values: np.ndarray):
    """Numpy twin of sketches.hll_parts (same float32 rho derivation)."""
    h1 = hash_f32_np(values, salt=0)
    h2 = hash_f32_np(values, salt=1)
    reg = (h1 & np.uint32(HLL_M - 1)).astype(np.int32)
    hv = np.maximum(h2, np.uint32(1)).astype(np.float32)
    nbits = np.floor(np.log2(hv)) + np.float32(1.0)
    rho = (np.float32(33.0) - nbits).astype(np.float32)
    return reg, rho


def hll_estimate_np(registers: np.ndarray) -> np.ndarray:
    m = registers.shape[-1]
    alpha = 0.7213 / (1.0 + 1.079 / m)
    z = np.sum(2.0 ** (-registers), axis=-1)
    raw = alpha * m * m / z
    zeros = np.sum(registers == 0.0, axis=-1)
    small = m * np.log(m / np.maximum(zeros, 1).astype(np.float32))
    return np.where((raw < 2.5 * m) & (zeros > 0), small, raw)


def hist_bin_np(values: np.ndarray) -> np.ndarray:
    v = np.asarray(values, np.float32)
    clamped = np.clip(np.abs(v), _HIST_LO, _HIST_HI * 0.999)
    with np.errstate(divide="ignore", invalid="ignore"):
        mag = np.floor(np.log(clamped / _HIST_LO) / _LOG_GAMMA).astype(np.int32)
    mag = np.clip(mag, 0, _HIST_HALF - 1)
    pos = _HIST_HALF + 1 + mag
    neg = _HIST_HALF - 1 - mag
    return np.where(v > 0, pos, np.where(v < 0, neg, _HIST_HALF)).astype(np.int32)


def _hh_slot_np(code: np.ndarray, d: int) -> np.ndarray:
    """Numpy twin of the per-depth slot hash in sketches.hh_update_parts."""
    from .sketches import HH_WIDTH, _hh_salt

    h = _splitmix32_np(
        code.astype(np.uint32) ^ np.uint32(_hh_salt(d)), 0x7FEB352D, 0x846CA68B
    )
    return (h % np.uint32(HH_WIDTH)).astype(np.int32)


def hh_update_parts_np(codes: np.ndarray, mf: np.ndarray):
    """Numpy twin of sketches.hh_update_parts (shadow fold)."""
    from .sketches import HH_BITS, HH_DEPTH, HH_WIDTH

    code = np.nan_to_num(codes, nan=0.0).astype(np.uint32)
    bits = [
        ((code >> np.uint32(b)) & np.uint32(1)).astype(np.float32)
        for b in range(HH_BITS)
    ]
    idx_parts, w_parts = [], []
    for d in range(HH_DEPTH):
        slot = _hh_slot_np(code, d)
        base = (d * HH_WIDTH + slot) * (1 + HH_BITS)
        idx_parts.append(base)
        w_parts.append(mf)
        for b in range(HH_BITS):
            idx_parts.append(base + 1 + b)
            w_parts.append(mf * bits[b])
    return np.stack(idx_parts, axis=1), np.stack(w_parts, axis=1)


def hh_dedupe_topk(codes_row, est_row, k: int):
    """Dedupe estimate-descending candidates (a code can appear once per
    depth) and trim to top-k (code, count) pairs: one key's list, in
    Python. The tail of the numpy components route (hh_topk_np) and the
    reference the device finalize route's array form (hh_topk_block) is
    held to, so both produce identical top lists."""
    seen = set()
    row = []
    for c, e in zip(codes_row, est_row):
        if e <= 0:
            break
        c = int(c)
        if c in seen:
            continue
        seen.add(c)
        row.append((c, int(round(e))))
        if len(row) >= k:
            break
    return row


def hh_topk_block(codes: np.ndarray, est: np.ndarray, k: int
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """`hh_dedupe_topk` over a whole (k2, n_keys) candidate block — one key
    a column, candidates down it as the device's `top_k` returned them — in
    array operations, no Python per key. Returns the kept codes and counts
    (int64, flat, key-major, a key's in candidate order) and the number
    kept per key: what `hh_split` cuts into the per-key lists."""
    with np.errstate(invalid="ignore"):  # a NaN is never among the kept
        code = codes.astype(np.int64)
        count = np.rint(est).astype(np.int64)  # half to even, as round()
    # the scalar loop stops at the first estimate <= 0
    keep = np.logical_and.accumulate(~(est <= 0), axis=0)
    # ... skips a code an earlier candidate had (every earlier one is
    # still alive where this one is), one comparison a distance between
    # the two rather than one a pair. Right for the k2 = 2 * topk of a rule;
    # hh_topk_np's D * W wide candidate list keeps the scalar tail for that
    # reason
    for d in range(1, len(code)):
        keep[d:] &= code[d:] != code[:-d]
    # ... and stops once it holds k
    keep &= np.cumsum(keep, axis=0) <= k
    kept = keep.T  # key-major: boolean indexing reads in C order
    return code.T[kept], count.T[kept], keep.sum(axis=0)


def hh_split(items: list, lens: np.ndarray) -> np.ndarray:
    """Cut a flat key-major list into an object column of per-key lists,
    `lens[j]` items for key j."""
    ends = np.cumsum(lens).tolist()
    return np.fromiter([items[a:b] for a, b in zip([0] + ends, ends)],
                       dtype=np.object_, count=len(ends))


def hh_topk_np(hh: np.ndarray, k: int) -> np.ndarray:
    """Recover per-key top-k (code, count) pairs from the linear
    heavy-hitters sketch. hh: (capacity, HH_SIZE). Returns an object array
    of [(code, est_count), ...] lists, count-descending.

    Recovery: per (depth, slot), bit-majority vote reconstructs the code
    that dominates the slot; a candidate must hash back to its own slot
    (garbage codes from mixed slots almost never do), and its count is the
    count-min estimate (min over depth totals at the code's slots)."""
    from .sketches import HH_BITS, HH_DEPTH, HH_WIDTH

    cap = hh.shape[0]
    a = hh.reshape(cap, HH_DEPTH, HH_WIDTH, 1 + HH_BITS)
    tot = a[..., 0]  # (cap, D, W)
    bits = (a[..., 1:] * 2.0) > tot[..., None]
    codes = np.zeros((cap, HH_DEPTH, HH_WIDTH), dtype=np.uint32)
    for b in range(HH_BITS):
        codes |= bits[..., b].astype(np.uint32) << np.uint32(b)
    ok = tot > 0
    wslots = np.arange(HH_WIDTH, dtype=np.int32)[None, :]
    for d in range(HH_DEPTH):
        ok[:, d, :] &= _hh_slot_np(codes[:, d, :], d) == wslots
    est = np.full(codes.shape, np.inf, dtype=np.float32)
    rows = np.arange(cap)[:, None]
    flat_codes = codes.reshape(cap, -1)
    for d2 in range(HH_DEPTH):
        s = _hh_slot_np(flat_codes, d2)  # (cap, D*W)
        est = np.minimum(est, tot[rows, d2, s].reshape(codes.shape))
    est = np.where(ok, est, 0.0)
    out = np.empty(cap, dtype=np.object_)
    out[:] = [[] for _ in range(cap)]
    flat_est = est.reshape(cap, -1)
    live = np.nonzero(flat_est.max(axis=1) > 0)[0]
    if len(live):
        order = np.argsort(-flat_est[live], axis=1)
        for li, i in enumerate(live.tolist()):
            out[i] = hh_dedupe_topk(
                flat_codes[i, order[li]], flat_est[i, order[li]], k)
    return out


#: bins a block of the two-level quantile search (HIST_BINS = 32 blocks)
_HIST_BLOCK = 32


def _hist_first_reaching(hist: np.ndarray, frac: float):
    """Per key the row total and the first bin whose cumulative count
    reaches frac x total — `argmax(cumsum(hist) >= frac * total)` without
    the cumulative sum over every bin of every key (17M sequential adds at
    16,384 keys x 1,024 bins): block sums first (the totals come from
    them), the cumulative sum over the blocks, then over the bins of the
    one block that reaches the target. Bin counts are whole numbers below
    2^24 carried in float32, so every partial sum is exact in either order
    and both forms name the same bin; they are non-negative, so the first
    block whose running sum reaches the target holds that bin."""
    bins = hist.shape[-1]
    per_block = np.add.reduceat(
        hist, np.arange(0, bins, _HIST_BLOCK), axis=-1)
    upto = np.cumsum(per_block, axis=-1)
    total = upto[..., -1]
    target = np.maximum(frac * total[..., None], 1e-9)
    at = np.argmax(upto >= target, axis=-1)[..., None]
    before = (np.take_along_axis(upto, at, axis=-1)
              - np.take_along_axis(per_block, at, axis=-1))
    blocks = hist.reshape(hist.shape[:-1] + (bins // _HIST_BLOCK,
                                             _HIST_BLOCK))
    inner = np.take_along_axis(blocks, at[..., None], axis=-2)[..., 0, :]
    within = np.argmax(np.cumsum(inner, axis=-1) + before >= target, axis=-1)
    return total, at[..., 0] * _HIST_BLOCK + within


def hist_quantile_np(hist: np.ndarray, frac: float) -> np.ndarray:
    total, idx = _hist_first_reaching(hist, frac)
    mag_idx = np.where(
        idx > _HIST_HALF, idx - _HIST_HALF - 1, _HIST_HALF - 1 - idx
    ).astype(np.float32)
    center = _HIST_LO * np.exp(mag_idx * _LOG_GAMMA) * float(np.sqrt(_GAMMA))
    val = np.where(
        idx == _HIST_HALF, 0.0, np.where(idx > _HIST_HALF, center, -center)
    )
    return np.where(total > 0, val, np.nan)


# -------------------------------------------------------- numpy final values
def final_value_np(spec: AggSpec, c: Dict[str, np.ndarray],
                   act: Optional[np.ndarray] = None) -> np.ndarray:
    """Numpy twin of DeviceGroupBy._final_value (for an exact spec, the
    int64 tail; `act`, the window's activity, bounds where it needs NULL)."""
    kind = spec.kind
    if spec.exact:
        return _exact_final(spec, c, act)
    if kind == "count":
        return c["n"]
    n = c.get("n")
    with np.errstate(invalid="ignore", divide="ignore"):
        if kind == "sum":
            return np.where(n > 0, c["s1"], np.nan)
        if kind == "avg":
            return np.where(n > 0, c["s1"] / np.maximum(n, 1.0), np.nan)
        if kind == "min":
            return np.where(n > 0, c["mn"], np.nan)
        if kind == "max":
            return np.where(n > 0, c["mx"], np.nan)
        if kind in ("stddev", "var"):
            mean = c["s1"] / np.maximum(n, 1.0)
            v = np.maximum(c["s2"] / np.maximum(n, 1.0) - mean * mean, 0.0)
            out = np.sqrt(v) if kind == "stddev" else v
            return np.where(n > 0, out, np.nan)
        if kind in ("stddevs", "vars"):
            mean = c["s1"] / np.maximum(n, 1.0)
            v = np.maximum(
                (c["s2"] - c["s1"] * mean) / np.maximum(n - 1.0, 1.0), 0.0
            )
            out = np.sqrt(v) if kind == "stddevs" else v
            return np.where(n >= 2, out, np.nan)
        if kind == "hll":
            regs = np.maximum(c["hll"], 0.0)
            return np.round(hll_estimate_np(regs))
        if kind == "percentile_approx":
            return hist_quantile_np(c["hist"], spec.frac)
        if kind == "heavy_hitters":
            # (code, count) pairs — the fused node decodes codes back to the
            # original values through its per-column ValueDict
            return hh_topk_np(c["hh"], spec.topk)
    raise ValueError(f"unknown device agg kind {kind}")


# ------------------------------------------------------------- host shadow
#: the shadow sums a micro-batch's row-additive components over the slots
#: it touches where its capacity is at least this many times the rows. The
#: full path costs the capacity once a component, the compact one the rows
#: and the slots they touch; on a TPU v5e host's CPU the two cross near
#: 1.5x for eight aggregates a row, 4x for four, 7x for a count alone
COMPACT_FACTOR = 4


class HostShadow:
    """Numpy mirror of the device fold over the tail rows of a closing
    window. Accumulates the same (n, s1, s2, mn, mx, hll, hist, act)
    components the device kernel keeps, merged into the pre-issued device
    result at emit time."""

    def __init__(self, plan: KernelPlan, comp_specs: Dict[str, List[int]],
                 capacity: int) -> None:
        self.plan = plan
        self.comp_specs = comp_specs
        self.capacity = capacity
        self.data: Dict[str, np.ndarray] = {}
        self.n_rows = 0
        for comp, spec_idxs in comp_specs.items():
            shape = (capacity,) + _comp_shape(comp, spec_idxs)
            init, dtype = _host_comp(comp)
            self.data[comp] = np.full(shape, init, dtype=dtype)
        self.data["act"] = np.zeros(capacity, dtype=np.float32)
        # a slot's index among the slots one micro-batch touches (`_touched`)
        self._slot_index: Optional[np.ndarray] = None

    def _ensure(self, max_slot: int) -> None:
        while max_slot >= self.capacity:
            for comp, arr in self.data.items():
                pad_shape = (self.capacity,) + arr.shape[1:]
                init, dtype = _host_comp(comp)
                pad = np.full(pad_shape, init, dtype=dtype)
                self.data[comp] = np.concatenate([arr, pad], axis=0)
            self.capacity *= 2
            self._slot_index = None

    def _touched(self, slots: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """The slots a micro-batch touches, ascending, and each row's index
        among them — without a sort: a mark a slot, its nonzeros, and a
        slot → index map kept at the shadow's capacity (only the entries of
        this call's slots are read, so stale ones do no harm)."""
        mark = np.zeros(self.capacity, dtype=np.bool_)
        mark[slots] = True
        touched = np.flatnonzero(mark)
        if self._slot_index is None:
            self._slot_index = np.empty(self.capacity, dtype=np.int32)
        self._slot_index[touched] = np.arange(len(touched), dtype=np.int32)
        return touched, self._slot_index[slots]

    def fold(self, cols: Dict[str, np.ndarray], slots: np.ndarray,
             valid: Optional[Dict[str, np.ndarray]] = None) -> int:
        """Mirror one micro-batch; returns the rows it took by the compact
        path (0 by the full one). Where the capacity is at least
        `COMPACT_FACTOR` times the rows, the row-additive components (act,
        n, s1, s2, i1) are summed over the slots the rows touch and added
        there, not over every slot: a float64 bincount adds each slot's
        rows in row order either way, so both paths give the same arrays
        bit for bit. The other components already cost what the rows cost."""
        n = len(slots)
        if n == 0:
            return 0
        self.n_rows += n
        self._ensure(int(slots.max()) if n else 0)
        valid = valid or {}
        cap = self.capacity
        compact = cap >= COMPACT_FACTOR * n
        if compact:
            dest, rbin = self._touched(slots)
            bins = len(dest)
        else:
            dest, rbin, bins = slice(None), slots, cap
        base = np.ones(n, dtype=np.bool_)
        if self.plan.filter_host is not None:
            base &= np.broadcast_to(
                # kuiperlint: ignore[host-sync]: host-shadow fold — `cols` are host numpy columns by contract, no device value in reach
                np.asarray(self.plan.filter_host(cols), dtype=np.bool_), (n,)
            )
        self.data["act"][dest] += np.bincount(
            rbin, weights=base.astype(np.float32), minlength=bins
        )[:bins].astype(np.float32)
        for i, spec in enumerate(self.plan.specs):
            if spec.arg is None:
                v = np.ones(n, dtype=np.float32)
                m = base
            elif spec.exact:
                (col,) = spec.arg.columns
                # kuiperlint: ignore[host-sync]: host-shadow fold on host columns (see filter_host above)
                v = np.asarray(cols[col], dtype=np.int64)
                vm = valid.get(col)
                m = base if vm is None else np.logical_and(base, vm)
            else:
                v = np.broadcast_to(
                    # kuiperlint: ignore[host-sync]: host-shadow fold on host columns (see filter_host above)
                    np.asarray(spec.arg_host(cols), dtype=np.float32), (n,)
                )
                m = base
                for col in spec.arg.columns:
                    vm = valid.get(col)
                    if vm is not None:
                        m = np.logical_and(m, vm)
                m = np.logical_and(m, ~np.isnan(v))
            if spec.filter_host is not None:
                m = np.logical_and(m, np.broadcast_to(
                    # kuiperlint: ignore[host-sync]: host-shadow fold on host columns (see filter_host above)
                    np.asarray(spec.filter_host(cols), dtype=np.bool_), (n,)
                ))
            mf = m.astype(np.float32)
            for comp in spec.components:
                k = self.comp_specs[comp].index(i)
                arr = self.data[comp]
                if comp == "n":
                    arr[dest, k] += np.bincount(rbin, weights=mf,
                                                minlength=bins)[:bins]
                elif comp == "s1":
                    arr[dest, k] += np.bincount(
                        rbin, weights=np.where(m, v, 0.0), minlength=bins
                    )[:bins]
                elif comp == "s2":
                    arr[dest, k] += np.bincount(
                        rbin, weights=np.where(m, v * v, 0.0), minlength=bins
                    )[:bins]
                elif comp == "mn":
                    if m.any():
                        np.minimum.at(arr[:, k], slots[m], v[m])
                elif comp == "mx":
                    if m.any():
                        np.maximum.at(arr[:, k], slots[m], v[m])
                elif comp == "i1":
                    if m.any():
                        with np.errstate(over="ignore"):
                            arr[dest, k] += sum_i64(rbin[m], v[m], bins)
                elif comp == "imn":
                    if m.any():
                        np.minimum.at(arr[:, k], slots[m], v[m])
                elif comp == "imx":
                    if m.any():
                        np.maximum.at(arr[:, k], slots[m], v[m])
                elif comp == "hll":
                    if m.any():
                        reg, rho = hll_parts_np(v)
                        kk = np.full(int(m.sum()), k)
                        np.maximum.at(arr, (slots[m], kk, reg[m]), rho[m])
                elif comp == "hist":
                    if m.any():
                        # one add a distinct (key, bin), not one a row:
                        # sort the rows' flat indices, add the run lengths
                        # (np.add.at costs several times that at the
                        # 200,000 rows of a sliding trigger's edge)
                        flat = ((slots[m].astype(np.int64) * arr.shape[1]
                                 + k) * arr.shape[2] + hist_bin_np(v)[m])
                        at, rows_at = np.unique(flat, return_counts=True)
                        arr.reshape(-1)[at] += rows_at
                elif comp == "hh":
                    if m.any():
                        idx, wts = hh_update_parts_np(v[m], mf[m])
                        sl = slots[m][:, None]
                        kk = np.full((int(m.sum()), 1), k)
                        np.add.at(arr, (sl, kk, idx), wts)
        return n if compact else 0


def unpack_components(arr: np.ndarray, layout) -> Dict[str, np.ndarray]:
    """Split the stacked (capacity, W) components array back into the
    per-component dict, per the kernel's _components_layout(). An exact
    plan's array is int32 words: each component is read back in its own
    device dtype (groupby.comp_dtype), float32 bit for bit."""
    cap = arr.shape[0]
    words = arr.dtype != np.float32
    out = {}
    for comp, col, w, shape in layout:
        part = (arr[:, col] if shape == () else
                arr[:, col:col + w].reshape((cap,) + shape))
        out[comp] = part.view(comp_dtype(comp)) if words else part
    return out


def merge_components(
    dev: Dict[str, np.ndarray], shadow: Optional[HostShadow], n_keys: int,
) -> Dict[str, np.ndarray]:
    """Device components ⊕ shadow components over the window's `n_keys`
    key slots — the slots in use when the window was issued; the rows
    behind them (spare capacity, keys that came later) are no part of it,
    so nothing is computed for them. Pads the device result when the key
    table grew during the tail (new keys exist only in the shadow)."""
    out: Dict[str, np.ndarray] = {}
    if shadow is not None and shadow.n_rows:
        shadow._ensure(n_keys - 1)
    for comp, d in dev.items():
        d = d[:n_keys]
        if comp in LIMB_COMPONENTS and d.dtype != np.int64:
            d = limbs_to_int64(comp, d)  # fetched words, not a shadow's
        if d.shape[0] < n_keys:
            init, _ = _host_comp(comp)
            pad_shape = (n_keys - d.shape[0],) + d.shape[1:]
            d = np.concatenate(
                [d, np.full(pad_shape, init, dtype=d.dtype)], axis=0
            )
        if shadow is not None and shadow.n_rows:
            s = shadow.data[comp][:n_keys]
            if comp in ("mn", "imn"):
                d = np.minimum(d, s)
            elif comp in ("mx", "hll", "imx"):
                d = np.maximum(d, s)
            else:
                with np.errstate(over="ignore"):
                    d = d + s
        out[comp] = d
    return out


def begin_pending(stacked, capacity: int, layout) -> "PendingFinalize":
    """Start the async device→host copy of a dispatched components array
    and wrap it — the ONE async-fetch protocol shared by the prefinalize,
    components_dyn, and sliding-ring dispatch sites."""
    stacked.copy_to_host_async()
    return PendingFinalize(stacked, capacity, layout)


class PendingFinalize:
    """Handle for an in-flight device components fetch, created one RTT
    before the window boundary.

    The fetch runs on its own thread from the moment of creation: the
    wait-until-ready control call can queue FIFO behind subsequently
    dispatched work, so registering the wait EARLY (before the
    tail's fold dispatches flood the link) is what makes the result be on
    host by the time the boundary fires. .get() then just joins the thread.
    """

    def __init__(self, stacked: Any, capacity: int, layout) -> None:
        import threading

        from ..utils import timex

        self.stacked = stacked  # one (capacity, W) device array = one leaf
        self.capacity = capacity
        self.layout = layout  # [(comp, col, width, per-key shape)]
        self._result: Optional[Dict[str, np.ndarray]] = None
        self._exc: Optional[BaseException] = None
        self._done = threading.Event()
        # telemetry for the emit path: when the fetch was issued / landed,
        # in ENGINE-clock ms — mock-clock runs see deterministic timings
        self.t_created = timex.now_ms()
        self.t_done: Optional[int] = None
        threading.Thread(
            target=self._fetch, name="prefinalize-fetch", daemon=True
        ).start()

    def _fetch(self) -> None:
        from ..utils import timex

        try:
            self._result = unpack_components(
                np.asarray(self.stacked), self.layout)
        except BaseException as exc:  # surfaced to the emit thread
            self._exc = exc
        finally:
            self.t_done = timex.now_ms()
            self._done.set()

    def ready(self) -> bool:
        return self._done.is_set()

    def fetch_ms(self) -> float:
        """Issue→landed latency (telemetry); -1 while still in flight."""
        if self.t_done is None:
            return -1.0
        return float(self.t_done - self.t_created)

    def get(self) -> Dict[str, np.ndarray]:
        self._done.wait()
        if self._exc is not None:
            raise self._exc
        return self._result
