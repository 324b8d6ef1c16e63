"""Log-bucketed latency histograms — the engine's SLO measurement layer.

The paper's north star is a tail-latency claim (p99 emit < 50ms for the
10k-device tumbling GROUP BY), but a last-value gauge cannot express a
percentile: the engine needs real distributions on the hot path. This is an
HDR-style histogram (Tene's HdrHistogram bucketing, as used by the TiLT and
in-order sliding-window-aggregation evaluations — arxiv 2301.12030 /
2009.13768 both report streaming latency as percentiles): values land in
log₂ buckets subdivided into 2^SUB_BITS linear sub-buckets, giving a fixed
relative error of 2^-SUB_BITS (6.25%) across the whole range with a small,
flat int array — no per-sample allocation, no sorting, O(1) record.

Recording takes one short lock; at the engine's batch granularity (one
record per dispatched item / per window emit, never per row) the cost is
~100ns against multi-microsecond dispatches — the bench records the
measured overhead against the fused fold (BENCH full_pipe
hist_overhead_pct).

Units are the caller's: StatManager records microseconds, the per-rule
end-to-end histogram records milliseconds. Values are clamped to
[0, 2^MAX_BITS).
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence

#: linear sub-buckets per octave = 2^SUB_BITS → relative error 2^-SUB_BITS
SUB_BITS = 4
_SUB = 1 << SUB_BITS
#: values clamp at 2^MAX_BITS - 1 (≈ 35 minutes in µs, ≈ 24 days in ms)
MAX_BITS = 41
_N_BUCKETS = _SUB + (MAX_BITS - SUB_BITS) * _SUB


def _index(v: int) -> int:
    """Bucket index of non-negative int `v` (clamped to the top bucket)."""
    if v < _SUB:
        return v  # exact linear range
    e = v.bit_length() - 1  # floor(log2 v) >= SUB_BITS
    if e >= MAX_BITS:
        return _N_BUCKETS - 1
    shift = e - SUB_BITS
    # mantissa sub-bucket within the octave [2^e, 2^(e+1))
    return _SUB * (e - SUB_BITS + 1) + ((v >> shift) - _SUB)


def _bucket_max(idx: int) -> int:
    """Largest value that maps to bucket `idx` (its inclusive upper edge)."""
    if idx < _SUB:
        return idx
    octave = idx >> SUB_BITS  # >= 1
    mant = idx & (_SUB - 1)
    return ((_SUB + mant + 1) << (octave - 1)) - 1


class LatencyHistogram:
    """Thread-safe log-bucketed histogram: record / merge / percentile /
    snapshot-and-decay. One flat count array, bounded error (6.25%)."""

    __slots__ = ("_counts", "count", "sum", "min", "max", "_lock")

    def __init__(self) -> None:
        self._counts: List[int] = [0] * _N_BUCKETS
        self.count = 0
        self.sum = 0
        self.min = 0
        self.max = 0
        self._lock = threading.Lock()

    # ------------------------------------------------------------- recording
    def record(self, value) -> None:
        v = int(value)
        if v < 0:
            v = 0
        with self._lock:
            self._counts[_index(v)] += 1
            if self.count == 0 or v < self.min:
                self.min = v
            if v > self.max:
                self.max = v
            self.count += 1
            self.sum += v

    def merge(self, other: "LatencyHistogram") -> None:
        """Fold `other`'s distribution into this one (e.g. per-instance
        histograms rolled up to a rule)."""
        with other._lock:
            counts = list(other._counts)
            ocount, osum = other.count, other.sum
            omin, omax = other.min, other.max
        if not ocount:
            return
        with self._lock:
            for i, c in enumerate(counts):
                if c:
                    self._counts[i] += c
            if self.count == 0 or omin < self.min:
                self.min = omin
            if omax > self.max:
                self.max = omax
            self.count += ocount
            self.sum += osum

    # --------------------------------------------------------------- queries
    def _percentiles_locked(self, qs: Sequence[float]) -> List[int]:
        """Values at each percentile of ASCENDING `qs`, ONE bucket walk.
        Caller holds the lock."""
        if self.count == 0:
            return [0] * len(qs)
        targets = [max(1, -(-int(self.count * q) // 100)) for q in qs]  # ceil
        out = [self.max] * len(qs)
        qi = 0
        seen = 0
        for i, c in enumerate(self._counts):
            if not c:
                continue
            seen += c
            while qi < len(targets) and seen >= targets[qi]:
                out[qi] = min(_bucket_max(i), self.max)
                qi += 1
            if qi >= len(targets):
                break
        return out

    def percentile(self, q: float) -> int:
        """Value at percentile q (0-100): the inclusive upper edge of the
        bucket where the cumulative count crosses q — an overestimate by at
        most the bucket's 6.25% relative width. 0 when empty."""
        with self._lock:
            return self._percentiles_locked([q])[0]

    def percentiles(self, qs: Sequence[float]) -> List[int]:
        order = sorted(range(len(qs)), key=lambda i: qs[i])
        with self._lock:
            vals = self._percentiles_locked([qs[i] for i in order])
        out = [0] * len(qs)
        for pos, i in enumerate(order):
            out[i] = vals[pos]
        return out

    def snapshot(self) -> Dict[str, int]:
        """The percentile summary the status/REST layers report — computed
        under ONE lock so a concurrent record burst cannot yield an
        inconsistent summary (p99 below p50, count disagreeing with the
        distribution the percentiles came from)."""
        with self._lock:
            p50, p90, p99 = self._percentiles_locked([50, 90, 99])
            return {
                "count": self.count,
                "p50": p50,
                "p90": p90,
                "p99": p99,
                "max": self.max,
            }

    def snapshot_and_decay(self, factor: float = 0.5) -> Dict[str, int]:
        """Snapshot, then scale every bucket by `factor` (0 clears) — a
        cheap sliding observation window for long-lived rules: old samples
        fade geometrically instead of dominating the distribution forever.
        min/max reset when the decayed histogram is empty. Snapshot and
        decay share ONE lock hold: a sample recorded between them would be
        wiped without ever appearing in any snapshot."""
        with self._lock:
            p50, p90, p99 = self._percentiles_locked([50, 90, 99])
            snap = {"count": self.count, "p50": p50, "p90": p90,
                    "p99": p99, "max": self.max}
            total = s = 0
            for i, c in enumerate(self._counts):
                if c:
                    nc = int(c * factor)
                    self._counts[i] = nc
                    total += nc
                    # bucket-resolution approximation of the decayed sum
                    s += nc * _bucket_max(i)
            self.count = total
            self.sum = min(int(self.sum * factor), s) if total else 0
            if total == 0:
                self.min = self.max = 0
        return snap

    def bucket_counts(self) -> List[int]:
        """Copy of the raw bucket counts — the health plane's delta
        windows subtract two of these to get the distribution of samples
        recorded BETWEEN evaluator ticks (the cumulative histogram itself
        must never be decayed while Prometheus scrapes it)."""
        with self._lock:
            return list(self._counts)

    def record_bucket_counts(self, counts: Sequence[int]) -> None:
        """Fold raw per-bucket count deltas (a `bucket_counts()`
        difference) into this histogram. min/max/sum are maintained at
        bucket resolution (upper edges) — the same ≤6.25% error as every
        other derived quantity."""
        total = s = 0
        lo = hi = -1
        for i, c in enumerate(counts):
            if c > 0:
                total += c
                s += c * _bucket_max(i)
                if lo < 0:
                    lo = i
                hi = i
        if not total:
            return
        with self._lock:
            for i, c in enumerate(counts):
                if c > 0:
                    self._counts[i] += c
            lo_v, hi_v = _bucket_max(lo), _bucket_max(hi)
            if self.count == 0 or lo_v < self.min:
                self.min = lo_v
            if hi_v > self.max:
                self.max = hi_v
            self.count += total
            self.sum += s

    def _cumulative_locked(self, bounds: Sequence[int]) -> List[int]:
        out = [0] * len(bounds)
        bi = 0
        cum = 0
        for i, c in enumerate(self._counts):
            if not c:
                continue
            edge = _bucket_max(i)
            while bi < len(bounds) and bounds[bi] < edge:
                out[bi] = cum
                bi += 1
            if bi >= len(bounds):
                break
            cum += c
        for j in range(bi, len(bounds)):
            out[j] = cum
        return out

    def cumulative(self, bounds: Sequence[int]) -> List[int]:
        """Cumulative counts at each upper bound (`le` semantics) for
        Prometheus histogram exposition. A sample counts toward the first
        bound >= its bucket's upper edge, so the mapping is conservative
        (never under-reports latency). `bounds` must be ascending."""
        with self._lock:
            return self._cumulative_locked(bounds)

    def export(self, bounds: Sequence[int]):
        """(cumulative bucket counts, total count, sum) captured under ONE
        lock — a concurrent record() between separate reads could otherwise
        leave a finite `le` bucket exceeding `+Inf` (non-monotonic series,
        NaN histogram_quantile)."""
        with self._lock:
            return self._cumulative_locked(bounds), self.count, self.sum


#: canonical `le` ladder (ms) for the per-rule ingest→emit histogram — spans
#: sub-SLO (the 50ms north star sits mid-ladder) to window-length dwells
E2E_BOUNDS_MS = (1, 2, 5, 10, 25, 50, 100, 250, 500,
                 1000, 2500, 5000, 10000, 30000, 60000)


#: `le` ladder (µs) of the per-boundary phase histogram, rendered in ms:
#: 0.1 ms (a timer's lateness) to 60 s (a million-group sink conversion),
#: six steps a decade so a quantile read off the buckets is at most a
#: step's width off
BOUNDARY_BOUNDS_US = tuple(
    int(m * 10 ** e) for e in range(2, 7)
    for m in (1, 1.5, 2, 3, 5, 7.5)) + (
    10_000_000, 15_000_000, 20_000_000, 30_000_000, 60_000_000)


def render_prom_histogram(out: List[str], name: str, labels: str,
                          hist: Optional[LatencyHistogram],
                          bounds: Sequence[int] = E2E_BOUNDS_MS,
                          scale: int = 1) -> None:
    """Append `{name}_bucket/_sum/_count` exposition lines for one labeled
    histogram (labels = pre-escaped `key="value"` pairs, no braces).
    `scale` divides bounds and sum on the way out: a histogram recorded in
    microseconds renders in milliseconds with `scale=1000`."""
    if hist is None:
        return
    sep = "," if labels else ""
    cum, count, total = hist.export(bounds)
    for b, c in zip(bounds, cum):
        le = b if scale == 1 else b / scale
        out.append(f'{name}_bucket{{{labels}{sep}le="{le:g}"}} {c}')
    out.append(f'{name}_bucket{{{labels}{sep}le="+Inf"}} {count}')
    out.append(f"{name}_sum{{{labels}}} "
               f"{total if scale == 1 else total / scale}")
    out.append(f"{name}_count{{{labels}}} {count}")
