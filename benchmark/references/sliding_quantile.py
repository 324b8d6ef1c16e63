"""Plain reference for a grouped, trigger-gated sliding rule — `GROUP BY key,
SLIDINGWINDOW(ss, L) OVER (WHEN value > threshold)` — emitting per key the row
count `c`, an approximate percentile of a column and the window's end stamp:
numpy in float64 over the rows that were sent, nothing of the engine.

A row over the threshold (a *trigger*; the generator places them, the
reference finds them as `pool.values > threshold`) makes the rule answer with
every key's aggregate over the rows stamped in (t - L, t], t being the
trigger row's stamp. Stamps are the source's ingest clock, so which rows a
window holds is not reproducible; what is, follows from how rows are stamped
and folded, and from the answers themselves:

- one publish (a *drain*) carries one stamp, stamps rise in send order and
  rows are folded in send order, so a window is a stretch [a, b) of the sent
  stream cut at drain edges;
- the rule answers when the micro-batch that holds the trigger row has been
  folded, so b lies between the end of the trigger's drain and the end of its
  micro-batch (drains behind the trigger's that share its millisecond);
- the window holds sum(c) rows, so a = b - sum(c): for each window one of at
  most `micro_batch_rows / drain_rows` stretches has to give **every key's
  exact `c`**;
- the j-th answer reports the j-th trigger's stamp (`we`), so the stamp of
  each trigger's drain is known: the low cut `we` - L of a window falls
  between two of them, and a has to lie between those two drains.

The percentile is held to the exact order statistic of the window's rows of
the key at the sketch's rank convention (the first bin whose cumulative count
reaches frac x n: rank ceil(frac x n), give or take one for float32
arithmetic), widened by the sketch's stated relative error (a log-histogram
answers with the geometric centre of the bin: sqrt(gamma) - 1) and float32
rounding. That is compared as a share of answers outside their interval and
a mean relative distance, over a sample of windows seeded from the pool.

The source's linger timer never fires in this configuration, so the last rows
sent (less than one micro-batch) stay unfolded and a trigger among them is not
due; `rows_due` says so.
"""
from __future__ import annotations

import math

import numpy as np

SAMPLED_WINDOWS = 4
FLOAT32_ROOM = 1e-4  # relative; float32 log/exp of the bin arithmetic


def _spec(params: dict) -> dict:
    return params["value"]


def period_of(params: dict) -> int:
    """Rows from one trigger to the next: `trigger_every_rows`, or where
    that does not divide the pool (a self-test's small pool) the greatest
    common divisor of the two, as the generator places them."""
    return math.gcd(int(_spec(params)["trigger_every_rows"]),
                    int(params["pool_rows"]))


def window_rows(payload, params: dict) -> float:
    """How many sent rows one emitted window newly answers: the rows up to
    its trigger that the trigger before it did not answer — one period (an
    answer proves every row up to its trigger folded)."""
    return float(period_of(params))


def n_triggers_due(rows_sent: int, params: dict) -> int:
    """Triggers whose row lies in a whole micro-batch of the sent rows: the
    source cuts a micro-batch when it is full and the linger never fires,
    so a last partial one is not folded."""
    mb = int(params["micro_batch_rows"])
    every = period_of(params)
    offset = int(_spec(params)["trigger_offset"]) % every
    whole = rows_sent // mb * mb
    return 0 if whole <= offset else (whole - offset - 1) // every + 1


def rows_due(rows_sent: int, params: dict) -> int:
    return n_triggers_due(rows_sent, params) * period_of(params)


def gamma_of(bins: int, params: dict) -> float:
    """The log-histogram's bin ratio: `bins` split into a negative half, a
    zero bin and a positive half, each half spanning [hist_lo, hist_hi)."""
    half = (int(bins) - 1) // 2
    return (float(params["hist_hi"]) / float(params["hist_lo"])) \
        ** (1.0 / (half - 1))


# ------------------------------------------------------------ what came out
def from_windows(windows, pool, params: dict) -> dict:
    """Per emitted window: `c` and the percentile per key (NaN where a key
    is absent), its end stamp, and what cannot be in any window — a key
    twice, an unknown key, groups that disagree on the stamp."""
    n = pool.n_keys
    skip, col = len(params["key_prefix"]), params["key_column"]
    pcol, ecol = params["quantile_column"], params["end_column"]
    c = np.zeros((len(windows), n), dtype=np.int64)
    p = np.full((len(windows), n), np.nan)
    we = np.zeros(len(windows), dtype=np.int64)
    odd = np.zeros(len(windows), dtype=bool)
    for j, w in enumerate(windows):
        seen = np.zeros(n, dtype=bool)
        stamps = set()
        for m in w.payload:
            key = int(m[col][skip:])
            if not 0 <= key < n or seen[key]:
                odd[j] = True
                continue
            seen[key] = True
            c[j, key] = m["c"]
            q = m[pcol]
            p[j, key] = np.nan if q is None else q
            stamps.add(m[ecol])
        odd[j] |= len(stamps) != 1
        we[j] = next(iter(stamps)) if stamps else 0
    return {"c": c, "p": p, "we": we, "odd": odd}


# --------------------------------------------------------- what was sent
class Stream:
    """The sent stream in drains: which drains hold a trigger row, and every
    key's rows in a stretch [a, b) of drains — from per-key prefix counts
    at the drain edges of one pool cycle where the pool was sent in cycles
    (as `run.py` sends it), else by counting the stretch."""

    def __init__(self, pool, sent, params: dict) -> None:
        self.pool = pool
        self.sent = np.asarray(sent, dtype=np.int64)
        self.dr = pool.drain_rows
        self.dpm = int(params["micro_batch_rows"]) // self.dr
        n_pool = len(pool.keys)
        self.cyclic = np.array_equal(
            self.sent, np.arange(len(self.sent)) % n_pool)
        if self.cyclic:
            per = np.zeros((n_pool + 1, pool.n_keys), dtype=np.int64)
            per[1:] = np.bincount(
                (np.arange(n_pool)[:, None] * pool.n_keys
                 + pool.keys).ravel(),
                minlength=n_pool * pool.n_keys).reshape(n_pool, -1)
            self.prefix = np.cumsum(per, axis=0)
        over = (pool.values > float(_spec(params)["threshold"])).sum(axis=1)
        due = n_triggers_due(len(self.sent) * self.dr, params)
        # stream index of the drain of each due trigger, in send order
        self.trigger_drain = np.repeat(
            np.arange(len(self.sent)), over[self.sent])[:due]
        self.due = due

    def counts(self, a: int, b: int) -> np.ndarray:
        """Rows per key over drains [a, b) of the stream."""
        if not self.cyclic:
            return np.bincount(self.pool.keys[self.sent[a:b]].ravel(),
                               minlength=self.pool.n_keys)
        n = len(self.prefix) - 1
        return ((b // n - a // n) * self.prefix[n]
                + self.prefix[b % n] - self.prefix[a % n])

    def rows(self, a: int, b: int, without=None):
        """(key, value) of every row of drains [a, b), less drain
        `without`."""
        idx = self.sent[a:b]
        if without is not None and a <= without < b:
            idx = np.delete(idx, without - a)
        return self.pool.keys[idx].ravel(), self.pool.values[idx].ravel()

    def fit(self, j: int, c_j: np.ndarray):
        """The stretch the j-th answer's counts describe: (a, b, keys whose
        `c` differs from their rows in it) — the best of the stretches that
        end between the trigger's drain and its micro-batch's end and hold
        sum(c) rows; (None, None, every key) where there is none."""
        n_keys = self.pool.n_keys
        total = int(c_j.sum())
        if j >= len(self.trigger_drain) or total % self.dr or total == 0:
            return None, None, n_keys
        d = int(self.trigger_drain[j])
        k = total // self.dr
        end = min((d // self.dpm + 1) * self.dpm, len(self.sent))
        best = (None, None, n_keys)
        for b in range(d + 1, end + 1):
            if b - k < 0:
                continue
            off = int((self.counts(b - k, b) != c_j).sum())
            if off < best[2] or best[0] is None:
                best = (b - k, b, off)
            if off == 0:
                break
        return best


def sample_of(n_windows: int, pool) -> list:
    """The windows whose percentiles are compared: a few, seeded from the
    pool (which the run's seed made)."""
    rng = np.random.default_rng(int(pool.keys[0, :64].sum()))
    take = min(SAMPLED_WINDOWS, n_windows)
    return sorted(rng.choice(n_windows, size=take, replace=False).tolist())


def _sortable(v32: np.ndarray) -> np.ndarray:
    """float32 -> uint32 whose order is the floats' order."""
    u = np.ascontiguousarray(v32, dtype=np.float32).view(np.uint32)
    neg = (u >> np.uint32(31)).astype(bool)
    return np.where(neg, ~u, u | np.uint32(0x80000000))


def _unsortable(u: np.ndarray) -> np.ndarray:
    pos = (u >> np.uint32(31)).astype(bool)
    back = np.where(pos, u & np.uint32(0x7FFFFFFF), ~u).astype(np.uint32)
    return back.view(np.float32).astype(np.float64)


def order_statistics(keys, values, n_keys: int, frac: float):
    """Per key over the given rows: how many, and the values at ranks r - 1,
    r, r + 1 (clipped to the key's rows) for r = ceil(frac x n) — one sort
    of (key, value) codes."""
    code = np.sort((keys.astype(np.uint64) << np.uint64(32))
                   | _sortable(values).astype(np.uint64))
    start = np.searchsorted(
        code, np.arange(n_keys + 1, dtype=np.uint64) << np.uint64(32))
    n = np.diff(start)
    r = np.ceil(frac * n - 1e-9).astype(np.int64)  # 1-based rank
    out = []
    for shift in (-1, 0, 1):
        at = start[:-1] + np.clip(r - 1 + shift, 0, np.maximum(n - 1, 0))
        at = np.minimum(at, max(len(code) - 1, 0))
        out.append(_unsortable(
            (code[at] & np.uint64(0xFFFFFFFF)).astype(np.uint32))
            if len(code) else np.zeros(n_keys))
    return n, out[0], out[1], out[2]


def _widen(x: np.ndarray, root: float, up: bool) -> np.ndarray:
    """The far end of what a log-histogram may answer for a value x: its
    bin's centre lies within a factor sqrt(gamma) of it, on x's side of 0."""
    f = root * (1.0 + FLOAT32_ROOM)
    grow = (x > 0) == up
    return np.where(grow, x * f, x / f)


def sketch_quantile(keys, values, n_keys: int, frac: float, bins: int,
                    params: dict) -> np.ndarray:
    """What a signed log-histogram of `bins` bins a key answers for the
    given rows, in float64: the centre of the first bin whose cumulative
    count reaches frac x n. (The controls' and the self-tests' stand-in for
    the program; the comparison itself never uses it.)"""
    lo, hi = float(params["hist_lo"]), float(params["hist_hi"])
    half = (int(bins) - 1) // 2
    log_gamma = math.log(gamma_of(bins, params))
    v = values.astype(np.float64)
    mag = np.clip(np.abs(v), lo, hi * 0.999)
    idx = np.clip(np.floor(np.log(mag / lo) / log_gamma).astype(np.int64),
                  0, half - 1)
    b = np.where(v > 0, half + 1 + idx, np.where(v < 0, half - 1 - idx, half))
    width = 2 * half + 1
    hist = np.bincount(keys * width + b, minlength=n_keys * width) \
        .reshape(n_keys, width)
    total = hist.sum(axis=1)
    cum = np.cumsum(hist, axis=1)
    at = np.argmax(cum >= np.maximum(frac * total, 1e-9)[:, None], axis=1)
    m = np.where(at > half, at - half - 1, half - 1 - at)
    centre = lo * np.exp((m + 0.5) * log_gamma)
    val = np.where(at == half, 0.0, np.where(at > half, centre, -centre))
    return np.where(total > 0, val, np.nan)


# ------------------------------------------------------------ the comparison
def compare(got, pool, sent, params: dict) -> dict:
    """The numbers compared, each beside its limit (PERF.md section 2 gives
    the readings each limit was set from)."""
    lim = params["limits"]
    stream = Stream(pool, sent, params)
    n_win = len(got["c"])
    n_cmp = min(n_win, stream.due)
    length_ms = int(params["window_s"]) * 1000
    counts_off = outside_bracket = 0
    fits = []
    for j in range(n_cmp):
        a, b, off = stream.fit(j, got["c"][j])
        counts_off += off
        fits.append((a, b) if off == 0 else None)
    counts_off += (n_win - n_cmp) * pool.n_keys  # answers no trigger owes
    # a window that holds two blocks' rows holds every key
    holds_all = got["c"].sum(axis=1) >= 2 * int(params["block_rows"])
    groups_off = int((got["odd"]
                      | (holds_all & ((got["c"] > 0).sum(axis=1)
                                      < pool.n_keys))).sum())
    # the stamps of the trigger drains bracket every window's low cut
    we = got["we"][:n_cmp]
    drains = stream.trigger_drain[:n_cmp]
    outside_bracket += int((np.diff(we) < 0).sum())
    for j, fit in enumerate(fits):
        if fit is None:
            continue
        after = min(int(np.searchsorted(we, we[j] - length_ms,
                                        side="right")), j)
        low = int(drains[after - 1]) + 1 if after > 0 else 0
        outside_bracket += int(not low <= fit[0] <= int(drains[after]))
    # the percentile, over the sampled windows, every key
    frac = float(params["quantile"])
    root = math.sqrt(gamma_of(int(params["hist_bins"]), params))
    n_p = outside = 0
    rel = 0.0
    for j in sample_of(n_win, pool):
        if j >= n_cmp or fits[j] is None:
            n_p += pool.n_keys  # nothing to hold it to: all outside
            outside += pool.n_keys
            rel += float(pool.n_keys)
            continue
        keys, values = stream.rows(*fits[j])
        n, x_lo, x, x_hi = order_statistics(keys, values, pool.n_keys, frac)
        has = n > 0
        p = got["p"][j][has]
        bad = np.isnan(p) | (p < _widen(x_lo[has], root, False)) \
            | (p > _widen(x_hi[has], root, True))
        err = np.abs(np.nan_to_num(p, nan=0.0) - x[has]) \
            / np.maximum(np.abs(x[has]), 1e-12)
        n_p += int(has.sum())
        outside += int(bad.sum())
        rel += float(np.minimum(err, 1.0).sum())
    numbers = {
        "windows_missing": (abs(stream.due - n_win), lim["windows_missing"]),
        "window_groups_off": (groups_off, lim["window_groups_off"]),
        "window_counts_off": (int(counts_off), lim["window_counts_off"]),
        "window_cut_outside_bracket": (
            int(outside_bracket), lim["window_cut_outside_bracket"]),
        "p99_outside_share": (outside / n_p if n_p else 1.0,
                              lim["p99_outside_share"]),
        "p99_mean_rel_err": (rel / n_p if n_p else 1.0,
                             lim["p99_mean_rel_err"]),
    }
    attempted = int(stream.due * pool.n_keys)
    failed = int(min(attempted, abs(stream.due - n_win) * pool.n_keys
                     + counts_off))
    return {"numbers": numbers, "attempted": attempted, "failed": failed}


def check(pool, sent, windows, params: dict) -> dict:
    return compare(from_windows(windows, pool, params), pool, sent, params)


# ---- controls: the reference put in the program's place, one thing broken
def program_fits(pool, sent, windows, params: dict):
    """The stretches as the program cut them in this run (from its answers'
    counts) and its stamps; the controls answer for the same stretches. A
    window whose counts fit no stretch gets the one that ends at its
    trigger's drain."""
    got = from_windows(windows, pool, params)
    stream = Stream(pool, sent, params)
    fits = []
    for j in range(min(len(got["c"]), stream.due)):
        a, b, _off = stream.fit(j, got["c"][j])
        if a is None:
            b = int(stream.trigger_drain[j]) + 1
            a = max(0, b - int(got["c"][j].sum()) // stream.dr)
        fits.append((a, b))
    return stream, fits, got["we"][:len(fits)]


def exact_answers(stream: Stream, fits, we, params: dict, bins=None,
                  without=None) -> dict:
    """What the rule owes for the stretches `fits`: exact `c`, and for the
    sampled windows the answer of a log-histogram of `bins` bins (the
    stated ones by default) over exactly those rows."""
    pool = stream.pool
    bins = int(params["hist_bins"]) if bins is None else bins
    got = {"c": np.zeros((len(fits), pool.n_keys), dtype=np.int64),
           "p": np.full((len(fits), pool.n_keys), np.nan),
           "we": np.asarray(we, dtype=np.int64).copy(),
           "odd": np.zeros(len(fits), dtype=bool)}
    for j, (a, b) in enumerate(fits):
        got["c"][j] = stream.counts(a, b)
        if without is not None and a <= without < b:
            got["c"][j] -= stream.counts(without, without + 1)
    for j in sample_of(len(fits), pool):
        keys, values = stream.rows(*fits[j], without=without)
        got["p"][j] = sketch_quantile(
            keys, values, pool.n_keys, float(params["quantile"]), bins,
            params)
    return got


def control_drain_lost(pool, sent, windows, params: dict) -> dict:
    """Breaks the guarantee itself: one drain of the due stream (the one
    before the middle window's trigger) is never counted; everything else
    is answered exactly, for the stretches the program cut."""
    stream, fits, we = program_fits(pool, sent, windows, params)
    lost = int(stream.trigger_drain[len(fits) // 2]) - 1
    return compare(exact_answers(stream, fits, we, params, without=lost),
                   pool, sent, params)


def control_edge_lost(pool, sent, windows, params: dict) -> dict:
    """Every window without the rows of its low edge bucket: the body of
    whole panes and the head are right, the part of the pane that the low
    cut `we` - L falls into is left out (as many drains as that part of a
    bucket holds at the window's own rate)."""
    stream, fits, we = program_fits(pool, sent, windows, params)
    bucket = int(params["bucket_ms"])
    length_ms = int(params["window_s"]) * 1000
    short = []
    for (a, b), t in zip(fits, we.tolist()):
        cut = t - length_ms
        edge_ms = (cut // bucket + 1) * bucket - cut
        short.append((min(a + round((b - a) * edge_ms / length_ms), b - 1),
                      b))
    return compare(exact_answers(stream, short, we, params), pool, sent,
                   params)


def control_stale_window(pool, sent, windows, params: dict) -> dict:
    """Every answer one trigger late: window j reports, under its own
    stamp, what window j - 1 owed."""
    stream, fits, we = program_fits(pool, sent, windows, params)
    return compare(exact_answers(stream, fits[:1] + fits[:-1], we, params),
                   pool, sent, params)


def control_bins_halved(pool, sent, windows, params: dict) -> dict:
    """The sketch computed one precision below the stated one: half the
    bins (sqrt(gamma) - 1 about 10 % instead of 4.9 %), every count
    right."""
    stream, fits, we = program_fits(pool, sent, windows, params)
    return compare(
        exact_answers(stream, fits, we, params,
                      bins=int(params["hist_bins"]) // 2),
        pool, sent, params)


CONTROLS = {"drain_lost": control_drain_lost,
            "edge_lost": control_edge_lost,
            "stale_window": control_stale_window,
            "bins_halved": control_bins_halved}
