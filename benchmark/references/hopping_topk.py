"""Plain reference for a grouped hopping rule (window length = 2 hops)
emitting per key and window the row count `c` and `top` = the k most frequent
values of a column with their counts, as a count-min sketch answers them —
numpy over the rows that were sent, nothing of the engine.

Boundaries fall on the wall clock, so which hop a row lands in is not
reproducible; what is, follows from the answers themselves. Rows are folded in
send order and a boundary falls between two micro-batches, so hop j is a
stretch [R_{j-1}, R_j) of the sent stream, window j holds hops j-1 and j, the
first emitted window holds one hop and the last one too. Hence the windows'
totals T_j = Σ_key c give the hops (H_0 = T_0, H_j = T_j - H_{j-1}; a run of
empty hops emits nothing and drops out of the numbering) and the cuts R_j,
and with them *each window's exact rows*:

- every row counted exactly once in each of its two windows, under its own
  key: per key Σ_j c = 2 x its sent rows, and c of every (window, key) equals
  the key's rows in [R_{j-2}, R_j);
- `top`: where the exact counts of the k-th and (k+1)-th most frequent value
  of a (window, key) lie further apart than the sketch's error bound, the k
  values reported are the exact top k; every reported count lies in
  [exact, exact + bound], bound = ceil(e / width x c): count-min never
  undercounts, and overcounts by at most that (its stated epsilon = e / width,
  held with probability 1 - e^-depth, which is why the comparison takes the
  share of counts outside the interval and the mean distance from the exact
  count as a share of the bound, and not a maximum).

The linger timer of the source never fires in this configuration, so the last
rows sent (less than one micro-batch) stay unanswered; `rows_due` says so.
"""
from __future__ import annotations

import math

import numpy as np


def window_rows(payload, params: dict) -> float:
    """How many sent rows one emitted window newly answers: a row is in two
    windows, so half of what the window holds (the first and the last
    window hold one hop each, which makes the halves add up exactly)."""
    return sum(m["c"] for m in payload) / 2


def rows_due(rows_sent: int, params: dict) -> int:
    """Whole micro-batches: the source cuts one when it is full and the
    linger never fires, so a last partial one is not folded."""
    mb = int(params["micro_batch_rows"])
    return rows_sent // mb * mb


# ------------------------------------------------------------ what came out
def from_windows(windows, pool, params: dict) -> dict:
    """Per emitted window and key: `c`, the reported values and counts
    (-1 where the list is shorter than k), and how often a key came twice
    in one window."""
    n, k = pool.n_keys, int(params["topk"])
    skip, col = len(params["key_prefix"]), params["key_column"]
    top_col = params["top_column"]
    c = np.zeros((len(windows), n), dtype=np.int64)
    val = np.full((len(windows), n, k), -1, dtype=np.int64)
    est = np.zeros((len(windows), n, k), dtype=np.int64)
    twice = 0
    too_long = 0
    for j, w in enumerate(windows):
        seen = np.zeros(n, dtype=bool)
        for m in w.payload:
            key = int(m[col][skip:])
            twice += int(seen[key])
            seen[key] = True
            c[j, key] = m["c"]
            top = m[top_col] or []
            too_long += int(len(top) > k)
            for i, pair in enumerate(top[:k]):
                v = pair["value"]  # anything but a sent integer is wrong
                val[j, key, i] = v if isinstance(v, int) else -2
                est[j, key, i] = pair["count"]
    return {"c": c, "val": val, "est": est, "twice": twice,
            "too_long": too_long}


# --------------------------------------------------------- what was sent
class Counts:
    """Exact rows per (key, value) of a stretch of the stream, kept sparse:
    `code` = key x (number of distinct values) + value index, sorted and
    distinct, and `n` rows of each."""

    def __init__(self, code, n, n_keys: int, nv: int) -> None:
        self.code, self.n, self.n_keys, self.nv = code, n, n_keys, nv

    def __add__(self, other: "Counts") -> "Counts":
        code = np.concatenate([self.code, other.code])
        n = np.concatenate([self.n, other.n])
        order = np.argsort(code, kind="stable")
        code, n = code[order], n[order]
        first = np.flatnonzero(np.r_[True, code[1:] != code[:-1]])
        if len(code) == 0:
            return self
        return Counts(code[first], np.add.reduceat(n, first), self.n_keys,
                      self.nv)

    def per_key(self) -> np.ndarray:
        return np.bincount(self.code // self.nv, weights=self.n,
                           minlength=self.n_keys).astype(np.int64)

    def of(self, key: np.ndarray, vidx: np.ndarray) -> np.ndarray:
        """Rows of (key, value index); 0 for an index of -1 or a pair that
        never came."""
        if len(self.code) == 0:
            return np.zeros(np.broadcast(key, vidx).shape, dtype=np.int64)
        want = key * self.nv + np.maximum(vidx, 0)
        i = np.minimum(np.searchsorted(self.code, want), len(self.code) - 1)
        return np.where((self.code[i] == want) & (vidx >= 0), self.n[i], 0)

    def ranked(self, m: int):
        """Per key its m most frequent values: (rows, value index), each
        [n_keys, m], most frequent first (ties: the smaller value first),
        0 / -1 where a key has fewer values."""
        cnt = np.zeros((self.n_keys, m), dtype=np.int64)
        vidx = np.full((self.n_keys, m), -1, dtype=np.int64)
        if len(self.code) == 0:
            return cnt, vidx
        key = self.code // self.nv
        order = np.lexsort((self.code, -self.n, key))
        key, n, code = key[order], self.n[order], self.code[order]
        keys = np.arange(self.n_keys)
        start = np.searchsorted(key, keys)
        end = np.searchsorted(key, keys, side="right")
        at = start[:, None] + np.arange(m)[None, :]
        ok = at < end[:, None]
        at = np.minimum(at, len(code) - 1)
        return np.where(ok, n[at], 0), np.where(ok, code[at] % self.nv, -1)


class Sent:
    """The due rows of the sent stream, in send order: key and value index
    per row (values are indexed into the pool's sorted distinct values)."""

    def __init__(self, pool, sent, params: dict) -> None:
        due = rows_due(len(sent) * pool.drain_rows, params)
        idx = np.asarray(sent, dtype=np.int64)
        self.values = np.unique(pool.values)
        self.key = pool.keys[idx].ravel()[:due]
        self.vidx = np.searchsorted(
            self.values, pool.values[idx].ravel()[:due]).astype(np.int64)
        self.n_keys = pool.n_keys
        self.due = due

    def hop(self, lo: int, hi: int) -> Counts:
        """Exact rows per (key, value) over rows [lo, hi)."""
        nv = len(self.values)
        code, n = np.unique(self.key[lo:hi] * nv + self.vidx[lo:hi],
                            return_counts=True)
        return Counts(code, n.astype(np.int64), self.n_keys, nv)

    def index_of(self, values: np.ndarray) -> np.ndarray:
        """Value → its index, -1 for a value never sent."""
        i = np.clip(np.searchsorted(self.values, values), 0,
                    len(self.values) - 1)
        return np.where(self.values[i] == values, i, -1)


def cuts_from(totals, due: int):
    """Hop sizes from the windows' totals, and the cuts between hops in the
    sent stream; `bad` counts what cannot be: a negative hop, a half row,
    cuts that do not end at the due rows."""
    hops = []
    prev = 0
    bad = 0
    for t in totals:
        h = int(t) - prev
        bad += int(h < 0)
        hops.append(max(h, 0))
        prev = hops[-1]
    cuts = np.minimum(np.cumsum([0] + hops), due)
    bad += int(bool(hops) and hops[-1] != 0) + int(cuts[-1] != due)
    return cuts, bad


def bound_of(c, params: dict):
    """Count-min's stated overcount for a key that holds `c` rows."""
    return np.ceil(math.e / float(params["cm_width"]) * c).astype(np.int64)


def exact_windows(stream: Sent, cuts):
    """Yields per window j (one per total): the exact rows per (key,
    value) over hops j-1 and j, and over hop j alone."""
    prev = stream.hop(0, 0)
    for j in range(len(cuts) - 1):
        new = stream.hop(int(cuts[j]), int(cuts[j + 1]))
        yield j, prev + new, new
        prev = new


def compare(got, pool, sent, params: dict) -> dict:
    """The numbers compared, each beside its limit (PERF.md section 2 gives
    the readings each limit was set from)."""
    lim = params["limits"]
    k = int(params["topk"])
    stream = Sent(pool, sent, params)
    want = np.bincount(stream.key, minlength=pool.n_keys)
    cuts, bad_cuts = cuts_from(got["c"].sum(axis=1), stream.due)
    counts_off = top_wrong = outside = n_est = n_separated = 0
    excess = 0.0
    keys = np.arange(pool.n_keys)[:, None]
    for j, win, _new in exact_windows(stream, cuts):
        exact_c = win.per_key()
        counts_off += int((exact_c != got["c"][j]).sum())
        bound = bound_of(exact_c, params)
        # the k-th and (k+1)-th largest exact counts of each key
        lead, _ = win.ranked(k + 1)
        kth, nxt = lead[:, k - 1], lead[:, k]
        separated = (kth - nxt > bound) & (got["c"][j] > 0)
        val, est = got["val"][j], got["est"][j]
        given = val >= 0
        exact = win.of(keys, stream.index_of(val))
        by_value = np.sort(val, axis=1)
        distinct = (by_value[:, 1:] != by_value[:, :-1]).all(axis=1)
        is_top = (given & (exact >= kth[:, None])).all(axis=1) & distinct
        top_wrong += int((separated & ~is_top).sum())
        n_separated += int(separated.sum())
        off = (est < exact) | (est > exact + bound[:, None])
        outside += int((off & given).sum())
        excess += float((np.abs(est - exact)
                         / np.maximum(bound, 1)[:, None])[given].sum())
        n_est += int(given.sum())
    numbers = {
        "keys_miscounted": (int((got["c"].sum(axis=0) != 2 * want).sum()),
                            lim["keys_miscounted"]),
        "key_twice_in_window": (int(got["twice"]),
                                lim["key_twice_in_window"]),
        "hop_cuts_inconsistent": (int(bad_cuts),
                                  lim["hop_cuts_inconsistent"]),
        "window_counts_off": (int(counts_off), lim["window_counts_off"]),
        "top_values_wrong": (
            int(top_wrong + got["too_long"] + int(n_separated == 0)),
            lim["top_values_wrong"]),
        "top_est_outside_share": (outside / max(n_est, 1),
                                  lim["top_est_outside_share"]),
        "top_est_mean_excess": (excess / max(n_est, 1),
                                lim["top_est_mean_excess"]),
    }
    attempted = int(want.sum())
    failed = int(np.abs(got["c"].sum(axis=0) - 2 * want).sum() // 2)
    return {"numbers": numbers, "attempted": attempted, "failed": failed}


def check(pool, sent, windows, params: dict) -> dict:
    return compare(from_windows(windows, pool, params), pool, sent, params)


# ---- controls: the reference put in the program's place, one thing broken
def exact_answers(pool, sent, cuts, params: dict, sketch=None) -> dict:
    """What the rule owes for the hops cut at `cuts`: exact `c`, the exact
    top k with their exact counts. `sketch(j, win, new)`, where given,
    returns the (key, value) counts window j's `top` is read from instead
    of the window's own."""
    k = int(params["topk"])
    stream = Sent(pool, sent, params)
    n_win = len(cuts) - 1
    got = {"c": np.zeros((n_win, pool.n_keys), dtype=np.int64),
           "val": np.full((n_win, pool.n_keys, k), -1, dtype=np.int64),
           "est": np.zeros((n_win, pool.n_keys, k), dtype=np.int64),
           "twice": 0, "too_long": 0}
    for j, win, new in exact_windows(stream, cuts):
        got["c"][j] = win.per_key()
        cnt, vidx = (win if sketch is None else sketch(j, win, new)) \
            .ranked(k)
        got["val"][j] = np.where(cnt > 0, stream.values[vidx], -1)
        got["est"][j] = cnt
    return got


def _program_cuts(pool, sent, windows, params: dict):
    """The hops as the program cut them in this run (its windows' totals);
    the controls answer for the same hops."""
    got = from_windows(windows, pool, params)
    due = rows_due(len(sent) * pool.drain_rows, params)
    return cuts_from(got["c"].sum(axis=1), due)[0]


def control_drain_lost(pool, sent, windows, params: dict) -> dict:
    """Breaks the guarantee itself: one drain of the due stream (the middle
    one) is never counted; everything else is answered exactly, for the
    hops the program cut."""
    cuts = _program_cuts(pool, sent, windows, params)
    dr = pool.drain_rows
    short = list(sent[:rows_due(len(sent) * dr, params) // dr])
    lost = len(short) // 2
    del short[lost]
    # the hops after the lost drain begin one drain earlier
    cuts = np.where(cuts > lost * dr, np.maximum(cuts - dr, lost * dr), cuts)
    got = exact_answers(pool, short, cuts,
                        {**params, "micro_batch_rows": dr})
    return compare(got, pool, sent, params)


def _middle_window_from_its_newest_hop(pool, sent, windows, params: dict):
    """Exact answers, but the middle window's `top` is read from its newest
    hop alone; returns them, that window's index and that hop's rows per
    key."""
    cuts = _program_cuts(pool, sent, windows, params)
    mid = (len(cuts) - 1) // 2
    newest = {}

    def sketch(j, win, new):
        if j != mid:
            return win
        newest["c"] = new.per_key()
        return new
    got = exact_answers(pool, sent, cuts, params, sketch=sketch)
    return got, mid, newest["c"]


def control_one_pane(pool, sent, windows, params: dict) -> dict:
    """One window (the middle one) built from one pane: `c` and `top` hold
    its newest hop only — a pane merge that forgot the older pane."""
    got, mid, newest_c = _middle_window_from_its_newest_hop(
        pool, sent, windows, params)
    got["c"][mid] = newest_c
    return compare(got, pool, sent, params)


def control_sketch_one_pane(pool, sent, windows, params: dict) -> dict:
    """One window's `top` read from a sketch one pane short, its `c`
    right: the counts say nothing, only the estimates can."""
    got, _mid, _c = _middle_window_from_its_newest_hop(
        pool, sent, windows, params)
    return compare(got, pool, sent, params)


def control_stale_top(pool, sent, windows, params: dict) -> dict:
    """Every window answers `top` from the window before it (a finalize
    that read the state one boundary late), with its own `c`."""
    cuts = _program_cuts(pool, sent, windows, params)
    held = {}

    def stale(j, win, new):
        src = held.get("win", win)
        held["win"] = win
        return src
    return compare(exact_answers(pool, sent, cuts, params, sketch=stale),
                   pool, sent, params)


CONTROLS = {"drain_lost": control_drain_lost,
            "one_pane": control_one_pane,
            "sketch_one_pane": control_sketch_one_pane,
            "stale_top": control_stale_top}
