"""Plain reference for a count per integer key in processing-time tumbling
windows (NEXmark Query 12): each answer is `{<key>, c, ws, we}` — numpy over
the rows that were sent, nothing of the engine.

Window boundaries fall on the wall clock, so which window a bid lands in is
not reproducible; what is, is what the query promises whatever the clock did:

  conservation  per bidder, the sum of `c` over every window the rule ever
                emitted equals the bids sent under that id: every bid counted
                exactly once, under its own bidder, in exactly one window;
  keys          a key comes back as the JSON integer that went in, is one
                that was sent, and appears once in a window;
  windows       every answer of a window carries the same `ws` and `we`,
                `we - ws` is the window's length, `ws` lies on the grid of
                that length, and no two emitted windows overlap (a window
                emitted twice overlaps itself).
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np


def window_rows(payload, params: dict) -> int:
    """How many sent rows one emitted window holds."""
    return sum(m["c"] for m in payload)


def rows_due(rows_sent: int, params: dict) -> int:
    """A time window closes on the clock, so every sent row is owed."""
    return rows_sent


def sent_counts(pool, sent) -> np.ndarray:
    """Bids sent per bidder (index into `pool.ids`): the pool is cycled, so
    a row counts once for each time its drain went out."""
    times = np.bincount(np.asarray(sent, dtype=np.int64),
                        minlength=len(pool.drains))
    return np.bincount(pool.keys.ravel(),
                       weights=np.repeat(times, pool.drain_rows),
                       minlength=pool.n_keys).astype(np.int64)


def from_windows(windows, pool, params: dict) -> dict:
    """What the answers say: Σc per bidder, and every count of a broken
    promise about keys and windows."""
    col, length = params["key_column"], int(params["window_ms"])
    cnt = np.zeros(pool.n_keys, dtype=np.int64)
    twice = bad_keys = bad_length = bad_grid = 0
    spans = []
    for w in windows:
        msgs = w.payload
        raw = [m[col] for m in msgs]
        is_int = np.fromiter((type(k) is int for k in raw), np.bool_,
                             len(raw))
        k = np.fromiter((k if ok else -1 for k, ok in zip(raw, is_int)),
                        np.int64, len(raw))
        at = np.searchsorted(pool.ids, k).clip(0, pool.n_keys - 1)
        known = is_int & (pool.ids[at] == k)
        bad_keys += int((~known).sum())
        c = np.fromiter((m["c"] for m in msgs), np.int64, len(msgs))
        twice += int(known.sum()) - len(np.unique(at[known]))
        np.add.at(cnt, at[known], c[known])
        edges = {(m["ws"], m["we"]) for m in msgs}
        bad_length += sum(1 for ws, we in edges if we - ws != length) \
            + max(len(edges) - 1, 0)
        bad_grid += sum(1 for ws, _ in edges if ws % length)
        spans.extend(edges)
    spans.sort()
    overlap = sum(1 for (_, we), (ws, _) in zip(spans, spans[1:]) if ws < we)
    return {"cnt": cnt, "twice": twice, "bad_keys": bad_keys,
            "bad_length": bad_length, "misplaced": bad_grid + overlap}


def compare(got: dict, want: np.ndarray, params: dict) -> dict:
    lim = params["limits"]
    numbers = {
        "keys_miscounted": (int((got["cnt"] != want).sum()),
                            lim["keys_miscounted"]),
        "key_twice_in_window": (int(got["twice"]),
                                lim["key_twice_in_window"]),
        "keys_not_sent_integers": (int(got["bad_keys"]),
                                   lim["keys_not_sent_integers"]),
        "window_length_off": (int(got["bad_length"]),
                              lim["window_length_off"]),
        "windows_overlapping_or_unaligned": (
            int(got["misplaced"]), lim["windows_overlapping_or_unaligned"]),
    }
    return {"numbers": numbers, "attempted": int(want.sum()),
            "failed": int(np.abs(got["cnt"] - want).sum())}


def check(pool, sent, windows, params: dict) -> dict:
    return compare(from_windows(windows, pool, params),
                   sent_counts(pool, sent), params)


# ---- controls: the program's own answers with one stated guarantee broken
def _drain_lost(pool, sent, windows, params: dict) -> dict:
    """qos 0 without the 'exactly once': the middle drain of the sent
    stream is never counted."""
    got = from_windows(windows, pool, params)
    got["cnt"] = got["cnt"] - np.bincount(
        pool.keys[sent[len(sent) // 2]], minlength=pool.n_keys)
    return compare(got, sent_counts(pool, sent), params)


def _copies(windows):
    return [SimpleNamespace(**{**vars(w),
                               "payload": [dict(m) for m in w.payload]})
            for w in windows]


def _keys_aliased(pool, sent, windows, params: dict) -> dict:
    """What a wrong integer hash gives: two bidder ids share a slot, so in
    every window the second's bids are counted under the first."""
    col = params["key_column"]
    seen = np.flatnonzero(sent_counts(pool, sent))
    a, b = (int(pool.ids[seen[len(seen) // 3]]),
            int(pool.ids[seen[2 * len(seen) // 3]]))
    out = _copies(windows)
    for w in out:
        by_key = {m[col]: m for m in w.payload}
        if b not in by_key:
            continue
        moved = by_key[b]
        if a in by_key:
            by_key[a]["c"] += moved["c"]
            w.payload.remove(moved)
        else:
            moved[col] = a
    return check(pool, sent, out, params)


def _window_merged(pool, sent, windows, params: dict) -> dict:
    """Two adjacent windows (the middle pair) emitted as one: every count
    is kept, the window is twice as long."""
    col = params["key_column"]
    out = _copies(windows)
    i = len(out) // 2
    first, second = out[i - 1], out.pop(i)
    by_key = {m[col]: m for m in first.payload}
    ws = min(m["ws"] for m in first.payload)
    we = max(m["we"] for m in second.payload)
    for m in second.payload:
        if m[col] in by_key:
            by_key[m[col]]["c"] += m["c"]
        else:
            first.payload.append(m)
    for m in first.payload:
        m["ws"], m["we"] = ws, we
    return check(pool, sent, out, params)


CONTROLS = {"drain_lost": _drain_lost, "keys_aliased": _keys_aliased,
            "window_merged": _window_merged}
