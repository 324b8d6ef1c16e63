"""Plain reference for a grouped tumbling rule emitting avg `a`, count `c`,
min `mn`, max `mx` per key and window — numpy over the rows that were sent,
nothing of the engine.

Window boundaries fall on the wall clock, so which window a row lands in is
not reproducible; what is, is conservation: over every window the rule ever
emitted, each key's Σc, Σa·c, min mn and max mx must equal the count, sum, min
and max of that key's sent rows. Every row counted exactly once, by key, with
its value: a lost, doubled, misrouted or mis-decoded row moves one of them.
"""
from __future__ import annotations

import numpy as np


def window_rows(payload, params: dict) -> int:
    """How many sent rows one emitted window holds."""
    return sum(m["c"] for m in payload)


def rows_due(rows_sent: int, params: dict) -> int:
    """How many of the sent rows the rule owes an answer for once it has
    drained: a time window closes on the clock, so all of them."""
    return rows_sent


def from_windows(windows, pool, params: dict):
    """Per key over every emitted window: Σc, Σ a·c, min mn, max mx, and
    how often a key came twice in one window."""
    n, skip = pool.n_keys, len(params["key_prefix"])
    cnt = np.zeros(n, dtype=np.int64)
    tot = np.zeros(n, dtype=np.float64)
    mn = np.full(n, np.inf)
    mx = np.full(n, -np.inf)
    twice = 0
    for w in windows:
        msgs = w.payload
        col = params["key_column"]
        k = np.fromiter((int(m[col][skip:]) for m in msgs), np.int64,
                        len(msgs))
        c = np.fromiter((m["c"] for m in msgs), np.int64, len(msgs))
        a = np.fromiter((m["a"] for m in msgs), np.float64, len(msgs))
        twice += len(k) - len(np.unique(k))
        np.add.at(cnt, k, c)
        np.add.at(tot, k, a * c)
        np.minimum.at(mn, k, np.fromiter(
            (m["mn"] for m in msgs), np.float64, len(msgs)))
        np.maximum.at(mx, k, np.fromiter(
            (m["mx"] for m in msgs), np.float64, len(msgs)))
    return {"cnt": cnt, "tot": tot, "mn": mn, "mx": mx, "twice": twice}


def _sent_rows(pool, sent):
    """(key, value, weight) per pool row: the pool is cycled, so a row
    counts once for each time its drain was sent."""
    times = np.bincount(np.asarray(sent, dtype=np.int64),
                        minlength=len(pool.drains))
    return (pool.keys.ravel(), pool.values.ravel(),
            np.repeat(times, pool.drain_rows).astype(np.float64))


def reference(pool, sent, params: dict):
    """The same four numbers from the rows themselves."""
    n = pool.n_keys
    k, v, w = _sent_rows(pool, sent)
    v = v.astype(np.float64)
    cnt = np.bincount(k, weights=w, minlength=n).astype(np.int64)
    tot = np.bincount(k, weights=w * v, minlength=n)
    live = w > 0
    mn = np.full(n, np.inf)
    mx = np.full(n, -np.inf)
    np.minimum.at(mn, k[live], v[live])
    np.maximum.at(mx, k[live], v[live])
    return {"cnt": cnt, "tot": tot, "mn": mn, "mx": mx, "twice": 0}


def compare(got, want, params: dict) -> dict:
    """The numbers compared, each beside its limit (PERF.md section 2 gives
    the readings each limit was set from)."""
    lim = params["limits"]
    seen = want["cnt"] > 0
    scale = np.maximum(np.abs(want["tot"]), 1.0)
    with np.errstate(invalid="ignore"):
        dmn = np.where(seen, np.abs(got["mn"] - want["mn"]), 0.0)
        dmx = np.where(seen, np.abs(got["mx"] - want["mx"]), 0.0)
    numbers = {
        "keys_miscounted": (int((got["cnt"] != want["cnt"]).sum()),
                            lim["keys_miscounted"]),
        "key_twice_in_window": (int(got["twice"]),
                                lim["key_twice_in_window"]),
        "sum_rel_err": (float((np.abs(got["tot"] - want["tot"])
                               / scale).max()), lim["sum_rel_err"]),
        "min_abs_err": (float(np.nan_to_num(dmn, nan=np.inf).max()),
                        lim["min_abs_err"]),
        "max_abs_err": (float(np.nan_to_num(dmx, nan=np.inf).max()),
                        lim["max_abs_err"]),
    }
    attempted = int(want["cnt"].sum())
    failed = int(np.abs(got["cnt"] - want["cnt"]).sum())
    return {"numbers": numbers, "attempted": attempted, "failed": failed}


def check(pool, sent, windows, params: dict) -> dict:
    return compare(from_windows(windows, pool, params),
                   reference(pool, sent, params), params)


# ---- controls: the reference put in the program's place, one thing broken
def _low_bfloat16(pool, sent, params: dict):
    """The step a later PR would be tempted by: values and sums carried in
    bfloat16 instead of float32 (half the upload bytes)."""
    import ml_dtypes

    bf = ml_dtypes.bfloat16
    low = reference(pool, sent, params)
    k, v, w = _sent_rows(pool, sent)
    v = v.astype(bf).astype(np.float64)
    low["tot"] = np.bincount(k, weights=w * v, minlength=pool.n_keys) \
        .astype(bf).astype(np.float64)
    low["mn"] = low["mn"].astype(bf).astype(np.float64)
    low["mx"] = low["mx"].astype(bf).astype(np.float64)
    return low


def _low_drain_lost(pool, sent, params: dict):
    """Breaks the guarantee itself: one drain of the sent stream (the
    middle one) is never counted — qos 0 without the 'exactly once'."""
    sent = list(sent)
    del sent[len(sent) // 2]
    return reference(pool, sent, params)


def _control(low):
    def run(pool, sent, windows, params: dict) -> dict:
        return compare(low(pool, sent, params), reference(pool, sent, params),
                       params)
    return run


CONTROLS = {"bfloat16": _control(_low_bfloat16),
            "drain_lost": _control(_low_drain_lost)}
