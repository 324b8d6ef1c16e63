"""Plain reference for a grouped count-window rule emitting `uniq` =
hll(value) per key — the exact distinct count, by numpy, over exactly the
rows each window must hold.

A count window of W rows holds sent rows [k·W, (k+1)·W) of the stream, in send
order, so window k's rows are known from what was sent. Every window's group
count is checked; the kept windows (a seeded sample, a 1M-dict payload each)
are compared key by key, all keys, against the exact distinct count within
the error the configuration states for its sketch.
"""
from __future__ import annotations

import numpy as np


def window_rows(payload, params: dict) -> int:
    """How many sent rows one emitted window holds."""
    return int(params["window_rows"])


def rows_due(rows_sent: int, params: dict) -> int:
    """Only whole count windows are ever answered."""
    return rows_sent // int(params["window_rows"]) * int(params["window_rows"])


def _exact(pool, sent, k: int, params: dict, short_by: int = 0):
    """Exact distinct values per key over count window k of the stream."""
    per = int(params["window_rows"]) // pool.drain_rows
    idx = np.asarray(sent[k * per:(k + 1) * per], dtype=np.int64)
    keys = pool.keys[idx].ravel()
    vals = pool.values[idx].ravel()
    if short_by:
        keys, vals = keys[:-short_by], vals[:-short_by]
    pairs = np.unique(np.stack([keys, vals]), axis=1)
    return np.bincount(pairs[0], minlength=pool.n_keys)


def from_windows(windows, pool, params: dict):
    skip, col = len(params["key_prefix"]), params["key_column"]
    kept = {}
    for w in windows:
        if w.payload is None:
            continue
        uniq = np.full(pool.n_keys, -1, dtype=np.int64)
        for m in w.payload:
            uniq[int(m[col][skip:])] = m["uniq"]
        kept[w.index] = uniq
    return {"n_windows": len(windows),
            "groups": [w.n_groups for w in windows], "kept": kept}


def compare(got, exact_of, n_due: int, pool, params: dict) -> dict:
    lim = params["limits"]
    sigma = float(params["hll_std_err"])
    outside = 0
    abs_err = 0.0
    n = 0
    for k, uniq in got["kept"].items():
        exact = exact_of(k)
        err = np.abs(uniq - exact)
        # the configuration's stated error: 3 sigma of the sketch, and never
        # finer than one (a register collision at tiny cardinalities)
        tol = np.maximum(1.0, np.ceil(3 * sigma * exact))
        outside += int((err > tol).sum())
        abs_err += float(err.sum())
        n += len(exact)
    W = int(params["window_rows"])
    missing = abs(n_due - got["n_windows"])
    numbers = {
        "windows_missing": (missing, lim["windows_missing"]),
        "windows_wrong_groups": (
            sum(1 for g in got["groups"] if g != pool.n_keys),
            lim["windows_wrong_groups"]),
        "no_window_compared": (int(not got["kept"]),
                               lim["no_window_compared"]),
        "outside_tol_share": (outside / max(n, 1),
                              lim["outside_tol_share"]),
        "mean_abs_err": (abs_err / max(n, 1), lim["mean_abs_err"]),
    }
    return {"numbers": numbers, "attempted": n_due * W,
            "failed": missing * W}


def _n_due(pool, sent, params: dict) -> int:
    return len(sent) * pool.drain_rows // int(params["window_rows"])


def check(pool, sent, windows, params: dict) -> dict:
    n_due = _n_due(pool, sent, params)
    return compare(from_windows(windows, pool, params),
                   lambda k: _exact(pool, sent, k, params), n_due, pool,
                   params)


# ---- control: the reference put in the program's place, a guarantee broken
def control_batch_short(pool, sent, windows, params: dict) -> dict:
    """'Each window holds exactly W consecutive rows', broken by the
    engine's own lossy step (drop-oldest under pressure): every kept window
    answers exactly, but for a window one micro-batch short."""
    n_due = _n_due(pool, sent, params)
    got = from_windows(windows, pool, params)
    got["kept"] = {k: _exact(pool, sent, k, params,
                             short_by=int(params["micro_batch_rows"]))
                   for k in got["kept"]}
    return compare(got, lambda k: _exact(pool, sent, k, params), n_due, pool,
                   params)


CONTROLS = {"batch_short": control_batch_short}
