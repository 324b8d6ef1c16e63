"""Plain reference for a grouped hopping rule (window length = 2 hops) that
emits per key and window the row count `c` and the exact `median` of a
float column — numpy over the rows that were sent, nothing of the engine.

Which hop a row lands in follows from the answers themselves, as in
`hopping_topk` (whose hop recovery this imports): rows fold in send order, a
boundary falls between two micro-batches, so the windows' totals Σ c give
the hops and the cuts, and with them each window's exact rows. Per window
and key the median is taken over the rows' values rounded to float32 (what
the configuration says the device holds), in float64: the middle value, or
the mean of the two middle values for an even count — eKuiper's `median`.

Numbers compared (limits in the configuration's `reference_params`):
- keys_miscounted: keys whose Σ_j c is not twice their sent rows;
- key_twice_in_window, hop_cuts_inconsistent, window_counts_off: as in
  `hopping_topk`;
- median_off: (window, key) answers further from the reference median than
  one float32 ulp of it (the device holds float32 values; the midpoint may
  be taken on either side), a missing or NULL median included;
- ids_not_sent_integers: groups whose key columns are not the JSON integers
  of a plug that was sent;
- window_length_off: windows whose end less start is not the window length,
  or whose start is off the hop grid.
"""
from __future__ import annotations

import numpy as np

from references import hopping_topk

window_rows = hopping_topk.window_rows
rows_due = hopping_topk.rows_due


def _codes(keys: np.ndarray, values32: np.ndarray) -> np.ndarray:
    """One int64 a row that sorts as (key, value) does: the key above 33
    bits of the float32 value's order (-0 and 0 one value)."""
    bits = (values32 + np.float32(0)).view(np.int32).astype(np.int64)
    order = np.where(bits < 0, -(bits & 0x7FFFFFFF) - 1, bits)
    return (keys.astype(np.int64) << 33) + (order + (1 << 32))


def _values(codes: np.ndarray) -> np.ndarray:
    """The float32 values (as float64) the codes were made from."""
    order = (codes & ((1 << 33) - 1)) - (1 << 32)
    bits = np.where(order < 0, (-order - 1) | 0x80000000, order)
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


def _medians_sorted(codes: np.ndarray, n_keys: int):
    """(count, median) per key from sorted codes: the median in float64,
    NaN where a key has no row."""
    cnt = np.bincount(codes >> 33, minlength=n_keys).astype(np.int64)
    if len(codes) == 0:
        return cnt, np.full(n_keys, np.nan)
    start = np.concatenate([[0], np.cumsum(cnt)[:-1]])
    ok = cnt > 0
    top = len(codes) - 1
    a = _values(codes[np.minimum(start + (cnt - 1) // 2, top)])
    b = _values(codes[np.minimum(start + cnt // 2, top)])
    med = np.where(cnt % 2 == 1, a, (a + b) / 2)
    return cnt, np.where(ok, med, np.nan)


def medians(keys: np.ndarray, values32: np.ndarray, n_keys: int):
    """(count, median) per key over rows (keys, float32 values): the median
    in float64, NaN where a key has no row."""
    return _medians_sorted(np.sort(_codes(keys, values32)), n_keys)


# ------------------------------------------------------------ what came out
def from_windows(windows, pool, params: dict) -> dict:
    """Per emitted window and plug: `c`, the median (NaN where absent or
    NULL); and the groups whose ids are not a sent plug's integers, keys
    twice in a window, windows of the wrong length."""
    n = pool.n_keys
    cols = params["id_columns"]
    plug_of = {tuple(int(x) for x in row): i
               for i, row in enumerate(pool.ids.tolist())}
    length = int(params["window_ms"])
    hop = int(params["hop_ms"])
    col = params["median_column"]
    c = np.zeros((len(windows), n), dtype=np.int64)
    med = np.full((len(windows), n), np.nan)
    twice = not_ids = length_off = 0
    for j, w in enumerate(windows):
        seen = np.zeros(n, dtype=bool)
        for m in w.payload:
            ids = tuple(m.get(k) for k in cols)
            if not all(type(x) is int for x in ids) or ids not in plug_of:
                not_ids += 1
                continue
            key = plug_of[ids]
            twice += int(seen[key])
            seen[key] = True
            c[j, key] = m["c"]
            v = m.get(col)
            med[j, key] = np.nan if v is None else float(v)
        if w.payload:
            m = w.payload[0]
            ws, we = m.get("ws"), m.get("we")
            length_off += int(not (isinstance(ws, int) and isinstance(we, int)
                                   and we - ws == length and ws % hop == 0))
    return {"c": c, "median": med, "twice": twice, "not_ids": not_ids,
            "length_off": length_off}


# ------------------------------------------------------------ what was sent
class Sent:
    """The due rows of the sent stream in send order: plug and float32
    value per row."""

    def __init__(self, pool, sent, params: dict) -> None:
        due = rows_due(len(sent) * pool.drain_rows, params)
        idx = np.asarray(sent, dtype=np.int64)
        self.key = pool.keys[idx].ravel()[:due]
        self.v32 = pool.values[idx].ravel()[:due].astype(np.float32)
        self.n_keys = pool.n_keys
        self.due = due


def exact_windows(stream: Sent, cuts):
    """Yields per window j (one per total): its exact (count, median) per
    plug over hops j-1 and j — each hop's codes sorted once, a window's two
    sorted hops merged (a stable sort finds the two runs)."""
    prev = np.zeros(0, dtype=np.int64)
    for j in range(len(cuts) - 1):
        lo, hi = int(cuts[j]), int(cuts[j + 1])
        new = np.sort(_codes(stream.key[lo:hi], stream.v32[lo:hi]))
        both = np.sort(np.concatenate([prev, new]), kind="stable")
        yield j, _medians_sorted(both, stream.n_keys)
        prev = new


def compare(got, pool, sent, params: dict) -> dict:
    lim = params["limits"]
    stream = Sent(pool, sent, params)
    want = np.bincount(stream.key, minlength=pool.n_keys)
    cuts, bad_cuts = hopping_topk.cuts_from(got["c"].sum(axis=1), stream.due)
    counts_off = median_off = 0
    for j, (cnt, med) in exact_windows(stream, cuts):
        counts_off += int((cnt != got["c"][j]).sum())
        has = cnt > 0
        ulp = np.abs(np.spacing(med.astype(np.float32))).astype(np.float64)
        with np.errstate(invalid="ignore"):
            off = ~(np.abs(got["median"][j] - med) <= ulp)
        median_off += int((off & has).sum())
    numbers = {
        "keys_miscounted": (int((got["c"].sum(axis=0) != 2 * want).sum()),
                            lim["keys_miscounted"]),
        "key_twice_in_window": (int(got["twice"]),
                                lim["key_twice_in_window"]),
        "hop_cuts_inconsistent": (int(bad_cuts),
                                  lim["hop_cuts_inconsistent"]),
        "window_counts_off": (int(counts_off), lim["window_counts_off"]),
        "median_off": (int(median_off), lim["median_off"]),
        "ids_not_sent_integers": (int(got["not_ids"]),
                                  lim["ids_not_sent_integers"]),
        "window_length_off": (int(got["length_off"]),
                              lim["window_length_off"]),
    }
    attempted = int(want.sum())
    failed = int(np.abs(got["c"].sum(axis=0) - 2 * want).sum() // 2)
    return {"numbers": numbers, "attempted": attempted, "failed": failed}


def check(pool, sent, windows, params: dict) -> dict:
    return compare(from_windows(windows, pool, params), pool, sent, params)


# ---- controls: the reference put in the program's place, one thing broken
def exact_answers(pool, sent, cuts, params: dict, values=None,
                  answer=None, median_cuts=None) -> dict:
    """What the rule owes for hops cut at `cuts`. `values(v32)` replaces
    the float32 values the medians are taken over; `answer(keys, v32,
    n_keys)` replaces the median itself; `median_cuts` takes the medians
    over hops cut there instead (the counts keep `cuts`)."""
    stream = Sent(pool, sent, params)
    if values is not None:
        stream.v32 = values(stream.v32)
    n_win = len(cuts) - 1
    got = {"c": np.zeros((n_win, pool.n_keys), dtype=np.int64),
           "median": np.full((n_win, pool.n_keys), np.nan),
           "twice": 0, "not_ids": 0, "length_off": 0}
    mc = cuts if median_cuts is None else median_cuts
    for j in range(n_win):
        lo, hi = int(cuts[max(j - 1, 0)]), int(cuts[j + 1])
        got["c"][j] = np.bincount(stream.key[lo:hi], minlength=pool.n_keys)
        lo, hi = int(mc[max(j - 1, 0)]), int(mc[j + 1])
        k, v = stream.key[lo:hi], stream.v32[lo:hi]
        got["median"][j] = (medians if answer is None else answer)(
            k, v, pool.n_keys)[1]
    return got


def _program_cuts(pool, sent, windows, params: dict):
    got = from_windows(windows, pool, params)
    due = rows_due(len(sent) * pool.drain_rows, params)
    return hopping_topk.cuts_from(got["c"].sum(axis=1), due)[0]


def control_drain_lost(pool, sent, windows, params: dict) -> dict:
    """One drain of the due stream (the middle one) is never counted;
    everything else is answered exactly, for the hops the program cut."""
    cuts = _program_cuts(pool, sent, windows, params)
    dr = pool.drain_rows
    short = list(sent[:rows_due(len(sent) * dr, params) // dr])
    lost = len(short) // 2
    del short[lost]
    cuts = np.where(cuts > lost * dr, np.maximum(cuts - dr, lost * dr), cuts)
    got = exact_answers(pool, short, cuts,
                        {**params, "micro_batch_rows": dr})
    return compare(got, pool, sent, params)


def control_hop_cut_moved(pool, sent, windows, params: dict) -> dict:
    """The medians of the windows beside the middle boundary taken as if
    it fell a micro-batch later (a selection that read a pane's rows a
    micro-batch off its cut), every count right."""
    cuts = np.array(_program_cuts(pool, sent, windows, params))
    moved = cuts.copy()
    mid = len(cuts) // 2
    mb = int(params["micro_batch_rows"])
    moved[mid] = min(int(cuts[mid]) + mb, int(cuts[mid + 1]))
    return compare(exact_answers(pool, sent, cuts, params,
                                 median_cuts=moved), pool, sent, params)


def control_bfloat16(pool, sent, windows, params: dict) -> dict:
    """The medians of the values rounded to bfloat16 (the precision below
    the configuration's float32), counts exact."""
    def to_bf16(v32):
        bits = v32.view(np.uint32).astype(np.uint64)
        bits = (bits + 0x7FFF + ((bits >> 16) & 1)) >> 16 << 16
        return bits.astype(np.uint32).view(np.float32)
    cuts = _program_cuts(pool, sent, windows, params)
    return compare(exact_answers(pool, sent, cuts, params, values=to_bf16),
                   pool, sent, params)


def control_lower_middle(pool, sent, windows, params: dict) -> dict:
    """For an even count the lower middle value instead of the mean of the
    two middle values."""
    def lower(keys, v32, n_keys):
        cnt, _ = medians(keys, v32, n_keys)
        order = np.lexsort((v32, keys))
        v = v32[order].astype(np.float64)
        start = np.concatenate([[0], np.cumsum(cnt)[:-1]])
        at = np.minimum(start + (cnt - 1) // 2, max(len(v) - 1, 0))
        return cnt, np.where(cnt > 0, v[at] if len(v) else np.nan, np.nan)
    cuts = _program_cuts(pool, sent, windows, params)
    return compare(exact_answers(pool, sent, cuts, params, answer=lower),
                   pool, sent, params)


def control_percentile_approx(pool, sent, windows, params: dict) -> dict:
    """`percentile_approx(value, 0.5)` in the median's place: what the
    repo's signed log-histogram (1,024 bins over [1e-9, 1e12), the
    `sliding_quantile` reference's stand-in for it) answers over each
    (window, plug)'s rows; counts exact."""
    from references import sliding_quantile

    hist = {"hist_lo": 1e-9, "hist_hi": 1e12}

    def approx(keys, v32, n_keys):
        cnt, _ = medians(keys, v32, n_keys)
        return cnt, sliding_quantile.sketch_quantile(
            keys, v32, n_keys, 0.5, 1024, hist)
    cuts = _program_cuts(pool, sent, windows, params)
    return compare(exact_answers(pool, sent, cuts, params, answer=approx),
                   pool, sent, params)


CONTROLS = {"drain_lost": control_drain_lost,
            "hop_cut_moved": control_hop_cut_moved,
            "bfloat16": control_bfloat16,
            "lower_middle": control_lower_middle,
            "percentile_approx": control_percentile_approx}
