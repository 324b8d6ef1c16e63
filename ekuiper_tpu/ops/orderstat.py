"""Exact order statistics on the device — eKuiper's `median`,
`percentile_cont` and `percentile_disc` (functions/funcs_agg.py
`_percentile`; reference funcs_agg.go) without a mergeable partial.

An order statistic needs a window's values themselves, so its state is a
row buffer and not a per-key partial. Every live pane holds `(slot int32,
value float32)` rows in the group-by state (`osv` / `oss`, shape (buffers,
panes, rung)) with the pane's fill (`osf`, int32 (panes,)):

- the fold appends a micro-batch's rows at the pane's fill in one dynamic
  update slice; a row whose value is NULL, NaN or non-numeric, or that a
  WHERE or FILTER rejects, keeps its place with the dead slot, so the fill
  advances by the micro-batch's rows and the host knows it without a sync;
- the rung (the buffers' last axis) climbs a power-of-two ladder from
  `first_rung` (128 micro-batches a pane), on the device (`jnp.pad`),
  before a micro-batch would not fit: a row is never dropped;
- at a boundary `select_rows` sorts the window's live rows by (slot,
  value) — one `lax.sort` — takes each slot's segment by binary search,
  and gathers for every order-statistic spec the slot's row count, the
  base rank it read from, and the four values at ranks base .. base + 3
  around `frac x (count - 1)`: capacity x 6 words a spec come back, never
  the rows — and the closing pane's held rows (`pane_rows`), the device's
  own count of what its buffers took;
- the host (`final_values`) finishes in float64 exactly as `_percentile`
  and `statistics.median` define it: the exact rank, the even-count
  midpoint, the interpolation.

Every order statistic of one argument under one FILTER reads one buffer
(KernelPlan.os_bufs). A pane's buffer is recycled as the pane is: its fill
goes back to 0 (the rows stay, past the fill, until overwritten).
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

#: the slot a buffered row takes when it belongs to no group: past every
#: key capacity, so it sorts after every live row
DEAD_SLOT = (1 << 31) - 1

#: state leaves of the buffers: values, slots, per-pane fill
OS_LEAVES = ("osf", "oss", "osv")

#: int32 words a spec's selection hands the host per key slot: the slot's
#: row count, the base rank, the values at ranks base .. base + 3
ROWS_PER_SPEC = 6

#: values fetched around the float32 rank: the host's float64 rank lies
#: within one of the device's, and the upper neighbour one past it
NEIGHBOURS = 4


#: micro-batches a pane's buffer holds at its first rung: every rung is a
#: shape the fold, the selection and the pane reset compile for (the
#: selection's sort about 40 s on a v5e host, whatever its length), so
#: the ladder starts where a pane of a saturated one-second hop fits with
#: room: 4,194,304 rows at 32,768-row micro-batches, against the ~2.3 M a
#: v5e host folds in a second — such a rule climbs only past 1.8 times
#: that rate, so its warm-up compiles every shape it will run
FIRST_RUNG_BATCHES = 128


def first_rung(micro_batch: int) -> int:
    """The buffers' first rung, in rows a pane (a power of two whenever
    the micro-batch is one)."""
    return FIRST_RUNG_BATCHES * int(micro_batch)


def rung_for(fill: int, rows: int, rung: int, micro_batch: int) -> int:
    """The rung a pane holding `fill` rows needs to take `rows` more: every
    chunk of a fold writes a whole padded micro-batch at the fill, so the
    last chunk's end must fit. Doubles `rung` until it does."""
    chunks = -(-max(int(rows), 1) // int(micro_batch))
    need = int(fill) + chunks * int(micro_batch)
    while rung < need:
        rung *= 2
    return rung


def init_leaves(n_bufs: int, n_panes: int, rung: int) -> dict:
    import jax.numpy as jnp

    return {
        "osv": jnp.zeros((n_bufs, n_panes, rung), dtype=jnp.float32),
        "oss": jnp.full((n_bufs, n_panes, rung), DEAD_SLOT,
                        dtype=jnp.int32),
        "osf": jnp.zeros((n_panes,), dtype=jnp.int32),
    }


def grow_leaves(state: dict, rung: int) -> dict:
    """The buffers padded to `rung` rows a pane, on the device; the fills
    are unchanged."""
    import jax.numpy as jnp

    out = dict(state)
    have = state["osv"].shape[-1]
    pad = [(0, 0), (0, 0), (0, rung - have)]
    out["osv"] = jnp.pad(state["osv"], pad)
    out["oss"] = jnp.pad(state["oss"], pad, constant_values=DEAD_SLOT)
    return out


def append(state: dict, bufs: List[Tuple[object, object]], slots, pane_idx,
           n_valid) -> dict:
    """Inside the fold: each buffer's rows of one padded micro-batch at the
    pane's fill — the value, and the slot where the row's mask holds (the
    dead slot elsewhere) — and the fill advanced by the real rows."""
    import jax.numpy as jnp
    from jax import lax

    fill = state["osf"][pane_idx]
    vals = jnp.stack([v.astype(jnp.float32) for v, _ in bufs])[:, None, :]
    keep = jnp.stack([jnp.where(m, slots, jnp.int32(DEAD_SLOT))
                      for _, m in bufs])[:, None, :]
    at = (jnp.int32(0), pane_idx, fill)
    state["osv"] = lax.dynamic_update_slice(state["osv"], vals, at)
    state["oss"] = lax.dynamic_update_slice(state["oss"], keep, at)
    state["osf"] = state["osf"].at[pane_idx].add(
        jnp.asarray(n_valid, dtype=jnp.int32))
    return state


def pane_rows(state: dict, pane):
    """The rows of `pane` that some buffer holds under a live slot: what
    the device took into its order statistics there since the pane's reset
    (kuiper_orderstat_rows_total counts it as a pane closes); 0 for a pane
    outside the state (-1: a finalize that closes none)."""
    import jax.numpy as jnp

    n_panes, rung = state["osf"].shape[0], state["oss"].shape[-1]
    at = jnp.clip(pane, 0, n_panes - 1)
    held = jnp.any(state["oss"][:, at, :] != jnp.int32(DEAD_SLOT), axis=0)
    held = jnp.logical_and(held, jnp.arange(rung, dtype=jnp.int32)
                           < state["osf"][at])
    n = jnp.sum(held, dtype=jnp.int32)
    return jnp.where(jnp.logical_and(pane >= 0, pane < n_panes), n, 0)


def select_rows(state: dict, pane_mask, capacity: int,
                os_specs: List[Tuple[int, float]]) -> dict:
    """The selection of every order-statistic spec over the panes in
    `pane_mask`: {spec position: [count, base, v0, v1, v2, v3]} as int32
    rows of `capacity` (values bit-cast). `os_specs` holds (buffer,
    fraction) a spec; each buffer read is sorted once."""
    import jax.numpy as jnp
    from jax import lax

    n_bufs, n_panes, rung = state["osv"].shape
    live = jnp.logical_and(
        jnp.arange(rung, dtype=jnp.int32)[None, :]
        < state["osf"][:, None], pane_mask[:, None])
    q = jnp.arange(capacity, dtype=jnp.int32)
    sorted_bufs = {}
    out = {}
    for pos, (buf, frac) in enumerate(os_specs):
        if buf not in sorted_bufs:
            s = jnp.where(live, state["oss"][buf],
                          jnp.int32(DEAD_SLOT)).reshape(-1)
            v = state["osv"][buf].reshape(-1)
            s, v = lax.sort((s, v), num_keys=2, is_stable=False)
            start = jnp.searchsorted(s, q, side="left").astype(jnp.int32)
            end = jnp.searchsorted(s, q, side="right").astype(jnp.int32)
            sorted_bufs[buf] = (v, start, end - start)
        v, start, cnt = sorted_bufs[buf]
        last = jnp.maximum(cnt - 1, 0)
        base = jnp.floor(jnp.float32(frac) * last.astype(jnp.float32)
                         ).astype(jnp.int32) - 1
        rows = [cnt, base]
        top = v.shape[0] - 1
        for j in range(NEIGHBOURS):
            at = jnp.clip(start + jnp.clip(base + j, 0, last), 0, top)
            rows.append(lax.bitcast_convert_type(v[at], jnp.int32))
        out[pos] = rows
    return out


def final_values(kind: str, frac: float, rows: np.ndarray) -> np.ndarray:
    """float64 results of one spec from its landed (6, n) int32 rows: NaN
    where the slot had no value (SQL NULL), else exactly what
    `funcs_agg._percentile` / `statistics.median` give over the values as
    float32 held them."""
    n = rows[0].astype(np.int64)
    base = rows[1].astype(np.int64)
    vals = np.ascontiguousarray(rows[2:2 + NEIGHBOURS]).view(np.float32) \
        .astype(np.float64)
    ok = n > 0
    last = np.maximum(n - 1, 0)
    idx = float(frac) * last.astype(np.float64)
    lo = np.floor(idx).astype(np.int64)
    w = idx - lo
    cols = np.arange(len(n))

    def at(rank):
        return vals[np.clip(rank - base, 0, NEIGHBOURS - 1), cols]

    a = at(lo)
    hi = np.minimum(lo + 1, last)
    with np.errstate(invalid="ignore", over="ignore"):
        if kind == "median":
            # statistics.median: the middle value, or the mean of the two
            out = np.where(w == 0, a, (a + at(hi)) / 2)
        elif kind == "percentile_cont":
            out = np.where(n == 1, a, a * (1 - w) + at(hi) * w)
        elif kind == "percentile_disc":
            up = np.minimum(np.round(idx + 0.5).astype(np.int64), last)
            out = np.where(w != 0, at(up), a)
        else:  # pragma: no cover - planner admits only ORDERSTAT_AGGS
            raise ValueError(f"not an order statistic: {kind}")
    return np.where(ok, out, np.nan)
