"""DEBS 2014 Grand Challenge smart-plug load readings as JSON, from a seed:
the source's seven-field record `id, timestamp, value, property, plug_id,
household_id, house_id`, load readings only (`property` 1).

Plugs (`plugs`): `n_plugs` over `n_houses` houses, as even as they divide (a
house one more where they do not); inside a house the plugs fill households
of `plugs_per_household` in turn, so `household_id` and `plug_id` are small
per-house numbers (the split is assumed: the source gives the totals). Every
plug reports at about the same rate, so the rows go round the plugs: each
round of `n_plugs` rows is a fresh seeded permutation of them.

Load, in W: a level per plug from a log-normal (`level_median_w`,
`level_sigma`; capped at `level_cap_w`), read with uniform noise of
`noise_share` either way; a `standby_share` of the plugs read exactly 0 but
for a `standby_active_share` of their readings. Values are rounded to
`decimals` places and written so: ties and zeros are meant to be there.
`id` rises from `first_id`, `timestamp` (unix seconds) from
`first_timestamp` at `events_per_s`.

`Pool.keys` is each row's plug (index into `Pool.ids`, the (house,
household, plug) id triples), `Pool.values` its load as the JSON says it.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

ROW = (b'{"id":%%d,"timestamp":%%d,"value":%%d.%%0%dd,"property":1,'
       b'"plug_id":%%d,"household_id":%%d,"house_id":%%d}')


@dataclass
class Pool:
    drains: list  # drains[i] is a list of `drain_rows` bytes payloads
    keys: np.ndarray  # int64 [n_drains, drain_rows], index into `ids`
    values: np.ndarray  # float64 [n_drains, drain_rows], load in W
    drain_rows: int
    n_keys: int
    ids: np.ndarray  # int64 [n_keys, 3]: house_id, household_id, plug_id


def plug_ids(m: dict) -> np.ndarray:
    """(house_id, household_id, plug_id) of every plug, by plug index."""
    n, houses = int(m["n_plugs"]), int(m["n_houses"])
    per = int(m["plugs_per_household"])
    out = []
    for h in range(houses):
        k = n // houses + (1 if h < n % houses else 0)
        out.extend((h, j // per, j % per) for j in range(k))
    return np.array(out, dtype=np.int64)


def make(seed: int, params: dict) -> Pool:
    rng = np.random.default_rng(seed)
    m = params["plugs"]
    n, drain_rows = int(params["pool_rows"]), int(params["drain_rows"])
    if n % drain_rows:
        raise ValueError("the pool holds whole drains")
    ids = plug_ids(m)
    n_plugs = len(ids)
    rounds = -(-n // n_plugs)
    keys = rng.permuted(np.tile(np.arange(n_plugs), (rounds, 1)),
                        axis=1).ravel()[:n]
    level = np.minimum(
        float(m["level_median_w"])
        * np.exp(float(m["level_sigma"]) * rng.standard_normal(n_plugs)),
        float(m["level_cap_w"]))
    standby = rng.random(n_plugs) < float(m["standby_share"])
    noise = float(m["noise_share"])
    load = level[keys] * (1.0 + rng.uniform(-noise, noise, n))
    idle = standby[keys] & (rng.random(n) >= float(m["standby_active_share"]))
    dec = int(m["decimals"])
    # the load in units of the last decimal written: an exact integer
    units = np.where(idle, 0, np.rint(load * 10 ** dec)).astype(np.int64)
    row_id = int(m["first_id"]) + np.arange(n, dtype=np.int64)
    ts = int(m["first_timestamp"]) + np.arange(n, dtype=np.int64) \
        // int(m["events_per_s"])
    h, hh, p = (ids[keys, i].tolist() for i in range(3))
    row = ROW % dec
    rows = [row % r for r in zip(row_id.tolist(), ts.tolist(),
                                 (units // 10 ** dec).tolist(),
                                 (units % 10 ** dec).tolist(), p, hh, h)]
    drains = [rows[i:i + drain_rows] for i in range(0, n, drain_rows)]
    # the load as the JSON says it: the decimal's nearest double, as a
    # parser reads it (one correctly rounded division of exact integers)
    values = units / float(10 ** dec)
    return Pool(drains, keys.reshape(-1, drain_rows).astype(np.int64),
                values.reshape(-1, drain_rows), drain_rows, n_plugs, ids)
