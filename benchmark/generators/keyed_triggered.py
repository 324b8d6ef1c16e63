"""Keyed JSON rows whose value column stays under a threshold except in rows
placed at known positions — what a trigger-gated rule (`SLIDINGWINDOW ...
OVER (WHEN value > threshold)`) needs: the benchmark, and not chance, says
when the rule answers.

Blocks, keys, seeding, drains and the returned `Pool` are `keyed_rows`';
only the value column differs, which `keyed_rows` cannot express (it cannot
place a row). Parameters as there, with `value` = {column, kind:
normal_triggered, mean, sd, decimals, threshold, trigger_value,
trigger_every_rows, trigger_offset}: a row draws normal(mean, sd) rounded to
`decimals`, capped one step below `threshold`; row `trigger_offset` of every
`trigger_every_rows` rows holds `trigger_value` instead, which lies over the
threshold. The cycled pool has to keep the cadence, so it holds whole periods:
where `trigger_every_rows` does not divide `pool_rows` (a self-test's small
pool) the period is their greatest common divisor. The reference finds the
placed rows as `pool.values > threshold`.
"""
from __future__ import annotations

import math

import numpy as np

from generators.keyed_rows import Pool, _draw_keys


def _draw_triggered(rng, spec: dict, n: int):
    """(values as sent, values as the FLOAT column reads them back)."""
    if spec.get("kind") != "normal_triggered":
        raise ValueError(f"unknown value kind {spec.get('kind')!r}")
    d = int(spec["decimals"])
    every = math.gcd(int(spec["trigger_every_rows"]), n)
    offset = int(spec["trigger_offset"]) % every
    threshold = float(spec["threshold"])
    if not float(spec["trigger_value"]) > threshold:
        raise ValueError("the placed value has to lie over the threshold")
    v = np.rint(rng.normal(spec["mean"], spec["sd"], n) * 10 ** d) / 10 ** d
    # the largest d-decimal value strictly below the threshold
    cap = (math.ceil(round(threshold * 10 ** d, 6)) - 1) / 10 ** d
    v = np.minimum(v, cap)
    v[offset::every] = float(spec["trigger_value"])
    return v, v.astype(np.float32)


def make(seed: int, params: dict) -> Pool:
    rng = np.random.default_rng(seed)
    n_keys = int(params["n_keys"])
    drain_rows = int(params["drain_rows"])
    block_rows = int(params["block_rows"])
    pool_rows = int(params["pool_rows"])
    if block_rows < n_keys or block_rows % drain_rows \
            or pool_rows % block_rows:
        raise ValueError("a block holds every key and whole drains, and the "
                         "pool whole blocks")
    keys = np.empty((pool_rows // block_rows, block_rows), dtype=np.int64)
    for b in range(keys.shape[0]):
        keys[b, :n_keys] = np.arange(n_keys)
        keys[b, n_keys:] = _draw_keys(rng, params.get("keys", {}), n_keys,
                                      block_rows - n_keys)
        rng.shuffle(keys[b])
    keys = keys.ravel()
    raw, as_read = _draw_triggered(rng, params["value"], pool_rows)
    template = b'{"%s":"%s%%d","%s":%%.%df}' % (
        params["key_column"].encode(), params["key_prefix"].encode(),
        params["value"]["column"].encode(), int(params["value"]["decimals"]))
    rows = [template % it for it in zip(keys.tolist(), raw.tolist())]
    drains = [rows[i:i + drain_rows] for i in range(0, pool_rows, drain_rows)]
    return Pool(drains, keys.reshape(-1, drain_rows),
                as_read.reshape(-1, drain_rows), drain_rows, n_keys)
