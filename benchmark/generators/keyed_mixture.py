"""Keyed JSON rows whose value column is a mixture: a few heavy values with
stated shares and a uniform tail of many distinct ones — what a rule that
ranks the most frequent values per key is there to tell apart.

Blocks, keys, seeding, drains and the returned `Pool` are `keyed_rows`';
only the value column differs, which `keyed_rows` cannot express. Parameters
as there, with `value` = {column, kind: mixture, heavy: [[value, share],
...], tail: {low, high}}: a row takes heavy value i with its share and else a
value uniform in [low, high). Values are integers and go out as JSON
integers.
"""
from __future__ import annotations

import numpy as np

from generators.keyed_rows import Pool, _draw_keys


def _draw_mixture(rng, spec: dict, n: int) -> np.ndarray:
    if spec.get("kind") != "mixture":
        raise ValueError(f"unknown value kind {spec.get('kind')!r}")
    values = rng.integers(int(spec["tail"]["low"]), int(spec["tail"]["high"]),
                          n)
    p = rng.random(n)
    edge = 0.0
    for value, share in spec["heavy"]:
        values[(p >= edge) & (p < edge + float(share))] = int(value)
        edge += float(share)
    if edge >= 1.0:
        raise ValueError("the heavy shares leave no tail")
    return values.astype(np.int64)


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
    values = _draw_mixture(rng, params["value"], pool_rows)
    template = b'{"%s":"%s%%d","%s":%%d}' % (
        params["key_column"].encode(), params["key_prefix"].encode(),
        params["value"]["column"].encode())
    rows = [template % it for it in zip(keys.tolist(), values.tolist())]
    drains = [rows[i:i + drain_rows] for i in range(0, pool_rows, drain_rows)]
    return Pool(drains, keys.reshape(-1, drain_rows),
                values.reshape(-1, drain_rows), drain_rows, n_keys)
