"""The one general row generator: keyed JSON rows from a seed and parameters.

A pool of `pool_rows` rows is cut into blocks of `block_rows`. Every block
holds each of the `n_keys` keys at least once and draws the rest from the key
distribution, then is shuffled; the value column is drawn per row. Rows are
pre-encoded JSON byte payloads grouped into drains of `drain_rows` (one
`memory.publish` each). The arrays the reference needs (key index and value
per row, by drain) come back beside the payloads.

Parameters (from the configuration's `rows`, overlaid by the traffic mix's
`rows`): n_keys, pool_rows, block_rows, drain_rows, key_column, key_prefix,
keys {distribution: uniform | zipf, s}, value {column, kind: normal |
integers, ...}.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Pool:
    drains: list  # drains[i] is a list of `drain_rows` bytes payloads
    keys: np.ndarray  # int64 [n_drains, drain_rows], key index per row
    values: np.ndarray  # [n_drains, drain_rows], value as the JSON reads back
    drain_rows: int
    n_keys: int


def _draw_keys(rng, spec: dict, n_keys: int, n: int) -> np.ndarray:
    kind = spec.get("distribution", "uniform")
    if kind == "uniform":
        return rng.integers(0, n_keys, n)
    if kind == "zipf":
        p = 1.0 / np.arange(1, n_keys + 1) ** float(spec["s"])
        return rng.choice(n_keys, size=n, p=p / p.sum())
    raise ValueError(f"unknown key distribution {kind!r}")


def _draw_values(rng, spec: dict, n: int):
    """(values as the reference sees them, %-format of one value)."""
    kind = spec["kind"]
    if kind == "normal":
        d = int(spec["decimals"])
        v = np.rint(rng.normal(spec["mean"], spec["sd"], n) * 10 ** d) \
            / 10 ** d
        # the engine's column is FLOAT: the JSON text read back as float32
        return v, b"%%.%df" % d, v.astype(np.float32)
    if kind == "integers":
        v = rng.integers(0, int(spec["high"]), n)
        return v, b"%d", v
    raise ValueError(f"unknown value kind {kind!r}")


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
    raw, fmt, as_read = _draw_values(rng, params["value"], pool_rows)
    template = b'{"%s":"%s%%d","%s":%s}' % (
        params["key_column"].encode(), params["key_prefix"].encode(),
        params["value"]["column"].encode(), fmt)
    rows = [template % it for it in zip(keys.tolist(), raw.tolist())]
    drains = [rows[i:i + drain_rows] for i in range(0, pool_rows, drain_rows)]
    return Pool(drains, keys.reshape(-1, drain_rows),
                as_read.reshape(-1, drain_rows), drain_rows, n_keys)
