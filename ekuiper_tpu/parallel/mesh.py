"""Device mesh construction — the scale-out substrate.

The reference scales by process-level fan-out (N rules × M goroutines, plugin
worker processes over nanomsg IPC — SURVEY §5); the TPU-native equivalent is
a jax.sharding.Mesh with two logical axes:

- "rows": data parallelism over incoming event batches (the analogue of the
  reference's shared-source fan-out);
- "keys": GROUP BY key-axis sharding — each device owns a contiguous slot
  range of the per-key aggregation state (the analogue obligation SURVEY §5
  names "sequence parallel" for this workload).

Collectives ride ICI: per-batch partial folds merge with psum over "rows";
emits all_gather over "keys" only at window triggers.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np


def make_mesh(
    rows: int = 1, keys: Optional[int] = None, devices: Optional[Sequence] = None,
):
    """Build a Mesh with axes ("rows", "keys"). Defaults to putting all
    devices on the keys axis (state capacity is usually the scale limit)."""
    import jax
    from jax.sharding import Mesh

    devs = list(devices) if devices is not None else jax.devices()
    n = len(devs)
    if keys is None:
        keys = n // rows
    if rows * keys != n:
        raise ValueError(f"mesh {rows}x{keys} != {n} devices")
    arr = np.asarray(devs).reshape(rows, keys)
    return Mesh(arr, ("rows", "keys"))


def ensure_devices(n: int):
    """Return the first n devices of the default platform, or raise.

    Never another platform's: a mesh asked for on a TPU host with fewer
    chips than the geometry needs must fail, not run quietly on host CPU
    devices. More CPU devices than the process started with can only come
    from a fresh process configured up front (tests/conftest.py sets
    JAX_PLATFORMS=cpu + --xla_force_host_platform_device_count) — this
    function never resets initialized backends: a running rule's state
    lives on them."""
    import jax

    if n < 1:
        raise ValueError(f"need a positive device count, got {n}")
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"{n} devices asked for, the default platform "
            f"({devs[0].platform}) has {len(devs)}")
    return devs[:n]


def mesh_cfg_from_env() -> Optional[Dict[str, Any]]:
    """Parse the deployment-wide KUIPER_MESH env into a mesh config dict:
    "RxK" (rows x keys), a bare shard count K (keys axis), or "auto"
    (all local devices on the keys axis, resolved at mesh-build time).
    Unset / "0" / "off" / "none" -> None. Parse errors return None with
    nothing raised — a malformed env var must not take rule planning
    down; the planner logs the single-chip fallback it causes."""
    raw = os.environ.get("KUIPER_MESH", "").strip().lower()
    if not raw or raw in ("0", "off", "none", "1"):
        return None
    if raw == "auto":
        return {"auto": True}
    try:
        if "x" in raw:
            rows_s, keys_s = raw.split("x", 1)
            rows, keys = int(rows_s), int(keys_s)
        else:
            rows, keys = 1, int(raw)
    except ValueError:
        return None
    if rows < 1 or keys < 1 or rows * keys < 2:
        return None
    return {"rows": rows, "keys": keys}


def resolve_auto_cfg(cfg: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Turn an {"auto": True} config into a concrete {"rows", "keys"}
    using the devices this process can already see (never provisions or
    resets backends). None when the host has fewer than 2 devices —
    auto sharding on a single chip is just the single-chip kernel."""
    if not cfg.get("auto"):
        return cfg
    import jax

    n = len(jax.devices())
    if n < 2:
        return None
    return {"rows": 1, "keys": n}


def mesh_from_options(mesh_cfg: dict):
    """Build a mesh from a rule's planOptimizeStrategy.mesh option, e.g.
    {"rows": 2, "keys": 4}. Uses existing devices only (real chips, or the
    virtual CPU mesh the test/dryrun environment pre-provisions) — planning
    a rule never resets jax backends out from under running rules."""
    rows = int(mesh_cfg.get("rows", 1))
    keys = int(mesh_cfg.get("keys", 1))
    if rows < 1 or keys < 1:
        raise ValueError(f"mesh axes must be positive, got {rows}x{keys}")
    devices = ensure_devices(rows * keys)
    return make_mesh(rows=rows, keys=keys, devices=devices)
