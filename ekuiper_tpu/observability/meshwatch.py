"""Mesh attribution — per-shard skew series and the collective-vs-compute
split for shard_map fold sites.

Two questions the fleet operator asks that no per-rule surface answers:

1. **Which chip is hot?** `observe()` diffs each live sharded kernel's
   `shard_stats()` rows against the previous observation: a per-shard
   rows/s EWMA plus `kuiper_mesh_skew_ratio` = hottest shard / mean over
   the window. A key-skewed workload (one device's key range absorbing
   most rows) shows up as a ratio far above 1.0; the health evaluator
   turns a sustained ratio above `KUIPER_MESH_SKEW_THRESHOLD` into a
   `shard_skew` bottleneck verdict and the QoS controller emits a
   structured `rebalance_hint` flight event (signal only — rebalancing
   itself is ROADMAP item 2's work).

2. **Collective or compute?** kernwatch already samples wall/dispatch
   timing for every `sharded.*` jit site but cannot say how much of the
   device time is the psum merge moving partials across chips.
   `collective_split()` prices that from first principles: the kernel's
   own `collective_bytes_per_fold()` (ring all-reduce bytes of the
   per-shard state slice) divided by the device class's ICI bandwidth,
   clamped to the sampled device time → `kuiper_mesh_collective_ms`.
   kernwatch's sampled-timing semantics are untouched — this module is a
   pure downstream consumer of `kernwatch.aggregate()`, and single-chip
   sites (R == 1 meshes, plain DeviceGroupBy) price to exactly zero.

Registry-driven like every watcher here: sharded kernels self-register in
`parallel/sharded.py`'s weakref registry; a collected kernel simply stops
contributing (its rows live on in the retired rollup).
"""
from __future__ import annotations

import os
import threading
import weakref
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..utils import timex

# Chip-to-chip interconnect bandwidth, GB/s, for the attribution estimate,
# matched by lowercase substring against kernwatch.device_spec()["kind"].
# Only the installed device is listed (TPU v5e: 1,600 Gbit/s, Google Cloud
# "TPU v5e" documentation); an unknown kind gets no estimate. The CPU row
# is not a device figure: it prices host-emulated "collectives" (memcpy
# class) so the 8-virtual-device CI meshes produce a nonzero, stable split
# for the tier-1 observatory tests.
MESH_LINK_GBS: Tuple[Tuple[str, float], ...] = (
    ("v5 lite", 200.0),
    ("v5e", 200.0),
    ("cpu", 8.0),
)

DEFAULT_SKEW_THRESHOLD = 2.0   # KUIPER_MESH_SKEW_THRESHOLD
DEFAULT_SKEW_MIN_ROWS = 256    # KUIPER_MESH_SKEW_MIN_ROWS — window floor


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, ""))
    except (TypeError, ValueError):
        return default


class _Track:
    """Per-kernel observation state (keyed weakly off the kernel)."""

    __slots__ = ("prev_rows", "prev_ms", "rate", "skew", "hot_shard",
                 "window_rows")

    def __init__(self) -> None:
        self.prev_rows: Optional[np.ndarray] = None
        self.prev_ms: Optional[int] = None
        self.rate: Optional[np.ndarray] = None  # rows/s EWMA per shard
        self.skew: Optional[float] = None
        self.hot_shard = 0
        self.window_rows = 0


class MeshWatch:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._tracks: "weakref.WeakKeyDictionary[Any, _Track]" = (
            weakref.WeakKeyDictionary())
        # last collective_bytes_per_fold per rule label — kept past kernel
        # death so retired kernwatch aggregates still price
        self._bytes_cache: Dict[str, int] = {}
        self._last_report: Dict[str, Dict[str, Any]] = {}
        self.threshold = _env_float(
            "KUIPER_MESH_SKEW_THRESHOLD", DEFAULT_SKEW_THRESHOLD)
        self.min_rows = int(_env_float(
            "KUIPER_MESH_SKEW_MIN_ROWS", DEFAULT_SKEW_MIN_ROWS))

    # ------------------------------------------------------------- skew
    def observe(self, now: Optional[int] = None) -> Dict[str, Dict[str, Any]]:
        """Diff every live sharded kernel against the last observation and
        refresh the per-rule skew report. Callers that hold locks which
        clock callbacks also take must pass `now` (same contract as the
        flight recorder's ts_ms)."""
        from ..parallel import sharded as _sharded

        if now is None:
            now = timex.now_ms()
        kernels = _sharded.registry().items()
        report: Dict[str, Dict[str, Any]] = {}
        with self._lock:
            for kernel, rule in kernels:
                label = rule or "__engine__"
                try:
                    stats = kernel.shard_stats()
                    rows = np.array([s["rows"] for s in stats],
                                    dtype=np.int64)
                except Exception:
                    continue
                tr = self._tracks.get(kernel)
                if tr is None:
                    tr = self._tracks[kernel] = _Track()
                if tr.prev_rows is None or len(tr.prev_rows) != len(rows):
                    window = rows  # first sight: cumulative counts
                    dt_ms = None
                else:
                    window = rows - tr.prev_rows
                    if np.any(window < 0):  # counter rebased (restore)
                        window = rows
                    dt_ms = (now - tr.prev_ms
                             if tr.prev_ms is not None else None)
                wsum = int(window.sum())
                if wsum >= max(self.min_rows, 1):
                    mean = float(window.mean())
                    tr.skew = float(window.max() / mean) if mean > 0 else None
                    tr.hot_shard = int(np.argmax(window))
                    tr.window_rows = wsum
                # else: carry the previous skew — a quiet interval is not
                # evidence the imbalance cleared
                if dt_ms and dt_ms > 0:
                    inst = window.astype(np.float64) * 1000.0 / dt_ms
                    tr.rate = (inst if tr.rate is None
                               or len(tr.rate) != len(inst)
                               else 0.5 * inst + 0.5 * tr.rate)
                tr.prev_rows = rows.copy()
                tr.prev_ms = now
                try:
                    self._bytes_cache[label] = int(
                        kernel.collective_bytes_per_fold())
                except Exception:
                    pass
                entry = {
                    "rule": label,
                    "mesh": getattr(kernel, "mesh_tag", ""),
                    "skew_ratio": tr.skew,
                    "hot_shard": tr.hot_shard,
                    "window_rows": tr.window_rows,
                    "skewed": bool(tr.skew is not None
                                   and tr.skew >= self.threshold),
                    "threshold": self.threshold,
                    "shards": [
                        {"shard": int(s["shard"]),
                         "rows": int(s["rows"]),
                         "keys": int(s["keys"]),
                         "rows_per_s": (float(tr.rate[i])
                                        if tr.rate is not None
                                        and i < len(tr.rate) else 0.0)}
                        for i, s in enumerate(stats)
                    ],
                }
                # one entry per rule: keep the widest window (a rule can
                # briefly own two kernels across a restore)
                prev = report.get(label)
                if prev is None or entry["window_rows"] >= prev["window_rows"]:
                    report[label] = entry
            self._last_report = report
        return report

    def skew_report(self) -> Dict[str, Dict[str, Any]]:
        """Last observe()'s per-rule skew entries (no re-observation)."""
        with self._lock:
            return dict(self._last_report)

    def rule_skew(self, rule: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            return self._last_report.get(rule or "__engine__")

    # -------------------------------------------------------- collective
    def _link_gbs(self) -> float:
        from . import kernwatch

        kind = str(kernwatch.device_spec().get("kind", "")).lower()
        for sub, gbs in MESH_LINK_GBS:
            if sub in kind:
                return gbs
        return 0.0  # unknown device: no collective estimate

    def collective_split(self) -> Dict[Tuple[str, str], Dict[str, Any]]:
        """Collective-vs-compute estimate for every sampled `sharded.*`
        site, (op, rule) keyed — a pure read of kernwatch.aggregate()."""
        from . import kernwatch

        link = self._link_gbs()
        agg = kernwatch.aggregate()
        with self._lock:
            bytes_cache = dict(self._bytes_cache)
        out: Dict[Tuple[str, str], Dict[str, Any]] = {}
        for (op, rule), v in agg.items():
            if not str(op).startswith("sharded."):
                continue
            label = rule or "__engine__"
            bpf = bytes_cache.get(label)
            if bpf is None and len(bytes_cache) == 1:
                # kernel registered under a different label than the
                # fold's rule context (direct-driven kernels in probes)
                bpf = next(iter(bytes_cache.values()))
            if bpf is None:
                continue
            samples = int(v.get("samples", 0))
            if samples <= 0:
                continue  # never-sampled sites add only zero rows
            device_us = float(v.get("device_us", 0.0))
            coll_us = 0.0
            # the byte model prices the fold psum; finalize's gathers are
            # capacity-axis local (docs/DISTRIBUTED.md) — compute-only
            if "fold" in str(op) and bpf > 0 and link > 0:
                coll_us = min(samples * bpf / (link * 1e3), device_us)
            out[(op, label)] = {
                "samples": samples,
                "device_us": device_us,
                "collective_us": coll_us,
                "compute_us": device_us - coll_us,
                "share": (coll_us / device_us) if device_us > 0 else 0.0,
                "bytes_per_fold": bpf,
                "link_gbs": link,
            }
        return out

    # ------------------------------------------------------------ render
    def render_prometheus(self, out: List[str], esc) -> None:
        report = self.observe()
        out.append("# TYPE kuiper_mesh_skew_ratio gauge")
        out.append("# HELP kuiper_mesh_skew_ratio hottest shard rows over "
                   "the mean across the mesh (per rule, last window)")
        for label in sorted(report):
            skew = report[label]["skew_ratio"]
            if skew is not None:
                out.append(
                    f'kuiper_mesh_skew_ratio{{rule="{esc(label)}"}} '
                    f'{skew:.4f}')
        out.append("# TYPE kuiper_mesh_shard_rows_per_s gauge")
        out.append("# HELP kuiper_mesh_shard_rows_per_s per-shard fold "
                   "rate EWMA (rows/s)")
        for label in sorted(report):
            for s in report[label]["shards"]:
                out.append(
                    f'kuiper_mesh_shard_rows_per_s{{rule="{esc(label)}",'
                    f'shard="{s["shard"]}"}} {s["rows_per_s"]:.1f}')
        split = self.collective_split()
        out.append("# TYPE kuiper_mesh_collective_ms counter")
        out.append("# HELP kuiper_mesh_collective_ms estimated cross-chip "
                   "collective time inside sampled sharded fold sites")
        for (op, label) in sorted(split):
            v = split[(op, label)]
            out.append(
                f'kuiper_mesh_collective_ms{{op="{esc(op)}",'
                f'rule="{esc(label)}"}} {v["collective_us"] / 1000.0:.3f}')
        out.append("# TYPE kuiper_mesh_collective_share gauge")
        out.append("# HELP kuiper_mesh_collective_share collective fraction "
                   "of sampled device time per sharded site (0-1)")
        for (op, label) in sorted(split):
            v = split[(op, label)]
            out.append(
                f'kuiper_mesh_collective_share{{op="{esc(op)}",'
                f'rule="{esc(label)}"}} {v["share"]:.4f}')

    def diagnostics(self) -> Dict[str, Any]:
        """GET /diagnostics/mesh + kuiperdiag "mesh" section."""
        split = self.collective_split()
        return {
            "skew": self.skew_report(),
            "collective": [
                {"op": op, "rule": label, **v}
                for (op, label), v in sorted(split.items())
            ],
            "threshold": self.threshold,
            "min_rows": self.min_rows,
            "link_gbs": self._link_gbs(),
        }


# ----------------------------------------------------------- module facade
_watch = MeshWatch()


def observe(now: Optional[int] = None) -> Dict[str, Dict[str, Any]]:
    return _watch.observe(now)


def skew_report() -> Dict[str, Dict[str, Any]]:
    return _watch.skew_report()


def rule_skew(rule: str) -> Optional[Dict[str, Any]]:
    return _watch.rule_skew(rule)


def collective_split() -> Dict[Tuple[str, str], Dict[str, Any]]:
    return _watch.collective_split()


def skew_threshold() -> float:
    return _watch.threshold


def render_prometheus(out: List[str], esc) -> None:
    _watch.render_prometheus(out, esc)


def diagnostics() -> Dict[str, Any]:
    return _watch.diagnostics()


def reset() -> None:
    """Test hook — drop tracks and re-read the env knobs."""
    global _watch
    _watch = MeshWatch()
