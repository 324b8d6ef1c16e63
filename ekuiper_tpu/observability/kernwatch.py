"""Kernel observatory — device-time & roofline attribution, the
device-side twin of devwatch.

Every timing the engine exported before this module was HOST wall clock:
a "fold" stage number conflates Python dispatch, XLA queueing, H2D
transfer, and the actual device compute. That makes the two questions
behind the sliding-latency and headroom roadmap items unanswerable:
*where do the 400-900ms sliding trigger stalls actually go*, and *how
close is the fused fold to the HBM-bandwidth roof*. TiLT (arxiv
2301.12030) argues stream-query optimization needs per-operator hardware
cost as a first-class signal; this module supplies it with two
low-overhead capture paths hooked into `devwatch.watched_jit` (every jit
site in the engine already routes through it):

- **Cost capture at lowering time.** When a site compiles, the lowered
  HLO's `cost_analysis()` is read (FLOPs, bytes accessed) and stored per
  compile signature. Backends that return no estimates (some CPU builds,
  remote plugins) degrade to `cost: None` — the timing plane keeps
  working without the roofline.
- **Sampled device timing.** Every Nth call (cadence per site *kind*:
  hot-path folds default 1/64, rare boundary ops 1/4 — a window boundary
  sync per ~40s of windows is noise, a per-batch sync is not) the wrapper
  times dispatch→`block_until_ready` and splits the call into
  host-dispatch vs device+transfer time by subtracting the site's
  host-dispatch floor (the running minimum dispatch time — pure host
  work, no device wait). Transfer is estimated from the host-resident
  argument bytes at the device's H2D bandwidth spec.

From the per-device peak table (`PEAK_SPECS`, read off
`jax.devices()[0].device_kind`) each sampled kernel gets a roofline
utilization: achieved FLOP/s against the compute roof and achieved
bytes/s against the HBM roof — the max of the two is how close the
kernel runs to *its* binding roof, and which one binds classifies it
compute- vs memory-bound.

Surfaces: `kuiper_kernel_{device_ms,dispatch_ms,flops,bytes,
roofline_util}` Prometheus families, `GET /diagnostics/kernels`, a
`device_time` section in `/rules/{id}/status`, the `kernels` section of
kuiperdiag bundles, the health plane's device/host bottleneck axis, and
the bench artifact's per-kernel summaries (`docs/OBSERVABILITY.md`
"Device time & roofline").
"""
from __future__ import annotations

import collections
import os
import threading
from typing import Any, Dict, List, Optional, Tuple

def _default_sampling() -> Dict[str, int]:
    return {
        "hot": int(os.environ.get("KUIPER_KERNWATCH_EVERY", "64") or 0),
        "boundary": int(os.environ.get("KUIPER_KERNWATCH_BOUNDARY_EVERY",
                                       "4") or 0),
    }


#: default sampling cadence per site kind (1/N calls pay a device sync);
#: 0 disables sampling for that kind (cost capture still runs)
DEFAULT_SAMPLING = _default_sampling()

#: per-device peak specs for the roofline: peak FLOP/s (the bf16 MXU
#: number — XLA's flop estimate counts MXU-eligible ops against it), HBM
#: bandwidth, and host→device link bandwidth. Keyed by a lowercase
#: substring of `jax.devices()[0].device_kind`; first match wins. Only
#: devices this repo has run on are listed (TPU v5e: Google Cloud "TPU
#: v5e" documentation); any other kind — the CPU included — has no spec
#: and reports utilization as None rather than against an invented peak.
PEAK_SPECS: Tuple[Tuple[str, Dict[str, float]], ...] = (
    ("v5 lite", {"name": "TPU v5e", "peak_flops": 197e12,
                 "hbm_gbs": 819.0, "h2d_gbs": 32.0}),
    ("v5e", {"name": "TPU v5e", "peak_flops": 197e12,
             "hbm_gbs": 819.0, "h2d_gbs": 32.0}),
)

_device_spec_cache: List[Optional[Dict[str, Any]]] = []  # [(kind, spec)]
_spec_lock = threading.Lock()


def device_spec() -> Dict[str, Any]:
    """{kind, spec|None} for the default jax device, cached after first
    successful read (a failed backend probe is NOT cached — the backend
    may simply not be initialized yet)."""
    with _spec_lock:
        if _device_spec_cache:
            return _device_spec_cache[0]  # type: ignore[return-value]
    try:
        import jax

        kind = jax.devices()[0].device_kind
    except Exception:
        return {"kind": "unavailable", "spec": None}
    low = str(kind).lower()
    spec = None
    for key, s in PEAK_SPECS:
        if key in low:
            spec = dict(s)
            break
    out = {"kind": str(kind), "spec": spec}
    with _spec_lock:
        if not _device_spec_cache:
            _device_spec_cache.append(out)
    return out


def roofline(flops: Optional[float], bytes_: Optional[float],
             compute_us: float,
             spec: Optional[Dict[str, float]]) -> Dict[str, Any]:
    """Utilization of the binding roof for one kernel execution:
    util = max(achieved FLOP/s / peak, achieved bytes/s / HBM peak); the
    larger ratio names the bound. Returns {} when cost or spec is
    missing, or the measured compute time is zero (nothing to divide)."""
    if spec is None or compute_us <= 0.0:
        return {}
    secs = compute_us / 1e6
    util_f = util_b = None
    if flops is not None and flops > 0 and spec.get("peak_flops"):
        util_f = (flops / secs) / spec["peak_flops"]
    if bytes_ is not None and bytes_ > 0 and spec.get("hbm_gbs"):
        util_b = (bytes_ / secs) / (spec["hbm_gbs"] * 1e9)
    if util_f is None and util_b is None:
        return {}
    if (util_b or 0.0) >= (util_f or 0.0):
        return {"util": round(util_b, 4), "bound": "memory"}
    return {"util": round(util_f, 4), "bound": "compute"}


class KernelRecord:
    """Per-jit-site device-time record, owned by its devwatch OpWatch
    (same lifetime: dies with the kernel object, retires into the
    module rollup so exported counters stay monotonic)."""

    __slots__ = ("op", "kind", "sample_every", "_n", "samples",
                 "device_us", "dispatch_us", "transfer_us",
                 "dispatch_floor_us", "cost", "cost_error",
                 "last_sample", "_util_sum", "_util_n", "_bound",
                 "_lock")

    def __init__(self, op: str, kind: str = "hot") -> None:
        self.op = op
        self.kind = kind if kind in DEFAULT_SAMPLING else "hot"
        self.sample_every = DEFAULT_SAMPLING[self.kind]
        self._n = 0
        self.samples = 0
        self.device_us = 0.0   # post-floor device+transfer wait, summed
        self.dispatch_us = 0.0  # host dispatch time, summed over samples
        self.transfer_us = 0.0  # H2D estimate from host-arg bytes, summed
        self.dispatch_floor_us: Optional[float] = None
        self.cost: Optional[Dict[str, float]] = None  # latest signature
        self.cost_error: Optional[str] = None
        self.last_sample: Optional[Dict[str, float]] = None
        self._util_sum = 0.0
        self._util_n = 0
        self._bound: Optional[str] = None
        self._lock = threading.Lock()

    # ------------------------------------------------------------ hot path
    def tick(self) -> bool:
        """Called once per wrapped call; True = this call is sampled.
        Unlocked counter — a lost increment under racing dispatch skews
        the cadence by one call, which is fine for telemetry."""
        n = self._n + 1
        self._n = n
        e = self.sample_every
        return e > 0 and n % e == 0

    # ------------------------------------------------------- compile path
    def on_compile(self, jitted: Any, args: tuple, kwargs: dict) -> None:
        """Capture XLA cost_analysis at lowering time (compiles only —
        `jit.lower` re-traces, which is noise against a real XLA compile
        but far too slow for the call path). Degrades gracefully when the
        backend returns no estimates."""
        try:
            ca = jitted.lower(*args, **kwargs).cost_analysis()
        except Exception as exc:
            self.cost_error = f"{type(exc).__name__}: {exc}"[:160]
            return
        if isinstance(ca, (list, tuple)):  # some backends: one per device
            ca = ca[0] if ca else None
        if not isinstance(ca, dict):
            self.cost_error = "no estimates from backend"
            return
        flops = _non_negative(ca.get("flops"))
        bytes_ = _non_negative(ca.get("bytes accessed"))
        if flops is None and bytes_ is None:
            self.cost_error = "no flops/bytes estimates from backend"
            return
        cost: Dict[str, float] = {}
        if flops is not None:
            cost["flops"] = flops
        if bytes_ is not None:
            cost["bytes"] = bytes_
        if flops and bytes_:
            cost["intensity"] = round(flops / bytes_, 4)
        self.cost = cost
        self.cost_error = None

    # ------------------------------------------------------- sampled path
    def sample(self, out: Any, t0: float, t1: float, args: tuple,
               kwargs: dict) -> None:
        """One sampled call: block on the outputs, then split the wall
        time into host-dispatch vs device(+transfer) components."""
        import time as _time

        try:
            import jax

            jax.block_until_ready(out)
        except Exception:
            return  # a sample must never break the call path
        t2 = _time.perf_counter()
        h2d = 0
        try:
            import numpy as np

            for leaf in jax.tree_util.tree_leaves((args, kwargs)):
                if isinstance(leaf, np.ndarray):
                    h2d += leaf.nbytes
        except Exception:
            pass
        self.record_sample((t1 - t0) * 1e6, (t2 - t0) * 1e6, h2d_bytes=h2d)

    def record_sample(self, dispatch_us: float, total_us: float,
                      h2d_bytes: int = 0) -> None:
        """Fold one measured (dispatch, total-blocked) pair into the
        record — the unit-testable core of `sample()`."""
        ds = device_spec()
        spec = ds.get("spec")
        with self._lock:
            floor = self.dispatch_floor_us
            if floor is None or dispatch_us < floor:
                floor = self.dispatch_floor_us = dispatch_us
            device_us = max(total_us - floor, 0.0)
            transfer_us = 0.0
            if h2d_bytes > 0 and spec is not None and spec.get("h2d_gbs"):
                # bytes / (GB/s * 1e9) seconds -> µs
                transfer_us = min(h2d_bytes / (spec["h2d_gbs"] * 1e3),
                                  device_us)
            compute_us = max(device_us - transfer_us, 0.0)
            self.samples += 1
            self.dispatch_us += dispatch_us
            self.device_us += device_us
            self.transfer_us += transfer_us
            cost = self.cost or {}
            rl = roofline(cost.get("flops"), cost.get("bytes"),
                          compute_us, spec)
            if rl:
                self._util_sum += rl["util"]
                self._util_n += 1
                self._bound = rl["bound"]
            self.last_sample = {
                "dispatch_us": round(dispatch_us, 1),
                "device_us": round(device_us, 1),
                "transfer_est_us": round(transfer_us, 1),
                **({"roofline_util": rl["util"]} if rl else {}),
            }

    def set_cost(self, flops: Optional[float],
                 bytes_: Optional[float]) -> None:
        """Synthetic-cost hook (check_metrics, tests)."""
        cost: Dict[str, float] = {}
        if flops is not None:
            cost["flops"] = float(flops)
        if bytes_ is not None:
            cost["bytes"] = float(bytes_)
        if flops and bytes_:
            cost["intensity"] = round(flops / bytes_, 4)
        self.cost = cost or None

    # ------------------------------------------------------------- queries
    def roofline_util(self) -> Optional[float]:
        with self._lock:
            if not self._util_n:
                return None
            return round(self._util_sum / self._util_n, 4)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            n = max(self.samples, 1)
            out: Dict[str, Any] = {
                "kind": self.kind,
                "sample_every": self.sample_every,
                "samples": self.samples,
                "device_us_total": round(self.device_us, 1),
                "dispatch_us_total": round(self.dispatch_us, 1),
                "transfer_est_us_total": round(self.transfer_us, 1),
                "device_us_mean": round(self.device_us / n, 1),
                "dispatch_us_mean": round(self.dispatch_us / n, 1),
                "dispatch_floor_us": (
                    round(self.dispatch_floor_us, 1)
                    if self.dispatch_floor_us is not None else None),
                "cost": dict(self.cost) if self.cost else None,
                "last_sample": (dict(self.last_sample)
                                if self.last_sample else None),
            }
            if self.cost_error:
                out["cost_error"] = self.cost_error
            if self._util_n:
                out["roofline_util"] = round(self._util_sum / self._util_n,
                                             4)
                out["bound"] = self._bound
        return out


def _non_negative(v: Any) -> Optional[float]:
    """Cost-analysis values can be absent, NaN, or -1 sentinels."""
    try:
        f = float(v)
    except (TypeError, ValueError):
        return None
    if f != f or f < 0.0:
        return None
    return f


# ----------------------------------------------------------- module state
_lock = threading.Lock()
#: (op, rule) -> retired counter rollup, fed by devwatch when an OpWatch
#: owner is collected — exported counters stay monotonic across restarts
_retired: Dict[Tuple[str, str], Dict[str, float]] = {}
RETIRED_CAP = 4096
#: counters of records collected since the rollup was last read, queued
#: by retire: ((op, rule), samples, device_us, dispatch_us, transfer_us)
_dying: collections.deque = collections.deque()


def retire(op: str, rule: str, kern: KernelRecord) -> None:
    """Queue a dying record's counters for the rollup (called from
    devwatch._Registry.retire_dead, under a __del__ that the collector
    may run inside an allocation made while `_lock` is held: no lock
    here; kern is mid-collection — plain counter reads only)."""
    if kern.samples == 0:
        return
    _dying.append(((op, rule), kern.samples, kern.device_us,
                   kern.dispatch_us, kern.transfer_us))


def _fold_dying() -> None:
    """Queued counters into the rollup; the caller holds `_lock`."""
    while _dying:
        key, samples, device_us, dispatch_us, transfer_us = _dying.popleft()
        acc = _retired.setdefault(key, {
            "samples": 0, "device_us": 0.0, "dispatch_us": 0.0,
            "transfer_us": 0.0})
        acc["samples"] += samples
        acc["device_us"] += device_us
        acc["dispatch_us"] += dispatch_us
        acc["transfer_us"] += transfer_us
    while len(_retired) > RETIRED_CAP:
        del _retired[next(iter(_retired))]


def _live() -> List[Tuple[str, str, KernelRecord]]:
    """[(op, rule, kern)] for every live watched site."""
    from . import devwatch

    return [(w.op, w.rule or "", w.kern)
            for w in devwatch.registry().watches()
            if getattr(w, "kern", None) is not None]


def set_sampling(hot: Optional[int] = None,
                 boundary: Optional[int] = None) -> Dict[str, int]:
    """Adjust sampling cadence live (module default + every live record
    of that kind). Returns the PRIOR defaults so a caller (the bench's
    instrumented segments) can restore them."""
    prior = dict(DEFAULT_SAMPLING)
    for kind, val in (("hot", hot), ("boundary", boundary)):
        if val is None:
            continue
        DEFAULT_SAMPLING[kind] = int(val)
        for _op, _rule, kern in _live():
            if kern.kind == kind:
                kern.sample_every = int(val)
    return prior


def aggregate() -> Dict[Tuple[str, str], Dict[str, Any]]:
    """Rollup by (op, rule) for the Prometheus exposition: counters
    include retired instances; gauges (cost, utilization) ride the live
    records."""
    with _lock:
        _fold_dying()
        out: Dict[Tuple[str, str], Dict[str, Any]] = {
            k: dict(v) for k, v in _retired.items()}
    for op, rule, kern in _live():
        snap = kern.snapshot()
        acc = out.setdefault((op, rule), {
            "samples": 0, "device_us": 0.0, "dispatch_us": 0.0,
            "transfer_us": 0.0})
        acc["samples"] += snap["samples"]
        acc["device_us"] += snap["device_us_total"]
        acc["dispatch_us"] += snap["dispatch_us_total"]
        acc["transfer_us"] += snap["transfer_est_us_total"]
        if snap.get("cost"):
            acc["cost"] = snap["cost"]
        if snap.get("roofline_util") is not None:
            acc["roofline_util"] = snap["roofline_util"]
            acc["bound"] = snap.get("bound")
    return out


def rule_ops_all() -> Dict[str, Dict[str, Dict[str, Any]]]:
    """{rule: {op: cumulative device-time counters}} for EVERY rule
    (live + retired) in ONE registry pass — the health evaluator fetches
    this once per tick and diffs per rule for the device/host bottleneck
    axis (a per-rule scan would make the tick O(rules x watches))."""
    out: Dict[str, Dict[str, Dict[str, Any]]] = {}
    with _lock:
        _fold_dying()
        for (op, rule), v in _retired.items():
            out.setdefault(rule, {})[op] = {
                "samples": v["samples"], "device_us": v["device_us"],
                "dispatch_us": v["dispatch_us"]}
    for op, rule, kern in _live():
        acc = out.setdefault(rule, {}).setdefault(
            op, {"samples": 0, "device_us": 0.0, "dispatch_us": 0.0})
        acc["samples"] += kern.samples
        acc["device_us"] += kern.device_us
        acc["dispatch_us"] += kern.dispatch_us
        util = kern.roofline_util()
        if util is not None:
            acc["roofline_util"] = util
            acc["bound"] = kern._bound
    return out


def rule_ops(rule_id: str) -> Dict[str, Dict[str, Any]]:
    """Cumulative per-op device-time counters for ONE rule."""
    return rule_ops_all().get(rule_id, {})


def rule_status(rule_id: str) -> Dict[str, Any]:
    """The `device_time` section of one rule's /status JSON: the rule's
    sampled host/device time split plus a per-op breakdown."""
    ops: Dict[str, Any] = {}
    device_us = dispatch_us = transfer_us = 0.0
    samples = 0
    for op, rule, kern in _live():
        if rule != rule_id:
            continue
        snap = kern.snapshot()
        ops[op] = {k: snap[k] for k in (
            "samples", "device_us_mean", "dispatch_us_mean", "cost")}
        for key in ("roofline_util", "bound", "cost_error"):
            if snap.get(key) is not None:
                ops[op][key] = snap[key]
        device_us += snap["device_us_total"]
        dispatch_us += snap["dispatch_us_total"]
        transfer_us += snap["transfer_est_us_total"]
        samples += snap["samples"]
    if not ops:
        return {}
    total = device_us + dispatch_us
    return {
        "samples": samples,
        "device_ms": round(device_us / 1e3, 3),
        "dispatch_ms": round(dispatch_us / 1e3, 3),
        "transfer_est_ms": round(transfer_us / 1e3, 3),
        "device_share": round(device_us / total, 4) if total else None,
        "ops": ops,
    }


def diagnostics() -> Dict[str, Any]:
    """The GET /diagnostics/kernels payload."""
    sites = []
    for op, rule, kern in _live():
        sites.append({"op": op, "rule": rule or None, **kern.snapshot()})
    sites.sort(key=lambda s: -s["device_us_total"])
    agg = aggregate()
    return {
        "device": device_spec(),
        "sampling": dict(DEFAULT_SAMPLING),
        "sites": sites,
        "totals": {
            "samples": int(sum(v["samples"] for v in agg.values())),
            "device_ms": round(
                sum(v["device_us"] for v in agg.values()) / 1e3, 3),
            "dispatch_ms": round(
                sum(v["dispatch_us"] for v in agg.values()) / 1e3, 3),
        },
    }


def totals_by_op(prefix: str = "") -> Dict[str, Dict[str, Any]]:
    """Live per-op rollup across rules (bench phase deltas)."""
    out: Dict[str, Dict[str, Any]] = {}
    for op, _rule, kern in _live():
        if prefix and not op.startswith(prefix):
            continue
        snap = kern.snapshot()
        acc = out.setdefault(op, {"samples": 0, "device_us": 0.0,
                                  "dispatch_us": 0.0, "transfer_us": 0.0})
        acc["samples"] += snap["samples"]
        acc["device_us"] += snap["device_us_total"]
        acc["dispatch_us"] += snap["dispatch_us_total"]
        acc["transfer_us"] += snap["transfer_est_us_total"]
        if snap.get("roofline_util") is not None:
            acc["roofline_util"] = snap["roofline_util"]
            acc["bound"] = snap.get("bound")
    return out


def bench_summary(top: int = 6) -> Dict[str, Any]:
    """Compact per-kernel summary for the bench artifact: the top-N sites
    by sampled device time."""
    rows = []
    for op, rule, kern in _live():
        snap = kern.snapshot()
        if not snap["samples"] and not snap.get("cost"):
            continue
        row = {"op": op, "samples": snap["samples"],
               "device_ms": round(snap["device_us_total"] / 1e3, 2),
               "dispatch_ms": round(snap["dispatch_us_total"] / 1e3, 2),
               "device_us_mean": snap["device_us_mean"]}
        cost = snap.get("cost") or {}
        if cost.get("flops"):
            row["flops"] = cost["flops"]
        if cost.get("bytes"):
            row["bytes"] = cost["bytes"]
        for key in ("roofline_util", "bound"):
            if snap.get(key) is not None:
                row[key] = snap[key]
        rows.append(row)
    rows.sort(key=lambda r: -r["device_ms"])
    return {"device": device_spec().get("kind"),
            "top": rows[:top]}


def reset() -> None:
    """Test hook: drop retired rollups, restore default cadences, and
    un-cache the device spec (tests monkeypatch it)."""
    with _lock:
        _dying.clear()
        _retired.clear()
    # in place: set_sampling and callers hold the dict itself
    DEFAULT_SAMPLING.update(_default_sampling())
    with _spec_lock:
        _device_spec_cache.clear()


# -------------------------------------------------------- Prometheus view
def render_prometheus(out: List[str], esc) -> None:
    """Append the kuiper_kernel_* families to a /metrics scrape. `esc` is
    the exposition label escaper (observability/prometheus.py _esc)."""
    rows = sorted(aggregate().items())

    def label(op: str, rule: str) -> str:
        return f'op="{esc(op)}",rule="{esc(rule or "__engine__")}"'

    fams = (
        ("kuiper_kernel_device_ms", "counter",
         "sampled device-side time per jit site (ms; post-dispatch-floor"
         " wait incl. transfer)",
         lambda v: round(v["device_us"] / 1e3, 3), lambda v: True),
        ("kuiper_kernel_dispatch_ms", "counter",
         "sampled host-dispatch time per jit site (ms)",
         lambda v: round(v["dispatch_us"] / 1e3, 3), lambda v: True),
        ("kuiper_kernel_flops", "gauge",
         "XLA cost-analysis FLOPs per call, latest compiled signature",
         lambda v: v["cost"]["flops"],
         # per-key gate: a bytes-only estimate must not fabricate a 0
         # FLOPs "measurement" (and vice versa) — absence means absence
         lambda v: bool((v.get("cost") or {}).get("flops"))),
        ("kuiper_kernel_bytes", "gauge",
         "XLA cost-analysis bytes accessed per call, latest signature",
         lambda v: v["cost"]["bytes"],
         lambda v: bool((v.get("cost") or {}).get("bytes"))),
        ("kuiper_kernel_roofline_util", "gauge",
         "sampled utilization of the binding device roof (compute or "
         "HBM), 1.0 = at the roof",
         lambda v: v["roofline_util"],
         lambda v: v.get("roofline_util") is not None),
    )
    for name, mtype, help_txt, value, want in fams:
        out.append(f"# TYPE {name} {mtype}")
        out.append(f"# HELP {name} {help_txt}")
        for (op, rule), v in rows:
            if want(v):
                out.append(f"{name}{{{label(op, rule)}}} {value(v)}")
