"""Streaming health plane — the first CONSUMER of the engine's telemetry.

Everything below already existed as raw signal: per-stage process/queue
histograms and stage timings (utils/metrics.py StatManager), the per-rule
ingest→emit distribution (runtime/topo.py e2e_hist), the drop taxonomy,
the XLA compile watcher (devwatch), the HBM byte probes (memwatch). What
was missing — ROADMAP item 5's "the engine has rich telemetry but nothing
consumes it" — is a component that reads those surfaces periodically and
renders a VERDICT per rule: *this rule is breaching its SLO, the
bottleneck is the upload stage, and event time is falling behind*.

The `HealthEvaluator` ticks on the engine clock (mock-clock friendly:
tests drive `tick()` directly or advance the clock) — the burn windows
are sample-count-aware (observation-indexed decay bounded by
IDLE_HOLD_TICKS, evidence-weighted burns via `_weighted_burn`), so
sub-second cadences judge slow-emitting rules without verdict flap —
and computes, per running rule:

- **SLO burn rate** — multi-window (fast/slow) burn against a per-rule
  latency + drop SLO. Each tick the delta of the rule's cumulative e2e
  histogram is folded into two evaluator-owned window histograms that
  are decayed geometrically via `LatencyHistogram.snapshot_and_decay`
  (fast ≈ 2-tick memory, slow ≈ 8-tick); burn = violating fraction /
  error budget, the standard SRE multi-window multi-burn shape (both
  windows must burn before the verdict escalates, so a single spike
  cannot flap it).
- **Bottleneck attribution** — per-tick deltas of every node's stage
  timings and busy time, mapped onto the canonical pipeline taxonomy
  (decode / upload / fold / emit_combine / sink — the time-centric
  decomposition argument of TiLT, arxiv 2301.12030), plus enqueue-time
  queue-depth high-water marks split upstream/downstream of the
  attributed node so backpressure direction is visible.
- **Event-time progress** — watermark lag (engine clock vs the rule's
  watermark), pane-ring occupancy (fused/shared event paths), buffered
  rows (host window path), and the per-member emit cursor for rules
  riding a shared pane fold (lag is reported PER RULE, not per store).
- **HBM headroom trend** — memwatch byte totals per tick, with a
  bytes/minute slope over the sample window.

Verdicts move healthy→degraded→breaching (and back) through a hysteresis
FSM: escalation needs `up_ticks` consecutive ticks above threshold,
recovery steps down one level per `down_ticks` quiet ticks. Every
transition emits a `rule_health` flight-recorder event and the current
verdicts export as the `kuiper_rule_health` / `kuiper_slo_burn_rate` /
`kuiper_watermark_lag_ms` / `kuiper_bottleneck_stage` Prometheus
families and the `GET /rules/{id}/health` + `GET /diagnostics/health`
REST views. This layer is what the later control-plane PRs (admission
control, QoS shedding, auto-sizing) will read.

On-demand deep capture lives here too: `capture_profile` runs a bounded
`jax.profiler.trace` plus a devwatch signature/memwatch dump into a
bundle directory (`POST /diagnostics/profile`, collected by
`tools/kuiperdiag.py --profile`).
"""
from __future__ import annotations

import json
import os
import threading
import time as _time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..utils import timex
from ..utils.infra import logger
from .histogram import LatencyHistogram

# ----------------------------------------------------------------- states
HEALTHY = "healthy"
DEGRADED = "degraded"
BREACHING = "breaching"
STATE_LEVEL = {HEALTHY: 0, DEGRADED: 1, BREACHING: 2}
_LEVEL_STATE = {v: k for k, v in STATE_LEVEL.items()}

#: canonical bottleneck taxonomy (TiLT-style stage decomposition of the
#: ingest→emit path); "host_expr" is host-side expression evaluation
#: (FilterNode vectorized/row WHERE, the row-interpreter fallback seam —
#: sql/expr_ir.py compiles these onto the device for fused rules);
#: "other" absorbs host-op busy time that belongs to none of the named
#: stages (projections, joins); "shard_skew" is mesh-level — a sharded
#: rule whose hottest shard absorbs ≥ KUIPER_MESH_SKEW_THRESHOLD times
#: the mean fold rows (observability/meshwatch.py) is bound by one
#: chip's key range, not by any pipeline stage
STAGES = ("decode", "upload", "fold", "emit_combine", "sink",
          "host_expr", "shard_skew", "other")

#: node-local stage labels → canonical taxonomy
_STAGE_CANON = {"decode": "decode", "ring": "decode", "ingest": "decode",
                "upload": "upload", "prep": "upload",
                "fold": "fold", "host_expr": "host_expr",
                # the fold's numpy mirror into an un-merged pre-issue's
                # shadow, and a sliding rule's ring of panes and rows: what
                # a micro-batch costs the folding thread beside `fold`
                "shadow_fold": "fold", "slide_ring": "fold",
                # after a boundary's emit: pane reset, the next tick's timers
                "boundary_reset": "emit_combine",
                "emit": "emit_combine", "sink": "sink"}

#: classes whose UNSTAGED busy time is boundary work (finalize + window
#: combine + emission) rather than row processing
_EMIT_CLASSES = {"FusedWindowAggNode", "SharedFoldNode", "WindowNode",
                 "SharedEmitNode"}

# -------------------------------------------------------------- SLO config
#: engine-default SLO, overridable per rule via options.slo (camelCase or
#: snake_case keys accepted — docs/OBSERVABILITY.md "Health plane")
DEFAULT_SLO = {
    "latency_p99_ms": 1000,        # e2e emit latency bound
    "target": 0.99,                # fraction of emits that must beat it
    "max_drop_ratio": 0.01,        # tolerated dropped/offered ratio
    "max_watermark_lag_ms": None,  # event-time lag bound (None = off)
}

_SLO_ALIASES = {
    "latencyP99Ms": "latency_p99_ms",
    "latency_p99_ms": "latency_p99_ms",
    "target": "target",
    "maxDropRatio": "max_drop_ratio",
    "max_drop_ratio": "max_drop_ratio",
    "maxWatermarkLagMs": "max_watermark_lag_ms",
    "max_watermark_lag_ms": "max_watermark_lag_ms",
}


def parse_slo(options: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """Resolve a rule's SLO config from its options (`"slo": {...}`),
    falling back to engine defaults; malformed values keep the default
    (a bad SLO must not stop a rule from being evaluated at all)."""
    out = dict(DEFAULT_SLO)
    raw = (options or {}).get("slo") or {}
    if not isinstance(raw, dict):
        return out
    for key, val in raw.items():
        norm = _SLO_ALIASES.get(key)
        if norm is None:
            continue
        try:
            if norm == "target":
                v = float(val)
                if 0.0 < v < 1.0:
                    out[norm] = v
            elif norm == "max_drop_ratio":
                v = float(val)
                if v > 0:
                    out[norm] = v
            else:
                v = int(val)
                if v > 0:
                    out[norm] = v
        except (TypeError, ValueError):
            continue
    return out


#: burn-rate multiple at/above which BOTH windows flag a breach; [1,
#: BREACH_BURN) is the degraded band — budget is being consumed faster
#: than sustainable but not catastrophically
BREACH_BURN = 6.0
#: geometric window decay per tick: fast ≈ 2-tick memory, slow ≈ 8-tick
FAST_DECAY = 0.5
SLOW_DECAY = 0.875
#: evidence-hold bound: zero-sample ticks HOLD the burn windows (a
#: sub-second evaluator must not flush a slow emitter's evidence
#: between window emissions), but only this many in a row — past it
#: the decay resumes so a rule whose traffic STOPS entirely (dead
#: broker, disconnected source) ages back to healthy instead of
#: freezing at its last verdict forever (which would also permanently
#: trip KUIPER_ADMISSION_DEFER_BREACHING)
IDLE_HOLD_TICKS = 16
#: default evaluator cadence (engine clock)
DEFAULT_INTERVAL_MS = int(os.environ.get("KUIPER_HEALTH_INTERVAL_MS",
                                         "5000") or 5000)
#: HBM trend window (ticks)
_HBM_SAMPLES = 12


class _RuleTrack:
    """Per-rule evaluator state across ticks."""

    __slots__ = ("fast_hist", "slow_hist", "prev_e2e", "prev_nodes",
                 "prev_queue", "prev_kern", "fast_drops", "slow_drops",
                 "fast_in", "slow_in", "state", "state_since_ms",
                 "ticks_in_state", "up_pend", "up_level", "down_pend",
                 "verdict", "peak_burn", "lat_idle", "drop_idle")

    def __init__(self, now_ms: int) -> None:
        self.fast_hist = LatencyHistogram()
        self.slow_hist = LatencyHistogram()
        self.prev_e2e: Optional[List[int]] = None
        self.prev_nodes: Dict[str, Dict[str, Any]] = {}
        self.prev_queue: Dict[str, int] = {}
        self.prev_kern: Dict[str, Dict[str, Any]] = {}
        self.fast_drops = 0.0
        self.slow_drops = 0.0
        self.fast_in = 0.0
        self.slow_in = 0.0
        self.state = HEALTHY
        self.state_since_ms = now_ms
        self.ticks_in_state = 0
        self.up_pend = 0
        self.up_level = 0
        self.down_pend = 0
        self.verdict: Optional[Dict[str, Any]] = None
        self.peak_burn = 0.0
        self.lat_idle = 0   # consecutive zero-sample ticks (latency)
        self.drop_idle = 0  # consecutive zero-traffic ticks (drops)


def _viol_fraction(hist: LatencyHistogram, bound_ms: int) -> Tuple[float, int]:
    """(fraction of window samples above `bound_ms`, window count). The
    bucket→bound mapping is conservative (histogram.py cumulative), so
    the fraction can only over-report violations — burn rate never
    flatters the SLO."""
    cum, count, _ = hist.export((int(bound_ms),))
    if count <= 0:
        return 0.0, 0
    return (count - cum[0]) / count, count


def _weighted_burn(violations: float, mass: float, budget: float) -> float:
    """Sample-count-aware burn: `violations` bad samples out of `mass`
    observed, against an error budget. The violating fraction is taken
    over at least the budget's own resolution (1/budget samples): a
    window too sparse to statistically resolve the budget cannot claim
    a full-rate burn off one or two samples — the exact flap churn_soak
    had to pin KUIPER_HEALTH_INTERVAL_MS=1500 to dodge (a sub-second
    evaluator tick between two window emissions saw a 1-sample window
    and swung the verdict on it). Unseen samples are presumed good —
    burn under-claims on thin evidence, never over-claims."""
    budget = max(budget, 1e-6)
    n_min = 1.0 / budget + 1.0
    return (violations / max(mass, n_min)) / budget


class HealthEvaluator:
    """Periodic per-rule health verdicts off the existing telemetry
    surfaces. `rules_fn()` yields `(rule_id, topo, options)` triples for
    every rule worth evaluating; everything else is read through public
    accessors on the topo's nodes. All sampling is read-only — a tick
    never blocks the data path beyond the StatManagers' short locks."""

    def __init__(self, rules_fn: Callable[[], List[tuple]],
                 interval_ms: int = DEFAULT_INTERVAL_MS,
                 up_ticks: int = 2, down_ticks: int = 3,
                 breach_burn: float = BREACH_BURN,
                 fast_decay: float = FAST_DECAY,
                 slow_decay: float = SLOW_DECAY) -> None:
        self._rules_fn = rules_fn
        self.interval_ms = int(interval_ms)
        self.up_ticks = max(int(up_ticks), 1)
        self.down_ticks = max(int(down_ticks), 1)
        self.breach_burn = float(breach_burn)
        self.fast_decay = float(fast_decay)
        self.slow_decay = float(slow_decay)
        self._tracks: Dict[str, _RuleTrack] = {}
        self._lock = threading.RLock()
        # single-flight guard for REST-triggered seeding ticks; ordered
        # BEFORE the clock/evaluator locks (held across tick()), never
        # taken from timer callbacks — see rule_health
        self._seed_mu = threading.Lock()
        self._timer = None
        self._running = False
        self.ticks = 0
        self.last_tick_us = 0.0
        self._hbm: deque = deque(maxlen=_HBM_SAMPLES)
        #: per-tick queue-peak memo (node identity → peak) — shared
        #: nodes are walked once per member rule, but the underlying
        #: high-water mark is read-and-reset
        self._tick_qpeaks: Dict[int, int] = {}

    # -------------------------------------------------------------- lifecycle
    def start(self) -> None:
        with self._lock:
            if self._running:
                return
            self._running = True
        self._arm()

    def stop(self) -> None:
        with self._lock:
            self._running = False
            if self._timer is not None:
                self._timer.stop()
                self._timer = None

    def _arm(self) -> None:
        self._timer = timex.after(self.interval_ms, self._fire)

    def _fire(self, ts: int) -> None:
        if not self._running:
            return
        try:
            self.tick()
        except Exception as exc:  # the evaluator must never kill a timer
            logger.warning("health evaluator tick failed: %s", exc)
        if self._running:
            self._arm()

    # ------------------------------------------------------------------- tick
    def tick(self) -> Dict[str, Dict[str, Any]]:
        """Evaluate every rule once. Returns {rule_id: verdict}."""
        # clock read BEFORE the evaluator lock: a mock-clock advance fires
        # _fire -> tick() while HOLDING the clock lock, so taking the
        # clock inside our lock would be the clock/evaluator ABBA square
        # (utils/lockcheck.py flags it — same class as the PR 6
        # clock/stats inversion)
        now = timex.now_ms()
        with self._lock:
            t0 = _time.perf_counter()
            sweep = True
            try:
                rules = list(self._rules_fn() or [])
            except Exception as exc:
                # transient registry failure: evaluate nothing this tick
                # but KEEP every track — deleting them would silently
                # reset breaching rules to healthy and make the next
                # tick re-seed the full cumulative e2e history as one
                # tick's delta
                logger.warning("health rules_fn failed: %s", exc)
                rules = []
                sweep = False
            self._tick_qpeaks: Dict[int, int] = {}
            # kernel-observatory counters for ALL rules in one registry
            # pass (observability/kernwatch.py) — _device_axis diffs per
            # rule against this tick-shared map
            from . import kernwatch

            try:
                self._tick_kern = kernwatch.rule_ops_all()
            except Exception:
                self._tick_kern = {}
            # mesh skew observed once per tick, shared by every rule's
            # attribution below (observability/meshwatch.py); ts passed
            # explicitly — we hold self._lock, the clock lock is off
            # limits (same ABBA discipline as the recorder calls)
            from . import meshwatch

            try:
                self._tick_mesh = meshwatch.observe(now)
            except Exception:
                self._tick_mesh = {}
            seen = set()
            for entry in rules:
                try:
                    rid, topo, options = entry
                except (TypeError, ValueError):
                    continue
                if topo is None:
                    continue
                seen.add(rid)
                try:
                    self._eval_rule(rid, topo, options or {}, now)
                except Exception as exc:
                    logger.warning("health eval of rule %s failed: %s",
                                   rid, exc)
            if sweep:
                for rid in [r for r in self._tracks if r not in seen]:
                    del self._tracks[rid]
            # engine-level HBM sample (memwatch probes; pull-model, cheap)
            from . import memwatch

            try:
                self._hbm.append((now, memwatch.registry().total_bytes()))
            except Exception:
                pass
            self.ticks += 1
            self.last_tick_us = (_time.perf_counter() - t0) * 1e6
            return {rid: tr.verdict for rid, tr in self._tracks.items()
                    if tr.verdict is not None}

    # ------------------------------------------------------------ per rule
    def _eval_rule(self, rid: str, topo: Any, options: Dict[str, Any],
                   now: int) -> None:
        tr = self._tracks.get(rid)
        if tr is None:
            tr = self._tracks[rid] = _RuleTrack(now)
        slo = parse_slo(options)

        # ---- latency window delta → fast/slow burn
        hist = getattr(topo, "e2e_hist", None)
        delta_n = 0
        if hist is not None:
            cur = hist.bucket_counts()
            prev = tr.prev_e2e
            if prev is None or sum(cur) < sum(prev):
                # first tick, or the source histogram was decayed/reset
                # (bench segments do): re-seed from the full cumulative
                delta = cur
            else:
                delta = [max(c - p, 0) for c, p in zip(cur, prev)]
            tr.prev_e2e = cur
            delta_n = sum(delta)
            tr.fast_hist.record_bucket_counts(delta)
            tr.slow_hist.record_bucket_counts(delta)
        budget = max(1.0 - slo["target"], 1e-6)
        bound = slo["latency_p99_ms"]
        frac_f, n_f = _viol_fraction(tr.fast_hist, bound)
        frac_s, n_s = _viol_fraction(tr.slow_hist, bound)
        # burn is weighted by the samples each window actually observed
        # (sparse windows cannot resolve the budget — see _weighted_burn)
        lat_burn_f = _weighted_burn(frac_f * n_f, n_f, budget)
        lat_burn_s = _weighted_burn(frac_s * n_s, n_s, budget)
        # snapshot the window percentiles, then decay toward next tick —
        # ONLY on ticks that observed samples: the windows index the last
        # N observations, not wall ticks, so an evaluator outpacing a
        # slow-emitting rule holds its evidence instead of flushing it
        # to zero between emissions (the verdict-flap class). The hold
        # is BOUNDED (IDLE_HOLD_TICKS): a rule whose traffic stops
        # entirely resumes decaying and ages back to healthy
        tr.lat_idle = 0 if delta_n else tr.lat_idle + 1
        hold_lat = 0 < tr.lat_idle <= IDLE_HOLD_TICKS
        lat_decay_f = 1.0 if hold_lat else self.fast_decay
        lat_decay_s = 1.0 if hold_lat else self.slow_decay
        fast_snap = tr.fast_hist.snapshot_and_decay(lat_decay_f)
        slow_snap = tr.slow_hist.snapshot_and_decay(lat_decay_s)

        # ---- node walk: stage deltas, drops, queue peaks
        nodes = list(getattr(topo, "all_nodes", lambda: [])())
        shared_nodes: List[Any] = []
        for st, _entry in getattr(topo, "live_shared", lambda: [])():
            shared_nodes.extend(getattr(st, "nodes", []))
        # data flows shared-source pipeline → own nodes; keep that order
        # for the upstream/downstream backpressure split
        ordered, seen_ids = [], set()
        for n in shared_nodes + nodes:
            if id(n) not in seen_ids:
                seen_ids.add(id(n))
                ordered.append(n)
        stage_us: Dict[str, float] = {s: 0.0 for s in STAGES}
        node_top: Dict[str, Tuple[str, float]] = {}  # node -> (stage, us)
        drops_d = ins_d = 0
        queue_peaks: Dict[str, int] = {}
        new_prev: Dict[str, Dict[str, Any]] = {}
        for node in ordered:
            stats = getattr(node, "stats", None)
            if stats is None or not hasattr(stats, "health_sample"):
                continue
            cur_s = stats.health_sample()
            prev_s = tr.prev_nodes.get(node.name, {})
            if cur_s.get("partial"):
                # lock-free sample lost the race repeatedly: keep the
                # old baseline and skip this node for the tick — using
                # the degraded sample as prev would attribute the node's
                # full cumulative history to the next delta
                new_prev[node.name] = prev_s
                continue
            new_prev[node.name] = cur_s
            best_stage, best_us = None, 0.0
            for stage, us in cur_s["stages"].items():
                d = us - prev_s.get("stages", {}).get(stage, 0)
                if d <= 0:
                    continue
                if stage.startswith("emit[") and stage.endswith("]"):
                    # shared-fold per-member emit stages
                    # (nodes_sharedfold stage="emit[<rule>]"): another
                    # member's emit work is staged time (so not in the
                    # unstaged remainder below) but must not
                    # be attributed to THIS rule's bottleneck
                    if stage[5:-1] != rid:
                        continue
                    canon = "emit_combine"
                else:
                    canon = _STAGE_CANON.get(
                        stage, "emit_combine" if stage.startswith("emit")
                        else "other")
                stage_us[canon] += d
                if d > best_us:
                    best_stage, best_us = canon, d
            # the worker's own ledger (utils/metrics.py cycle_end): its
            # busy time outside every stage that closed on it — not busy
            # less the stage rows, which other threads write too
            rem = cur_s["unstaged_us"] - prev_s.get("unstaged_us", 0)
            if rem > 0:
                op_type = getattr(node, "op_type", "op")
                if op_type == "source":
                    canon = "decode"
                elif op_type == "sink":
                    canon = "sink"
                elif type(node).__name__ in _EMIT_CLASSES:
                    canon = "emit_combine"
                else:
                    canon = "other"
                stage_us[canon] += rem
                if rem > best_us:
                    best_stage, best_us = canon, rem
            if best_stage is not None:
                node_top[node.name] = (best_stage, best_us)
            drops_d += cur_s["dropped"] - prev_s.get("dropped", 0)
            if getattr(node, "op_type", "") == "source":
                ins_d += cur_s["in"] - prev_s.get("in", 0)
            # queue spikes: enqueue-time high-water since last tick, plus
            # the live depth (covers sustained levels with no enqueues).
            # take_queue_peak_tick is read-and-reset, and shared-subtopo /
            # shared-fold nodes are walked once PER MEMBER RULE in a tick
            # — memoize per node so every member sees the same peak
            # instead of only the first-evaluated one
            peak = self._tick_qpeaks.get(id(node))
            if peak is None:
                peak = 0
                take = getattr(stats, "take_queue_peak_tick", None)
                if take is not None:
                    peak = take()
                q = getattr(node, "inq", None)
                if q is not None:
                    try:
                        peak = max(peak, q.qsize())
                    except Exception:
                        pass
                self._tick_qpeaks[id(node)] = peak
            queue_peaks[node.name] = peak
        tr.prev_nodes = new_prev

        # ---- drop burn (same fast/slow decayed windows, scalar form,
        # same sample-count weighting and observation-indexed decay)
        drops_d = max(drops_d, 0)
        ins_d = max(ins_d, 0)
        tr.fast_drops += drops_d
        tr.slow_drops += drops_d
        tr.fast_in += ins_d
        tr.slow_in += ins_d
        drop_budget = max(slo["max_drop_ratio"], 1e-6)
        drop_ratio_f = tr.fast_drops / max(tr.fast_in, tr.fast_drops, 1.0)
        drop_ratio_s = tr.slow_drops / max(tr.slow_in, tr.slow_drops, 1.0)
        drop_burn_f = _weighted_burn(
            tr.fast_drops, max(tr.fast_in, tr.fast_drops, 1.0), drop_budget)
        drop_burn_s = _weighted_burn(
            tr.slow_drops, max(tr.slow_in, tr.slow_drops, 1.0), drop_budget)
        tr.drop_idle = 0 if (drops_d or ins_d) else tr.drop_idle + 1
        if not 0 < tr.drop_idle <= IDLE_HOLD_TICKS:
            tr.fast_drops *= self.fast_decay
            tr.fast_in *= self.fast_decay
            tr.slow_drops *= self.slow_decay
            tr.slow_in *= self.slow_decay

        # ---- bottleneck attribution + backpressure direction
        total_us = sum(stage_us.values())
        bottleneck: Dict[str, Any] = {"stage": None, "share": 0.0}
        if total_us > 0:
            dom = max(stage_us, key=lambda s: stage_us[s])
            bn_node = None
            bn_us = -1.0
            for name, (stage, us) in node_top.items():
                if stage == dom and us > bn_us:
                    bn_node, bn_us = name, us
            up_names, down_names, split = [], [], False
            for node in ordered:
                if node.name == bn_node:
                    split = True
                    continue
                (down_names if split else up_names).append(node.name)
            up_peak = max([queue_peaks.get(n, 0) for n in up_names],
                          default=0)
            down_peak = max([queue_peaks.get(n, 0) for n in down_names],
                            default=0)
            up_trend = up_peak - max(
                [tr.prev_queue.get(n, 0) for n in up_names], default=0)
            down_trend = down_peak - max(
                [tr.prev_queue.get(n, 0) for n in down_names], default=0)
            if up_peak > max(down_peak, 0) and up_trend >= 0:
                forming = "upstream"
            elif down_peak > 0 and down_trend >= 0:
                forming = "downstream"
            else:
                forming = "none"
            bottleneck = {
                "stage": dom,
                "node": bn_node,
                "share": round(stage_us[dom] / total_us, 4),
                "stage_us": {s: int(v) for s, v in stage_us.items() if v},
                "backpressure": {
                    "forming": forming,
                    "upstream": {"peak": up_peak, "trend": up_trend},
                    "downstream": {"peak": down_peak, "trend": down_trend},
                },
            }
        tr.prev_queue = queue_peaks

        # ---- device/host axis (observability/kernwatch.py): per-tick
        # deltas of the rule's sampled kernel timings split the dominant
        # stage's wall time into device-side compute/transfer vs
        # host-side dispatch, and carry the hottest kernel's roofline
        # utilization — "fold is dominant" becomes "fold is
        # device-compute-bound at 71% of the HBM roof"
        device_time = self._device_axis(rid, tr,
                                        getattr(self, "_tick_kern", None))
        if device_time is not None and bottleneck.get("stage"):
            bottleneck["axis"] = device_time["axis"]
            bottleneck["device_time"] = device_time

        # ---- mesh attribution (observability/meshwatch.py): a sharded
        # rule whose hottest shard absorbs a super-threshold multiple of
        # the mean fold rows is bound by one chip's key range — that
        # outranks stage attribution (the skewed chip IS the dominant
        # stage's critical path). Attribution only: burn math and the
        # health FSM are untouched, so a skewed-but-meeting-SLO rule
        # stays HEALTHY with a shard_skew verdict attached.
        mesh = (getattr(self, "_tick_mesh", None) or {}).get(rid)
        if mesh is not None:
            bottleneck["mesh"] = {
                "skew_ratio": mesh.get("skew_ratio"),
                "hot_shard": mesh.get("hot_shard"),
                "mesh": mesh.get("mesh"),
                "skewed": bool(mesh.get("skewed")),
            }
            if mesh.get("skewed"):
                hot = next(
                    (s for s in mesh.get("shards", [])
                     if s["shard"] == mesh.get("hot_shard")), None)
                total = sum(s["rows"] for s in mesh.get("shards", [])) or 1
                bottleneck["stage"] = "shard_skew"
                bottleneck["node"] = f"shard:{mesh.get('hot_shard')}"
                bottleneck["share"] = round(
                    (hot["rows"] / total) if hot else 0.0, 4)

        # ---- event-time progress (watermark lag, pane occupancy)
        wm_info = self._watermark_probe(rid, ordered, now)

        # ---- verdict: burn thresholds + watermark bound, with hysteresis
        # burn_f/burn_s (per-window max across signals) are the REPORTED
        # fast/slow gauges; the THRESHOLD test is per signal — a signal
        # must burn in BOTH its windows before it escalates, so a fast
        # latency spike coinciding with residual slow-window drop burn
        # cannot degrade a rule neither signal would degrade alone (it
        # would also emit a reason-less transition: the reasons guards
        # below are per signal too)
        burn_f = max(lat_burn_f, drop_burn_f)
        burn_s = max(lat_burn_s, drop_burn_s)
        tr.peak_burn = max(tr.peak_burn, burn_f, burn_s)
        worst = max(min(lat_burn_f, lat_burn_s),
                    min(drop_burn_f, drop_burn_s))
        reasons: List[str] = []
        breach = worst >= self.breach_burn
        degrade = worst >= 1.0
        if min(lat_burn_f, lat_burn_s) >= 1.0:
            reasons.append(
                f"latency burn fast={lat_burn_f:.1f}x slow="
                f"{lat_burn_s:.1f}x (p99 bound {bound}ms)")
        if min(drop_burn_f, drop_burn_s) >= 1.0:
            reasons.append(
                f"drop burn fast={drop_burn_f:.1f}x slow="
                f"{drop_burn_s:.1f}x (budget {slo['max_drop_ratio']})")
        mwl = slo["max_watermark_lag_ms"]
        lag = wm_info.get("lag_ms")
        if mwl and lag is not None:
            if lag > 3 * mwl:
                breach = True
                reasons.append(
                    f"watermark lag {lag}ms > 3x bound {mwl}ms")
            elif lag > mwl:
                degrade = True
                reasons.append(f"watermark lag {lag}ms > bound {mwl}ms")
        target = (BREACHING if breach
                  else DEGRADED if degrade else HEALTHY)
        prev_state = tr.state
        lvl_t, lvl_c = STATE_LEVEL[target], STATE_LEVEL[tr.state]
        if lvl_t > lvl_c:
            tr.up_pend += 1
            # escalate to the MINIMUM level sustained across the whole
            # pending run — a single breach-level spike inside an
            # otherwise-degraded run must not page as breaching (the
            # "up_ticks consecutive ticks above threshold" promise is
            # per level, not per direction)
            tr.up_level = (lvl_t if tr.up_pend == 1
                           else min(tr.up_level, lvl_t))
            tr.down_pend = 0
            if tr.up_pend >= self.up_ticks:
                tr.state = _LEVEL_STATE[tr.up_level]
                tr.up_pend = 0
        elif lvl_t < lvl_c:
            tr.down_pend += 1
            tr.up_pend = 0
            if tr.down_pend >= self.down_ticks:
                tr.state = _LEVEL_STATE[lvl_c - 1]  # step down one level
                tr.down_pend = 0
        else:
            tr.up_pend = 0
            tr.down_pend = 0
        if tr.state != prev_state:
            tr.state_since_ms = now
            tr.ticks_in_state = 0
            from ..runtime.events import recorder

            severity = ("error" if tr.state == BREACHING
                        else "warn" if tr.state == DEGRADED else "info")
            recorder().record(
                "rule_health", rule=rid, severity=severity,
                # ts_ms: we hold self._lock, which mock-clock callbacks
                # also take (_fire -> tick) — record() must not read the
                # clock on our behalf (see FlightRecorder.record)
                ts_ms=now,
                state=tr.state, previous=prev_state,
                burn_fast=round(burn_f, 2), burn_slow=round(burn_s, 2),
                bottleneck=bottleneck.get("stage"),
                watermark_lag_ms=lag,
                **({"reasons": reasons[:3]} if reasons else {}))
        tr.ticks_in_state += 1

        tr.verdict = {
            "rule": rid,
            "state": tr.state,
            "since_ms": tr.state_since_ms,
            "ticks_in_state": tr.ticks_in_state,
            "slo": slo,
            "burn_rate": {
                "fast": round(burn_f, 3), "slow": round(burn_s, 3),
                "latency_fast": round(lat_burn_f, 3),
                "latency_slow": round(lat_burn_s, 3),
                "drop_fast": round(drop_burn_f, 3),
                "drop_slow": round(drop_burn_s, 3),
                "breach_threshold": self.breach_burn,
            },
            "latency": {
                "window_fast": fast_snap, "window_slow": slow_snap,
                "violating_fast": round(frac_f, 4) if n_f else 0.0,
                "violating_slow": round(frac_s, 4) if n_s else 0.0,
                # window evidence mass — what the burns were weighted by
                "samples_fast": n_f, "samples_slow": n_s,
                "tick_samples": delta_n,
            },
            "drops": {
                "tick_dropped": drops_d, "tick_offered": ins_d,
                "ratio_fast": round(drop_ratio_f, 5),
                "ratio_slow": round(drop_ratio_s, 5),
            },
            "bottleneck": bottleneck,
            "watermark": wm_info,
            "hbm": self._rule_hbm(rid),
            **({"reasons": reasons} if reasons else {}),
        }

    @staticmethod
    def _device_axis(rid: str, tr: "_RuleTrack",
                     ops: Optional[Dict[str, Any]] = None
                     ) -> Optional[Dict[str, Any]]:
        """Tick delta of the rule's kernwatch counters → device vs host
        attribution. None when no kernel was sampled this tick (the axis
        is only asserted on evidence, never inferred). `ops` is the
        tick-shared kernwatch.rule_ops_all() map; None falls back to a
        single-rule fetch (direct callers, tests)."""
        if ops is not None:
            cur = {op: dict(v) for op, v in (ops.get(rid) or {}).items()}
        else:
            from . import kernwatch

            try:
                cur = kernwatch.rule_ops(rid)
            except Exception:
                return None
        prev = tr.prev_kern
        tr.prev_kern = cur
        dev_d = disp_d = 0.0
        samp_d = 0
        top_op: Optional[str] = None
        top_dev = -1.0
        for op, c in cur.items():
            p = prev.get(op, {})
            sd = c["samples"] - p.get("samples", 0)
            if sd <= 0:
                continue
            dd = max(c["device_us"] - p.get("device_us", 0.0), 0.0)
            pd = max(c["dispatch_us"] - p.get("dispatch_us", 0.0), 0.0)
            samp_d += sd
            dev_d += dd
            disp_d += pd
            if dd > top_dev:
                top_dev, top_op = dd, op
        if samp_d <= 0:
            return None
        total = dev_d + disp_d
        share = dev_d / total if total > 0 else 0.0
        out: Dict[str, Any] = {
            "axis": "device" if share >= 0.5 else "host",
            "device_share": round(share, 4),
            "device_us": int(dev_d),
            "dispatch_us": int(disp_d),
            "samples": int(samp_d),
            "op": top_op,
        }
        top = cur.get(top_op) or {}
        if top.get("roofline_util") is not None:
            out["roofline_util"] = top["roofline_util"]
            out["bound"] = top.get("bound")
        return out

    @staticmethod
    def _watermark_probe(rid: str, nodes: List[Any],
                         now: int) -> Dict[str, Any]:
        """Event-time progress read off the rule's live nodes. Lazy class
        imports — observability must not import the runtime at module
        load. Shared-fold members report THEIR OWN emit cursor (lag is a
        per-rule fact even when the pane store is shared)."""
        from ..runtime.nodes_fused import FusedWindowAggNode
        from ..runtime.nodes_sharedfold import SharedFoldNode
        from ..runtime.nodes_window import WatermarkNode, WindowNode

        wm_ts: Optional[int] = None
        occupancy: Optional[float] = None
        buffered = 0
        cursor: Optional[int] = None
        event_time = False
        for node in nodes:
            if isinstance(node, WatermarkNode):
                ts = node.watermark_ts()
                if ts is not None and (wm_ts is None or ts > wm_ts):
                    wm_ts = ts
                event_time = True
            elif isinstance(node, SharedFoldNode):
                occ = node.pane_occupancy()
                occupancy = occ if occupancy is None else max(occupancy,
                                                              occ)
                cur = node.member_cursor_ms(rid)
                if cur is not None:
                    cursor = cur
                event_time = event_time or node.is_event_time
            elif isinstance(node, FusedWindowAggNode):
                occ = node.pane_occupancy()
                if occ is not None:
                    occupancy = (occ if occupancy is None
                                 else max(occupancy, occ))
                event_time = event_time or node.is_event_time
            elif isinstance(node, WindowNode):
                buffered += node.occupancy_rows()
                event_time = event_time or node.is_event_time
        lag = max(now - wm_ts, 0) if wm_ts is not None else None
        out: Dict[str, Any] = {"event_time": event_time, "lag_ms": lag,
                               "watermark_ts": wm_ts,
                               "buffered_rows": buffered}
        if occupancy is not None:
            out["pane_occupancy"] = round(occupancy, 4)
        if cursor is not None:
            out["emit_cursor_ms"] = cursor
        return out

    @staticmethod
    def _rule_hbm(rid: str) -> Dict[str, Any]:
        from . import memwatch

        total = 0
        for (component, rule), n in memwatch.registry().aggregate().items():
            if rule == rid:
                total += n
        return {"bytes": total}

    # ---------------------------------------------------------------- queries
    def verdicts(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {rid: tr.verdict for rid, tr in self._tracks.items()
                    if tr.verdict is not None}

    def has_track(self, rule_id: str) -> bool:
        """True once the evaluator has attempted this rule at least once
        (a track exists even when evaluation raised — REST callers use
        this to avoid forcing a tick per request for a rule that will
        never produce a verdict)."""
        with self._lock:
            return rule_id in self._tracks

    def rule_health(self, rule_id: str,
                    refresh_if_missing: bool = True) -> Optional[Dict[str, Any]]:
        """Last verdict for one rule; when the evaluator has never seen
        the rule (installed after it, or never ticked) one synchronous
        tick seeds it. A rule with a track but no verdict (its eval
        raises) does NOT re-tick — off-cadence ticks decay the burn
        windows and collapse the FSM hysteresis for every other rule, so
        a polled endpoint must not be able to trigger them repeatedly."""
        with self._lock:
            tr = self._tracks.get(rule_id)
        if tr is None and refresh_if_missing:
            # tick() OUTSIDE our lock: it reads the engine clock first,
            # and a mock advance fires _fire -> tick while holding the
            # clock lock — ticking reentrantly under self._lock was the
            # evaluator half of the clock/health ABBA utils/lockcheck.py
            # caught on day one (clock orders before the evaluator lock).
            # _seed_mu keeps the seeding single-flight: concurrent polls
            # for an untracked rule must produce ONE off-cadence tick,
            # not one each (off-cadence ticks decay every rule's burn
            # windows — see the docstring above)
            with self._seed_mu:
                with self._lock:
                    tr = self._tracks.get(rule_id)
                if tr is None:
                    self.tick()
                    with self._lock:
                        tr = self._tracks.get(rule_id)
        with self._lock:
            return tr.verdict if tr is not None else None

    def peak_burn(self, rule_id: str) -> float:
        with self._lock:
            tr = self._tracks.get(rule_id)
            return round(tr.peak_burn, 3) if tr is not None else 0.0

    def hbm_trend(self) -> Dict[str, Any]:
        """Engine HBM headroom trend off the per-tick memwatch samples."""
        with self._lock:
            samples = list(self._hbm)
        if not samples:
            return {"bytes": 0, "trend_bytes_per_min": 0.0, "samples": 0}
        cur = samples[-1][1]
        trend = 0.0
        if len(samples) >= 2:
            dt_ms = samples[-1][0] - samples[0][0]
            if dt_ms > 0:
                trend = (cur - samples[0][1]) * 60_000.0 / dt_ms
        return {"bytes": cur, "trend_bytes_per_min": round(trend, 1),
                "samples": len(samples)}

    def diagnostics(self) -> Dict[str, Any]:
        """The GET /diagnostics/health payload."""
        return {
            "evaluator": {
                "interval_ms": self.interval_ms,
                "ticks": self.ticks,
                "last_tick_us": round(self.last_tick_us, 1),
                "up_ticks": self.up_ticks,
                "down_ticks": self.down_ticks,
                "breach_burn": self.breach_burn,
            },
            "hbm": self.hbm_trend(),
            "rules": self.verdicts(),
        }


# ------------------------------------------------------------- singleton
_evaluator: Optional[HealthEvaluator] = None
_install_lock = threading.Lock()


def install(rules_fn: Callable[[], List[tuple]],
            interval_ms: int = DEFAULT_INTERVAL_MS,
            start: bool = True, **kw) -> HealthEvaluator:
    """Install (replacing any prior) the engine-wide evaluator. The REST
    server installs one over its rule registry at boot."""
    global _evaluator
    with _install_lock:
        if _evaluator is not None:
            _evaluator.stop()
        _evaluator = HealthEvaluator(rules_fn, interval_ms=interval_ms,
                                     **kw)
        ev = _evaluator
    if start:
        ev.start()
    return ev


def evaluator() -> Optional[HealthEvaluator]:
    return _evaluator


def rule_verdict(rule_id: str) -> Optional[Dict[str, Any]]:
    """Last verdict WITHOUT forcing a tick — status JSON enrichment must
    not pay evaluation cost per call."""
    ev = _evaluator
    if ev is None:
        return None
    return ev.rule_health(rule_id, refresh_if_missing=False)


def reset() -> None:
    """Test hook: stop and drop the installed evaluator."""
    global _evaluator
    with _install_lock:
        if _evaluator is not None:
            _evaluator.stop()
        _evaluator = None


# -------------------------------------------------------- Prometheus view
def render_prometheus(out: List[str], esc) -> None:
    """Append the health-plane families to a /metrics scrape."""
    ev = _evaluator
    if ev is None:
        return
    verdicts = sorted(ev.verdicts().items())
    out.append("# TYPE kuiper_rule_health gauge")
    out.append("# HELP kuiper_rule_health verdict per rule "
               "(0 healthy, 1 degraded, 2 breaching)")
    for rid, v in verdicts:
        out.append(f'kuiper_rule_health{{rule="{esc(rid)}"}} '
                   f"{STATE_LEVEL.get(v['state'], 0)}")
    out.append("# TYPE kuiper_slo_burn_rate gauge")
    out.append("# HELP kuiper_slo_burn_rate SLO error-budget burn "
               "multiple per rule and window (>=1 unsustainable)")
    for rid, v in verdicts:
        br = v["burn_rate"]
        for window in ("fast", "slow"):
            out.append(
                f'kuiper_slo_burn_rate{{rule="{esc(rid)}",'
                f'window="{window}"}} {br[window]}')
    out.append("# TYPE kuiper_watermark_lag_ms gauge")
    out.append("# HELP kuiper_watermark_lag_ms event-time watermark lag "
               "behind the engine clock per rule (ms)")
    for rid, v in verdicts:
        lag = v["watermark"].get("lag_ms")
        if lag is not None:
            out.append(
                f'kuiper_watermark_lag_ms{{rule="{esc(rid)}"}} {lag}')
    out.append("# TYPE kuiper_bottleneck_stage gauge")
    out.append("# HELP kuiper_bottleneck_stage dominant pipeline stage "
               "per rule (value = its share of stage time this tick)")
    for rid, v in verdicts:
        bn = v["bottleneck"]
        if bn.get("stage"):
            out.append(
                f'kuiper_bottleneck_stage{{rule="{esc(rid)}",'
                f'stage="{esc(bn["stage"])}"}} {bn["share"]}')


# ------------------------------------------------------- profile capture
#: hard cap on one capture — the endpoint must stay "bounded" even when
#: a caller asks for minutes
PROFILE_MAX_MS = 30_000
_profile_lock = threading.Lock()


def capture_profile(duration_ms: int = 1000,
                    out_dir: Optional[str] = None) -> Dict[str, Any]:
    """On-demand deep capture: a bounded `jax.profiler.trace` plus a
    devwatch signature dump, memwatch snapshot, and current health
    verdicts, written into one bundle directory. Wall-clock bounded (the
    profiler measures real time; the engine clock may be mocked). One
    capture at a time — the profiler is a process-global resource."""
    dur_ms = min(max(int(duration_ms), 50), PROFILE_MAX_MS)
    if not _profile_lock.acquire(blocking=False):
        raise RuntimeError("a profile capture is already running")
    try:
        if out_dir is None:
            from ..utils.config import get_config

            out_dir = os.path.join(
                get_config().store.path, "profiles",
                # kuiperlint: ignore[clock-discipline]: bundle dirs need unique wall timestamps — a frozen mock clock would collide captures
                f"profile_{int(_time.time() * 1000)}")
        os.makedirs(out_dir, exist_ok=True)
        result: Dict[str, Any] = {"dir": out_dir, "duration_ms": dur_ms}
        t0 = _time.perf_counter()
        try:
            import jax

            jax.profiler.start_trace(out_dir)
            try:
                # kuiperlint: ignore[clock-discipline]: jax.profiler.trace records wall time; timex.sleep under a mock clock would end the capture instantly
                _time.sleep(dur_ms / 1000.0)
            finally:
                jax.profiler.stop_trace()
            result["trace"] = "ok"
        except Exception as exc:
            # a capture with no device trace still carries the dumps —
            # degrade, never fail the endpoint
            result["trace"] = f"unavailable: {exc}"
        result["captured_s"] = round(_time.perf_counter() - t0, 3)
        from . import devwatch, memwatch

        dump = {
            # kuiperlint: ignore[clock-discipline]: postmortem bundles are correlated against external logs by wall time, not engine time
            "generated_at_ms": int(_time.time() * 1000),
            "xla": {
                "totals": devwatch.registry().totals(),
                "sites": [{**w.snapshot(),
                           "signatures": w.signature_dump()}
                          for w in devwatch.registry().watches()],
            },
            "memory": memwatch.diagnostics(),
        }
        ev = _evaluator
        if ev is not None:
            dump["health"] = ev.diagnostics()
        dump_path = os.path.join(out_dir, "devwatch_dump.json")
        with open(dump_path, "w") as f:
            json.dump(dump, f, indent=2, default=str)
        files = []
        for root, _dirs, names in os.walk(out_dir):
            for name in names:
                files.append(os.path.relpath(os.path.join(root, name),
                                             out_dir))
        result["files"] = sorted(files)
        return result
    finally:
        _profile_lock.release()
