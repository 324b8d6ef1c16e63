"""Adaptive QoS control plane — the first ACTUATOR on the engine's
telemetry.

The observability stack built across the last PRs measures everything:
per-rule SLO burn, bottleneck stage, watermark lag, HBM trend, XLA
compile storms, drop taxonomy. The health plane
(observability/health.py) turns those into per-rule VERDICTS — and until
now nothing acted on them: a rule-churn storm or a hot-key skew shift
degraded every rule equally through global drop-oldest backpressure.
This module closes the loop (ROADMAP item 5) with three bounded,
logged actuators:

- **Admission control** — `admit_rule()` prices a candidate rule BEFORE
  it starts: the sharing cost model's fold/emit coefficients
  (planner/sharing.py, the Factor-Windows currency) give its steady-state
  device cost, memwatch + the health plane's HBM trend bound its memory
  claim, and devwatch's compile-storm counters flag a bad moment to add
  compile load. The decision is structured — accept | reject(reason,
  price) | queue(reason, price) — never a bare exception: a rejected
  rule's caller gets the price that condemned it, a queued rule is
  retried every control tick and started when pressure clears.

- **SLO-driven load shedding** — when the health FSM holds a rule at
  `breaching`, the controller sheds THAT RULE's input at its topo entry
  nodes (runtime/topo.py entry_nodes — downstream of shared work,
  upstream of the rule's private pipeline) through the existing drop
  taxonomy (`StatManager.inc_dropped(reason="shed_qos")`). The shed
  fraction climbs a per-qos-class ladder with hysteresis mirroring the
  health FSM (`up_ticks` breaching ticks per escalation, `down_ticks`
  healthy ticks per step down); `qosClass: critical` rules are never
  shed. Every transition is a flight-recorder event.

- **Auto-sizing** — when the attributed bottleneck is `decode` or
  `upload` on a rule that is not healthy, the controller resizes the
  source's decode pool (more parse workers) or ingest ring (deeper
  decode→fold overlap), bounded by `KUIPER_AUTOSIZE_MAX_POOL/RING`,
  cooled down between actions, stepped back toward the configured size
  after sustained health, and logged + flight-recorded per action.
  Inline sources (decode_pool_size=0) are never converted — that path
  is bit-for-bit deterministic by contract.

Configuration (all read at decision time, so tests/bench set per-case):

  KUIPER_CONTROL_INTERVAL_MS            controller cadence (default 5s)
  KUIPER_ADMISSION=0                    disable admission (accept all)
  KUIPER_HBM_BUDGET_MB                  reject when current+projected HBM
                                        exceeds it (0 = off)
  KUIPER_ADMISSION_FOLD_BUDGET_US_PER_S reject when the committed fold
                                        ledger + price exceeds it (0=off)
  KUIPER_ADMISSION_SIG_BUDGET           reject when the candidate's
                                        jitcert-certified signature count
                                        exceeds it (0 = off)
  KUIPER_ADMISSION_DEFER_BREACHING      queue new rules while >= N rules
                                        are breaching (0 = off)
  KUIPER_ADMISSION_DEFER_STORMS=0       stop queueing on compile storms
  KUIPER_AUTOSIZE_MAX_POOL / _MAX_RING  autosize upper bounds (default 6)

Prometheus families (docs/OBSERVABILITY.md + docs/RESILIENCE.md):
kuiper_admission_total{decision}, kuiper_shed_total{rule,qos},
kuiper_autosize_events_total.
"""
from __future__ import annotations

import os
import threading
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from ..utils import timex
from ..utils.infra import EngineError, logger

# ------------------------------------------------------------- QoS classes
#: per-class shed ladders: level 1..n -> fraction of the rule's input
#: discarded at its entry nodes. `critical` is exempt — it rides global
#: backpressure only. The class is a RULE option (`qosClass`), distinct
#: from the checkpoint `qos` level.
SHED_LADDERS: Dict[str, tuple] = {
    "low": (0.25, 0.50, 0.75, 0.90),
    "standard": (0.10, 0.25, 0.50, 0.75),
    "high": (0.05, 0.10, 0.25, 0.50),
    "critical": (),
}

DEFAULT_QOS_CLASS = "standard"


def parse_qos_class(options: Optional[Dict[str, Any]]) -> str:
    """Rule QoS class off its options (`qosClass`/`qos_class`); unknown
    values fall back to `standard` (a typo must not exempt a rule from
    shedding — nor subject it to the `low` ladder)."""
    raw = (options or {}).get("qosClass",
                              (options or {}).get("qos_class"))
    cls = str(raw).strip().lower() if raw is not None else DEFAULT_QOS_CLASS
    return cls if cls in SHED_LADDERS else DEFAULT_QOS_CLASS


# --------------------------------------------------------------- admission
#: admission pricing coefficients beyond the sharing model's: rough
#: steady-state cost of a host-path rule per batch (row loop + project +
#: sink), and the HBM projection's pane multiplier (panes + emit staging)
HOST_BATCH_US = 50.0
HBM_PANE_FACTOR = 4

DEFAULT_INTERVAL_MS = int(os.environ.get("KUIPER_CONTROL_INTERVAL_MS",
                                         "5000") or 5000)
#: admission queue bound — past it, queueing degrades to reject (a queue
#: that grows without bound during a storm is its own meltdown)
ADMISSION_QUEUE_CAP = 64


class AdmissionRejected(EngineError):
    """A rule was refused admission. Carries the STRUCTURED decision
    (reason + price) — the REST layer serializes it instead of a bare
    error string, per the control plane's no-opaque-rejections
    contract."""

    def __init__(self, decision: Dict[str, Any]) -> None:
        super().__init__(
            f"rule admission rejected: {decision.get('reason', '?')}")
        self.decision = decision


def _env_float(name: str, default: float = 0.0) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def placement_shards() -> int:
    """Chips available for rule placement — the multi-chip serving mode's
    admission axis (docs/DISTRIBUTED.md). KUIPER_MESH geometry when set
    ("RxK"/"K", or "auto" = every local device); 1 otherwise, which keeps
    every single-chip deployment's admission semantics bit-identical."""
    from ..parallel.mesh import mesh_cfg_from_env

    cfg = mesh_cfg_from_env()
    if cfg is None:
        return 1
    if cfg.get("auto"):
        try:
            import jax

            return max(len(jax.devices()), 1)
        except Exception:
            return 1
    return max(int(cfg.get("rows", 1)) * int(cfg.get("keys", 1)), 1)


def _placement_for(price: Dict[str, Any], loads: List[float],
                   budget_bytes: float) -> Optional[Dict[str, Any]]:
    """Pick a placement for a candidate against the per-chip committed
    ledger: a mesh-eligible rule spreads its claim 1/K across every chip
    (its state is key-range sharded), anything else lands whole on the
    least-loaded chip. Returns the placement dict, or None when no chip
    (set) can hold the claim within the per-chip budget."""
    K = len(loads)
    cur_share = float(price.get("hbm_current_bytes", 0)) / max(K, 1)
    projected = float(price.get("hbm_projected_bytes", 0))
    if price.get("placement_eligible") and K > 1:
        share = projected / K
        if max(loads) + share + cur_share <= budget_bytes:
            return {"mode": "sharded", "shards": list(range(K)),
                    "bytes_per_shard": int(share)}
        return None
    chip = int(np_argmin(loads))
    if loads[chip] + projected + cur_share <= budget_bytes:
        return {"mode": "single", "shards": [chip],
                "bytes_per_shard": int(projected)}
    return None


def np_argmin(vals: List[float]) -> int:
    best, best_v = 0, None
    for i, v in enumerate(vals):
        if best_v is None or v < best_v:
            best, best_v = i, v
    return best


def bill_placement(price: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Placement for an already-admitted rule being (re)billed OUTSIDE
    the admission gate (boot recovery, operator start of a stopped
    rule): the same math as the gate, but it never rejects — a claim
    that no longer fits still bills where it would land, so the
    per-chip ledger reflects what actually runs after a restart
    instead of re-gating admissions against an empty ledger."""
    ctl = _controller
    K = placement_shards()
    budget = _env_float("KUIPER_HBM_BUDGET_MB") * 1024 * 1024
    if ctl is None or K <= 1 or budget <= 0:
        # with the budget unset the admission gate never places rules
        # either — billing only here would make the ledger (and the
        # kuiper_shard_rules gauge) appear out of nowhere after restarts
        return None
    loads = ctl.shard_loads(K)
    p = _placement_for(price, loads, budget)
    if p is not None:
        return p
    # nothing fits (the fleet outgrew the budget while it ran): bill
    # where the claim lands anyway, so the ledger reflects what runs
    projected = int(price.get("hbm_projected_bytes", 0))
    if price.get("placement_eligible"):
        return {"mode": "sharded", "shards": list(range(K)),
                "bytes_per_shard": projected // K}
    return {"mode": "single", "shards": [np_argmin(loads)],
            "bytes_per_shard": projected}


def _tier_price_slots(price: Dict[str, Any], plan, stmt, opts) -> int:
    """Hot-set slot claim for a tiered candidate (0 = untiered).
    Mirrors the planner's eligibility gates (planner/planner.py
    _build_device_chain + the fused node's window-type gate) so
    admission prices exactly what would be built; memoized in
    price["tier"] so the signature pricing and the HBM projection read
    one decision."""
    cached = price.get("tier")
    if cached is not None:
        return int(cached.get("hot_slots", 0))
    price["tier"] = {}
    try:
        from ..ops.tierstore import plan_tier_layout
        from ..planner.planner import resolve_tier_budget_mb
        from ..sql import ast as _ast

        budget = resolve_tier_budget_mb(opts)
        w = stmt.window
        if (not budget or stmt.sorts or stmt.limit is not None
                or (opts.plan_optimize_strategy or {}).get("mesh")
                or w is None
                or w.window_type not in (_ast.WindowType.TUMBLING_WINDOW,
                                         _ast.WindowType.HOPPING_WINDOW,
                                         _ast.WindowType.SLIDING_WINDOW)
                or any(s.kind == "heavy_hitters" for s in plan.specs)
                or plan.has_orderstat):
            return 0
        # the SAME pane count the node derives — a hopping rule's
        # per-key state is n_panes times wider, and pricing with 1 pane
        # would disagree with the node about whether the tier even
        # engages (unpriced tier jit sites / over-claimed HBM)
        if w.window_type == _ast.WindowType.HOPPING_WINDOW:
            iv = max(w.interval_ms() or 0, 1)
            n_panes = max((w.length_ms() + iv - 1) // iv, 1)
        elif w.window_type == _ast.WindowType.SLIDING_WINDOW:
            from ..ops.slidingring import ring_layout_for

            n_panes = ring_layout_for(w, plan).n_panes
        else:
            n_panes = 1
        layout = plan_tier_layout(
            plan, int(n_panes), opts.key_slots, budget,
            scan_interval_ms=opts.tier_scan_ms,
            window_ms=w.interval_ms() or w.length_ms())
        if layout is None:
            return 0
        # the node builds at the pow2-capped hot target (nodes_fused.py
        # uses the SAME TierLayout.hot_capacity) — price exactly that,
        # never more than the untiered request
        claim = min(int(opts.key_slots), layout.hot_capacity())
        price["tier"] = {"hot_slots": claim,
                        "demote_batch": int(layout.demote_batch)}
        return claim
    except Exception:
        return 0


def _relational_signatures(stmt, opts) -> int:
    """Certified signature count for the relational tier a host-chain
    rule would instantiate: the join-ring pad-pair ladder when the ON
    clause lowers, segscan shift/sort when lag or the rank family
    lowers. Non-lowering pieces cost nothing — they run as host python."""
    from ..observability import jitcert
    from ..planner import relational
    from ..planner.planner import _analytic_calls, _window_func_calls
    from ..sql.expr_ir import NotVectorizable

    kw: Dict[str, Any] = {}
    if stmt.joins and opts.join_impl == "device":
        try:
            low = relational.lower_join(stmt, stmt.joins)
            rl, rr = low.resid_signature()
            kw.update(join=True, join_resid_l=rl, join_resid_r=rr)
        except NotVectorizable:
            pass
    if opts.analytic_impl == "device":
        analytic = _analytic_calls(stmt)
        if analytic:
            try:
                relational.lower_analytics(analytic)
                kw["analytic_shift"] = True
            except NotVectorizable:
                pass
        wf = _window_func_calls(stmt)
        if wf:
            try:
                if relational.lower_window_funcs(wf).device_eligible():
                    kw["analytic_sort"] = True
            except NotVectorizable:
                pass
    if not kw:
        return 0
    return jitcert.estimate_relational_signatures(**kw)


def price_rule(rule, store) -> Dict[str, Any]:
    """Price a candidate rule off the live cost model + telemetry.
    Degrades per component — a rule the planner cannot price (graph
    rules, parse oddities) gets a zero-cost component, never an
    exception: admission must not be a new way for create to crash."""
    price: Dict[str, Any] = {
        "fold_us_per_s": 0.0,
        "path": "unknown",
        "hbm_projected_bytes": 0,
        "hbm_current_bytes": 0,
        "hbm_trend_bytes_per_min": 0.0,
        "compile_storms_total": 0,
        # compile load priced STATICALLY off the jitcert certificate:
        # the closed signature set this rule's kernel may trace at its
        # construction capacity (observability/jitcert.py) — admission
        # no longer waits for devwatch's live storm edge to learn a
        # candidate is compile-heavy. None = UNKNOWN (pricing failed /
        # unpriceable plan): gates treat unknown as compile load, so an
        # estimation failure can never open the storm-bypass
        "certified_new_signatures": None,
    }
    from ..observability import devwatch, memwatch
    from ..planner import sharing

    try:
        price["hbm_current_bytes"] = memwatch.registry().total_bytes()
    except Exception:
        pass
    try:
        from ..observability import health

        ev = health.evaluator()
        if ev is not None:
            price["hbm_trend_bytes_per_min"] = \
                ev.hbm_trend()["trend_bytes_per_min"]
    except Exception:
        pass
    try:
        price["compile_storms_total"] = \
            devwatch.registry().totals()["storms"]
    except Exception:
        pass
    try:
        from ..ops.aggspec import extract_kernel_plan
        from ..planner.planner import explain as plan_explain
        from ..planner.planner import merged_options
        from ..sql.parser import parse_select

        stmt = parse_select(rule.sql)
        opts = merged_options(rule)
        batches_per_s = 1000.0 / max(opts.micro_batch_linger_ms, 1)
        plan = None
        try:
            plan = extract_kernel_plan(stmt)
        except Exception:
            plan = None
        if plan is None:
            price["path"] = "host"
            price["fold_us_per_s"] = round(HOST_BATCH_US * batches_per_s, 1)
            price["certified_new_signatures"] = 0  # no fused kernel
            # relational kernels (join ring / segscan) still compile on
            # the host chain — price their certified signature sets so a
            # join-heavy candidate cannot slip past the compile budget
            try:
                price["certified_new_signatures"] = \
                    _relational_signatures(stmt, opts)
            except Exception as exc:
                logger.warning(
                    "relational pricing failed for rule %s: %s",
                    rule.id, exc)
        else:
            n_specs = len(plan.specs)
            explain = {}
            try:
                explain = plan_explain(rule, store)
            except Exception:
                pass
            share = explain.get("sharing") or {}
            if share.get("decision") == "shared":
                # marginal cost of joining the fleet: the emit-combine
                # overhead the sharing model already estimated — the
                # fold itself is already being paid for, and the store's
                # executables already exist (0 certified new signatures)
                price["path"] = "device-shared"
                price["fold_us_per_s"] = float(
                    (share.get("estimates") or {})
                    .get("emit_overhead_us_per_s", 0.0))
                price["certified_new_signatures"] = 0
            else:
                price["path"] = "device-private"
                price["fold_us_per_s"] = round(
                    (sharing.FOLD_DISPATCH_US
                     + sharing.FOLD_SPEC_US * n_specs) * batches_per_s, 1)
                try:
                    from ..observability import jitcert
                    from ..sql import ast as _ast

                    # pane count does not enter: it changes signature
                    # SHAPES, not the executable count the budget gates
                    # on (one executable per capacity step either way).
                    # DABA sliding rules price their ring sites too
                    # (advance/flip/query + components_dyn) — without
                    # this the budget under-prices sliding candidates
                    ring_slots = 0
                    if (stmt.window is not None
                            and stmt.window.window_type
                            == _ast.WindowType.SLIDING_WINDOW
                            and opts.sliding_impl == "daba"):
                        from ..ops.slidingring import ring_layout_for

                        ring_slots = ring_layout_for(
                            stmt.window, plan).n_ring_panes
                    sig_args = (plan, 1, opts.micro_batch_rows,
                                _tier_price_slots(price, plan, stmt, opts)
                                or opts.key_slots)
                    sig_kw = dict(
                        sliding_ring_slots=ring_slots,
                        tier_demote_batch=(price.get("tier", {})
                                           .get("demote_batch", 0)))
                    price["certified_new_signatures"] = \
                        jitcert.estimate_plan_signatures(
                            *sig_args, **sig_kw)
                    # AOT ledger: signatures a fleet bake already
                    # persisted are NOT compile debt — the signature
                    # budget gates on `uncached` when the disk cache is
                    # on (runtime/aotcache.py, docs/AOT_CACHE.md)
                    from . import aotcache

                    price["compile"] = aotcache.plan_compile_price(
                        jitcert.estimate_plan_certs(*sig_args, **sig_kw))
                except Exception as exc:
                    # leave the UNKNOWN sentinel: failing open here
                    # would both disarm the signature budget and route
                    # a compile-heavy candidate through the storm
                    # bypass — the exact class the gate exists to defer
                    logger.warning(
                        "jitcert pricing failed for rule %s: %s",
                        rule.id, exc)
                    price["certify_error"] = str(exc)[:200]
            # projected window-state claim: one f32 slot per key per agg
            # spec, times the pane/staging multiplier (documented in
            # docs/RESILIENCE.md — a bound, not an allocation). A TIERED
            # rule claims its HOT-SET footprint, not its full
            # cardinality: cold keys spill to host, so a high-cardinality
            # rule whose hot set fits is admitted where the untiered
            # projection would 429 it.
            slot_claim = (_tier_price_slots(price, plan, stmt, opts)
                          or opts.key_slots)
            price["hbm_projected_bytes"] = int(
                slot_claim * max(n_specs, 1) * 4 * HBM_PANE_FACTOR)
            # placement (multi-chip serving): a rule the planner would
            # shard spreads its state claim 1/K across the mesh — the
            # HBM gate then places it instead of rejecting at the
            # single-chip budget (docs/DISTRIBUTED.md)
            try:
                from ..planner.planner import mesh_request

                req = mesh_request(opts, plan)
                price["placement_eligible"] = req["mode"] == "sharded"
                if req["mode"] == "sharded":
                    price["mesh_source"] = req.get("source")
            except Exception:
                price["placement_eligible"] = False
            if share:
                price["sharing"] = {
                    "decision": share.get("decision"),
                    "reason": share.get("reason", "")[:160],
                }
    except Exception as exc:
        price["price_error"] = str(exc)[:200]
    return price


def _static_gates(price: Dict[str, Any],
                  committed_us_per_s: float,
                  ctl: "Optional[QoSController]" = None,
                  rule_id: Optional[str] = None
                  ) -> Optional[Dict[str, Any]]:
    """Budget gates that need no controller: return a reject decision or
    None. Budgets default OFF (env unset) — admission then accepts.
    With a controller AND a multi-chip mesh (KUIPER_MESH), the HBM
    budget becomes PER-CHIP and placement-aware: the candidate is
    assigned to the least-loaded shard (or spread 1/K when its plan
    shards) instead of rejecting at the single-chip budget."""
    hbm_budget_mb = _env_float("KUIPER_HBM_BUDGET_MB")
    if hbm_budget_mb > 0:
        budget = hbm_budget_mb * 1024 * 1024
        K = placement_shards()
        if ctl is not None and K > 1:
            loads = ctl.shard_loads(K, exclude=rule_id)
            placement = _placement_for(price, loads, budget)
            if placement is None:
                projected = price["hbm_projected_bytes"]
                return {
                    "decision": "reject",
                    "reason": (
                        f"projected HBM {projected / 1e6:.1f}MB does not "
                        f"fit any of {K} chips' {hbm_budget_mb:.0f}MB "
                        "per-chip budgets (KUIPER_HBM_BUDGET_MB; "
                        "least-loaded "
                        f"{min(loads) / 1e6:.1f}MB committed)"),
                    "price": price,
                }
            price["placement"] = placement
        else:
            projected = (price["hbm_current_bytes"]
                         + price["hbm_projected_bytes"])
            if projected > budget:
                return {
                    "decision": "reject",
                    "reason": (
                        f"projected HBM {projected / 1e6:.1f}MB exceeds "
                        f"the {hbm_budget_mb:.0f}MB budget "
                        "(KUIPER_HBM_BUDGET_MB)"),
                    "price": price,
                }
    fold_budget = _env_float("KUIPER_ADMISSION_FOLD_BUDGET_US_PER_S")
    if fold_budget > 0:
        if committed_us_per_s + price["fold_us_per_s"] > fold_budget:
            return {
                "decision": "reject",
                "reason": (
                    f"fold cost {price['fold_us_per_s']:.0f}us/s on top of "
                    f"{committed_us_per_s:.0f}us/s already committed "
                    f"exceeds the {fold_budget:.0f}us/s budget "
                    "(KUIPER_ADMISSION_FOLD_BUDGET_US_PER_S)"),
                "price": price,
            }
    sig_budget = int(_env_float("KUIPER_ADMISSION_SIG_BUDGET"))
    if sig_budget > 0:
        certified = price.get("certified_new_signatures")
        # unknown (None) passes THIS gate — rejecting on a pricing
        # failure would make every unpriceable host rule a 429; the
        # storm gate below stays conservative for unknowns instead
        priced = certified
        ledger = price.get("compile")
        if (priced is not None and ledger is not None
                and ledger.get("enabled") and not ledger.get("truncated")):
            # warm fleet image: only certified-but-UNCACHED signatures
            # are compile debt — executables the AOT bake persisted load
            # in tens of ms, they cannot stall the serve path
            priced = int(ledger.get("uncached", priced))
        if priced is not None and int(priced) > sig_budget:
            return {
                "decision": "reject",
                "reason": (
                    f"certified uncached compile surface of {priced} XLA "
                    f"signatures (certified {certified}) exceeds the "
                    f"{sig_budget}-signature budget "
                    "(KUIPER_ADMISSION_SIG_BUDGET; jitcert certificate "
                    "at construction capacity minus AOT-cached "
                    "executables)"),
                "price": price,
            }
    return None


# -------------------------------------------------------------- controller
class _RuleCtl:
    """Per-rule controller state across ticks."""

    __slots__ = ("shed_level", "breach_run", "clear_run", "qos_class",
                 "shed_rows_seen", "autosize_cool", "orig_sizes",
                 "missing_runs", "skew_run", "hint_active")

    def __init__(self) -> None:
        self.shed_level = 0
        self.breach_run = 0
        self.clear_run = 0
        self.qos_class = DEFAULT_QOS_CLASS
        self.shed_rows_seen = 0
        self.autosize_cool = 0
        self.orig_sizes: Dict[str, Dict[str, int]] = {}
        self.missing_runs = 0
        # mesh skew hysteresis (observability/meshwatch.py): consecutive
        # skewed ticks, and whether a rebalance_hint is currently open
        self.skew_run = 0
        self.hint_active = False


class QoSController:
    """Periodic actuator over the health plane's verdicts. `rules_fn()`
    yields the same (rule_id, topo, options) triples the HealthEvaluator
    consumes; `start_fn(rule_id)` starts a queued rule when admission
    pressure clears; `verdicts_fn()` defaults to the installed health
    evaluator's last verdicts (injectable for tests)."""

    def __init__(self, rules_fn: Callable[[], List[tuple]],
                 start_fn: Optional[Callable[[str], None]] = None,
                 verdicts_fn: Optional[Callable[[], Dict[str, Any]]] = None,
                 unqueue_fn: Optional[Callable[[str], None]] = None,
                 interval_ms: int = DEFAULT_INTERVAL_MS,
                 up_ticks: int = 2, down_ticks: int = 3) -> None:
        self._rules_fn = rules_fn
        self._start_fn = start_fn
        self._verdicts_fn = verdicts_fn
        # called when an entry leaves the queue WITHOUT being started
        # (dequeue-time reject) — the registry wires this to drop the
        # persisted admission_queue slot, or a restart would resurrect
        # a rule the controller already refused
        self._unqueue_fn = unqueue_fn
        self.interval_ms = int(interval_ms)
        self.up_ticks = max(int(up_ticks), 1)
        self.down_ticks = max(int(down_ticks), 1)
        self._lock = threading.RLock()
        self._timer = None
        self._running = False
        self.ticks = 0
        self._tracks: Dict[str, _RuleCtl] = {}
        # admission bookkeeping
        self._adm_counts = {"accept": 0, "reject": 0, "queue": 0}
        self._aqueue: Dict[str, Dict[str, Any]] = {}  # rid -> entry
        self._committed: Dict[str, float] = {}  # rid -> fold_us_per_s
        # per-chip HBM ledger (multi-chip serving): rid -> placement
        # {"mode": "sharded"|"single", "shards": [chip...],
        #  "bytes_per_shard": int} — billed at commit, released with the
        # rule; shard_loads() folds them into per-chip committed bytes
        self._placements: Dict[str, Dict[str, Any]] = {}
        self._prev_storms = 0
        self._storm_active = False
        # shed accounting: monotonic row totals per (rule, qos class) —
        # survives topo restarts (node counters reset with the topo)
        self._shed_totals: Dict[tuple, int] = {}
        # autosize accounting
        self.autosize_events = 0
        self._autosize_log: deque = deque(maxlen=64)
        # mesh skew accounting: rebalance_hint events raised (lifetime)
        self._rebalance_hints = 0

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
        except Exception as exc:  # the controller must never kill a timer
            logger.warning("qos controller tick failed: %s", exc)
        if self._running:
            self._arm()

    # -------------------------------------------------------------- admission
    def storm_active(self) -> bool:
        """True when a compile storm fired since the last control tick —
        a bad moment to admit new compile load."""
        from ..observability import devwatch

        try:
            now_storms = devwatch.registry().totals()["storms"]
        except Exception:
            return False
        with self._lock:
            return self._storm_active or now_storms > self._prev_storms

    def breaching_count(self) -> int:
        verdicts = self._verdicts()
        return sum(1 for v in verdicts.values()
                   if v.get("state") == "breaching")

    def committed_us_per_s(self) -> float:
        with self._lock:
            return sum(self._committed.values())

    def note_admission(self, decision: str) -> None:
        with self._lock:
            self._adm_counts[decision] = \
                self._adm_counts.get(decision, 0) + 1

    def commit(self, rule_id: str, fold_us_per_s: float,
               placement: Optional[Dict[str, Any]] = None) -> None:
        with self._lock:
            self._committed[rule_id] = float(fold_us_per_s)
            if placement:
                self._placements[rule_id] = dict(placement)

    def release(self, rule_id: str) -> None:
        """Rule deleted: drop its admission ledger entry + queue slot +
        placement billing + controller track (shed TOTALS survive —
        monotonic counters)."""
        with self._lock:
            self._committed.pop(rule_id, None)
            self._placements.pop(rule_id, None)
            self._aqueue.pop(rule_id, None)
            self._tracks.pop(rule_id, None)

    def shard_loads(self, n_shards: Optional[int] = None,
                    exclude: Optional[str] = None) -> List[float]:
        """Committed HBM bytes per chip off the placement ledger — the
        per-chip half of the admission gate and the kuiper_shard_hbm_*
        families. Sized to max(n_shards, highest billed chip + 1).
        `exclude` drops one rule's own billing (an UPDATE replaces its
        claim — gating it against itself would double-bill the HBM
        axis, the same contract the fold-budget gate keeps)."""
        K = n_shards if n_shards is not None else placement_shards()
        with self._lock:
            placements = [p for rid, p in self._placements.items()
                          if rid != exclude]
        for p in placements:
            for c in p.get("shards", ()):
                K = max(K, int(c) + 1)
        loads = [0.0] * max(K, 1)
        for p in placements:
            share = float(p.get("bytes_per_shard", 0))
            for c in p.get("shards", ()):
                loads[int(c)] += share
        return loads

    def placement_state(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {rid: dict(p) for rid, p in self._placements.items()}

    def enqueue(self, rule_id: str, decision: Dict[str, Any]) -> bool:
        """Park a queue-decided rule for retry at control ticks. False
        when the queue is full (the caller downgrades to reject). The
        `queue` counter + flight event are recorded HERE, on success —
        counting at decision time would misreport a full-queue
        downgrade as both queued and rejected."""
        now = timex.now_ms()
        with self._lock:
            if len(self._aqueue) >= ADMISSION_QUEUE_CAP \
                    and rule_id not in self._aqueue:
                return False
            self._aqueue[rule_id] = {
                "rule": rule_id,
                "reason": decision.get("reason", ""),
                "price": decision.get("price", {}),
                "enqueued_ms": now,
                "attempts": 0,
            }
        self.note_admission("queue")
        from .events import recorder

        recorder().record(
            "admission", rule=rule_id, severity="info", ts_ms=now,
            decision="queue", reason=decision.get("reason", ""))
        return True

    def queued(self, rule_id: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            entry = self._aqueue.get(rule_id)
            return dict(entry) if entry is not None else None

    def claim(self, rule_id: str) -> Optional[Dict[str, Any]]:
        """Atomically pop a queued rule and commit its price — the ONE
        place the dequeue+commit invariant lives (the controller's own
        drain and the registry's operator-start override both use it).
        Returns the entry, or None when the rule wasn't queued."""
        with self._lock:
            entry = self._aqueue.pop(rule_id, None)
            if entry is None:
                return None
            price = entry.get("price") or {}
            self._committed[rule_id] = float(
                price.get("fold_us_per_s", 0.0))
            if price.get("placement"):
                self._placements[rule_id] = dict(price["placement"])
            return entry

    def _drain_admission_queue(self, now: int) -> None:
        """Retry queued rules; start the ones whose pressure cleared.
        Starts run OUTSIDE the controller lock — start_fn reaches the
        rule registry, whose locks must never nest under ours."""
        with self._lock:
            pending = list(self._aqueue.items())
        if not pending:
            return
        defer, reason = self._pressure()
        if defer:
            with self._lock:
                for _rid, entry in pending:
                    entry["attempts"] += 1
            return
        from .events import recorder

        for rid, entry in pending:
            # the budget gates re-run at dequeue time: N rules queued
            # during one storm each passed the gates against a ledger
            # that excluded the others — starting them all unchecked
            # could jointly blow the very budgets the gates enforce
            with self._lock:
                pending_entry = self._aqueue.get(rid)
                committed = sum(v for r, v in self._committed.items()
                                if r != rid)
            if pending_entry is None:
                continue
            price = dict(pending_entry.get("price") or {})
            price.setdefault("fold_us_per_s", 0.0)
            price.setdefault("hbm_projected_bytes", 0)
            # the HBM side must re-gate against LIVE telemetry — the
            # enqueue-time snapshot is exactly what the queue period
            # may have invalidated
            try:
                from ..observability import memwatch

                price["hbm_current_bytes"] = \
                    memwatch.registry().total_bytes()
            except Exception:
                price.setdefault("hbm_current_bytes", 0)
            rej = _static_gates(price, committed, ctl=self, rule_id=rid)
            if rej is not None:
                with self._lock:
                    self._aqueue.pop(rid, None)
                self.note_admission("reject")
                recorder().record(
                    "admission", rule=rid, severity="warn", ts_ms=now,
                    decision="reject", dequeued=True,
                    reason=rej["reason"])
                logger.warning("queued rule %s rejected at dequeue: %s",
                               rid, rej["reason"])
                if self._unqueue_fn is not None:
                    try:
                        self._unqueue_fn(rid)
                    except Exception:
                        pass
                continue
            with self._lock:
                # the gate re-run may have picked a placement against
                # the LIVE ledger — claim() must commit that, not the
                # enqueue-time snapshot
                if rid in self._aqueue:
                    self._aqueue[rid]["price"] = price
            entry = self.claim(rid)
            if entry is None:
                continue
            self.note_admission("accept")
            recorder().record(
                "admission", rule=rid, severity="info", ts_ms=now,
                decision="accept", dequeued=True,
                queued_ms=max(now - entry.get("enqueued_ms", now), 0),
                reason="admission pressure cleared")
            if self._start_fn is not None:
                try:
                    self._start_fn(rid)
                except Exception as exc:
                    logger.warning(
                        "queued rule %s failed to start: %s", rid, exc)

    def _pressure(self, price: Optional[Dict[str, Any]] = None) -> tuple:
        """(defer?, reason) — the transient conditions that QUEUE a new
        rule instead of accepting or rejecting it outright. A candidate
        whose jitcert certificate prices ZERO new signatures (shared /
        host path) adds no compile load and is never storm-deferred;
        an UNKNOWN count (None — pricing failed) defers like compile
        load, never bypasses."""
        certified = (price or {}).get("certified_new_signatures")
        adds_compile_load = (price is None or certified is None
                             or int(certified) > 0)
        if os.environ.get("KUIPER_ADMISSION_DEFER_STORMS", "1") != "0" \
                and adds_compile_load and self.storm_active():
            return True, ("an XLA compile storm is active; new compile "
                          "load is deferred until it clears")
        breach_gate = int(_env_float("KUIPER_ADMISSION_DEFER_BREACHING"))
        if breach_gate > 0:
            n = self.breaching_count()
            if n >= breach_gate:
                return True, (f"{n} rule(s) are breaching their SLO; "
                              "admission deferred until the engine "
                              "recovers")
        return False, ""

    # ----------------------------------------------------------------- tick
    def _verdicts(self) -> Dict[str, Any]:
        if self._verdicts_fn is not None:
            try:
                return self._verdicts_fn() or {}
            except Exception:
                return {}
        from ..observability import health

        ev = health.evaluator()
        if ev is None:
            return {}
        try:
            return ev.verdicts()
        except Exception:
            return {}

    def tick(self) -> Dict[str, Any]:
        """One control pass: update the storm edge, walk every rule's
        verdict through the shed ladder + autosizer, then retry the
        admission queue. Returns a {rule: action} summary (tests)."""
        # clock BEFORE the lock: mock-clock advances fire _fire -> tick
        # while holding the clock lock (same ABBA class health.tick
        # documents; clock orders first)
        now = timex.now_ms()
        verdicts = self._verdicts()
        from ..observability import devwatch

        actions: Dict[str, Any] = {}
        with self._lock:
            try:
                storms = devwatch.registry().totals()["storms"]
                self._storm_active = storms > self._prev_storms
                self._prev_storms = storms
            except Exception:
                self._storm_active = False
            try:
                rules = list(self._rules_fn() or [])
            except Exception as exc:
                logger.warning("qos controller rules_fn failed: %s", exc)
                rules = []
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
                    act = self._control_rule(rid, topo, options or {},
                                             verdicts.get(rid), now)
                    if act:
                        actions[rid] = act
                except Exception as exc:
                    logger.warning("qos control of rule %s failed: %s",
                                   rid, exc)
            # tracks are swept with a GRACE period, not on first miss: a
            # rule mid-restart (kill/restore, update) briefly has no live
            # topo, and dropping its track then would reset the shed
            # ladder + re-baseline its shed accounting mid-storm
            for rid in [r for r in self._tracks if r not in seen]:
                tr = self._tracks[rid]
                tr.missing_runs += 1
                if tr.missing_runs > 10:
                    del self._tracks[rid]
            for rid in seen:
                if rid in self._tracks:
                    self._tracks[rid].missing_runs = 0
            self.ticks += 1
        self._drain_admission_queue(now)
        return actions

    # ------------------------------------------------------------- per rule
    def _control_rule(self, rid: str, topo: Any, options: Dict[str, Any],
                      verdict: Optional[Dict[str, Any]],
                      now: int) -> Dict[str, Any]:
        tr = self._tracks.get(rid)
        if tr is None:
            tr = self._tracks[rid] = _RuleCtl()
        tr.qos_class = parse_qos_class(options)
        ladder = SHED_LADDERS[tr.qos_class]
        # a rule UPDATE can change the class under a live shed level —
        # clamp to the new ladder (critical's empty ladder clamps to 0,
        # i.e. the re-assert below clears the gate) or the indexing
        # throws and this rule drops out of control forever
        if tr.shed_level > len(ladder):
            tr.shed_level = len(ladder)
        state = (verdict or {}).get("state", "healthy")
        act: Dict[str, Any] = {}

        # ---- shed accounting: fold the entry nodes' shed_qos counters
        # into the monotonic per-(rule, qos) totals. A restarted topo
        # resets its node counters — cur < seen re-baselines, no
        # negative deltas, no double counting.
        try:
            cur_rows = topo.shed_rows()
        except Exception:
            cur_rows = tr.shed_rows_seen
        delta = cur_rows - tr.shed_rows_seen
        if delta < 0:
            delta = cur_rows
        if delta > 0:
            key = (rid, tr.qos_class)
            self._shed_totals[key] = self._shed_totals.get(key, 0) + delta
        tr.shed_rows_seen = cur_rows

        # ---- re-assert the gate after a topo restart: the shed LEVEL
        # lives here, the fraction lives on the (rebuildable) entry
        # nodes — a restarted rule must not silently resume unshed while
        # the controller believes it is relieved
        expected = ladder[tr.shed_level - 1] if tr.shed_level > 0 else 0.0
        try:
            if abs(topo.shed_fraction() - expected) > 1e-9:
                topo.set_shed(expected)
        except Exception:
            pass

        # ---- shed ladder with health-FSM-mirrored hysteresis
        if state == "breaching":
            tr.breach_run += 1
            tr.clear_run = 0
        elif state == "healthy":
            tr.clear_run += 1
            tr.breach_run = 0
        else:  # degraded holds the current level
            tr.breach_run = 0
            tr.clear_run = 0
        target = tr.shed_level
        if ladder and tr.breach_run >= self.up_ticks \
                and tr.shed_level < len(ladder):
            target = tr.shed_level + 1
            tr.breach_run = 0
        elif tr.clear_run >= self.down_ticks and tr.shed_level > 0:
            target = tr.shed_level - 1
            tr.clear_run = 0
        if target != tr.shed_level:
            prev_level = tr.shed_level
            tr.shed_level = target
            frac = ladder[target - 1] if target > 0 else 0.0
            topo.set_shed(frac)
            from .events import recorder

            severity = "warn" if target > prev_level else "info"
            recorder().record(
                "shed", rule=rid, severity=severity, ts_ms=now,
                level=target, previous=prev_level,
                fraction=frac, qos=tr.qos_class,
                state=state)
            logger.log(
                30 if target > prev_level else 20,
                "rule %s: shed level %d -> %d (%.0f%% of input, qos "
                "class %s, health %s)", rid, prev_level, target,
                frac * 100, tr.qos_class, state)
            act["shed"] = {"level": target, "fraction": frac}
        if state == "breaching" and not ladder and verdict is not None:
            act.setdefault("shed", {"level": 0, "fraction": 0.0,
                                    "exempt": "critical"})

        # ---- autosize off the attributed bottleneck
        if tr.autosize_cool > 0:
            tr.autosize_cool -= 1
        else:
            auto = self._autosize_rule(rid, topo, tr, verdict, state, now)
            if auto:
                tr.autosize_cool = 3  # cooldown: one action per ~3 ticks
                act["autosize"] = auto

        # ---- mesh skew -> rebalance_hint (signal only: actually moving
        # key ranges is ROADMAP item 2's rebalancer; this gives it — and
        # the operator — the structured trigger). Shed-ladder-style
        # hysteresis: a hint opens after up_ticks consecutive skewed
        # observations, closes once the run drains back to zero (one
        # step per clear tick), and never re-fires while open.
        mesh = ((verdict or {}).get("bottleneck") or {}).get("mesh")
        if mesh is not None and mesh.get("skewed"):
            tr.skew_run += 1
            if tr.skew_run >= self.up_ticks and not tr.hint_active:
                tr.hint_active = True
                self._rebalance_hints += 1
                from .events import recorder

                recorder().record(
                    "rebalance_hint", rule=rid, severity="warn", ts_ms=now,
                    skew_ratio=mesh.get("skew_ratio"),
                    hot_shard=mesh.get("hot_shard"),
                    mesh=mesh.get("mesh"),
                    shard_loads=self.shard_loads())
                logger.warning(
                    "rule %s: mesh skew %.2fx on shard %s (mesh %s) — "
                    "rebalance hint raised", rid,
                    mesh.get("skew_ratio") or 0.0,
                    mesh.get("hot_shard"), mesh.get("mesh"))
                act["rebalance_hint"] = {
                    "skew_ratio": mesh.get("skew_ratio"),
                    "hot_shard": mesh.get("hot_shard"),
                }
        else:
            if tr.skew_run >= 1:
                tr.skew_run -= 1
            if tr.skew_run == 0 and tr.hint_active:
                tr.hint_active = False
                from .events import recorder

                recorder().record(
                    "rebalance_hint", rule=rid, severity="info", ts_ms=now,
                    cleared=True)
        return act

    def _autosize_rule(self, rid: str, topo: Any, tr: _RuleCtl,
                       verdict: Optional[Dict[str, Any]], state: str,
                       now: int) -> Optional[Dict[str, Any]]:
        max_pool = int(_env_float("KUIPER_AUTOSIZE_MAX_POOL", 6))
        max_ring = int(_env_float("KUIPER_AUTOSIZE_MAX_RING", 6))
        srcs = [n for n in list(getattr(topo, "sources", []))
                + [n for st, _ in getattr(topo, "live_shared",
                                          lambda: [])()
                   for n in getattr(st, "nodes", [])]
                if hasattr(n, "resize_ingest")
                and getattr(n, "decode_pool_size", 0) > 0]
        if not srcs:
            return None
        bn = (verdict or {}).get("bottleneck") or {}
        stage = bn.get("stage")
        src = srcs[0]
        orig = tr.orig_sizes.setdefault(src.name, {
            "pool_size": src.decode_pool_size,
            "ring_depth": src.ring_depth,
        })
        action = None
        if state != "healthy" and stage == "decode" \
                and src.decode_pool_size < max_pool:
            applied = src.resize_ingest(
                pool_size=src.decode_pool_size + 1)
            action = {"node": src.name, "action": "grow_pool",
                      "stage": stage, "applied": applied}
        elif state != "healthy" and stage == "upload" \
                and src.ring_depth < max_ring:
            applied = src.resize_ingest(ring_depth=src.ring_depth + 1)
            action = {"node": src.name, "action": "grow_ring",
                      "stage": stage, "applied": applied}
        elif state == "healthy" and tr.clear_run >= 2 * self.down_ticks:
            # sustained health: step back toward the configured sizes
            if src.decode_pool_size > orig["pool_size"]:
                applied = src.resize_ingest(
                    pool_size=src.decode_pool_size - 1)
                action = {"node": src.name, "action": "shrink_pool",
                          "stage": stage, "applied": applied}
            elif src.ring_depth > orig["ring_depth"]:
                applied = src.resize_ingest(
                    ring_depth=src.ring_depth - 1)
                action = {"node": src.name, "action": "shrink_ring",
                          "stage": stage, "applied": applied}
        if action is None:
            return None
        self.autosize_events += 1
        self._autosize_log.append({"ts_ms": now, "rule": rid, **action})
        from .events import recorder

        recorder().record(
            "autosize", rule=rid, severity="info", ts_ms=now, **{
                k: v for k, v in action.items() if k != "applied"},
            **(action.get("applied") or {}))
        logger.info("rule %s: autosize %s on %s (bottleneck %s) -> %s",
                    rid, action["action"], action["node"], stage,
                    action.get("applied"))
        return action

    # ---------------------------------------------------------------- queries
    def shed_state(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            out = {}
            for rid, tr in self._tracks.items():
                ladder = SHED_LADDERS[tr.qos_class]
                lvl = min(tr.shed_level, len(ladder))  # mid-update clamp
                out[rid] = {
                    "level": lvl,
                    "fraction": ladder[lvl - 1] if lvl > 0 else 0.0,
                    "qos": tr.qos_class,
                    "rows": tr.shed_rows_seen,
                }
            return out

    def shed_totals(self) -> Dict[tuple, int]:
        with self._lock:
            return dict(self._shed_totals)

    def admission_counts(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._adm_counts)

    def diagnostics(self) -> Dict[str, Any]:
        """The GET /diagnostics/control payload."""
        with self._lock:
            queued = [dict(e) for e in self._aqueue.values()]
            committed = sum(self._committed.values())
            autosize_recent = list(self._autosize_log)
        return {
            "controller": {
                "interval_ms": self.interval_ms,
                "ticks": self.ticks,
                "up_ticks": self.up_ticks,
                "down_ticks": self.down_ticks,
            },
            "admission": {
                "decisions": self.admission_counts(),
                "queued": queued,
                "committed_fold_us_per_s": round(committed, 1),
                "budgets": {
                    "hbm_budget_mb": _env_float("KUIPER_HBM_BUDGET_MB"),
                    "fold_budget_us_per_s": _env_float(
                        "KUIPER_ADMISSION_FOLD_BUDGET_US_PER_S"),
                    "defer_breaching": int(_env_float(
                        "KUIPER_ADMISSION_DEFER_BREACHING")),
                },
                "storm_active": self.storm_active(),
            },
            "placement": {
                "shards": placement_shards(),
                "committed_bytes_per_shard": [
                    int(v) for v in self.shard_loads()],
                "rules": self.placement_state(),
            },
            "shedding": self.shed_state(),
            "shed_totals": {
                f"{rid}|{qos}": n
                for (rid, qos), n in sorted(self.shed_totals().items())},
            "autosize": {
                "events": self.autosize_events,
                "recent": autosize_recent,
            },
            "mesh": self._mesh_diagnostics(),
        }

    def _mesh_diagnostics(self) -> Dict[str, Any]:
        """Controller-side mesh view: skew/hint hysteresis per rule plus
        the meshwatch skew report — the "mesh" section of
        /diagnostics/control and the explain "mesh" detail's hint state."""
        from ..observability import meshwatch

        with self._lock:
            rules = {
                rid: {"skew_run": tr.skew_run,
                      "hint_active": tr.hint_active}
                for rid, tr in self._tracks.items()
                if tr.skew_run or tr.hint_active
            }
            hints = self._rebalance_hints
        try:
            skew = meshwatch.skew_report()
        except Exception:
            skew = {}
        return {
            "rebalance_hints_total": hints,
            "rules": rules,
            "skew": skew,
            "threshold": meshwatch.skew_threshold(),
        }


# -------------------------------------------------------------- singleton
_controller: Optional[QoSController] = None
_install_lock = threading.Lock()


def install(rules_fn: Callable[[], List[tuple]],
            start_fn: Optional[Callable[[str], None]] = None,
            interval_ms: int = DEFAULT_INTERVAL_MS,
            start: bool = True, **kw) -> QoSController:
    """Install (replacing any prior) the engine-wide controller. The
    REST server installs one over its rule registry at boot."""
    global _controller
    with _install_lock:
        if _controller is not None:
            _controller.stop()
        _controller = QoSController(rules_fn, start_fn=start_fn,
                                    interval_ms=interval_ms, **kw)
        ctl = _controller
    if start:
        ctl.start()
    return ctl


def controller() -> Optional[QoSController]:
    return _controller


def reset() -> None:
    """Test hook: stop and drop the installed controller."""
    global _controller
    with _install_lock:
        if _controller is not None:
            _controller.stop()
        _controller = None


# ------------------------------------------------------- admission helpers
def admit_rule(rule, store, allow_queue: bool = True) -> Dict[str, Any]:
    """The admission decision for one candidate rule: {"decision":
    accept|reject|queue, "reason", "price"}. Pure read — callers act on
    it (RuleRegistry.create/update). Works without an installed
    controller (static budget gates only; pressure deferral and
    counters need the controller). `allow_queue=False` (updates — the
    old definition keeps running, there is nothing to defer) skips the
    pressure gate entirely so no phantom queue decision is counted or
    flight-recorded."""
    if os.environ.get("KUIPER_ADMISSION", "1") == "0":
        return {"decision": "accept", "reason": "admission disabled",
                "price": {}}
    ctl = _controller
    price = price_rule(rule, store)
    committed = ctl.committed_us_per_s() if ctl is not None else 0.0
    # a rule replacing itself (update) must not be double-billed
    if ctl is not None:
        with ctl._lock:
            committed -= ctl._committed.get(rule.id, 0.0)
    decision = _static_gates(price, max(committed, 0.0), ctl=ctl,
                             rule_id=rule.id)
    if decision is None and ctl is not None and allow_queue:
        defer, reason = ctl._pressure(price)
        if defer:
            decision = {"decision": "queue", "reason": reason,
                        "price": price}
    if decision is None:
        decision = {"decision": "accept", "reason": "within budgets",
                    "price": price}
    if ctl is not None:
        # queue decisions are counted/flight-recorded by enqueue() on
        # SUCCESS — counting here would misreport a full-queue
        # downgrade (429) as queued
        if decision["decision"] != "queue":
            ctl.note_admission(decision["decision"])
        if decision["decision"] == "reject":
            from .events import recorder

            recorder().record(
                "admission", rule=rule.id, severity="warn",
                decision="reject", reason=decision["reason"],
                fold_us_per_s=price.get("fold_us_per_s"),
                path=price.get("path"))
    return decision


# -------------------------------------------------------- Prometheus view
def render_prometheus(out: List[str], esc) -> None:
    """Append the control-plane families to a /metrics scrape."""
    ctl = _controller
    if ctl is None:
        return
    out.append("# TYPE kuiper_admission_total counter")
    out.append("# HELP kuiper_admission_total rule admission decisions "
               "by outcome (accept/reject/queue)")
    counts = ctl.admission_counts()
    for decision in ("accept", "reject", "queue"):
        out.append(
            f'kuiper_admission_total{{decision="{decision}"}} '
            f"{counts.get(decision, 0)}")
    out.append("# TYPE kuiper_shed_total counter")
    out.append("# HELP kuiper_shed_total rows shed per rule by the SLO "
               "control plane, labeled by qos class "
               "(reason=shed_qos in the drop taxonomy)")
    for (rid, qos), n in sorted(ctl.shed_totals().items()):
        out.append(
            f'kuiper_shed_total{{rule="{esc(rid)}",qos="{esc(qos)}"}} '
            f"{n}")
    out.append("# TYPE kuiper_autosize_events_total counter")
    out.append("# HELP kuiper_autosize_events_total decode pool / ingest "
               "ring autosize actions taken by the control plane")
    out.append(f"kuiper_autosize_events_total {ctl.autosize_events}")
    # placement-aware admission (multi-chip serving): the per-chip HBM
    # ledger the gate places rules against, plus rules placed per chip
    loads = ctl.shard_loads()
    placements = ctl.placement_state()
    rules_per = [0] * len(loads)
    for p in placements.values():
        for c in p.get("shards", ()):
            if 0 <= int(c) < len(rules_per):
                rules_per[int(c)] += 1
    out.append("# TYPE kuiper_shard_hbm_committed_bytes gauge")
    out.append("# HELP kuiper_shard_hbm_committed_bytes admission-"
               "committed HBM bytes per placement shard (per-chip "
               "ledger, runtime/control.py)")
    for i, v in enumerate(loads):
        out.append(
            f'kuiper_shard_hbm_committed_bytes{{shard="{i}"}} {int(v)}')
    out.append("# TYPE kuiper_shard_rules gauge")
    out.append("# HELP kuiper_shard_rules rules placed on each shard by "
               "placement-aware admission")
    for i, v in enumerate(rules_per):
        out.append(f'kuiper_shard_rules{{shard="{i}"}} {v}')
