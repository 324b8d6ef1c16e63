"""Cost-based cross-rule window-aggregate sharing — the planner pass that
rewrites correlated rules onto one shared pane fold.

A fleet of dashboards/alert rules over one stream typically watches the
SAME stream with the SAME GROUP BY and correlated windows (the ROADMAP's
"millions of users" shape); the engine already shares the source, decode,
key encode and device upload across them (runtime/subtopo.py +
runtime/ingest.py), but the expensive ops/groupby.py device fold still ran
once per rule. Following "Factor Windows" (arxiv 2008.12379), rules whose
windows are integer multiples of a common pane (the GCD of their
lengths/intervals) can share one pane-granular fold and reconstruct each
window as a pane merge — the constant-time merge structure the kernel
already uses for hopping windows (arxiv 2009.13768).

This module decides WHEN that rewrite pays off and wires it up:

- **Correlation test** — same stream config (subtopo key), same GROUP BY
  key set, unionable device aggregate specs, tumbling/hopping windows
  whose length/interval are multiples of the common pane. WHERE clauses
  need NOT match: each member's predicate lifts into per-spec device
  FILTER masks + a private activity spec over the pooled fold
  (ops/aggspec.py lift_predicate, per "On the Semantic Overlap of
  Operators in Stream Processing Engines") — identical-WHERE peers
  still dedup their specs outright, different-WHERE peers coexist as
  masked specs in ONE fold dispatch. Everything else keeps a private
  fold.
- **Cost model** — sharing saves one whole fold dispatch per batch per
  member rule, and costs a finer-grained pane merge at each member's
  window emit. The rewrite happens only when the estimated per-second
  fold savings exceed the emit-combine overhead; the decision (and both
  estimates) is visible in `GET /rules/{id}/explain` and
  `tools/probe_sharing.py`.
- **Declarations** — rules declare their windows at plan time, so a batch
  of correlated rules created together gets a store whose pane is the GCD
  across ALL of them (the store's pane is fixed once built; later rules
  join only if their windows are multiples of it — otherwise they get an
  explicit, logged private-fold fallback).

qos>0 rules always fall back to a private fold (rule-scoped checkpoint
barriers cannot flow through a shared pipeline) — explicitly logged, not
silent convention.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from ..ops.aggspec import KernelPlan
from ..sql import ast
from ..utils.infra import logger

#: hard cap on how many shared panes one window may span — past this the
#: per-emit pane merge and the (n_panes, capacity, k) state footprint stop
#: paying for the saved fold
MAX_SPAN_PANES = 64

# Cost-model coefficients (µs), calibrated against the bench's recorded
# per-stage timings: a fused fold dispatch costs a fixed kernel-launch +
# input-build overhead plus a per-spec increment; an emit-time pane merge
# costs per extra pane merged. Absolute values matter less than the ratio:
# folds happen per BATCH (tens-hundreds/s), emit combines per WINDOW
# (typically < 1/s), which is why sharing nearly always wins except for
# very short windows or very wide pane spans.
FOLD_DISPATCH_US = 150.0
FOLD_SPEC_US = 12.0
COMBINE_PANE_US = 4.0

_decl_lock = threading.Lock()
#: store_key -> rule_id -> {"length_ms", "interval_ms", "plan"}
_declared: Dict[str, Dict[str, Dict[str, Any]]] = {}


def reset() -> None:
    """Test hook: forget every plan-time declaration."""
    with _decl_lock:
        _declared.clear()


def declare(store_key: str, rule_id: str, length_ms: int, interval_ms: int,
            plan: KernelPlan) -> None:
    with _decl_lock:
        _declared.setdefault(store_key, {})[rule_id] = {
            "length_ms": int(length_ms),
            "interval_ms": int(interval_ms),
            "plan": plan,
        }


def declarations(store_key: str) -> List[Dict[str, Any]]:
    with _decl_lock:
        return list(_declared.get(store_key, {}).values())


@contextmanager
def probe_declarations(rule_id: str):
    """Scope a planning PROBE (rule validation): any declaration the probe
    makes or overwrites for `rule_id` is rolled back on exit, while
    concurrent declare/undeclare for OTHER rules (REST handlers are
    threaded) pass through untouched — a wholesale snapshot/restore would
    resurrect concurrently-deleted rules' declarations."""
    with _decl_lock:
        before = {k: dict(v[rule_id]) for k, v in _declared.items()
                  if rule_id in v}
    try:
        yield
    finally:
        with _decl_lock:
            for k in list(_declared):
                if rule_id in _declared[k] and k not in before:
                    del _declared[k][rule_id]
                    if not _declared[k]:
                        del _declared[k]
            for k, old in before.items():
                _declared.setdefault(k, {})[rule_id] = old


def snapshot_declarations() -> Dict[str, Dict[str, Dict[str, Any]]]:
    """Deep-enough copy of the declaration registry — probe paths
    (rule validation) plan without leaving OR OVERWRITING candidacy."""
    with _decl_lock:
        return {k: dict(v) for k, v in _declared.items()}


def restore_declarations(snap) -> None:
    with _decl_lock:
        _declared.clear()
        _declared.update(snap)


def undeclare(rule_id: str) -> None:
    """Forget a rule's sharing candidacy (rule delete/update): ghost
    declarations would otherwise skew the peer count — a later lone rule
    would 'share' with deleted peers forever — and permanently constrain
    the pane GCD of future stores."""
    with _decl_lock:
        for key in list(_declared):
            _declared[key].pop(rule_id, None)
            if not _declared[key]:
                del _declared[key]


def _peer_decls(store_key: str, rule_id: str) -> List[Dict[str, Any]]:
    with _decl_lock:
        return [d for rid, d in _declared.get(store_key, {}).items()
                if rid != rule_id]


@dataclass
class Decision:
    share: bool
    reason: str
    store_key: str = ""
    estimates: Dict[str, Any] = field(default_factory=dict)
    #: structurally shareable (declared as a candidate even when share is
    #: False — e.g. no peers yet): a later correlated rule then sees this
    #: one as a peer, and a replan of this rule joins the fleet
    eligible: bool = False


def _window_ms(w: ast.Window) -> tuple:
    length = w.length_ms()
    if w.window_type == ast.WindowType.HOPPING_WINDOW:
        interval = w.interval_ms() or length
    else:
        interval = length
    return length, interval


def store_key(subtopo_key: str, stmt: ast.SelectStatement, opts) -> str:
    """Identity of a shareable pane store: the stream pipeline plus every
    plan facet that must match bit-for-bit across members — the GROUP BY
    key set and the time domain. The WHERE clause is deliberately NOT a
    facet any more: predicate lifting (ops/aggspec.py lift_predicate)
    turns each member's WHERE into per-spec device FILTER masks over one
    pooled fold, so rules that differ only in predicate share a store
    (PAPERS.md "On the Semantic Overlap of Operators in Stream
    Processing Engines")."""
    dims = ",".join(d.expr.name for d in stmt.dimensions
                    if isinstance(d.expr, ast.FieldRef))
    return (f"{subtopo_key}|fold|dims={dims}"
            f"|evt={int(opts.is_event_time)}:{opts.late_tolerance_ms}"
            f"{_mesh_facet(opts)}")


def _mesh_facet(opts) -> str:
    """Mesh identity facet of the store key: rules whose sharding
    decision differs must never pool one pane store (a replicated and a
    key-range-sharded ring have different placement). Pure option/env
    parse — the unresolved form ("auto") is the facet, so the key stays
    stable between plan and store build."""
    from .planner import mesh_request

    req = mesh_request(opts)
    if req["mode"] != "sharded":
        return ""
    cfg = req["cfg"] or {}
    if cfg.get("auto"):
        return "|mesh=auto"
    return f"|mesh={cfg.get('rows', 1)}x{cfg.get('keys', 1)}"


def decide(stmt: ast.SelectStatement, opts, plan: KernelPlan,
           subtopo_key: str, rule_id: str,
           has_direct_emit: bool = True,
           lifted: Optional[KernelPlan] = None) -> Decision:
    """The sharing decision for one rule. Pure: consults live stores and
    plan-time declarations but mutates neither (explain/probe call this
    repeatedly). `plan` is the rule's private plan; `lifted` its
    predicate-lifted form (computed here when absent) — declarations,
    union coverage, and the cost model all run on the LIFTED plan, the
    shape that would actually join the pooled fold."""
    from ..ops.aggspec import lift_predicate

    if lifted is None:
        lifted = lift_predicate(plan, stmt.condition)
    key = store_key(subtopo_key, stmt, opts)
    if lifted is None:
        # the WHERE∧FILTER conjunction does not device-compile (pieces
        # conflicted when conjoined): an unlifted filtered plan must
        # never enter a pooled union — stay private, don't declare
        return Decision(
            False, "predicate lift not compilable (WHERE/FILTER "
            "conjunction has no device form) — private fold", key)
    plan = lifted

    def no(reason: str, est: Optional[dict] = None) -> Decision:
        return Decision(False, reason, key, est or {})

    w = stmt.window
    if not getattr(opts, "shared_fold", True):
        return no("sharedFold option off")
    if opts.qos > 0:
        return no(f"qos={opts.qos} requires rule-scoped checkpoint "
                  "barriers; shared folds serve qos=0 only")
    if not opts.share_source:
        return no("share_source off (no shared subtopo to ride)")
    if w is None or w.window_type not in (
            ast.WindowType.TUMBLING_WINDOW, ast.WindowType.HOPPING_WINDOW):
        wt = w.window_type.value if w is not None else "none"
        return no(f"window type {wt} is not pane-decomposable across rules")
    # mesh-sharded rules POOL like any others — the store key's mesh
    # facet groups same-mesh peers onto one key-range-sharded pane
    # store (ops/panestore.py mesh=). Only the placement differs.
    if any(s.kind == "heavy_hitters" for s in plan.specs):
        return no("heavy_hitters state is node-local (value dictionary)")
    if getattr(plan, "has_exact", False):
        return no("exact BIGINT partials (int64 limbs) are rule-local")
    if getattr(plan, "has_orderstat", False):
        from .planner import ORDERSTAT_DECLINES

        return no(ORDERSTAT_DECLINES["shared_fold"])
    if not has_direct_emit:
        return no("post-agg tail is not vectorizable (no direct emit)")
    length, interval = _window_ms(w)

    from ..ops.panestore import pane_gcd, spec_map_into, union_plan
    from ..runtime import nodes_sharedfold

    peers = _peer_decls(key, rule_id)
    live = nodes_sharedfold.get_store(key)
    if live is not None:
        pane = live.pane_ms
        if length % pane or interval % pane:
            return no(f"live store pane {pane}ms does not divide this "
                      f"window ({length}/{interval}ms)")
        if length // pane > live.n_panes - 1:
            return no(f"window spans {length // pane} panes; live store "
                      f"holds {live.n_panes}")
        try:
            spec_map_into(live.plan, plan)
        except KeyError:
            return no("live store's union plan does not cover this "
                      "rule's aggregates")
        n_new = 0  # covered by the live union
    else:
        vals = [length, interval]
        for d in peers:
            vals += [d["length_ms"], d["interval_ms"]]
        pane = pane_gcd(vals)
        if peers:
            union, _ = union_plan([d["plan"] for d in peers] + [plan])
            n_new = len(union.specs) - len(
                union_plan([d["plan"] for d in peers])[0].specs)
        else:
            n_new = 0
    span = length // pane
    if span > MAX_SPAN_PANES:
        return no(f"window spans {span} panes at the {pane}ms shared pane "
                  f"(cap {MAX_SPAN_PANES})")
    if live is None and not peers:
        # a lone rule gains nothing from a shared fold and would give up
        # the private node's latency-hiding emit pipeline — stay private,
        # but the caller DECLARES this rule (eligible=True) so the next
        # correlated rule shares, and a replan of this one joins the fleet
        return Decision(
            False, "no correlated peer rules declared yet — a lone rule "
            "keeps the private fused node (latency-hiding emit); declared "
            "as a sharing candidate for future peers",
            key, {"pane_ms": pane, "span_panes": span, "peers": 0},
            eligible=True)

    # ---- cost model: saved fold/s vs added emit-combine/s ----
    batches_per_s = 1000.0 / max(opts.micro_batch_linger_ms, 1)
    windows_per_s = 1000.0 / max(interval, 1)
    own_panes = (1 if w.window_type == ast.WindowType.TUMBLING_WINDOW
                 else max(length // max(interval, 1), 1))
    # once one peer rides the store, this rule's whole private fold
    # disappears; the union fold only grows by this rule's NEW specs
    saved_us_per_s = (FOLD_DISPATCH_US
                      + FOLD_SPEC_US * (len(plan.specs) - n_new)) \
        * batches_per_s
    overhead_us_per_s = COMBINE_PANE_US * max(span - own_panes, 0) \
        * windows_per_s
    est = {
        "pane_ms": pane,
        "span_panes": span,
        "peers": len(peers),
        "saved_fold_us_per_s": round(saved_us_per_s, 1),
        "emit_overhead_us_per_s": round(overhead_us_per_s, 1),
        "assumed_batches_per_s": round(batches_per_s, 1),
    }
    if saved_us_per_s <= overhead_us_per_s:
        return Decision(
            False,
            f"estimated fold savings ({saved_us_per_s:.0f}us/s) do not "
            f"cover the emit-combine overhead ({overhead_us_per_s:.0f}us/s)",
            key, est, eligible=True)
    return Decision(
        True,
        f"correlated with {len(peers)} declared peer rule(s); saves "
        f"~{saved_us_per_s:.0f}us/s of fold for "
        f"~{overhead_us_per_s:.0f}us/s of emit combine",
        key, est, eligible=True)


def _store_builder(store_key_: str, subtopo_key: str, build_nodes,
                   display: str, opts, is_event_time: bool,
                   late_tolerance_ms: int, fallback_decl: Dict[str, Any]):
    """Builder the pool calls when the first member resolves: the pane is
    the GCD across every window DECLARED for this key by then, so a batch
    of correlated rules created together gets one store serving all of
    them. `fallback_decl` is the resolving rule's own declaration — a
    concurrent delete/update can empty the key's declarations between
    plan and open, and the store must still serve at least its resolver."""

    def build():
        from ..ops.panestore import pane_gcd, union_plan
        from ..runtime import nodes_sharedfold as sf
        from ..runtime.subtopo import SubTopoRef

        # a declaration made AFTER some member's decide() can shrink the
        # GCD enough to blow that member's span past the cap (decide-time
        # vs build-time race): drop the finest-grained declarations from
        # the pane computation until every surviving span fits — the
        # dropped rules fail their attach, and their restart replans
        # against the live store's pane (private-fold fallback)
        decls = sorted(declarations(store_key_) or [fallback_decl],
                       key=lambda d: (d["interval_ms"], d["length_ms"]))
        while True:
            vals: List[int] = []
            for d in decls:
                vals += [d["length_ms"], d["interval_ms"]]
            pane = pane_gcd(vals)
            spans = [d["length_ms"] // pane for d in decls] or [1]
            if max(spans) <= MAX_SPAN_PANES or len(decls) <= 1:
                break
            decls = decls[1:]
        slack = (-(-max(late_tolerance_ms, 0) // pane)
                 if is_event_time else 0)
        n_panes = min(max(spans) + slack + 2, 255)
        union, _ = union_plan([d["plan"] for d in decls])
        # same-mesh members (the store key's mesh facet) get a key-range-
        # sharded pane ring: resolve the rule options' mesh request here
        # at build time (device backends are up by now)
        from .planner import mesh_request

        req = mesh_request(opts)
        mesh_cfg = req["cfg"] if req["mode"] == "sharded" else None
        return sf.SharedFoldNode(
            store_key_, display, union, pane, n_panes,
            subtopo_ref=SubTopoRef(subtopo_key, build_nodes),
            capacity=opts.key_slots, micro_batch=opts.micro_batch_rows,
            is_event_time=is_event_time,
            late_tolerance_ms=late_tolerance_ms,
            buffer_length=opts.buffer_length,
            mesh_cfg=mesh_cfg)

    return build


def try_plan_shared(topo, stmt: ast.SelectStatement, kernel_plan: KernelPlan,
                    opts, rule, store):
    """Attempt the shared-fold rewrite for one rule. Returns the rule's
    emit-hop node (the chain tail the sinks connect to) when the rewrite
    applies, else None (the caller builds the private device chain).
    Fallbacks are logged — loudly when the rule explicitly asked for
    sharing (the qos>0 case of ISSUE satellite #2)."""
    from ..ops.emit import build_direct_emit
    from ..runtime import nodes_sharedfold as sf
    from .planner import _subtopo_spec

    ropts = rule.options or {}
    # both spellings reach merged_options (alias table), so both count as
    # an explicit request for the loud-fallback logging contract
    explicit = bool(ropts.get("sharedFold", ropts.get("shared_fold")))
    tbl = stmt.sources[0]
    try:
        subkey, build_nodes, stream = _subtopo_spec(
            tbl.name, tbl.name, opts, store)
    except Exception as exc:
        logger.debug("rule %s: no shareable source pipeline (%s)",
                     rule.id, exc)
        return None
    dims = [d.expr.name for d in stmt.dimensions]
    direct = build_direct_emit(stmt, kernel_plan, dims)
    # predicate lifting: the member's WHERE becomes per-spec device
    # FILTER masks + a private activity spec over the pooled fold
    # (ops/aggspec.py lift_predicate) — this LIFTED plan is what the
    # rule declares, joins, and emits from
    from ..ops.aggspec import lift_predicate

    lifted = lift_predicate(kernel_plan, stmt.condition)
    decision = decide(stmt, opts, kernel_plan, subkey, rule.id,
                      has_direct_emit=direct is not None, lifted=lifted)
    length, interval = _window_ms(stmt.window)
    if decision.eligible:
        # candidacy is declared even when this rule stays private (no
        # peers yet / cost) so later correlated rules see it as a peer
        # and the store's pane GCD covers its windows
        declare(decision.store_key, rule.id, length, interval, lifted)
    if not decision.share:
        loud = explicit or opts.qos > 0
        log = logger.warning if loud else logger.debug
        log("rule %s: shared-fold rewrite declined — %s; planning a "
            "private fold", rule.id, decision.reason)
        if loud:
            # the operator asked for sharing (or qos forces privacy):
            # leave a flight-recorder breadcrumb, not just a log line
            from ..runtime.events import recorder

            recorder().record(
                "qos_private_fallback", rule=rule.id,
                reason=decision.reason, qos=opts.qos, explicit=explicit)
        return None
    # display name must be UNIQUE per store: two stores on the same
    # stream+dims (different WHERE / time-domain facets) with one name
    # would emit duplicate Prometheus series and invalidate the scrape
    import zlib

    tag = zlib.crc32(decision.store_key.encode()) & 0xFFFF
    display = f"shared_fold[{tbl.name}:{'+'.join(dims) or '*'}#{tag:04x}]"
    builder = _store_builder(
        decision.store_key, subkey, build_nodes, display, opts,
        opts.is_event_time, opts.late_tolerance_ms,
        fallback_decl={"length_ms": length, "interval_ms": interval,
                       "plan": lifted})
    spec = sf.MemberSpec(
        rule_id=rule.id, length_ms=length, interval_ms=interval,
        plan=lifted, direct_emit=direct, dims=dims,
        emit_columnar=opts.emit_columnar, act_idx=lifted.act_idx)
    entry = sf.SharedEmitNode(f"{rule.id}_shared_emit",
                              buffer_length=opts.buffer_length)
    topo.add_op(entry)
    topo.add_shared_source(
        sf.SharedFoldRef(decision.store_key, spec, builder), entry)
    logger.info("rule %s: window aggregates ride %s — %s",
                rule.id, display, decision.reason)
    return entry


def explain_decision(rule, stmt: ast.SelectStatement, opts,
                     kernel_plan: KernelPlan, store) -> Dict[str, Any]:
    """The sharing section of GET /rules/{id}/explain: decision, reason,
    cost estimates, and the live store (if one exists) this rule would
    join. Read-only — never declares or builds."""
    from ..ops.emit import build_direct_emit
    from ..runtime import nodes_sharedfold as sf
    from .planner import _subtopo_spec

    tbl = stmt.sources[0]
    try:
        subkey, _, _ = _subtopo_spec(tbl.name, tbl.name, opts, store)
    except Exception as exc:
        return {"decision": "private", "reason": f"no source pipeline: {exc}"}
    dims = [d.expr.name for d in stmt.dimensions
            if isinstance(d.expr, ast.FieldRef)]
    direct = build_direct_emit(stmt, kernel_plan, dims)
    d = decide(stmt, opts, kernel_plan, subkey, rule.id,
               has_direct_emit=direct is not None)
    out: Dict[str, Any] = {
        "decision": "shared" if d.share else "private",
        "reason": d.reason,
        "estimates": d.estimates,
    }
    live = sf.get_store(d.store_key)
    if live is not None:
        out["live_store"] = {
            "name": live.name,
            "members": live.member_count(),
            "pane_ms": live.pane_ms,
            "n_panes": live.n_panes,
            "fold_dedup_ratio": round(live.fold_dedup_ratio(), 4),
        }
    return out
