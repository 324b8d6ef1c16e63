"""Rule planner — analogue of eKuiper's planner.Plan (internal/topo/planner/
planner.go:39): parse SQL, load stream definitions, build the logical chain
(DataSource → AnalyticFuncs? → Window? → Filter → Join? → Aggregate → Having →
WindowFuncs? → Order → ProjectSet? → Project → sinks), then choose the
physical form:

**Fused device path** (the incremental-agg rewrite taken to its conclusion,
reference planner.go:910-999): processing-time TUMBLING/HOPPING/COUNT window
whose aggregates, WHERE and dimensions all compile to the device kernel →
SourceNode → FusedWindowAggNode → [Having] → [Order] → Project → sinks.

**Host path**: everything else, with the full operator chain and vectorized
filtering where expressions allow.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set

from ..data.types import Field as SchemaField, Schema
from ..functions import registry
from ..io import registry as io_registry
from ..ops.aggspec import extract_kernel_plan
from ..runtime.nodes_fused import FusedWindowAggNode
from ..runtime.nodes_join import JoinNode
from ..runtime.nodes_ops import (
    AggregateNode, AnalyticNode, FilterNode, HavingNode, OrderNode,
    ProjectNode, ProjectSetNode, WindowFuncNode,
)
from ..runtime.nodes_sink import SinkNode
from ..runtime.nodes_source import SourceNode
from ..runtime.nodes_window import WatermarkNode, WindowNode
from ..runtime.topo import Topo
from ..sql import ast
from ..sql.parser import parse_select
from ..utils.config import RuleOptionConfig, get_config
from ..utils.cron import parse_duration_ms
from ..utils.infra import PlanError, logger


@dataclass
class RuleDef:
    """Rule definition JSON shape (reference: internal/pkg/def/rule.go)."""

    id: str
    sql: str
    actions: List[Dict[str, Dict[str, Any]]] = field(default_factory=list)
    options: Dict[str, Any] = field(default_factory=dict)
    graph: Optional[Dict[str, Any]] = None  # graph-API rule (PlanByGraph)
    tags: List[str] = field(default_factory=list)  # rule.go Tags

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "RuleDef":
        return RuleDef(
            id=d.get("id", ""),
            sql=d.get("sql", ""),
            actions=d.get("actions", []),
            options=d.get("options", {}),
            graph=d.get("graph"),
            tags=list(d.get("tags") or []),
        )

    def to_dict(self) -> Dict[str, Any]:
        out = {
            "id": self.id, "sql": self.sql,
            "actions": self.actions, "options": self.options,
        }
        if self.graph is not None:
            out["graph"] = self.graph
        if self.tags:
            out["tags"] = self.tags
        return out


def resolve_tier_budget_mb(opts: RuleOptionConfig) -> float:
    """The HBM budget (MB) driving a rule's tiered key-state placement
    (ops/tierstore.py): `tierHotMb` when set, else the engine-wide
    KUIPER_HBM_BUDGET_MB the QoS admission ledger already prices
    against; 0 disables. `tierStore="on"` without any budget is a plan
    error — a forced tier with no budget has no hot target."""
    mode = (opts.tier_store or "auto").lower()
    if mode == "off":
        return 0.0
    from ..ops.tierstore import env_hbm_budget_mb

    budget = float(opts.tier_hot_mb or 0)
    if budget <= 0:
        budget = env_hbm_budget_mb()
    if mode == "on" and budget <= 0:
        raise PlanError(
            "tierStore=on needs a budget: set tierHotMb or "
            "KUIPER_HBM_BUDGET_MB")
    return max(budget, 0.0)


def mesh_request(opts: RuleOptionConfig, plan=None) -> Dict[str, Any]:
    """The sharding decision for one rule, WITHOUT building a mesh (pure
    option/env parse — safe for explain, sharing store keys, and QoS
    pricing). Resolution order:

      1. `planOptimizeStrategy.mesh = {"rows": R, "keys": K}` — explicit
         geometry (the original opt-in; build failures are PlanErrors).
      2. `planOptimizeStrategy.shards = "auto" | K | "off"` — the serving
         mode: "auto" takes KUIPER_MESH when set, else every local device
         on the keys axis; an integer K puts K shards on the keys axis;
         "off"/0 pins the rule single-chip even under KUIPER_MESH.
      3. `KUIPER_MESH` env ("RxK", "K", or "auto") — the deployment-wide
         default for rules that say nothing.

    Returns {"mode": "sharded"|"single-chip", "cfg": dict|None,
    "source": str|None, "reason": str}. Auto/env selections degrade to
    single-chip (never PlanError) — the fallback reason lands in the
    explain "shards" section and the planner log."""
    from ..parallel.mesh import mesh_cfg_from_env

    strategy = getattr(opts, "plan_optimize_strategy", None) or {}
    explicit = strategy.get("mesh")
    if explicit:
        return {"mode": "sharded", "cfg": dict(explicit),
                "source": "planOptimizeStrategy.mesh",
                "reason": "explicit mesh geometry"}
    shards = strategy.get("shards")
    cfg, source = None, None
    if shards is not None:
        s = str(shards).strip().lower()
        if s in ("0", "off", "none", "false", "1"):
            return {"mode": "single-chip", "cfg": None,
                    "source": f"shards={shards}",
                    "reason": "sharding disabled by rule option"}
        if s == "auto":
            cfg = mesh_cfg_from_env() or {"auto": True}
            source = "shards=auto"
        else:
            try:
                k = int(s)
            except ValueError:
                raise PlanError(
                    f"invalid shards option {shards!r}: use 'auto', "
                    "'off', or a shard count")
            cfg = {"rows": 1, "keys": k}
            source = f"shards={k}"
    else:
        cfg = mesh_cfg_from_env()
        if cfg is not None:
            source = "KUIPER_MESH"
    if cfg is None:
        return {"mode": "single-chip", "cfg": None, "source": None,
                "reason": "no mesh requested"}
    if plan is not None and any(
            s.kind == "heavy_hitters" for s in plan.specs):
        return {"mode": "single-chip", "cfg": None, "source": source,
                "reason": "heavy_hitters state is node-local (value "
                          "dictionary) — single-chip kernel"}
    if plan is not None and getattr(plan, "has_exact", False):
        return {"mode": "single-chip", "cfg": None, "source": source,
                "reason": EXACT_INT_SINGLE_CHIP}
    if plan is not None and getattr(plan, "has_orderstat", False):
        return {"mode": "single-chip", "cfg": None, "source": source,
                "reason": ORDERSTAT_DECLINES["mesh"]}
    return {"mode": "sharded", "cfg": cfg, "source": source,
            "reason": "key-range-partitioned GROUP BY state across the "
                      "device mesh"}


#: the named reason a mesh declines an exact-integer plan (explain
#: "shards", the planner log): the sharded kernel keeps float32 partials
EXACT_INT_SINGLE_CHIP = ("exact BIGINT aggregates (int64 limb state) are "
                         "single-chip — the sharded kernel keeps float32 "
                         "partials")


#: the placements that cannot carry an order statistic's row buffers
#: (ops/orderstat.py), by name, and why: the explain "order_stats"
#: section lists them for a device plan; a rule whose own placement is one
#: of the first kind goes to the host operator (reason
#: `orderstat_placement`), the others only lose the feature
ORDERSTAT_DECLINES = {
    "mesh": "exact order statistics keep their row buffers on one chip — "
            "the sharded kernel merges per-key partials only",
    "shared_fold": "order-statistic row buffers are rule-local — the "
                   "shared pane store merges per-key partials only",
    "tier_store": "the cold tier packs per-key partials; a row buffer has "
                  "no per-key row to spill",
    "sliding_ring": "the sliding ring combines per-key partials per "
                    "trigger; a row buffer has none",
    "pre_issue": "the pre-issued finalize's host shadow mirrors per-key "
                 "partials, not the rows a selection needs",
    "qos_checkpoint": "a qos >= 1 checkpoint does not carry the row "
                      "buffers",
    "window": "row buffers recycle with the panes of a processing-time "
              "tumbling or hopping window",
    "heavy_hitters": "a heavy-hitters boundary assembles its own finalize",
    "exact_bigint": "exact BIGINT partials finish in their own int64 tail",
    "bigint": "the row buffers hold float32 values, which round a BIGINT "
              "past 2^24 — the host operator sorts the integers exactly",
}


def orderstat_host_reason(stmt: ast.SelectStatement, opts, plan,
                          int_columns: Optional[Set[str]] = None
                          ) -> Optional[str]:
    """The placement that keeps a plan with order statistics off the
    device path (an ORDERSTAT_DECLINES key), or None where it may take it:
    processing-time TUMBLING / HOPPING on one chip, qos 0, with no
    heavy_hitters or exact BIGINT spec beside it, over no column the
    stream declares BIGINT (`int_columns`)."""
    w = stmt.window
    if w.window_type == ast.WindowType.SLIDING_WINDOW:
        return "sliding_ring"
    if opts.is_event_time or w.window_type not in (
            ast.WindowType.TUMBLING_WINDOW, ast.WindowType.HOPPING_WINDOW):
        return "window"
    if (opts.plan_optimize_strategy or {}).get("mesh"):
        return "mesh"
    if opts.qos > 0:
        return "qos_checkpoint"
    if any(s.kind == "heavy_hitters" for s in plan.specs):
        return "heavy_hitters"
    if plan.has_exact:
        return "exact_bigint"
    if int_columns and any(
            isinstance(node, ast.FieldRef) and node.name in int_columns
            for s in plan.specs if s.os_buf >= 0
            for node in ast.walk(s.call.args[0])):
        return "bigint"
    return None


def merged_options(rule: RuleDef) -> RuleOptionConfig:
    base = get_config().rule
    opts = RuleOptionConfig(**{**base.__dict__})
    alias = {
        "isEventTime": "is_event_time",
        "lateTolerance": "late_tolerance_ms",
        "bufferLength": "buffer_length",
        "sendError": "send_error",
        "checkpointInterval": "checkpoint_interval_ms",
        "qos": "qos",
        "concurrency": "concurrency",
        "debug": "debug",
        "planOptimizeStrategy": "plan_optimize_strategy",
        "prefinalizeLeadMs": "prefinalize_lead_ms",
        "decodePoolSize": "decode_pool_size",
        "decodeShards": "decode_shards",
        "ingestRingDepth": "ingest_ring_depth",
        "ingestPrepUpload": "ingest_prep_upload",
        "slidingDevRingMb": "sliding_dev_ring_mb",
        "slidingImpl": "sliding_impl",
        "joinImpl": "join_impl",
        "analyticImpl": "analytic_impl",
        "sharedFold": "shared_fold",
        "tierStore": "tier_store",
        "tierHotMb": "tier_hot_mb",
        "tierScanMs": "tier_scan_ms",
    }
    for k, v in rule.options.items():
        key = alias.get(k, k)
        if not hasattr(opts, key):
            continue
        cur = getattr(opts, key)
        try:
            if key.endswith("_ms"):
                # int ms (reference form) or Go-style duration ('1s', '5m');
                # '' and bools would coerce to degenerate 0/1ms — reject
                if isinstance(v, bool) or (isinstance(v, str) and not v.strip()):
                    raise ValueError(f"not a duration: {v!r}")
                v = parse_duration_ms(v)
            elif isinstance(cur, bool):
                if isinstance(v, str):
                    low = v.strip().lower()
                    if low in ("true", "1"):
                        v = True
                    elif low in ("false", "0"):
                        v = False
                    else:
                        raise ValueError(f"not a boolean: {v!r}")
                else:
                    v = bool(v)
            elif isinstance(cur, int) and not isinstance(v, bool):
                v = int(v)
        except Exception as exc:
            raise PlanError(f"invalid rule option {k}={v!r}: {exc}") from exc
        setattr(opts, key, v)
    return opts


def load_stream_def(name: str, store) -> ast.StreamStmt:
    from ..sql.parser import parse

    table = store.kv("stream")
    raw, ok = table.get_ok(name)
    if not ok:
        table = store.kv("table")
        raw, ok = table.get_ok(name)
    if not ok:
        raise PlanError(f"stream {name} not found")
    stmt = parse(raw["sql"] if isinstance(raw, dict) else raw)
    if not isinstance(stmt, ast.StreamStmt):
        raise PlanError(f"definition of {name} is not a stream/table")
    return stmt


def declared_int_columns(stmt: ast.SelectStatement, store) -> Set[str]:
    """Columns the rule's one stream declares BIGINT: its sum / avg / min /
    max over them take exact int64 partials on the device plan. Empty for
    a schemaless stream, a join, or a stream the store lacks (the plan then
    fails where it always did)."""
    from ..data.types import DataType

    if store is None or len(stmt.sources) != 1 or stmt.joins:
        return set()
    try:
        stream = load_stream_def(stmt.sources[0].name, store)
    except PlanError:
        return set()
    return {f.name for f in stream.fields if f.type == DataType.BIGINT}


def schema_of(stream: ast.StreamStmt) -> Schema:
    return Schema(fields=[
        SchemaField(name=f.name, type=f.type, elem_type=f.elem_type)
        for f in stream.fields
    ])


# ---------------------------------------------------------------- analysis
def _analytic_calls(stmt: ast.SelectStatement) -> List[ast.Call]:
    out, seen = [], set()
    for root in stmt.expressions():
        for node in ast.walk(root):
            if isinstance(node, ast.Call) and registry.is_analytic(node.name):
                if node.func_id not in seen:
                    seen.add(node.func_id)
                    out.append(node)
    return out


def _window_func_calls(stmt: ast.SelectStatement) -> List[ast.Call]:
    out = []
    for root in stmt.expressions():
        for node in ast.walk(root):
            if isinstance(node, ast.Call):
                fd = registry.lookup(node.name)
                if fd is not None and fd.ftype == registry.WINDOW_FUNC:
                    out.append(node)
    return out


def _srf_field(stmt: ast.SelectStatement) -> Optional[ast.Field]:
    for f in stmt.fields:
        if isinstance(f.expr, ast.Call) and registry.is_srf(f.expr.name):
            return f
    return None


def _has_aggregates(stmt: ast.SelectStatement) -> bool:
    for root in stmt.expressions():
        if ast.has_aggregate(root):
            return True
    return False


def device_path_eligible(
    stmt: ast.SelectStatement, opts: RuleOptionConfig,
    int_columns: Optional[Set[str]] = None,
) -> Optional[Any]:
    """Returns the KernelPlan if the rule can take the fused device path.
    `int_columns` (declared_int_columns) gives sum / avg / min / max of a
    declared BIGINT column exact int64 partials; a sliding window, or an
    explicit mesh, cannot carry them and leaves such a rule to the host
    operator, which is exact (reason `bigint_exact_placement`)."""
    if not opts.use_device_kernel:
        return None
    w = stmt.window
    if w is None:
        return None
    if w.window_type not in (
        ast.WindowType.TUMBLING_WINDOW,
        ast.WindowType.HOPPING_WINDOW,
        ast.WindowType.COUNT_WINDOW,
        ast.WindowType.SLIDING_WINDOW,
        ast.WindowType.SESSION_WINDOW,
        ast.WindowType.STATE_WINDOW,
    ):
        return None
    # event-time sessions: the per-session structure resolves host-side at
    # watermark time (sort/split), then each session is a plain pane-0 fold
    # + sync finalize — both run through the sharded kernel, so mesh is OK
    if w.window_type == ast.WindowType.STATE_WINDOW:
        from ..sql.compiler import try_compile

        # device state windows: vectorizable begin/emit conditions.
        # Event time OK — the watermark node orders rows, after which the
        # begin/emit toggle scan is identical to processing time (the host
        # path's _ingest_row STATE branch is watermark-agnostic too).
        # Mesh OK — the toggle scan runs host-side; span folds + the sync
        # finalize run through the sharded kernel like any other window.
        # A WHERE clause filters BEFORE the window on the host path — a
        # filtered row must not toggle the window, so such rules stay
        # host-side (the same pre/post-WHERE divergence as COUNT windows)
        if stmt.condition is not None:
            return None
        if try_compile(w.begin_condition, mode="host") is None or \
                try_compile(w.emit_condition, mode="host") is None:
            return None
    if w.window_type == ast.WindowType.SLIDING_WINDOW:
        from ..sql.compiler import try_compile

        # device sliding: processing-time, trigger-gated (per-row emission
        # without a condition belongs on the exact host path). Mesh OK:
        # pane-vector folds, the scratch refold, and the dyn finalize all
        # run sharded (parallel/sharded.py); heavy_hitters plans are
        # already mesh-excluded below (node-local value dictionary)
        if opts.is_event_time:
            return None
        if w.trigger_condition is None or try_compile(
            w.trigger_condition, mode="host"
        ) is None:
            return None
    # event-time COUNT: the watermark node late-drops + orders rows, after
    # which a count window folds exactly like processing time (the host
    # path's _ingest_row is watermark-agnostic too, nodes_window.py:235)
    # event-time × mesh: supported — the sharded kernel routes per-row pane
    # vectors under shard_map (parallel/sharded.py _build_fold_vec), with
    # the scalar fast path for single-bucket batches
    if opts.is_event_time and w.window_type in (
        ast.WindowType.TUMBLING_WINDOW, ast.WindowType.HOPPING_WINDOW
    ):
        # pane ids ship as uint8 — shapes needing >255 live panes (window
        # span + late-tolerance slack) stay on the host buffering path
        bucket = (w.interval_ms()
                  if w.window_type == ast.WindowType.HOPPING_WINDOW
                  and w.interval_ms() else w.length_ms())
        span = max(w.length_ms() // max(bucket, 1), 1)
        slack = -(-max(opts.late_tolerance_ms, 0) // max(bucket, 1))
        if max(span + slack + 2, 4) > 255:
            return None
    if w.window_type == ast.WindowType.COUNT_WINDOW:
        if w.interval:
            return None  # overlapping count windows -> host buffering
        if stmt.condition is not None:
            # count-window length counts post-WHERE rows (host path filters
            # before the window); the kernel can't know the filtered count
            # per batch without a sync, so keep these on the host path
            return None
    if w.window_type == ast.WindowType.HOPPING_WINDOW:
        iv, ln = w.interval or 0, w.length or 0
        if iv <= 0 or iv > ln or ln % iv != 0:
            # pane decomposition requires interval | length; otherwise merged
            # panes would span more time than the window
            return None
    if w.filter is not None:
        return None
    if (w.trigger_condition is not None
            and w.window_type != ast.WindowType.SLIDING_WINDOW):
        return None
    if stmt.joins or _srf_field(stmt) or _analytic_calls(stmt) or _window_func_calls(stmt):
        return None
    dims: List[ast.FieldRef] = []
    for d in stmt.dimensions:
        if not isinstance(d.expr, ast.FieldRef):
            return None
        dims.append(d.expr)
    dim_names = {d.name for d in dims}
    allowed_scalars = {"window_start", "window_end", "window_trigger"}
    for f in stmt.fields:
        if isinstance(f.expr, ast.Wildcard):
            return None
        for node in ast.walk(f.expr):
            if isinstance(node, ast.FieldRef) and not _under_agg(f.expr, node):
                if node.name not in dim_names:
                    return None
            if isinstance(node, ast.Call) and not registry.is_aggregate(node.name):
                fd = registry.lookup(node.name)
                if fd is None:
                    return None
                if fd.ftype != registry.SCALAR or fd.stateful:
                    if node.name not in allowed_scalars:
                        return None
    if stmt.having is not None:
        for node in ast.walk(stmt.having):
            if isinstance(node, ast.FieldRef) and not _under_agg(stmt.having, node):
                if node.name not in dim_names:
                    return None
    # ORDER BY exprs must read only dims or kernel aggregates — groups carry
    # a single synthetic representative row
    for sf in stmt.sorts:
        expr = sf.expr if sf.expr is not None else ast.FieldRef(sf.name, sf.stream)
        for node in ast.walk(expr):
            if isinstance(node, ast.FieldRef) and not _under_agg(expr, node):
                if node.name not in dim_names:
                    return None
    plan = extract_kernel_plan(stmt, int_columns=int_columns)
    if plan is not None and plan.has_exact and (
            w.window_type == ast.WindowType.SLIDING_WINDOW
            or (opts.plan_optimize_strategy or {}).get("mesh")):
        # the sliding ring and an explicit mesh keep float32 partials
        from ..ops.aggspec import _note_fallback
        from ..sql.expr_ir import NotVectorizable

        _note_fallback("placement", None, NotVectorizable(
            "exact BIGINT aggregates are not carried by a sliding ring or "
            "an explicit mesh", reason="bigint_exact_placement"))
        return None
    if plan is not None and plan.has_orderstat:
        where = orderstat_host_reason(stmt, opts, plan, int_columns)
        if where is not None:
            # the host window operator keeps the rows and is exact
            from ..ops.aggspec import _note_fallback
            from ..sql.expr_ir import NotVectorizable

            _note_fallback("placement", None, NotVectorizable(
                f"{where}: {ORDERSTAT_DECLINES[where]}",
                reason="orderstat_placement"))
            return None
    if plan is not None and any(
        s.kind == "heavy_hitters" for s in plan.specs
    ):
        # heavy_hitters: the reversible value dictionary lives on the single
        # fused node (codes are node-local), so the sharded kernel is out;
        # and the result is a list — it must be a bare SELECT field, not an
        # operand of HAVING/ORDER/composite expressions
        if (opts.plan_optimize_strategy or {}).get("mesh"):
            return None
        roots = ([stmt.having] if stmt.having is not None else []) + [
            sf.expr for sf in stmt.sorts if sf.expr is not None
        ]
        for f in stmt.fields:
            if not (isinstance(f.expr, ast.Call)
                    and f.expr.name == "heavy_hitters"):
                roots.append(f.expr)
        for root in roots:
            for node in ast.walk(root):
                if isinstance(node, ast.Call) and node.name == "heavy_hitters":
                    return None
    return plan


def _under_agg(root: ast.Expr, target: ast.Expr) -> bool:
    """Is `target` inside an aggregate call within `root`?"""
    found = [False]

    def walk_in(e: ast.Expr, in_agg: bool) -> None:
        if e is target and in_agg:
            found[0] = True
            return
        child_in_agg = in_agg or (
            isinstance(e, ast.Call) and registry.is_aggregate(e.name)
        )
        for c in e.children():
            walk_in(c, child_in_agg)

    walk_in(root, False)
    return found[0]


# ------------------------------------------------------------------- build
def plan_rule(rule: RuleDef, store) -> Topo:
    if rule.graph is not None:
        from .graph import plan_by_graph

        return plan_by_graph(rule, store)
    if not rule.sql:
        raise PlanError("rule has no sql")
    stmt = parse_select(rule.sql)
    opts = merged_options(rule)
    topo = Topo(
        rule.id, qos=opts.qos, checkpoint_interval_ms=opts.checkpoint_interval_ms
    )

    # joined tables that are registered lookup TABLEs get a LookupJoinNode;
    # joined STREAMs get their own source + the stream-stream JoinNode
    lookup_joins: List[ast.Join] = []
    stream_joins: List[ast.Join] = []
    for j in stmt.joins:
        if _is_lookup_table(j.table.name, store):
            lookup_joins.append(j)
        else:
            stream_joins.append(j)

    if stream_joins and stmt.window is None:
        # same contract as the reference: stream-stream joins pair rows
        # WITHIN a window collection (join_operator.go); without one the
        # pairing set is undefined
        raise PlanError("stream-stream JOIN requires a window")

    # sources — shared via the subtopo pool (one ingest+decode pipeline per
    # stream config, reference subtopo_pool.go:34) when the rule is qos=0;
    # checkpointed rules keep a private source so barriers stay rule-scoped
    stream_tbls = list(stmt.sources) + [j.table for j in stream_joins]
    # alias-qualified refs resolve against the emitter name, so any join
    # (including lookup-only) keeps ref_name naming
    multi = len(stream_tbls) > 1 or bool(stmt.joins)
    # column pruning (optimizer.py ColumnPruner analogue): drop columns the
    # statement can never read at the rule's ingest edge
    from .optimizer import referenced_columns

    needed = referenced_columns(stmt)
    kernel_plan = device_path_eligible(
        stmt, opts, declared_int_columns(stmt, store))
    # expression host fallbacks: when the ONLY thing keeping this rule
    # off the fused device path is an uncompilable expression, count it
    # (kuiper_expr_host_fallback_total{reason}) so the health plane can
    # name host expression eval instead of binning it as "other"
    from ..ops.aggspec import take_expr_fallbacks
    from ..sql.compiler import record_host_fallback

    expr_notes = take_expr_fallbacks()
    if kernel_plan is None and expr_notes:
        for note in expr_notes:
            record_host_fallback(note["reason"])
        logger.info(
            "rule %s: host expression path — %s", rule.id,
            "; ".join(f"{n['kind']}: {n['reason']}" for n in expr_notes))

    # shared pane fold (planner/sharing.py): correlated rules over one
    # stream fold once into a pooled pane store and combine per window —
    # when the rewrite applies, the rule needs no per-rule source entry at
    # all (its data flows source → shared fold → its emit hop)
    tail = None
    if kernel_plan is not None and len(stream_tbls) == 1 and not stmt.joins:
        from .sharing import try_plan_shared

        tail = try_plan_shared(topo, stmt, kernel_plan, opts, rule, store)

    if tail is None:
        source_nodes: List[SourceNode] = []
        for tbl in stream_tbls:
            src_name = tbl.ref_name if multi else tbl.name
            source_nodes.append(
                _plan_stream_source(tbl.name, src_name, opts, store, topo,
                                    project_columns=needed))

        if kernel_plan is not None and len(source_nodes) == 1 \
                and not lookup_joins:
            tail = _build_device_chain(
                topo, stmt, kernel_plan, source_nodes[0], opts,
                rule_id=rule.id
            )
        else:
            tail = _build_host_chain(
                topo, stmt, source_nodes, opts, rule.id,
                stream_joins=stream_joins, lookup_joins=lookup_joins,
                store=store,
                source_names=[t.ref_name if multi else t.name
                              for t in stream_tbls])

    # sinks
    actions = rule.actions or [{"log": {}}]
    for i, action in enumerate(actions):
        for sink_type, props in action.items():
            _build_sink_chain(topo, tail, sink_type, props or {}, i, opts,
                              rule.id, store)
    return topo


def plan_rule_group(group_id: str, rules: List[RuleDef], store) -> Topo:
    """Plan N homogeneous rules as ONE topology: shared ingest, one
    vmapped device program (parallel/multirule.py), per-rule sink chains.
    The rules must share a single source and be identical up to numeric
    literals in WHERE; all run at qos=0 (the group is a fan-out optimization,
    reference test/benchmark/multiple_rules)."""
    from ..ops.emit import build_direct_emit
    from ..parallel.multirule import build_rule_batch
    from ..runtime.nodes_multirule import MultiRuleFusedNode
    from ..runtime.subtopo import SharedEntryNode

    if not rules:
        raise PlanError("empty rule group")
    stmts = [parse_select(r.sql) for r in rules]
    srcs = {tuple(t.name for t in s.sources) for s in stmts}
    if len(srcs) != 1 or len(stmts[0].sources) != 1:
        raise PlanError("rule group must share exactly one source stream")
    try:
        spec = build_rule_batch([r.id for r in rules], stmts)
    except ValueError as exc:
        raise PlanError(str(exc))
    stmt = spec.stmt
    opts = merged_options(rules[0])
    opts.qos = 0
    topo = Topo(group_id, qos=0)
    from .optimizer import referenced_columns

    needed = referenced_columns(stmt)
    if needed is not None:
        # canonicalized WHERE literals are injected params, not columns
        needed = {c for c in needed if not c.startswith("__param_")}
    src = _plan_stream_source(stmt.sources[0].name, stmt.sources[0].name,
                              opts, store, topo, project_columns=needed)
    dims = [d.expr for d in stmt.dimensions]
    direct = build_direct_emit(stmt, spec.plan, [d.name for d in dims])
    if direct is None:
        raise PlanError("rule group tail is not vectorizable")
    node = MultiRuleFusedNode(
        "group_agg", stmt.window, spec, dims=dims,
        capacity=opts.key_slots, micro_batch=opts.micro_batch_rows,
        direct_emit=direct, emit_columnar=opts.emit_columnar,
        buffer_length=opts.buffer_length,
    )
    topo.add_op(node)
    src.connect(node)
    for r in rules:
        entry = SharedEntryNode(f"{r.id}_out", buffer_length=opts.buffer_length)
        topo.add_op(entry)
        node.add_rule_output(r.id, entry)
        actions = r.actions or [{"log": {}}]
        for i, action in enumerate(actions):
            for sink_type, props in action.items():
                _build_sink_chain(topo, entry, sink_type, props or {}, i,
                                  opts, r.id, store)
    return topo


def _is_lookup_table(name: str, store) -> bool:
    _, ok = store.kv("table").get_ok(name)
    return ok


def _make_lookup_join_node(lj: ast.Join, k: int, opts, store):
    from ..runtime.nodes_join import LookupJoinNode

    tdef = load_stream_def(lj.table.name, store)
    tprops = _source_props(tdef, store)
    if tdef.options.key:
        tprops.setdefault("key", tdef.options.key)
    lookup = io_registry.create_lookup(tdef.options.type or "memory")
    lookup.configure(tdef.options.datasource, tprops)
    return LookupJoinNode(
        f"lookup_join_{k}" if k else "lookup_join", lookup, lj,
        key_fields=_equality_key_fields(lj),
        cache_ttl_ms=int(tprops.get("cacheTtl", 60_000)),
        buffer_length=opts.buffer_length,
    )


def _stream_side_qualifiers(join: ast.Join) -> set:
    """Stream aliases referenced by the ON clause's non-table sides — the
    chains a LookupJoinNode must sit on."""
    table = join.table.ref_name
    out = set()
    if join.on is not None:
        for node in ast.walk(join.on):
            if isinstance(node, ast.FieldRef) and node.stream and \
                    node.stream != table:
                out.add(node.stream)
    return out


def _equality_key_fields(join: ast.Join) -> List:
    """(stream_field, table_field) pairs from an equality ON clause; exactly
    one side of each equality must be qualified by the joined table's
    ref_name (silently guessing would query the wrong column)."""
    table = join.table.ref_name
    pairs = []

    def walk(e):
        if isinstance(e, ast.BinaryExpr):
            if e.op == "AND":
                walk(e.lhs)
                walk(e.rhs)
                return
            if e.op == "=" and isinstance(e.lhs, ast.FieldRef) and isinstance(
                e.rhs, ast.FieldRef
            ):
                if e.lhs.stream == table and e.rhs.stream != table:
                    pairs.append((e.rhs.name, e.lhs.name))
                    return
                if e.rhs.stream == table and e.lhs.stream != table:
                    pairs.append((e.lhs.name, e.rhs.name))
                    return
                raise PlanError(
                    f"lookup join ON equality must qualify exactly one side "
                    f"with the table alias {table!r}: {e!r}")
        raise PlanError(
            f"lookup join ON clause must be equality conditions, got {e!r}")

    if join.on is not None:
        walk(join.on)
    return pairs


def _with_ts_field(project_columns, stream, opts):
    """Pruning set + the event-time timestamp field (which the stream must
    always retain) — THE one definition, shared by the subtopo builder and
    the per-rule entry projection so the two can never drift."""
    ts_field = stream.options.timestamp if opts.is_event_time else ""
    if project_columns is not None and ts_field:
        return set(project_columns) | {ts_field}
    return project_columns


def _subtopo_spec(stream_name: str, src_name: str, opts, store,
                  project_columns=None):
    """(subtopo pool key, node builder, stream def) for one stream's
    shareable ingest pipeline — factored out of _plan_stream_source so the
    shared-fold pass (planner/sharing.py) can key its pane stores on the
    same identity without planning a per-rule entry."""
    stream = load_stream_def(stream_name, store)
    props = _source_props(stream, store)
    ts_field = stream.options.timestamp if opts.is_event_time else ""
    project_columns = _with_ts_field(project_columns, stream, opts)

    def build_nodes(name=src_name):
        nodes = []
        stype = stream.options.type or "memory"
        connector = io_registry.create_source(stype)
        connector.configure(stream.options.datasource, props)
        from ..io.converters import get_converter

        converter = get_converter(
            stream.options.format or "json",
            delimiter=stream.options.delimiter or ",",
            fields=[f.name for f in stream.fields] or None,
            schema_id=stream.options.schemaid,
        )
        if props.get("decompression"):
            # bytes payloads are decompressed before FORMAT decode
            # (reference: planner_source.go decompress stage)
            from ..utils.codecs import get_compressor

            _, decomp = get_compressor(props["decompression"])
            converter = _DecompressingConverter(converter, decomp)
        if props.get("decryption"):
            from ..utils.codecs import get_encryptor

            converter = _DecryptingConverter(
                converter, get_encryptor(props["decryption"], props))
        node = SourceNode(
            name, connector, converter=converter,
            schema=schema_of(stream),
            timestamp_field=ts_field,
            strict_validation=stream.options.strict_validation,
            micro_batch_rows=opts.micro_batch_rows,
            linger_ms=opts.micro_batch_linger_ms,
            buffer_length=opts.buffer_length,
            decode_pool_size=opts.decode_pool_size,
            decode_shards=opts.decode_shards,
            ring_depth=opts.ingest_ring_depth,
            prep_upload=opts.ingest_prep_upload,
            # private pipeline: prune at decode, to this rule's columns.
            # A shared pipeline is built unpruned and decodes the union of
            # what its riders read: each hands its set over on attach
            # (SrcSubTopo.attach -> SourceNode.set_decode_columns), the
            # rider's own projection stays in its entry.
            project_columns=(None if opts.share_source and opts.qos == 0
                             else project_columns),
        )
        nodes.append(node)
        # per-interval latest-batch throttle (planner_source.go:146). A
        # dedicated prop, NOT `interval`: poll sources (file/httppull/
        # simulator) already use `interval` as their poll period.
        if props.get("rateLimitInterval"):
            from ..runtime.nodes_chain import RateLimitNode

            rl = RateLimitNode(f"{name}_ratelimit",
                               interval_ms=int(props["rateLimitInterval"]),
                               buffer_length=opts.buffer_length)
            node.connect(rl)
            nodes.append(rl)
        return nodes

    from ..runtime import subtopo as subtopo_pool

    key = subtopo_pool.subtopo_key(stream_name, {
        # everything that changes what the pipeline emits, including the
        # emitter name (join rules match rows by emitter == alias) and
        # the connector identity (type/datasource can change across
        # DROP/CREATE STREAM between plans)
        "name": src_name,
        "type": stream.options.type or "memory",
        "datasource": stream.options.datasource,
        "props": props,
        "format": stream.options.format or "json",
        "fields": [f.name for f in stream.fields],
        "ts": ts_field,
        "strict": stream.options.strict_validation,
        "mb": opts.micro_batch_rows,
        "linger": opts.micro_batch_linger_ms,
        "pool": [opts.decode_pool_size, opts.decode_shards,
                 opts.ingest_ring_depth, opts.ingest_prep_upload],
    })
    return key, build_nodes, stream


def _plan_stream_source(stream_name: str, src_name: str, opts, store,
                        topo: Topo, project_columns=None):
    """Build (or ride) the ingest+decode pipeline for one stream: a pooled
    shared subtopo for qos=0 rules, a topo-private SourceNode otherwise.
    Returns the node rule chains connect to."""
    key, build_nodes, stream = _subtopo_spec(
        stream_name, src_name, opts, store, project_columns=project_columns)
    project_columns = _with_ts_field(project_columns, stream, opts)

    if opts.share_source and opts.qos == 0:
        from ..runtime.subtopo import SharedEntryNode, SubTopoRef

        entry = SharedEntryNode(f"{src_name}_shared",
                                project_columns=project_columns,
                                buffer_length=opts.buffer_length)
        topo.add_op(entry)
        topo.add_shared_source(SubTopoRef(key, build_nodes), entry)
        return entry

    if opts.share_source and opts.qos > 0:
        # explicit, logged fallback (ISSUE 4 satellite): the qos=0-only
        # restriction on pooled pipelines was silent convention before —
        # checkpoint barriers are rule-scoped and cannot flow through a
        # pipeline serving other rules
        logger.info(
            "rule %s: qos=%d requires rule-scoped checkpoint barriers — "
            "using a private source pipeline (shared subtopos and shared "
            "folds serve qos=0 rules only)", topo.rule_id, opts.qos)

    nodes = build_nodes()
    topo.add_source(nodes[0])
    for extra in nodes[1:]:
        topo.add_op(extra)
    return nodes[-1]


def _build_sink_chain(topo: Topo, tail, sink_type: str, props: Dict[str, Any],
                      idx: int, opts: RuleOptionConfig, rule_id: str,
                      store) -> None:
    """Assemble the per-action sink chain (planner_sink.go:36-253):
    [batch] → [encode] → [compress] → [encrypt] → [cache] → sink."""
    from ..io.converters import get_converter
    from ..runtime.nodes_chain import (
        BatchNode, CacheNode, CompressNode, EncryptNode,
    )

    head = tail
    batch_size = int(props.get("batchSize", 0))
    linger_ms = int(props.get("lingerInterval", 0))
    if batch_size > 0 or linger_ms > 0:
        node = BatchNode(f"{sink_type}_{idx}_batch", size=batch_size,
                         linger_ms=linger_ms, buffer_length=opts.buffer_length)
        topo.add_op(node)
        head = head.connect(node)
    # bytes stages only make sense for bytes-capable sinks (file/mqtt/...);
    # FORMAT-encoding for them happens inside the sink itself unless a
    # compression/encryption stage forces an explicit encode here
    compression = props.get("compression", "")
    encryption = props.get("encryption", "")
    transform_in_chain = bool(compression or encryption)
    if transform_in_chain:
        # transform must precede encode so the projected/templated payload is
        # what gets compressed/encrypted (planner_sink.go chain order); the
        # terminal SinkNode then passes opaque payloads through untouched
        from ..runtime.nodes_chain import EncodeNode, TransformNode

        tr = TransformNode(
            f"{sink_type}_{idx}_transform",
            send_single=bool(props.get("sendSingle", False)),
            fields=props.get("fields"),
            exclude_fields=props.get("excludeFields"),
            data_template=props.get("dataTemplate", ""),
            omit_if_empty=bool(props.get("omitIfEmpty", False)),
            buffer_length=opts.buffer_length,
        )
        topo.add_op(tr)
        head = head.connect(tr)
        conv = get_converter(props.get("format", "json"),
                             delimiter=props.get("delimiter", ","),
                             schema_id=props.get("schemaId", ""))
        enc = EncodeNode(f"{sink_type}_{idx}_encode", conv,
                         buffer_length=opts.buffer_length)
        topo.add_op(enc)
        head = head.connect(enc)
    if compression:
        node = CompressNode(f"{sink_type}_{idx}_compress", compression,
                            buffer_length=opts.buffer_length)
        topo.add_op(node)
        head = head.connect(node)
    if encryption:
        node = EncryptNode(f"{sink_type}_{idx}_encrypt", encryption, props,
                           buffer_length=opts.buffer_length)
        topo.add_op(node)
        head = head.connect(node)
    cache_node = None
    if props.get("enableCache"):
        cache_node = CacheNode(
            f"{sink_type}_{idx}_cache",
            store_kv=store.kv(f"sinkcache:{rule_id}:{sink_type}_{idx}"),
            memory_threshold=int(props.get("memoryCacheThreshold", 1024)),
            max_disk_cache=int(props.get("maxDiskCache", 1024 * 1024)),
            resend_interval_ms=int(props.get("resendInterval", 100)),
            buffer_length=opts.buffer_length,
        )
        topo.add_op(cache_node)
        head = head.connect(cache_node)
    sink = io_registry.create_sink(sink_type)
    sink.configure(props)
    node = SinkNode(
        f"{sink_type}_{idx}",
        sink,
        send_single=(not transform_in_chain
                     and bool(props.get("sendSingle", False))),
        fields=None if transform_in_chain else props.get("fields"),
        exclude_fields=(None if transform_in_chain
                        else props.get("excludeFields")),
        data_template=("" if transform_in_chain
                       else props.get("dataTemplate", "")),
        omit_if_empty=(not transform_in_chain
                       and bool(props.get("omitIfEmpty", False))),
        retry_count=int(props.get("retryCount", 0)),
        retry_interval_ms=int(props.get("retryInterval", 1000)),
        cache_node=cache_node,
        buffer_length=opts.buffer_length,
    )
    topo.add_sink(node)
    head.connect(node)


class _DecompressingConverter:
    """Wrap a FORMAT converter so bytes payloads are decompressed first
    (reference: planner_source.go decompress stage)."""

    def __init__(self, inner, decompress) -> None:
        self._inner = inner
        self._decompress = decompress

    def decode(self, raw):
        return self._inner.decode(self._decompress(bytes(raw)))

    def encode(self, data):
        return self._inner.encode(data)


class _DecryptingConverter:
    """Wrap a FORMAT converter so bytes payloads are decrypted first."""

    def __init__(self, inner, encryptor) -> None:
        self._inner = inner
        self._enc = encryptor

    def decode(self, raw):
        return self._inner.decode(self._enc.decrypt(bytes(raw)))

    def encode(self, data):
        return self._inner.encode(data)


def _source_props(stream: ast.StreamStmt, store) -> Dict[str, Any]:
    """Source props from conf_key profiles stored in the config KV
    (reference: internal/conf/yaml_config_ops.go)."""
    props: Dict[str, Any] = {}
    if stream.options.conf_key:
        conf = store.kv("source_conf")
        stored, ok = conf.get_ok(
            f"{stream.options.type or 'memory'}:{stream.options.conf_key}"
        )
        if ok and isinstance(stored, dict):
            props.update(stored)
    return props


def _build_device_chain(
    topo: Topo, stmt, kernel_plan, src: SourceNode, opts: RuleOptionConfig,
    rule_id: str,
):
    from ..ops.emit import build_direct_emit

    dims = [d.expr for d in stmt.dimensions]
    # full fusion: compile HAVING/ORDER/LIMIT/projection into the vectorized
    # emit tail when possible — the whole rule becomes fold + direct emit
    direct = build_direct_emit(stmt, kernel_plan, [d.name for d in dims])
    mesh = None
    req = mesh_request(opts, kernel_plan)
    shard_info: Dict[str, Any] = {k: req.get(k)
                                  for k in ("mode", "source", "reason")}
    if req["mode"] == "sharded":
        from ..parallel.mesh import mesh_from_options, resolve_auto_cfg

        cfg = req["cfg"]
        explicit = req["source"] == "planOptimizeStrategy.mesh"
        try:
            resolved = resolve_auto_cfg(cfg)
            if resolved is None:
                raise ValueError("fewer than 2 devices visible")
            mesh = mesh_from_options(resolved)
            shard_info["mesh"] = dict(resolved)
            shard_info["shards"] = int(resolved["keys"])
        except Exception as exc:
            if explicit:
                raise PlanError(f"cannot build device mesh {cfg}: {exc}")
            # auto/env selection degrades to the single-chip kernel —
            # a deployment-wide KUIPER_MESH must not brick rule create
            # on a 1-device box
            mesh = None
            shard_info = {"mode": "single-chip", "source": req["source"],
                          "reason": f"mesh unavailable ({exc}) — "
                                    "single-chip fallback"}
            logger.info("rule %s: %s", rule_id, shard_info["reason"])
    # sliding ring geometry is chosen HERE, at plan time, from the
    # window/delay/pane declarations (ops/slidingring.py) — the node and
    # the jitcert certificates both consume the same layout
    ring_layout = None
    if stmt.window.window_type == ast.WindowType.SLIDING_WINDOW:
        from ..ops.slidingring import ring_layout_for

        # budget-aware geometry: wide sketch plans (hll front stacks)
        # coarsen their buckets until the ring's static HBM footprint
        # fits slidingDevRingMb, instead of silently refolding
        ring_layout = ring_layout_for(
            stmt.window, kernel_plan, capacity=opts.key_slots,
            budget_mb=opts.sliding_dev_ring_mb)
    # tiered key state (ops/tierstore.py): resolve the HBM budget that
    # drives the hot/cold placement at PLAN time. Gated off for shapes
    # where spilled-group emission can't ride the direct tail (ORDER BY /
    # LIMIT order across the device+spilled split) and for mesh kernels;
    # the node itself gates window types and heavy_hitters.
    tier_budget_mb = resolve_tier_budget_mb(opts)
    if tier_budget_mb and (stmt.sorts or stmt.limit is not None
                           or mesh is not None):
        tier_budget_mb = 0.0
    fused = FusedWindowAggNode(
        "window_agg", stmt.window, kernel_plan, dims,
        capacity=opts.key_slots, micro_batch=opts.micro_batch_rows,
        rule_id=rule_id, buffer_length=opts.buffer_length,
        direct_emit=direct, mesh=mesh,
        prefinalize_lead_ms=opts.prefinalize_lead_ms,
        emit_columnar=opts.emit_columnar,
        is_event_time=opts.is_event_time,
        late_tolerance_ms=opts.late_tolerance_ms,
        dev_ring_budget_mb=opts.sliding_dev_ring_mb,
        sliding_impl=opts.sliding_impl,
        ring_layout=ring_layout,
        tier_budget_mb=tier_budget_mb,
        tier_scan_ms=opts.tier_scan_ms,
    )
    fused.shard_info = shard_info  # explain/status "shards" section twin
    topo.add_op(fused)
    # hand the kernel-input shape to the source's ingest prep at PLAN time
    # (runtime/ingest.py IngestPrepCtx): the decode pool's upload stage then
    # pre-encodes keys + device_puts kernel columns from the FIRST batch.
    # Paths without the hook (rate-limited chains, host path) still get
    # registered by the fused node's first _shared_device_inputs call.
    reg = getattr(src, "register_prep_spec", None)
    if reg is not None and getattr(fused.gb, "accepts_device_inputs", False) \
            and fused.wt != ast.WindowType.SLIDING_WINDOW:
        # sliding excluded: its folds upload through _upload_sliding_inputs
        # (whose pre-padded buffers the _dev_ring must own for trigger-time
        # mask refolds) — a prep upload would be a second, unused copy
        reg(fused.prep_spec())
    if fused.tier is not None:
        # async prefetch: the decode pool's ordered drainer spots
        # returning demoted keys in batch k+1 and starts their packed
        # rows' H2D copy while batch k still folds (runtime/ingest.py)
        reg2 = getattr(src, "register_tier_prefetch", None)
        if reg2 is not None:
            reg2(fused.tier.prefetch)
    if opts.is_event_time:
        # event-time: watermark generation + late drop feeds the kernel's
        # per-row pane routing (columnar all the way)
        wm = WatermarkNode("watermark",
                           late_tolerance_ms=opts.late_tolerance_ms,
                           buffer_length=opts.buffer_length)
        topo.add_op(wm)
        src.connect(wm)
        wm.connect(fused)
    else:
        src.connect(fused)
    if direct is not None:
        return fused  # tail ops folded into the vectorized emit
    tail = fused
    if stmt.having is not None:
        hv = HavingNode("having", stmt.having, rule_id=rule_id,
                        buffer_length=opts.buffer_length)
        topo.add_op(hv)
        tail = tail.connect(hv)
    if stmt.sorts:
        on = OrderNode("order", stmt.sorts, buffer_length=opts.buffer_length)
        topo.add_op(on)
        tail = tail.connect(on)
    proj = ProjectNode("project", stmt.fields, rule_id=rule_id,
                       limit=stmt.limit, buffer_length=opts.buffer_length)
    topo.add_op(proj)
    return tail.connect(proj)


def _make_join_node(stmt, stream_joins, opts: RuleOptionConfig,
                    rule_id: str) -> JoinNode:
    """Stream-stream join operator: the device ring when the ON clause
    lowers (planner/relational.py), else the host nested loop — with the
    structured reason recorded so /explain and the fallback counter name
    exactly why the plan stayed on host."""
    left = stmt.sources[0].ref_name
    if opts.join_impl == "device":
        from ..sql.compiler import record_host_fallback
        from ..sql.expr_ir import NotVectorizable

        from . import relational
        from ..runtime.nodes_relational import DeviceJoinNode

        try:
            lowering = relational.lower_join(stmt, stream_joins)
            return DeviceJoinNode("join", stream_joins, left_name=left,
                                  lowering=lowering,
                                  buffer_length=opts.buffer_length)
        except NotVectorizable as nv:
            record_host_fallback(nv.reason)
    return JoinNode("join", stream_joins, left_name=left,
                    buffer_length=opts.buffer_length)


def _make_analytic_node(stmt, analytic, opts: RuleOptionConfig,
                        rule_id: str) -> AnalyticNode:
    if opts.analytic_impl == "device":
        from ..sql.compiler import record_host_fallback
        from ..sql.expr_ir import NotVectorizable

        from . import relational
        from ..runtime.nodes_relational import DeviceAnalyticNode

        try:
            lowering = relational.lower_analytics(analytic)
            return DeviceAnalyticNode("analytic", analytic,
                                      lowering=lowering, rule_id=rule_id,
                                      buffer_length=opts.buffer_length)
        except NotVectorizable as nv:
            record_host_fallback(nv.reason)
    return AnalyticNode("analytic", analytic, rule_id=rule_id,
                        buffer_length=opts.buffer_length)


def _make_window_func_node(wf, opts: RuleOptionConfig) -> WindowFuncNode:
    """rank/dense_rank/lead are whole-collection functions — they always
    route through the vector operator (a per-row exec cannot see the
    value order); `analytic_impl` only decides whether exact-float32 rank
    batches use the segscan sort kernel."""
    from . import relational

    if not any(c.name in relational.VECTOR_WINDOW_FUNCS for c in wf):
        return WindowFuncNode("window_func", wf,
                              buffer_length=opts.buffer_length)
    from ..sql.compiler import record_host_fallback
    from ..sql.expr_ir import NotVectorizable
    from ..runtime.nodes_relational import VectorWindowFuncNode

    use_device = False
    if opts.analytic_impl == "device":
        try:
            lowering = relational.lower_window_funcs(wf)
            use_device = lowering.device_eligible()
        except NotVectorizable as nv:
            record_host_fallback(nv.reason)
    return VectorWindowFuncNode("window_func", wf, use_device=use_device,
                                buffer_length=opts.buffer_length)


def _build_host_chain(
    topo: Topo, stmt, source_nodes: List[SourceNode], opts: RuleOptionConfig,
    rule_id: str, stream_joins=None, lookup_joins=None, store=None,
    source_names=None,
):
    if stream_joins is None:
        stream_joins = stmt.joins
    lookup_joins = lookup_joins or []
    # lookup joins bind per-STREAM, before the watermark merge and before
    # WHERE/window (reference lookup_node.go sits right after decode): the
    # node must only see rows of the stream its ON clause references, even
    # under event time where all chains later merge at the watermark node.
    # Targeting tracks each stream's CURRENT tail by stream name (node names
    # drift through _shared/_ratelimit/lookup hops).
    names = source_names or [n.name for n in source_nodes]
    tails = dict(zip(names, source_nodes))
    for k, lj in enumerate(lookup_joins):
        node = _make_lookup_join_node(lj, k, opts, store)
        qs = sorted(_stream_side_qualifiers(lj) & tails.keys())
        if not qs:
            qs = list(tails.keys())
        topo.add_op(node)
        for t in {id(tails[q]): tails[q] for q in qs}.values():
            t.connect(node)
        for q in qs:
            tails[q] = node
    seen_ids: set = set()
    tail_of_sources = []
    for t in tails.values():
        if id(t) not in seen_ids:
            seen_ids.add(id(t))
            tail_of_sources.append(t)

    # event-time: watermark generation + late drop
    if opts.is_event_time:
        wm = WatermarkNode("watermark", late_tolerance_ms=opts.late_tolerance_ms,
                           buffer_length=opts.buffer_length)
        topo.add_op(wm)
        for s in tail_of_sources:
            s.connect(wm)
        chain = [wm]
    else:
        chain = list(tail_of_sources)

    def attach(node):
        topo.add_op(node)
        for t in chain:
            t.connect(node)
        chain.clear()
        chain.append(node)
        return node

    analytic = _analytic_calls(stmt)
    if analytic:
        attach(_make_analytic_node(stmt, analytic, opts, rule_id))
    # predicate pushdown: WHERE before the window when it has no analytic refs
    where_pushed = False
    if stmt.condition is not None and not analytic:
        attach(FilterNode("filter", stmt.condition, buffer_length=opts.buffer_length))
        where_pushed = True
    if stmt.window is not None:
        attach(WindowNode("window", stmt.window,
                          is_event_time=opts.is_event_time, rule_id=rule_id,
                          buffer_length=opts.buffer_length))
    if stmt.condition is not None and not where_pushed:
        attach(FilterNode("filter", stmt.condition, buffer_length=opts.buffer_length))
    if stream_joins:
        attach(_make_join_node(stmt, stream_joins, opts, rule_id))
    if stmt.dimensions:
        attach(AggregateNode("aggregate", [d.expr for d in stmt.dimensions],
                             buffer_length=opts.buffer_length))
    if stmt.having is not None:
        attach(HavingNode("having", stmt.having, rule_id=rule_id,
                          buffer_length=opts.buffer_length))
    wf = _window_func_calls(stmt)
    if wf:
        attach(_make_window_func_node(wf, opts))
    if stmt.sorts:
        attach(OrderNode("order", stmt.sorts, buffer_length=opts.buffer_length))
    tail = attach(ProjectNode(
        "project", stmt.fields, rule_id=rule_id, limit=stmt.limit,
        is_agg=_has_aggregates(stmt) and not stmt.dimensions,
        buffer_length=opts.buffer_length,
    ))
    srf = _srf_field(stmt)
    if srf is not None:
        # project computed the SRF list column; expand it into rows
        tail = attach(ProjectSetNode(
            "project_set", srf.output_name or srf.name,
            buffer_length=opts.buffer_length,
        ))
    return tail


def explain(rule: RuleDef, store) -> Dict[str, Any]:
    """Plan explanation (REST /rules/{id}/explain analogue)."""
    stmt = parse_select(rule.sql)
    opts = merged_options(rule)
    kernel_plan = device_path_eligible(
        stmt, opts, declared_int_columns(stmt, store))
    from ..ops.aggspec import take_expr_fallbacks

    notes = take_expr_fallbacks()
    int_section = _explain_int_aggregates(kernel_plan, notes)
    os_section = _explain_order_stats(kernel_plan, notes, opts)
    sharing_info = None
    if kernel_plan is not None and len(stmt.sources) == 1 and not stmt.joins:
        from . import sharing as sharing_mod

        try:
            sharing_info = sharing_mod.explain_decision(
                rule, stmt, opts, kernel_plan, store)
        except Exception as exc:  # explain must never fail on the probe
            sharing_info = {"decision": "private", "reason": str(exc)}
    shared = bool(sharing_info and sharing_info.get("decision") == "shared")
    path = ("device-fused-shared" if shared
            else "device-fused" if kernel_plan is not None else "host")
    ops: List[str] = ["source"]
    if shared:
        ops.append("shared_pane_fold[TPU]")
        ops.append("emit_combine")
    elif kernel_plan is not None:
        ops.append("fused_window_groupby_agg[TPU]")
        if stmt.having is not None:
            ops.append("having")
        if stmt.sorts:
            ops.append("order")
        ops.append("project")
    else:
        if opts.is_event_time:
            ops.append("watermark")
        if _analytic_calls(stmt):
            ops.append("analytic")
        if stmt.condition is not None:
            ops.append("filter")
        if stmt.window is not None:
            ops.append(f"window[{stmt.window.window_type.value}]")
        if stmt.joins:
            ops.append("join")
        if stmt.dimensions:
            ops.append("aggregate")
        if stmt.having is not None:
            ops.append("having")
        if stmt.sorts:
            ops.append("order")
        ops.append("project")
    ops.append("sink")
    out: Dict[str, Any] = {"path": path, "operators": ops}
    if int_section is not None:
        out["int_aggregates"] = int_section
    if os_section is not None:
        out["order_stats"] = os_section
    if sharing_info is not None:
        out["sharing"] = sharing_info
    # shards section: the placement decision this rule's plan would make
    # (docs/DISTRIBUTED.md serving mode) — resolved against the devices
    # this process can see, but never building a mesh (explain is a probe)
    if kernel_plan is not None:
        try:
            req = mesh_request(opts, kernel_plan)
            info: Dict[str, Any] = {k: req.get(k)
                                    for k in ("mode", "source", "reason")}
            if req["mode"] == "sharded":
                from ..parallel.mesh import resolve_auto_cfg

                try:
                    resolved = resolve_auto_cfg(req["cfg"])
                except Exception:
                    resolved = None
                if resolved is None:
                    info = {"mode": "single-chip",
                            "source": req["source"],
                            "reason": "mesh unavailable (fewer than 2 "
                                      "devices) — single-chip fallback"}
                else:
                    info["mesh"] = dict(resolved)
                    info["shards"] = int(resolved["keys"])
            out["shards"] = info
        except Exception as exc:  # explain must never fail on the probe
            out["shards"] = {"mode": "unknown", "reason": str(exc)}
    # mesh section (fleet observatory): LIVE skew + rebalance-hint state
    # for a rule already serving sharded — read-only off meshwatch and
    # the installed controller, never building a mesh (explain stays a
    # probe; the signal feeds ROADMAP item 2's rebalancer)
    if (out.get("shards") or {}).get("mode") == "sharded":
        try:
            from ..observability import meshwatch
            from ..runtime import control as _control

            mesh_info: Dict[str, Any] = {
                "skew": meshwatch.rule_skew(rule.id),
                "threshold": meshwatch.skew_threshold(),
            }
            ctl = _control.controller()
            if ctl is not None:
                ctl_mesh = ctl._mesh_diagnostics()
                mesh_info["hint"] = ctl_mesh["rules"].get(rule.id)
                mesh_info["rebalance_hints_total"] = (
                    ctl_mesh["rebalance_hints_total"])
            out["mesh"] = mesh_info
        except Exception as exc:  # explain must never fail on the probe
            out["mesh"] = {"error": str(exc)}
    # sliding section (ISSUE 15 satellite): which sliding implementation
    # this plan takes and WHY a DABA request falls back to the exact
    # refold — the mesh ring is future work, so a sharded plan's refold
    # must be attributable here and in the flight recorder, never silent
    if kernel_plan is not None and stmt.window is not None and \
            stmt.window.window_type == ast.WindowType.SLIDING_WINDOW:
        requested = opts.sliding_impl
        impl, reason = "daba", None
        if requested != "daba":
            impl, reason = "refold", f"slidingImpl={requested} requested"
        elif (out.get("shards") or {}).get("mode") == "sharded":
            impl, reason = ("refold",
                            "sharded kernel: the mesh DABA ring is future "
                            "work — exact refold path")
        elif any(s.kind == "heavy_hitters" for s in kernel_plan.specs):
            impl, reason = ("refold",
                            "heavy_hitters finalize is host-assembled — "
                            "exact refold path")
        out["sliding"] = {"requested": requested, "impl": impl,
                          "fallback_reason": reason}
    # aot section (docs/AOT_CACHE.md): the executable-cache posture of
    # this plan's certified compile surface — how many signatures the
    # jitcert certificate closes over, how many a fleet bake already
    # persisted (cache hits at boot), and the live per-site hit/miss
    # counters once the rule is serving. A "cached: 0" on a warm fleet
    # image is a bake gap: first emit will pay compiles
    if kernel_plan is not None:
        try:
            from ..observability import jitcert as _jitcert
            from ..runtime import aotcache

            ring_slots = 0
            if (stmt.window is not None
                    and stmt.window.window_type
                    == ast.WindowType.SLIDING_WINDOW
                    and opts.sliding_impl == "daba"):
                from ..ops.slidingring import ring_layout_for

                ring_slots = ring_layout_for(
                    stmt.window, kernel_plan).n_ring_panes
            aot = aotcache.plan_compile_price(_jitcert.estimate_plan_certs(
                kernel_plan, 1, opts.micro_batch_rows, opts.key_slots,
                sliding_ring_slots=ring_slots))
            live = aotcache.site_report(rule.id)
            if live:
                aot["serving"] = live
            out["aot"] = aot
        except Exception as exc:  # explain must never fail on the probe
            out["aot"] = {"error": str(exc)}
    # structured expression-compilation report: which WHERE/arg/FILTER
    # pieces device-compile and which fall back to the row interpreter
    # (with NotVectorizable reason slugs) — so "path: host" is
    # attributable instead of opaque
    from ..ops.aggspec import explain_expressions, take_expr_fallbacks

    try:
        out["expressions"] = explain_expressions(stmt)
    except Exception as exc:  # explain must never fail on the probe
        out["expressions"] = {"error": str(exc)}
    # relational pieces (joins / analytic / window funcs) join the same
    # report: each names its device-vs-host verdict with the reason slug
    # the fallback counter would carry
    try:
        from . import relational

        pieces = relational.explain_relational(
            stmt, stream_joins=stmt.joins)
        for p in pieces:  # rule options veto the lowering verdict
            if p["kind"] == "join" and opts.join_impl != "device":
                p.update(path="host", reason="join_impl_option")
            elif p["kind"] in ("analytic", "window_func") \
                    and opts.analytic_impl != "device":
                p.update(path="host", reason="analytic_impl_option")
        if pieces and isinstance(out["expressions"], dict):
            out["expressions"].setdefault("pieces", []).extend(pieces)
            hosted = [p for p in pieces if p.get("path") == "host"]
            if hosted:
                out["expressions"]["host_fallbacks"] = (
                    out["expressions"].get("host_fallbacks", 0)
                    + len(hosted))
    except Exception as exc:  # explain must never fail on the probe
        if isinstance(out.get("expressions"), dict):
            out["expressions"]["relational_error"] = str(exc)
    take_expr_fallbacks()  # drop probe-recorded notes (explain is read-only)
    try:
        out["source_columns"] = _explain_source_columns(stmt, opts, store)
    except Exception as exc:  # explain must never fail on the probe
        out["source_columns"] = {"error": str(exc)}
    return out


def _explain_int_aggregates(kernel_plan, notes) -> Optional[Dict[str, Any]]:
    """The "int_aggregates" section of explain: per sum / avg / min / max
    of the device plan whether it keeps exact int64 partials (a declared
    BIGINT column) or float32 ones, and why; or why a BIGINT kept the rule
    off the device plan. None where neither applies."""
    from ..ops.aggspec import EXACT_INT_AGGS, _call_key

    host = [{"kind": n["kind"], "reason": n["reason"], "detail": n["detail"]}
            for n in notes if n["reason"].startswith("bigint_")]
    if kernel_plan is None:
        return {"path": "host", "declined": host} if host else None
    aggs = []
    for spec in kernel_plan.specs:
        if spec.kind not in EXACT_INT_AGGS or spec.arg is None:
            continue
        entry = {"agg": _call_key(spec.call),
                 "path": "exact" if spec.exact else "float32"}
        if not spec.exact:
            entry["reason"] = spec.float_reason
            entry["counted"] = ("integer rows past 2^24 under "
                                "kuiper_agg_int_fallback_total{reason}")
        aggs.append(entry)
    return {"aggregates": aggs} if aggs else None


def _explain_order_stats(kernel_plan, notes, opts
                         ) -> Optional[Dict[str, Any]]:
    """The "order_stats" section of explain: for a device plan its row
    buffers (argument, FILTER, the order statistics that read each, the
    first rung) and every placement that cannot carry them, by name; or
    the placement that sent the rule to the host operator. None where the
    rule has no order statistic."""
    from ..ops.aggspec import _call_key, _expr_key
    from ..ops.orderstat import first_rung

    host = [{"placement": n["detail"].split(":", 1)[0],
             "reason": n["reason"], "detail": n["detail"]}
            for n in notes if n["reason"] == "orderstat_placement"]
    if kernel_plan is None:
        return {"path": "host", "declined": host} if host else None
    if not kernel_plan.has_orderstat:
        return None
    bufs = []
    for b in range(len(kernel_plan.os_bufs)):
        specs = [s for s in kernel_plan.specs if s.os_buf == b]
        bufs.append({
            "column": _expr_key(specs[0].call.args[0]),
            "filter": _expr_key(specs[0].call.filter),
            "fractions": [{"agg": _call_key(s.call), "kind": s.kind,
                           "frac": s.frac} for s in specs],
            "rung": first_rung(opts.micro_batch_rows),
        })
    return {"path": "device", "buffers": bufs,
            "declined": [{"placement": k, "reason": v}
                         for k, v in ORDERSTAT_DECLINES.items()
                         if k not in ("heavy_hitters", "exact_bigint",
                                      "window", "bigint")]}


def _explain_source_columns(stmt, opts, store) -> Dict[str, Any]:
    """Per stream of the statement: the columns this rule reads, and the
    columns the stream's source pipeline decodes — for a shared pipeline
    that is live, the union over the rules attached to it right now; else
    what this rule alone would make it decode. "*" = every column."""
    from ..runtime import subtopo as subtopo_pool
    from .optimizer import referenced_columns

    needed = referenced_columns(stmt)
    lookups = {j.table.name for j in stmt.joins
               if _is_lookup_table(j.table.name, store)}
    tables = list(stmt.sources) + [j.table for j in stmt.joins
                                   if j.table.name not in lookups]
    multi = len(tables) > 1 or bool(stmt.joins)
    shared = bool(opts.share_source and opts.qos == 0)
    out: Dict[str, Any] = {}
    for tbl in tables:
        key, _, stream = _subtopo_spec(
            tbl.name, tbl.ref_name if multi else tbl.name, opts, store)
        reads = _with_ts_field(needed, stream, opts)
        mine = "*" if reads is None else sorted(reads)
        live = subtopo_pool.peek(key) if shared else None
        out[tbl.name] = {
            "pipeline": "shared" if shared else "private",
            "reads": mine,
            "decoded": (live.source.decoded_columns() if live is not None
                        else mine),
        }
    return out
