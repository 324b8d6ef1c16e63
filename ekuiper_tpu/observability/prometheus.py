"""Prometheus text exposition for engine + per-rule/per-op metrics
(analogue of metrics/metrics.go:64-88 + internal/server/prome_init.go).

No client library: the text format is lines of
`name{labels} value` with `# TYPE`/`# HELP` headers — rendered directly
from the rules' StatManagers on each scrape, so there is no second
bookkeeping system to keep in sync (the reference wires its StatManager
into promauto gauges the same way).

Every metric family carries a HELP line and is cataloged in
docs/OBSERVABILITY.md; tools/check_metrics.py lints that invariant from
the tier-1 suite. Nodes owned by a SHARED subtopo (one physical source
serving N rules) are emitted exactly once, under rule="__shared__" —
per-rule emission double-counted their records_*_total in any PromQL sum.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Tuple

from .histogram import (
    BOUNDARY_BOUNDS_US,
    E2E_BOUNDS_MS,
    render_prom_histogram,
)

_STATE_VALUES = {"running": 1, "stopped": 0}

#: (metric name == StatManager snapshot key, help) — values come off the
#: per-node snapshot taken once per scrape, so every line of one node is
#: a consistent cut
_COUNTERS = (
    ("records_in_total", "items received by the op"),
    ("records_out_total", "items emitted by the op"),
    ("exceptions_total", "per-item errors swallowed by the op"),
    ("idle_us_total", "time the op's worker waited in its empty input "
     "queue: starved (us)"),
    ("backpressure_us_total", "time senders waited for room in the op's "
     "full input queue, counted on their threads: blocked (us)"),
    # the worker's cycle ledger: wall = idle + busy, busy = staged + unstaged
    ("busy_us_total", "time the op's worker spent outside its get, a cycle "
     "of its loop at a time (us): with idle_us_total, the worker's wall"),
    ("busy_cpu_us_total", "thread-CPU time of the op's worker over the "
     "same cycles (us)"),
    ("unstaged_us_total", "busy time of the op's worker outside every "
     "stage that closed on that thread (us)"),
    ("unstaged_cpu_us_total", "thread-CPU time of the op's worker in its "
     "cycles and outside every stage (us)"),
)
#: the one counter whose snapshot (and rule status) key is older than its
#: metric name
_SNAPSHOT_KEY = {"busy_us_total": "process_time_us_total"}
_GAUGES = (
    ("buffer_length", "input queue occupancy"),
    ("process_latency_us", "last dispatch latency (engine clock, us)"),
)
_STAGES = (
    ("stage_us_total", "total_us", "cumulative wall time per pipeline stage"),
    ("stage_cpu_us_total", "cpu_us",
     "cumulative thread-CPU time per pipeline stage: wall minus this is "
     "time the stage was open with its thread off the core"),
    ("stage_calls_total", "calls", "invocations per pipeline stage"),
    ("stage_rows_total", "rows", "rows handled per pipeline stage"),
)
#: per-op latency-distribution quantiles exported per scrape — keys into
#: the StatManager snapshot's histogram summaries (computed once per node
#: per scrape, reused here instead of re-scanning the histograms). Label
#: name is `q`, NOT the reserved `quantile` (promtool flags that label on
#: anything but summary-typed metrics).
_QUANTILES = (("p50", "0.5"), ("p90", "0.9"), ("p99", "0.99"))

#: rule label shared nodes are emitted under (matches the subtopo's
#: rule context, runtime/subtopo.py _FanoutTopoShim)
SHARED_RULE_LABEL = "__shared__"

# kuiperlint: ignore[clock-discipline]: process uptime is wall-clock by definition — mocking it would misreport restarts to operators
_START_TIME = time.time()


def _esc(v: str) -> str:
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", " ")


def _family(out: List[str], name: str, mtype: str, help_txt: str) -> None:
    out.append(f"# TYPE {name} {mtype}")
    out.append(f"# HELP {name} {help_txt}")


def render(rule_registry) -> str:
    """Scrape callback: rule states + every node's StatManager."""
    out: List[str] = []
    _family(out, "kuiper_rule_status", "gauge", "1 running, 0 stopped")
    rows: List[Tuple[str, Any]] = []
    shared_nodes: Dict[int, Any] = {}  # id(node) -> node, emitted ONCE
    e2e_rows: List[Tuple[str, Any]] = []  # (rule_id, LatencyHistogram)
    boundary_rows: List[Tuple[str, Dict[str, Any]]] = []  # by phase
    for entry in rule_registry.list():
        rule_id = entry["id"]
        out.append(
            f'kuiper_rule_status{{rule="{_esc(rule_id)}"}} '
            f"{_STATE_VALUES.get(str(entry.get('status', '')).lower(), 0)}")
        rs = rule_registry.state(rule_id)
        topo = rs.topo if rs is not None else None
        if topo is not None:
            for node in topo.all_nodes():
                rows.append((rule_id, node))
            for subtopo, _ in topo.live_shared():
                for node in subtopo.nodes:
                    shared_nodes.setdefault(id(node), node)
            e2e_rows.append((rule_id, topo.e2e_hist))
            boundary_rows.append((rule_id, topo.boundary_hists))
    rows.extend((SHARED_RULE_LABEL, node) for node in shared_nodes.values())
    snaps = [(rule_id, node, node.stats.snapshot()) for rule_id, node in rows]

    def op_labels(rule_id: str, node: Any) -> str:
        return (f'rule="{_esc(rule_id)}",op="{_esc(node.name)}",'
                f'type="{_esc(node.op_type)}"')

    for mname, help_txt in _COUNTERS:
        _family(out, f"kuiper_op_{mname}", "counter", help_txt)
        key = _SNAPSHOT_KEY.get(mname, mname)
        for rule_id, node, snap in snaps:
            out.append(f"kuiper_op_{mname}{{{op_labels(rule_id, node)}}} "
                       f"{snap[key]}")
    for mname, help_txt in _GAUGES:
        _family(out, f"kuiper_op_{mname}", "gauge", help_txt)
        for rule_id, node, snap in snaps:
            out.append(f"kuiper_op_{mname}{{{op_labels(rule_id, node)}}} "
                       f"{snap[mname]}")
    # drop taxonomy (utils/metrics.py inc_dropped): data discarded BY
    # DESIGN, labeled by reason — buffer_full (drop-oldest backpressure),
    # pane_recycle, decode_error, stale_watermark, shed_qos (SLO-driven
    # shedding, runtime/control.py). Distinct from exceptions_total,
    # which counts operator ERRORS only.
    _family(out, "kuiper_node_dropped_total", "counter",
            "items discarded by design, labeled by reason (buffer_full/"
            "pane_recycle/decode_error/stale_watermark/shed_qos)")
    for rule_id, node, snap in snaps:
        for reason, n in sorted(snap["dropped_total"].items()):
            out.append(
                f"kuiper_node_dropped_total{{{op_labels(rule_id, node)},"
                f'reason="{_esc(reason)}"}} {n}')
    # per-edge queue depth: the node's input queue IS its fan-in edge
    # set's buffer (one bounded queue per node). Reported as the MAX of
    # the live occupancy and the enqueue-time high-water mark since the
    # last scrape (StatManager.note_queue_depth) — a backpressure spike
    # that fills and drains BETWEEN scrapes (or between health-evaluator
    # ticks) would otherwise be invisible to burn-rate math
    _family(out, "kuiper_node_queue_depth", "gauge",
            "peak input-queue occupancy since last scrape "
            "(enqueue-time high-water mark, floor = live occupancy)")
    for rule_id, node, _snap in snaps:
        q = getattr(node, "inq", None)
        if q is not None:
            take = getattr(node.stats, "take_queue_peak_scrape", None)
            peak = take() if take is not None else 0
            out.append(
                f"kuiper_node_queue_depth{{{op_labels(rule_id, node)}}} "
                f"{max(q.qsize(), peak)}")
    # per-op latency DISTRIBUTIONS (observability/histogram.py): dispatch
    # busy time and input-queue wait as quantile gauges — the per-op view
    # of the tail the e2e histogram aggregates per rule
    for mname, snap_key, help_txt in (
            ("process_latency_quantile_us", "process_latency_us_hist",
             "dispatch busy-time percentile (us, log-bucketed histogram)"),
            ("queue_wait_quantile_us", "queue_wait_us_hist",
             "input-queue wait percentile (us, log-bucketed histogram)")):
        _family(out, f"kuiper_op_{mname}", "gauge", help_txt)
        for rule_id, node, snap in snaps:
            summary = snap[snap_key]
            for key, qlabel in _QUANTILES:
                out.append(
                    f"kuiper_op_{mname}{{{op_labels(rule_id, node)},"
                    f'q="{qlabel}"}} {summary[key]}')
    # per-stage pipeline timings (decode/ring/upload/fold): the ingest-
    # pipeline balance — which stage a node's wall time goes to — read
    # straight off the StatManagers' stage accounting
    stage_rows = [(rule_id, node, stage, st)
                  for rule_id, node, snap in snaps
                  for stage, st in snap["stage_timings"].items()]
    for mname, key, help_txt in _STAGES:
        _family(out, f"kuiper_op_{mname}", "counter", help_txt)
        for rule_id, node, stage, st in stage_rows:
            out.append(
                f"kuiper_op_{mname}{{{op_labels(rule_id, node)},"
                f'stage="{_esc(stage)}"}} {st[key]}')
    # ingest-pipeline occupancy: ring depth (decoded batches awaiting their
    # emission turn) and decode-queue depth (jobs awaiting a worker) per
    # pooled source — backpressure becomes a visible gauge instead of an
    # inference from throughput dips
    pool_rows = []
    for rule_id, node in rows:
        depths_fn = getattr(node, "pool_depths", None)
        if depths_fn is None:
            continue
        depths = depths_fn()
        if depths is not None:
            pool_rows.append((rule_id, node, depths))
    for mname, idx, help_txt in (
            ("ingest_ring_depth", 0,
             "decoded batches in the ordered ring (submitted, not emitted)"),
            ("decode_pool_queue", 1,
             "decode jobs waiting for a pool worker")):
        _family(out, f"kuiper_{mname}", "gauge", help_txt)
        for rule_id, node, depths in pool_rows:
            out.append(
                f'kuiper_{mname}{{rule="{_esc(rule_id)}",'
                f'op="{_esc(node.name)}"}} {depths[idx]}')
    # what the native decoder met, by its own tallies (added once a
    # micro-batch by the source): object members decoded into a column or
    # stepped over because no attached rule reads them, and payload bytes
    tally_rows = [(rule_id, node, node.decode_tally)
                  for rule_id, node in rows
                  if getattr(node, "decode_tally", None) is not None]
    _family(out, "kuiper_source_decode_fields_total", "counter",
            "JSON object members the native decoder met, by fate: kept "
            "(decoded into a column) or skipped (no attached rule reads it)")
    for rule_id, node, tally in tally_rows:
        for fate in ("kept", "skipped"):
            out.append(
                f'kuiper_source_decode_fields_total{{rule="{_esc(rule_id)}",'
                f'op="{_esc(node.name)}",fate="{fate}"}} {tally[fate]}')
    _family(out, "kuiper_source_decode_bytes_total", "counter",
            "payload bytes the native decoder read")
    for rule_id, node, tally in tally_rows:
        out.append(
            f'kuiper_source_decode_bytes_total{{rule="{_esc(rule_id)}",'
            f'op="{_esc(node.name)}"}} {tally["bytes"]}')
    # which encode served the GROUP BY key columns (ops/keytable.py): the
    # column's dtype picks the path, this says which one a rule's keys take
    _family(out, "kuiper_keytable_encode_rows_total", "counter",
            "rows slot-encoded by a key table, by path: native_int (int64 "
            "table), native_str (byte-keyed table), hashed (dict map), "
            "sorted (np.unique)")
    for rule_id, node in rows:
        if not hasattr(node, "keytable_encode_rows"):
            continue
        for path, n in sorted((node.keytable_encode_rows() or {}).items()):
            out.append(
                f'kuiper_keytable_encode_rows_total{{rule="{_esc(rule_id)}",'
                f'op="{_esc(node.name)}",path="{path}"}} {n}')
    # host -> device staging of the window node's folds (ops/groupby.py
    # `fold`): runtime calls made, what the `fold_h2d` stage's time buys,
    # and arguments the device held already; hit share = resident /
    # (resident + transfers)
    for family, attr, help_txt in (
            ("kuiper_fold_transfers_total", "fold_transfers",
             "host->device runtime calls the staging of a window node's "
             "folds made (one a chunk with anything still on the host: "
             "padded columns, masks, slots, a partial row count, a pane "
             "vector)"),
            ("kuiper_fold_resident_args_total", "fold_resident_args",
             "fold arguments that staging took from the kernel's "
             "device-resident scalar table (a scalar pane, a full "
             "micro-batch's row count) and made no runtime call for")):
        _family(out, family, "counter", help_txt)
        for rule_id, node in rows:
            n = getattr(node, attr, None)
            if n is not None:
                out.append(
                    f'{family}{{rule="{_esc(rule_id)}",'
                    f'op="{_esc(node.name)}"}} {n}')
    # shared pane folds (runtime/nodes_sharedfold.py): pool-level gauges —
    # members per store and the fold-dedup ratio (1 - folds run / folds N
    # private rules would have run). The store node's own op metrics (incl.
    # the per-rule emit-combine stage timings, stage="emit[<rule>]") ride
    # the rule="__shared__" rows above via each rider's live_shared()
    # nodes, so only the pool-level aggregates are emitted here.
    from ..runtime import nodes_sharedfold as _sharedfold

    fold_stores = _sharedfold.live_stores()
    for mname, mtype, help_txt, value in (
            ("kuiper_shared_fold_rules", "gauge",
             "member rules riding each shared pane fold",
             lambda st: st.member_count()),
            ("kuiper_shared_fold_dedup_ratio", "gauge",
             "1 - device folds run / folds N private rules would have run",
             lambda st: round(st.fold_dedup_ratio(), 4)),
            ("kuiper_shared_fold_windows_total", "counter",
             "per-rule windows emitted from shared pane folds",
             lambda st: st.windows_emitted)):
        _family(out, mname, mtype, help_txt)
        for st in fold_stores:
            out.append(f'{mname}{{op="{_esc(st.name)}"}} {value(st)}')
    # sliding windows on the DABA ring (runtime/nodes_fused.py): triggers
    # by the path that served the window body — a rule that falls to the
    # dyn path every trigger pays a window-length pane merge each time
    _family(out, "kuiper_sliding_triggers_total", "counter",
            "sliding-window triggers answered, by the path that served the "
            "window body: fast (one combine of the ring's running "
            "partials), flip (partials rebuilt from the panes first), dyn "
            "(traced-mask pane merge, the exact fallback), edge (every row "
            "in the host edge shadow)")
    for rule_id, node in rows:
        for path, n in sorted(getattr(node, "sliding_triggers", {}).items()):
            out.append(
                f"kuiper_sliding_triggers_total{{{op_labels(rule_id, node)},"
                f'path="{_esc(path)}"}} {n}')
    # ... and by where the trigger was finished: on the device (the tail
    # program: edge rows scattered, body and edges combined, final values;
    # a compact fetch) or on the host (shadow, merge and final values in
    # numpy over the fetched sketch) — device / (device + host) is the
    # share of triggers that never moved a sketch over the link
    _family(out, "kuiper_sliding_tail_total", "counter",
            "sliding-window triggers by where their tail ran: device (paths "
            "fast and flip: slidingring.tail returns final values), host "
            "(paths dyn and edge: edge shadow, merge and final values in "
            "numpy)")
    for rule_id, node in rows:
        for tail, n in sorted(getattr(node, "sliding_tails", {}).items()):
            out.append(
                f"kuiper_sliding_tail_total{{{op_labels(rule_id, node)},"
                f'tail="{_esc(tail)}"}} {n}')
    # the SLO headline: per-rule ingest→emit latency as a real Prometheus
    # histogram (_bucket/_sum/_count with le labels) — histogram_quantile()
    # over it answers "is p99 emit under 50ms" directly
    _family(out, "kuiper_rule_e2e_latency_ms", "histogram",
            "ingest->emit end-to-end latency per rule (ms)")
    for rule_id, hist in e2e_rows:
        render_prom_histogram(
            out, "kuiper_rule_e2e_latency_ms", f'rule="{_esc(rule_id)}"',
            hist, E2E_BOUNDS_MS)
    # ... and the engine's side of it per window boundary, by phase: timer
    # lateness + queue (trigger_delay), finalize/fetch/merge (emit), sink
    # queue + convert + deliver (sink) — fed by the boundary's own spans
    _family(out, "kuiper_boundary_ms", "histogram",
            "engine time per window boundary by phase: trigger_delay, "
            "emit, sink (ms)")
    for rule_id, hists in boundary_rows:
        for phase, hist in hists.items():
            render_prom_histogram(
                out, "kuiper_boundary_ms",
                f'rule="{_esc(rule_id)}",phase="{phase}"', hist,
                BOUNDARY_BOUNDS_US, scale=1000)
    # engine-health planes (devwatch: XLA trace-vs-hit accounting;
    # kernwatch: sampled device time + roofline; memwatch: per-component
    # device/host byte probes) — module-global registries, so they render
    # once per scrape, not per rule
    from . import devwatch, health, kernwatch, memwatch

    devwatch.render_prometheus(out, _esc)
    kernwatch.render_prometheus(out, _esc)
    memwatch.render_prometheus(out, _esc)
    # AOT executable cache (runtime/aotcache.py): pre-built-executable
    # hit/miss/build accounting + the warmup-failure counter — the
    # zero-compile-serving plane's scrape surface
    from ..runtime import aotcache as _aotcache

    _aotcache.render_prometheus(out, _esc)
    # tiered key state (ops/tierstore.py): demote/promote counters,
    # cold-tier residency and host arena bytes per tiered rule
    from ..ops import tierstore

    tierstore.render_prometheus(out, _esc)
    # multi-chip sharded serving (parallel/sharded.py): per-shard fold
    # rows and key occupancy for every live mesh kernel
    from ..parallel import sharded as _sharded

    _sharded.render_prometheus(out, _esc)
    # mesh attribution (observability/meshwatch.py): per-rule shard skew
    # ratio + rows/s, collective-vs-compute split of the sharded fold
    # sites — observes the shard registry at scrape time
    from . import meshwatch as _meshwatch

    _meshwatch.render_prometheus(out, _esc)
    # telemetry timeline (observability/timeline.py): on-disk segment
    # count/bytes of the durable snapshot ring (absent when none is
    # installed)
    from . import timeline as _timeline

    _timeline.render_prometheus(out, _esc)
    # relational tier (ops/joinring.py, ops/segscan.py): join-ring rows,
    # matches, per-window host fallbacks and ring bytes; segscan rows
    # and partial spills per rule
    from ..ops import joinring as _joinring
    from ..ops import segscan as _segscan

    _joinring.render_prometheus(out, _esc)
    _segscan.render_prometheus(out, _esc)
    # expression host fallbacks (sql/compiler.py counters): plan-time
    # count of expressions routed to the row interpreter, by structured
    # NotVectorizable reason — the metric the health plane's bottleneck
    # attribution pairs with the "host_expr" stage
    from ..sql.compiler import host_fallback_counts

    _family(out, "kuiper_expr_host_fallback_total", "counter",
            "expressions that fell back to the host row interpreter at "
            "plan time, by NotVectorizable reason")
    for reason, n in sorted((host_fallback_counts()
                             or {"none": 0}).items()):
        out.append(
            f'kuiper_expr_host_fallback_total{{reason="{_esc(reason)}"}} '
            f"{n}")
    # health plane (observability/health.py): per-rule verdict, SLO burn
    # rate, watermark lag, bottleneck stage — computed at evaluator ticks,
    # rendered from the last verdicts (a scrape never forces a tick)
    health.render_prometheus(out, _esc)
    # QoS control plane (runtime/control.py): admission decisions, rows
    # shed per rule/qos class, autosize action count — rendered from the
    # installed controller's counters (absent when none is installed)
    from ..runtime import control as _control

    _control.render_prometheus(out, _esc)
    _family(out, "kuiper_uptime_seconds", "gauge",
            "seconds since engine start")
    # kuiperlint: ignore[clock-discipline]: wall-clock pair of _START_TIME above
    out.append(f"kuiper_uptime_seconds {time.time() - _START_TIME:.1f}")
    return "\n".join(out) + "\n"


class TextResponse(str):
    """Marker: REST dispatch replies text/plain instead of json."""

    content_type = "text/plain; version=0.0.4; charset=utf-8"
