"""Flagship benchmark: 10k-device tumbling-window GROUP BY on one TPU chip.

Reproduces the reference's select_aggr_rule.jmx scenario (TUMBLINGWINDOW avg
over an MQTT demo stream) at TPU scale: 10,000 devices, avg/count/min/max
aggregates, measured through the real engine node (key encode + device fold
+ window emit), not just the raw kernel.

Phase T (throughput) saturates the host→device link. Every row folds on
device; every window emits from a pre-issued DEVICE fetch the boundary
waits for — the reported rows/s therefore includes the full cost of
device-served emission. The per-window source tag (device/sync) is reported
so a sync finalize can never masquerade as a pre-issued device number (r02
post-mortem). Paced emit latency is the benchmark's (`tumbling10k.paced`).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Baseline = the reference's best published single-node throughput for its
streaming hot path (12k msg/s on a Raspberry Pi 3B+, README.md:98 — see
BASELINE.md; the reference publishes no TPU-class numbers).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

N_DEVICES = 10_000
BATCH_ROWS = 65_536
KEY_SLOTS = 16_384
WARMUP_BATCHES = 3
BASELINE_MSG_S = 12_000.0

# Total wall-clock budget for the WHOLE bench run. The driver wraps
# `python bench.py` in a hard 900s timeout; r05 died to it (rc=124, no
# artifact) because the full-pipe SUBPROCESS alone was allowed 900s. Every
# phase budget is now capped by the remaining global budget, and a global
# watchdog emits the final self-contained JSON just before the driver
# would kill us.
TOTAL_BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "870"))
_DEADLINE: list = []  # [epoch_seconds], set by main()


def _remaining_s() -> float:
    """Seconds left in the global budget (inf outside main())."""
    if not _DEADLINE:
        return float("inf")
    return _DEADLINE[0] - time.time()


def phase_budget(nominal_s: float, remaining_s=None,
                 reserve_s: float = 15.0,
                 later_floor_s: float = 0.0) -> float:
    """Wall-clock budget for one phase: its nominal allowance clamped so
    the phase can never spend past the global deadline minus a reserve
    for the final-JSON flush, minus the floors of every later phase
    (`later_floor_s`, see PHASE_FLOORS). THE invariants (unit-tested,
    tests/test_bench_budget.py — the r05 rc=124 post-mortem class of bug):
    for any sequence of phases each consuming at most its clamped budget,
    total spend stays within TOTAL_BUDGET_S; and when the roster's floors
    fit the budget, every phase is offered at least min(nominal, floor)
    seconds no matter how greedily earlier phases spent theirs."""
    rem = _remaining_s() if remaining_s is None else remaining_s
    return min(float(nominal_s), max(rem - reserve_s - later_floor_s, 0.0))


#: roster-ordered (tag, minimum useful seconds) per phase. phase_budget()
#: subtracts the floors of every LATER phase from the remaining global
#: budget before granting one, so a single slow phase can never starve
#: the rest of the roster out of the artifact (BENCH_r05's rc=124: the
#: full_pipe child alone was allowed the driver's whole 900s, so nothing
#: after it — or even the final JSON — ever ran). A floor is a guarantee
#: of OPPORTUNITY, not a spend: fast phases return their unused share to
#: the pool. Floors sum to well under TOTAL_BUDGET_S (asserted in
#: tests/test_bench_budget.py).
PHASE_FLOORS = (
    ("full-pipe", 110.0),
    ("full-pipe-contended", 90.0),
    ("hetero 256-rule", 90.0),
    ("phase_throughput", 60.0),
    ("sliding", 50.0),
    ("heavy_hitters", 30.0),
    ("hll_1m", 60.0),
    ("event_time", 25.0),
    ("rule_group", 25.0),
    ("filter_heavy", 25.0),
    ("join_heavy", 15.0),
    ("multi_rule_shared", 30.0),
    ("multi_rule_shared_mixed", 25.0),
    ("key_cardinality", 45.0),
    ("multichip_full_pipe", 40.0),
    ("cold_start", 30.0),
    ("churn_soak", 45.0),
)


def later_floor(tag: str) -> float:
    """Sum of the floors of every phase AFTER `tag` in the roster (0.0
    for a tag not in the roster — ad-hoc phases get the plain greedy
    carve)."""
    names = [n for n, _ in PHASE_FLOORS]
    if tag not in names:
        return 0.0
    i = names.index(tag)
    return float(sum(f for _, f in PHASE_FLOORS[i + 1:]))

# Every phase records its key metrics here via record(); the final stdout
# JSON line carries the whole dict under "phases", so the driver artifact
# is self-contained even when its output tail is byte-truncated
# (VERDICT r4 weak #2: the 1M full-pipe claim was orphaned exactly that way)
RESULTS: dict = {}


def record(phase: str, **kv) -> None:
    d = {k: (round(v, 1) if isinstance(v, float) else v)
         for k, v in kv.items()}
    RESULTS[phase] = d
    # subprocess-isolated phases get their record lines re-parsed by the
    # parent (_run_isolated); plain stderr so humans can read them too
    print("#R " + json.dumps({phase: d}), file=sys.stderr, flush=True)


def _flush_record_dump() -> None:
    """One `#R ` line carrying EVERYTHING recorded so far — the dying
    gasp of a watchdog. Per-record lines already stream out as phases
    finish, but when a watchdog fires inside a subprocess-isolated phase
    the child's stdout JSON is discarded; this stderr line is what the
    parent's harvest (`_harvest_phase_stderr`) folds into the artifact's
    `phases` (the r05 class: a killed child left `parsed` null)."""
    try:
        print("#R " + json.dumps(dict(RESULTS)), file=sys.stderr,
              flush=True)
    except Exception:
        pass

def _block_marker(marker) -> None:
    """Pace the dispatch queue: wait for a buffer captured one mark ago.
    Capture sites take a tiny SLICE of the state (`state["act"][:1]`) —
    a fresh buffer nothing ever donates, whose computation completes no
    earlier than the state it was cut from — because the state array
    itself is donated to a later fold on backends that honor
    donate_argnums (CPU jax does): blocking the raw array raised
    INVALID_ARGUMENT and killed the sliding phase on every CPU round,
    and skipping deleted markers instead would silently disable pacing
    on exactly those backends. The deleted-buffer tolerance below is a
    last-resort guard for races, not the mechanism."""
    if marker is None:
        return
    import jax

    try:
        deleted = getattr(marker, "is_deleted", None)
        if deleted is not None and deleted():
            return
        jax.block_until_ready(marker)
    except Exception as exc:
        # ONLY the donation race between the check and the block is
        # benign; a real device fault must propagate (the marker is the
        # in-flight bound — swallowing it would let the loop dispatch
        # unboundedly and measure client RAM, not the pipeline)
        msg = str(exc).lower()
        if "deleted" not in msg and "donated" not in msg:
            raise


# Phase T: saturated link; long windows amortize the boundary's device wait.
# 20 windows -> >=20 device-served boundary samples (r03 recorded only 4,
# too thin for a latency claim)
T_WINDOW_BATCHES = 64
T_PRE_ISSUE_AT = (48,)
T_WINDOWS = 20
T_BLOCK_EVERY = 16  # bound the dispatch queue (client buffers uploads)

SQL = (
    "SELECT deviceId, avg(temperature) AS avg_t, count(*) AS cnt, "
    "min(temperature) AS min_t, max(temperature) AS max_t "
    "FROM demo GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)"
)


def bench_rule_group(batches, kt_slots) -> None:
    """256 homogeneous rules (per-rule thresholds) as ONE vmapped device
    program — the TPU answer to the reference's shared-source fan-out
    benchmark (300 rules x 500 msg/s = 150k rule-msg/s on 2 cores,
    README.md:144-156). Prints a stderr metric line; the headline JSON line
    stays the single-rule bench."""
    import jax
    from ekuiper_tpu.parallel.multirule import BatchedGroupBy, build_rule_batch
    from ekuiper_tpu.sql.parser import parse_select

    n_rules = 256
    stmts = [
        parse_select(
            "SELECT deviceId, avg(temperature) AS a, count(*) AS c "
            f"FROM demo WHERE temperature > {10.0 + 0.1 * r} "
            "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)"
        )
        for r in range(n_rules)
    ]
    spec = build_rule_batch([f"r{r}" for r in range(n_rules)], stmts)
    gb = BatchedGroupBy(spec, capacity=kt_slots, micro_batch=BATCH_ROWS)
    state = gb.init_state()
    from ekuiper_tpu.ops.keytable import KeyTable

    kt = KeyTable(kt_slots)
    cols = [{"temperature": b.columns["temperature"]} for b in batches]
    # warmup compile (one program for all 256 rules)
    slots, _ = kt.encode_column(batches[0].columns["deviceId"])
    state = gb.fold(state, dict(cols[0]), slots)
    gb.finalize(state, kt.n_keys)
    jax.block_until_ready(state)
    rows = 0
    n = 0
    t0 = time.time()
    while time.time() - t0 < 10.0:
        # full per-batch host path: key encode runs every batch (shared
        # across all 256 rules — that IS the group win)
        slots, _ = kt.encode_column(batches[n % 4].columns["deviceId"])
        state = gb.fold(state, dict(cols[n % 4]), slots)
        rows += BATCH_ROWS
        n += 1
    outs, act = gb.finalize(state, kt.n_keys)  # one transfer for all rules
    elapsed = time.time() - t0
    assert outs[1].shape[0] == n_rules and np.all(act[0] >= act[-1])
    rule_rows = rows * n_rules / elapsed
    print(
        f"# 256-rule group: {rows:,} rows x {n_rules} rules in {elapsed:.2f}s"
        f" = {rule_rows:,.0f} rule-rows/s through one vmapped program"
        f" (reference fan-out baseline: 150,000 rule-msg/s)",
        file=sys.stderr,
    )
    record("homogeneous_256_vmapped", rule_rows_per_sec=rule_rows)


def _delivery_latency_line(issue_ts, deliver_ts) -> str:
    """issue→delivered stats for FIFO-paired async emissions. A delivery
    can legitimately be skipped (no active keys / empty projection); a
    skip would silently shift every later pair, so pairs are only trusted
    when the counts match — otherwise the skew is reported, not hidden."""
    k = min(len(issue_ts), len(deliver_ts))
    if not k:
        return "no triggers fired"
    skipped = len(issue_ts) - len(deliver_ts)
    e2e_ms = [(deliver_ts[i] - issue_ts[i][0]) * 1000 for i in range(k)]
    line = (f"issue→delivered p50={np.percentile(e2e_ms, 50):.0f}ms "
            f"p99={np.percentile(e2e_ms, 99):.0f}ms")
    if skipped > 0:
        line += f" (UNPAIRED: {skipped} skipped deliveries, stats skewed)"
    return line


def bench_sliding_percentile(batches, kt_slots) -> None:
    """BASELINE config #3: SLIDINGWINDOW percentile_approx over 10k keys on
    the device path — saturated ingest with sparse trigger rows (OVER WHEN),
    each emitting the exact (t-L, t] window via pane merge + edge refolds.
    Prints a stderr metric line."""
    import jax

    from ekuiper_tpu.data.batch import ColumnBatch
    from ekuiper_tpu.ops.aggspec import extract_kernel_plan
    from ekuiper_tpu.ops.emit import build_direct_emit
    from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode
    from ekuiper_tpu.sql.parser import parse_select
    from ekuiper_tpu.utils import timex

    sql = ("SELECT deviceId, percentile_approx(temperature, 0.99) AS p99, "
           "count(*) AS c FROM demo GROUP BY deviceId, "
           "SLIDINGWINDOW(ss, 10) OVER (WHEN temperature > 44.5)")
    stmt = parse_select(sql)
    plan = extract_kernel_plan(stmt)
    assert plan is not None, "sliding bench rule must be device-eligible"
    node = FusedWindowAggNode(
        "slide", stmt.window, plan, dims=[d.expr for d in stmt.dimensions],
        capacity=kt_slots, micro_batch=BATCH_ROWS,
        direct_emit=build_direct_emit(stmt, plan, ["deviceId"]),
        emit_columnar=True)
    node.state = node.gb.init_state()
    emits = []
    deliver_ts = []
    node.broadcast = lambda item: (emits.append(item),
                                   deliver_ts.append(time.time()))
    issue_ts = []
    orig_emit = node._emit_sliding

    def timed_emit(t):
        t0 = time.time()
        orig_emit(t)
        issue_ts.append((t0, (time.time() - t0) * 1000))

    node._emit_sliding = timed_emit

    def stamped(i, spike=False):
        b = batches[i % len(batches)]
        cols = b.columns
        if spike:  # one trigger row (>44.5 threshold): alert-style cadence
            t = cols["temperature"].copy()
            t[0] = 99.0
            cols = {"deviceId": cols["deviceId"], "temperature": t}
        return ColumnBatch(
            n=b.n, columns=cols,
            timestamps=np.full(b.n, timex.now_ms(), dtype=np.int64),
            emitter=b.emitter)

    # implementation-agnostic warmup: the node warms ITS trigger path —
    # ring advance/flip/query (+ the components_dyn fallback) under
    # slidingImpl=daba, fold_masked (the mask-only edge refold) under
    # refold — so neither round profiles or warms a dead kernel
    node._warmup()
    node.process(stamped(0))  # warm (vector+scalar folds, trigger path)
    node._emit_sliding(timex.now_ms())  # warm emission path
    node._drain_async_emits()
    jax.block_until_ready(node.state)
    print(f"# sliding implementation: {node.sliding_impl}",
          file=sys.stderr)
    # the sliding phase is WHERE the 865ms stalls lived (BENCH_r04) — run
    # it with dense device-timing sampling so kernel_split can decompose
    # every trigger's emission path (slidingring.query/advance/flip +
    # components_dyn on the DABA rounds; fold_masked / finalize_dyn /
    # components on refold rounds) into dispatch / compile /
    # device-compute / transfer — proving the finalize_dyn stall is gone
    # on the DABA path, not renamed. The probe starts AFTER warmup so
    # steady-state numbers aren't polluted by warmup compiles, but
    # mid-segment compiles (a real stall component) are counted
    from ekuiper_tpu.observability import kernwatch

    prior_sampling = kernwatch.set_sampling(hot=8, boundary=1)
    try:
        kernel_split = _kernel_split_probe()
        emits.clear()
        deliver_ts.clear()
        issue_ts.clear()
        rows = 0
        n = 0
        marker = None
        t0 = time.time()
        while time.time() - t0 < 12.0:
            node.process(stamped(n, spike=(n % 40 == 39)))
            rows += BATCH_ROWS
            n += 1
            if n % T_BLOCK_EVERY == 0:
                _block_marker(marker)
                marker = node.state["act"][:1]  # non-donated slice
        node._drain_async_emits()
        jax.block_until_ready(node.state)
        elapsed = time.time() - t0
        # trigger emissions deliver via the emit worker: report BOTH the fold
        # stall (time the trigger spends in the fold stream — the dispatch) and
        # the issue->delivered latency the sink observes
        if issue_ts:
            stall_ms = [d for _, d in issue_ts]
            lat = (f"fold stall p50={np.percentile(stall_ms, 50):.1f}ms "
                   f"max={max(stall_ms):.0f}ms; "
                   + _delivery_latency_line(issue_ts, deliver_ts))
        else:
            lat = "no triggers fired"
        print(
            f"# sliding percentile (10s window, 10k keys, device path): "
            f"{rows:,} rows in {elapsed:.2f}s ({rows / elapsed:,.0f} rows/s), "
            f"{len(issue_ts)} trigger emissions, {lat}",
            file=sys.stderr,
        )
        k = min(len(issue_ts), len(deliver_ts))
        e2e = [(deliver_ts[i] - issue_ts[i][0]) * 1000 for i in range(k)]
        record("sliding_saturated", rows_per_sec=rows / elapsed,
               triggers=len(issue_ts),
               sliding_impl=node.sliding_impl,
               fold_stall_p50_ms=float(np.percentile(
                   [d for _, d in issue_ts], 50)) if issue_ts else None,
               fold_stall_max_ms=float(max(d for _, d in issue_ts))
               if issue_ts else None,
               deliver_p50_ms=float(np.percentile(e2e, 50)) if k else None,
               # HEADLINE (tools/benchdiff.py): trigger→sink emit tail —
               # a sliding-latency regression gates ci_gate every round
               emit_p99_ms=float(np.percentile(e2e, 99)) if k else None,
               kernel_split=kernel_split(),
               jitcert=_jitcert_fields())
        # paced segment (phase-L analogue): at sustainable load the delivery
        # latency is what a sink actually observes — the saturated segment
        # above queues the finalize behind ~16 in-flight fold dispatches
        kernel_split = _kernel_split_probe()  # fresh deltas for this segment
        emits.clear()
        deliver_ts.clear()
        issue_ts.clear()
        interval = BATCH_ROWS / 1_000_000  # pace at 1M rows/s
        rows = 0
        n = 0
        t0 = time.time()
        while time.time() - t0 < 8.0:
            target = t0 + n * interval
            delay = target - time.time()
            if delay > 0:
                time.sleep(delay)
            node.process(stamped(n, spike=(n % 5 == 4)))
            rows += BATCH_ROWS
            n += 1
        node._drain_async_emits()
        jax.block_until_ready(node.state)
        elapsed = time.time() - t0
        print(
            f"# sliding percentile paced (1.0M rows/s): {rows:,} rows in "
            f"{elapsed:.2f}s ({rows / elapsed:,.0f} rows/s), {len(issue_ts)} "
            f"trigger emissions, "
            f"{_delivery_latency_line(issue_ts, deliver_ts)}",
            file=sys.stderr,
        )
        k = min(len(issue_ts), len(deliver_ts))
        e2e = [(deliver_ts[i] - issue_ts[i][0]) * 1000 for i in range(k)]
        record("sliding_paced", rows_per_sec=rows / elapsed,
               triggers=len(issue_ts),
               sliding_impl=node.sliding_impl,
               fold_stall_p50_ms=float(np.percentile(
                   [d for _, d in issue_ts], 50)) if issue_ts else None,
               fold_stall_max_ms=float(max(d for _, d in issue_ts))
               if issue_ts else None,
               deliver_p50_ms=float(np.percentile(e2e, 50)) if k else None,
               # deliver_p99_ms keeps r01-r05 trajectory continuity and
               # stays report-only; emit_p99_ms is the SAME quantity under
               # the gated name (HEADLINE twin of sliding_saturated)
               deliver_p99_ms=float(np.percentile(e2e, 99)) if k else None,
               emit_p99_ms=float(np.percentile(e2e, 99)) if k else None,
               kernel_split=kernel_split(),
               jitcert=_jitcert_fields())
    finally:
        # dense sampling must not leak into later phases even if a
        # segment dies mid-run
        kernwatch.set_sampling(**prior_sampling)


def bench_hopping_heavy_hitters(batches, kt_slots) -> None:
    """BASELINE config #2: HOPPINGWINDOW GROUP BY device_id over 10k
    sensors with the count-min heavy-hitters UDF on the fused device path
    (linear group-testing sketch, device-side candidate recovery + top-k).
    Prints a stderr metric line."""
    import jax

    from ekuiper_tpu.data.batch import ColumnBatch
    from ekuiper_tpu.data.rows import WindowRange
    from ekuiper_tpu.ops.aggspec import extract_kernel_plan
    from ekuiper_tpu.ops.emit import build_direct_emit
    from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode
    from ekuiper_tpu.sql.parser import parse_select

    sql = ("SELECT deviceId, heavy_hitters(code, 3) AS top, count(*) AS c "
           "FROM demo GROUP BY deviceId, HOPPINGWINDOW(ss, 10, 5)")
    stmt = parse_select(sql)
    plan = extract_kernel_plan(stmt)
    assert plan is not None, "hh bench rule must be device-eligible"
    node = FusedWindowAggNode(
        "hh", stmt.window, plan, dims=[d.expr for d in stmt.dimensions],
        capacity=kt_slots, micro_batch=BATCH_ROWS,
        direct_emit=build_direct_emit(stmt, plan, ["deviceId"]),
        emit_columnar=True)
    node.state = node.gb.init_state()
    emits = []  # (ColumnBatch, emit_info) from the async worker
    node.broadcast = lambda item: emits.append((item, node.last_emit_info))
    # skewed event codes: 3 heavy values + a 2000-distinct tail
    rng = np.random.default_rng(7)
    hh_batches = []
    for b in batches:
        p = rng.random(b.n)
        code = np.where(
            p < 0.35, 7, np.where(p < 0.55, 13, np.where(
                p < 0.70, 99, rng.integers(100, 2100, b.n)))).astype(np.int64)
        hh_batches.append(ColumnBatch(
            n=b.n, columns={"deviceId": b.columns["deviceId"], "code": code},
            timestamps=b.timestamps, emitter=b.emitter))

    def boundary(end_ms):
        # async hh boundary: dispatch + rotate, delivery on the worker
        t0 = time.time()
        node._emit_hh_async(WindowRange(end_ms - 10_000, end_ms))
        ms = (time.time() - t0) * 1000
        node.cur_pane = (node.cur_pane + 1) % node.n_panes
        node.state = node.gb.reset_pane(node.state, node.cur_pane)
        return ms

    node.process(hh_batches[0])  # warm fold
    boundary(5_000)  # warm compact hh finalize
    node._drain_async_emits()
    jax.block_until_ready(node.state)
    emits.clear()
    rows = 0
    n = 0
    emit_ms = []
    # paced at the north-star load: boundary fetches queue FIFO behind
    # in-flight folds, so emit latency is only meaningful when the link
    # has headroom
    interval = BATCH_ROWS / 1_100_000
    t0 = time.time()
    while time.time() - t0 < 10.0:
        target = t0 + n * interval
        delay = target - time.time()
        if delay > 0:
            time.sleep(delay)
        node.process(hh_batches[n % len(hh_batches)])
        rows += BATCH_ROWS
        n += 1
        if n % 16 == 0:  # one hop boundary per ~16 batches (~1s)
            emit_ms.append(boundary(5_000 * (n // 16 + 1)))
    node._drain_async_emits()
    jax.block_until_ready(node.state)
    elapsed = time.time() - t0
    # sanity: the heaviest value must lead every emitted top list
    top_col = emits[0][0].columns["top"]
    assert top_col[0][0]["value"] == 7, f"bad top list: {top_col[0]}"
    deliv = [i["fetch_ms"] for _, i in emits if i]
    lat = (f"boundary dispatch p50={np.percentile(emit_ms, 50):.1f}ms, "
           f"issue→delivered p50={np.percentile(deliv, 50):.0f}ms"
           if emit_ms and deliv else "no boundaries")
    print(
        f"# hopping heavy-hitters (10s/5s, 10k keys, count-min device "
        f"sketch): {rows:,} rows in {elapsed:.2f}s "
        f"({rows / elapsed:,.0f} rows/s), {len(emits)} window emits, {lat}",
        file=sys.stderr,
    )
    record("hopping_heavy_hitters", rows_per_sec=rows / elapsed,
           emits=len(emits),
           dispatch_p50_ms=float(np.percentile(emit_ms, 50))
           if emit_ms else None,
           deliver_p50_ms=float(np.percentile(deliv, 50))
           if deliv else None)


def bench_countwindow_hll_1m(kt_slots) -> None:
    """BASELINE config #4: COUNTWINDOW HyperLogLog distinct-count with 1M-key
    GROUP BY cardinality — stresses KeyTable growth to >=1M slots, on-device
    state doubling, and the wide-register HLL fold at HBM scale.
    Prints a stderr metric line."""
    import jax

    from ekuiper_tpu.data.batch import ColumnBatch
    from ekuiper_tpu.data.rows import WindowRange
    from ekuiper_tpu.ops.aggspec import extract_kernel_plan
    from ekuiper_tpu.ops.emit import build_direct_emit
    from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode
    from ekuiper_tpu.sql.parser import parse_select

    n_keys_total = 1_000_000
    window_rows = 2_097_152  # 32 batches per count window
    sql = (f"SELECT deviceId, hll(uid) AS uniq FROM demo "
           f"GROUP BY deviceId, COUNTWINDOW({window_rows})")
    stmt = parse_select(sql)
    plan = extract_kernel_plan(stmt)
    assert plan is not None, "hll bench rule must be device-eligible"
    # pre-sized hash-slot table (SURVEY §7 hard-part c): growing 16k->1M
    # re-specializes the fold executable per doubling (~6 recompiles), so a
    # known-cardinality rule sizes up front; the grow path itself is covered
    # by tests (test_groupby.py grow + test_heavy_hitters device grows)
    node = FusedWindowAggNode(
        "hll1m", stmt.window, plan, dims=[d.expr for d in stmt.dimensions],
        capacity=1 << 20, micro_batch=BATCH_ROWS,
        direct_emit=build_direct_emit(stmt, plan, ["deviceId"]),
        emit_columnar=True)
    node.state = node.gb.init_state()
    emits = []  # (ColumnBatch, emit_info) pairs from the async worker
    node.broadcast = lambda item: emits.append((item, node.last_emit_info))
    rng = np.random.default_rng(11)
    ids = np.array([f"dev_{i}" for i in range(n_keys_total)], dtype=np.object_)
    # one full count-window of DISTINCT batches (32 x 64k draws ≈ 878k
    # distinct keys of the 1M id space) — recycling fewer batches would cap
    # the key cardinality the bench claims to stress
    hll_batches = []
    for _ in range(window_rows // BATCH_ROWS):
        idx = rng.integers(0, n_keys_total, BATCH_ROWS)
        hll_batches.append(ColumnBatch(
            n=BATCH_ROWS,
            columns={"deviceId": ids[idx],
                     "uid": rng.integers(0, 5_000_000, BATCH_ROWS)},
            timestamps=np.zeros(BATCH_ROWS, dtype=np.int64), emitter="demo"))
    node.process(hll_batches[0])  # warm fold (1M-slot executable)
    node._emit(WindowRange(0, 0))  # warm finalize + emit tail executables
    node.state = node.gb.reset_pane(node.state, 0)
    node.kt.clear()
    node._rows_in_window = 0
    jax.block_until_ready(node.state)
    emits.clear()

    def run_windows(k: int):
        rows = n = 0
        marker = None
        want = len(emits) + k
        t0 = time.time()
        while time.time() - t0 < 60.0 and len(emits) < want:
            node.process(hll_batches[n % len(hll_batches)])
            rows += BATCH_ROWS
            n += 1
            if n % T_BLOCK_EVERY == 0:
                _block_marker(marker)
                marker = node.state["act"][:1]  # non-donated slice
        node._drain_async_emits()
        jax.block_until_ready(node.state)
        return rows, time.time() - t0

    # window 1: cold dictionary — every batch inserts new keys
    cold_rows, cold_s = run_windows(1)
    # windows 2-3: steady state — keys known, pure fold + async emit cadence
    warm_rows, warm_s = run_windows(2)
    state_gb = sum(
        np.prod(v.shape) * 4 for v in node.state.values()) / 1e9
    fetch_ms = [i["fetch_ms"] for _, i in emits if i]
    lat = (f"async emit issue→delivered p50={np.percentile(fetch_ms, 50):.0f}ms"
           if fetch_ms else "no window completed")
    # sanity on the last emit: ~full key coverage, sane per-key estimates
    if emits:
        uniq = emits[-1][0].columns["uniq"]
        assert len(uniq) > 800_000 and 0 < np.median(uniq) < 50, \
            f"bad hll emit: {len(uniq):,} groups, median {np.median(uniq)}"
    print(
        f"# countwindow hll @1M keys: steady {warm_rows:,} rows in "
        f"{warm_s:.2f}s ({warm_rows / max(warm_s, 1e-9):,.0f} rows/s; "
        f"cold-dictionary window {cold_rows / max(cold_s, 1e-9):,.0f} "
        f"rows/s), keys={node.kt.n_keys:,} in {node.gb.capacity:,} device "
        f"slots, state={state_gb:.2f}GB, {len(emits)} count-window "
        f"emits (device-async), {lat}",
        file=sys.stderr,
    )
    record("countwindow_hll_1m",
           steady_rows_per_sec=warm_rows / max(warm_s, 1e-9),
           cold_rows_per_sec=cold_rows / max(cold_s, 1e-9),
           keys=node.kt.n_keys, slots=node.gb.capacity,
           state_gb=round(state_gb, 2), emits=len(emits),
           deliver_p50_ms=float(np.percentile(fetch_ms, 50))
           if fetch_ms else None)

    # capacity headroom (VERDICT r4 weak #6): push past the pre-sized 1M
    # slots to ~1.5M-key cardinality — KeyTable doubles and the device
    # state grows MID-STREAM (one fold re-specialization at the new
    # capacity); the window must complete with no overflow and full key
    # coverage. Reported separately: the one-off grow compile is a
    # capacity event, not steady-state throughput.
    grow_ids = np.array(
        [f"dev_{i}" for i in range(1_500_000)], dtype=np.object_)
    slots_before = node.gb.capacity
    emits_before = len(emits)
    grow_batches = []
    # TWO full windows: async emit timing can leave a partial window open
    # entering this segment, so only the second window's emit is guaranteed
    # to cover a pure grow-space row range
    for _ in range(2 * (window_rows // BATCH_ROWS)):
        idx = rng.integers(0, 1_500_000, BATCH_ROWS)
        grow_batches.append(ColumnBatch(
            n=BATCH_ROWS,
            columns={"deviceId": grow_ids[idx],
                     "uid": rng.integers(0, 5_000_000, BATCH_ROWS)},
            timestamps=np.zeros(BATCH_ROWS, dtype=np.int64),
            emitter="demo"))
    t0 = time.time()
    for b in grow_batches:
        node.process(b)
    node._drain_async_emits()
    jax.block_until_ready(node.state)
    grow_s = time.time() - t0
    assert node.kt.n_keys > 1_100_000, \
        f"grow segment covered only {node.kt.n_keys:,} keys"
    assert node.gb.capacity > slots_before, "state never grew past 1M slots"
    assert node.kt.n_keys <= node.gb.capacity, "slot-table overflow"
    assert len(emits) > emits_before, "grow window never emitted"
    uniq = emits[-1][0].columns["uniq"]
    assert len(uniq) > 1_100_000, f"grow emit covered {len(uniq):,} groups"
    grow_rows = 2 * window_rows
    print(
        f"# hll capacity grow: {node.kt.n_keys:,} keys grew device slots "
        f"{slots_before:,} -> {node.gb.capacity:,} mid-stream; "
        f"{grow_rows:,} rows in {grow_s:.2f}s "
        f"({grow_rows / grow_s:,.0f} rows/s incl. the one-off grow "
        f"recompile), emit covered {len(uniq):,} groups",
        file=sys.stderr,
    )
    record("hll_capacity_grow", keys=node.kt.n_keys,
           slots=node.gb.capacity, slots_before=slots_before,
           rows_per_sec_incl_recompile=grow_rows / grow_s)


def bench_key_cardinality(kt_slots, budget_s: float = 240.0) -> None:
    """ISSUE 13 phase: distinct-key cardinality 1M -> 10M (attempted)
    under a FIXED HBM budget, with the tiered key state
    (ops/tierstore.py) absorbing the overflow — a hot core keeps its
    dense device slots while a marching cold tail demotes to the host
    arena and its slots recycle. Records rows/s, emit p99, spill/promote
    rates, and the device-slot ceiling per cardinality checkpoint, plus
    a sub-budget byte-parity segment vs the untiered path."""
    import jax

    from ekuiper_tpu.data.batch import ColumnBatch
    from ekuiper_tpu.ops.aggspec import extract_kernel_plan
    from ekuiper_tpu.ops.emit import build_direct_emit
    from ekuiper_tpu.runtime.events import Trigger
    from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode
    from ekuiper_tpu.sql.parser import parse_select

    from ekuiper_tpu.ops.tierstore import env_hbm_budget_mb

    budget_mb = env_hbm_budget_mb() or 64.0
    sql = ("SELECT deviceId, sum(v) AS s, count(*) AS c FROM demo "
           "GROUP BY deviceId, TUMBLINGWINDOW(ss, 1)")
    stmt = parse_select(sql)
    plan = extract_kernel_plan(stmt)
    assert plan is not None

    def mk(tier_mb, capacity):
        n = FusedWindowAggNode(
            "keycard", stmt.window, plan,
            dims=[d.expr for d in stmt.dimensions],
            capacity=capacity, micro_batch=BATCH_ROWS,
            direct_emit=build_direct_emit(stmt, plan, ["deviceId"]),
            emit_columnar=True, prefinalize_lead_ms=0,
            tier_budget_mb=tier_mb, tier_scan_ms=1)
        n.state = n.gb.init_state()
        return n

    # ---- sub-budget byte-parity segment: tier ENGAGED but cardinality
    # below the hot target — emissions must be byte-identical to the
    # untiered path (acceptance gate)
    par_t, par_p = mk(0.01, 4096), mk(0.0, 4096)
    pe_t, pe_p = [], []
    par_t.broadcast = lambda item: pe_t.append(item)
    par_p.broadcast = lambda item: pe_p.append(item)
    rng = np.random.default_rng(13)
    par_ids = np.array([f"p{i}" for i in range(1000)], dtype=np.object_)
    for w in range(3):
        idx = rng.integers(0, 1000, 8192)
        vals = rng.normal(50, 10, 8192)
        for n in (par_t, par_p):
            n.process(ColumnBatch(
                n=8192, columns={"deviceId": par_ids[idx].copy(),
                                 "v": vals.copy()},
                timestamps=np.zeros(8192, dtype=np.int64),
                emitter="demo"))
            n.on_trigger(Trigger(ts=(w + 1) * 1000))
    for n in (par_t, par_p):
        n._drain_async_emits()

    def _rows(emits):
        out = []
        for cb in emits:
            cols = getattr(cb, "columns", None)
            if cols is None:
                continue
            out.append({k: np.asarray(v).tobytes()
                        if np.asarray(v).dtype != np.object_
                        else tuple(v) for k, v in sorted(cols.items())})
        return out

    parity = (par_t.tier is not None and _rows(pe_t) == _rows(pe_p))

    # ---- cardinality sweep under the fixed budget
    node = mk(budget_mb, 1 << 20)
    assert node.tier is not None, "tier must engage for the sweep"
    emits = []
    t_bound = [0.0]
    node.broadcast = lambda item: emits.append(
        (time.perf_counter() - t_bound[0]) * 1000.0)
    hot_n = 1 << 18
    fresh_per_batch = 2048
    hot_ids = np.array([f"hot_{i}" for i in range(hot_n)],
                       dtype=np.object_)
    targets = [1_000_000, 3_000_000, 10_000_000]
    checkpoints = {}
    fresh_cursor = 0
    rows = 0
    wn = 0
    t0 = time.time()
    deadline = t0 + budget_s
    seg_t0, seg_rows = t0, 0
    marker = None
    nb = 0
    while targets and time.time() < deadline:
        idx = rng.integers(0, hot_n, BATCH_ROWS - fresh_per_batch)
        fresh = np.array(
            [f"k{fresh_cursor + i}" for i in range(fresh_per_batch)],
            dtype=np.object_)
        fresh_cursor += fresh_per_batch
        ids = np.concatenate([hot_ids[idx], fresh])
        node.process(ColumnBatch(
            n=BATCH_ROWS,
            columns={"deviceId": ids,
                     "v": rng.normal(50, 10, BATCH_ROWS)},
            timestamps=np.zeros(BATCH_ROWS, dtype=np.int64),
            emitter="demo"))
        rows += BATCH_ROWS
        seg_rows += BATCH_ROWS
        nb += 1
        if nb % 4 == 0:
            wn += 1
            t_bound[0] = time.perf_counter()
            node.on_trigger(Trigger(ts=wn * 1000))
            _block_marker(marker)
            marker = node.state["act"][:1]
        total_distinct = hot_n + fresh_cursor
        if total_distinct >= targets[0]:
            node._drain_async_emits()
            jax.block_until_ready(node.state)
            seg_s = max(time.time() - seg_t0, 1e-9)
            t = node.tier
            checkpoints[str(targets[0])] = {
                "rows_per_sec": seg_rows / seg_s,
                "emit_p99_ms": (float(np.percentile(emits, 99))
                                if emits else None),
                "device_slots": node.gb.capacity,
                "resident_cold": len(t.store),
                "tier_host_mb": round(t.store.nbytes() / 2**20, 1),
                "demoted_total": t.demoted_total,
                "promoted_total": t.promoted_total,
                "spill_per_sec": round(t.demoted_total / seg_s, 1),
            }
            targets.pop(0)
            seg_t0, seg_rows = time.time(), 0
    node._drain_async_emits()
    jax.block_until_ready(node.state)
    total_s = time.time() - t0
    t = node.tier
    keys_reached = hot_n + fresh_cursor
    dev_state_mb = sum(
        int(getattr(a, "nbytes", 0) or 0)
        for a in node.state.values()) / 2**20
    print(
        f"# key_cardinality: {keys_reached:,} distinct keys attempted "
        f"({len(checkpoints)} checkpoints) under {budget_mb:.0f}MB budget "
        f"in {total_s:.1f}s — {rows / max(total_s, 1e-9):,.0f} rows/s, "
        f"device slots {node.gb.capacity:,} ({dev_state_mb:.1f}MB state), "
        f"{t.demoted_total:,} demoted / {t.promoted_total:,} promoted / "
        f"{t.recycled_total:,} recycled, cold-resident {len(t.store):,} "
        f"({t.store.nbytes() / 2**20:.1f}MB host), parity={parity}",
        file=sys.stderr,
    )
    record("key_cardinality",
           keys_reached=keys_reached,
           rows_per_sec=rows / max(total_s, 1e-9),
           emit_p99_ms=(float(np.percentile(emits, 99))
                        if emits else None),
           device_slots=node.gb.capacity,
           device_state_mb=round(dev_state_mb, 1),
           budget_mb=budget_mb,
           demoted_total=t.demoted_total,
           promoted_total=t.promoted_total,
           recycled_total=t.recycled_total,
           resident_cold=len(t.store),
           tier_host_mb=round(t.store.nbytes() / 2**20, 1),
           subbudget_parity=bool(parity),
           checkpoints=checkpoints)
    assert parity, "tiered emissions diverged from untiered at " \
                   "sub-budget cardinality"


def _harvest_phase_stderr(stderr, tag: str) -> bool:
    """Re-parse a phase subprocess's stderr: merge its `#R ` record lines
    into RESULTS (so PARTIAL progress survives a timeout/kill) and relay
    its human `# ` lines. Returns True when the phase's own metric line
    made it out."""
    if isinstance(stderr, bytes):
        stderr = stderr.decode(errors="replace")
    lines = (stderr or "").splitlines()
    for line in lines:
        if line.startswith("#R "):
            try:
                RESULTS.update(json.loads(line[3:]))
            except ValueError:
                pass
        elif line.startswith("# "):
            print(line, file=sys.stderr)
    return any(line.startswith(f"# {tag}") for line in lines)


def _run_isolated(func: str, tag: str, timeout: float = 900) -> None:
    """Run a bench phase in a subprocess: phases that open+close threaded
    topos against the TPU can intermittently crash native client
    teardown at exit — isolation keeps the headline bench process alive.

    The subprocess rides the same per-phase watchdog discipline as the
    in-process phases (r05 post-mortem: _full_pipe_main got the whole 900s
    driver budget, so the DRIVER timed out first and nothing was
    recorded): its timeout is capped by the remaining global budget, the
    child arms its own watchdog (BENCH_CHILD_BUDGET_S) so it dies with
    its partial records flushed, and a parent-side TimeoutExpired still
    harvests whatever `#R ` lines the child printed before the kill."""
    import subprocess

    timeout = phase_budget(timeout, reserve_s=20.0,
                           later_floor_s=later_floor(tag))
    if timeout < 30.0:
        print(f"# {tag}: skipped — {_remaining_s():.0f}s of global budget "
              "left", file=sys.stderr)
        RESULTS[f"{tag}_error"] = "skipped: global budget exhausted"
        return
    env = dict(os.environ)
    env["BENCH_CHILD_BUDGET_S"] = str(int(max(timeout - 15.0, 15.0)))
    try:
        r = subprocess.run(
            [sys.executable, "-c", f"import bench; bench.{func}()"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, timeout=timeout, text=True, env=env)
        if not _harvest_phase_stderr(r.stderr, tag):
            print(f"# {tag}: subprocess failed rc={r.returncode}",
                  file=sys.stderr)
            RESULTS.setdefault(f"{tag}_error", f"subprocess rc={r.returncode}")
    except subprocess.TimeoutExpired as exc:
        # partial per-phase records STILL land in the artifact
        _harvest_phase_stderr(exc.stderr, tag)
        print(f"# {tag}: subprocess timed out after {timeout:.0f}s "
              "(partial records harvested)", file=sys.stderr)
        RESULTS[f"{tag}_error"] = f"timeout after {timeout:.0f}s"
    except Exception as exc:
        print(f"# {tag}: {exc}", file=sys.stderr)
        RESULTS[f"{tag}_error"] = str(exc)


def bench_churn_soak() -> None:
    _run_isolated("_churn_soak_main", "churn_soak", timeout=600)


def _churn_soak_main() -> None:
    """Sustained-churn QoS soak (ISSUE 9): an in-process engine under
    rule create/update/delete churn, hot-key skew shifts, backpressure
    waves, and a mid-storm kill/restore — while the health plane +
    runtime/control.py close the loop. Green means: every dropped row
    carries a taxonomy reason, the breaching victim rule is shed by qos
    class while the healthy workload rules hold their emit p99, and
    admission rejections come back structured (reason + price).

    Runs on CPU jax (forced below): the phase measures the CONTROL
    plane, not device throughput, and the parent bench process may
    still own the TPU client."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    # fast control cadence: both intervals are read at module import,
    # which happens below — this subprocess is fresh
    # SUB-SECOND health cadence, below the 1s workload window: the burn
    # windows are sample-count-aware now (observability/health.py
    # _weighted_burn + observation-indexed decay), so a tick landing
    # between two window emissions holds its evidence instead of
    # decaying to zero and flapping the verdict — the 1500ms pin this
    # phase used to need is exactly the flap this soak now regresses
    os.environ.setdefault("KUIPER_HEALTH_INTERVAL_MS", "900")
    os.environ.setdefault("KUIPER_CONTROL_INTERVAL_MS", "500")
    child_budget = float(os.environ.get("BENCH_CHILD_BUDGET_S", "0") or 0)
    dog = PhaseWatchdog()
    if child_budget > 0:
        dog.arm("churn_soak_child", child_budget)
    from ekuiper_tpu.io import memory as mem
    from ekuiper_tpu.server.rest import RestApi
    from ekuiper_tpu.store import kv
    from tools.chaos import ChaosHarness

    mem.reset()
    api = RestApi(kv.get_store())
    # pool=2: the device-path rules ride POOLED sources so the storm
    # drives the decode pool + ingest ring end-to-end and the autosize
    # actuator has something real to resize (inline memory sources are
    # contractually never converted — the old soak could not see a
    # single autosize event)
    h = ChaosHarness(api, pool=2)
    h.ensure_stream()
    work = h.workload_rules(4, window_s=1, slo_p99_ms=5000)
    victim = h.victim_rule()
    ck = h.checkpoint_rule()
    # soak window: bounded by the child budget minus teardown headroom
    soak_s = 70.0
    if child_budget > 0:
        soak_s = min(soak_s, max(child_budget - 25.0, 20.0))
    t0 = time.time()
    deadline = t0 + soak_s
    kill_at = t0 + soak_s * 0.55
    next_wave = t0 + 10.0
    next_progress = t0 + 10.0
    hot, rows = 0, 0
    last_shift = t0
    recover_stats: dict = {}
    killed = False
    # fleet observatory duty cycle under churn: observe() + full-scrape
    # timeline snapshot at the production default 5s cadence inside the
    # soak, so observatory_overhead_pct is measured against a live
    # 25-rule fleet (the rules here are single-chip, so skew/collective
    # read ~0 — the leaves exist report-only for trajectory tracking)
    from ekuiper_tpu.observability import meshwatch as _meshwatch
    obs_s = 0.0
    next_obs = t0 + 5.0
    # offered load calibrated to keep the HEALTHY fleet comfortably
    # inside its SLO on one CPU: the soak demonstrates per-rule
    # isolation (victim shed, workload holds), not saturation collapse
    # — the waves are what push individual rules over
    while time.time() < deadline:
        h.churn_step(target_live=25)
        h.publish_skew(1000, hot_key=hot)
        rows += 1000
        now = time.time()
        if now - last_shift >= 7.0:
            # ONE discrete skew shift per interval — a per-iteration
            # modulo test would re-shift ~30x during each 7th second
            # and turn the hot key into uniform noise
            hot = (hot + 31) % 256
            last_shift = now
        if now >= next_wave:
            h.backpressure_wave(8_000)
            rows += 8_000
            next_wave = now + 10.0
        if not killed and now >= kill_at:
            # checkpoint, then crash — recovery must come from the
            # barrier snapshot, not a graceful stop-time save
            rs = api.rules.state(ck)
            if rs is not None and rs.topo is not None:
                rs.topo.trigger_checkpoint()
                time.sleep(0.5)
            running = h.hard_kill()
            recover_stats = h.recover(running)
            killed = True
        if now >= next_obs:
            # thread CPU time, not wall: on a saturated box a wall
            # clock mostly measures GIL contention with the workload,
            # not what the observatory itself costs
            ot = time.thread_time()
            _meshwatch.observe()
            if api.timeline is not None:
                api.timeline.snapshot()
            obs_s += time.thread_time() - ot
            next_obs = now + 5.0
        if now >= next_progress:
            # partial progress survives a watchdog/timeout kill as a
            # harvested `#R ` line (the r05 rc=124 class)
            s = h.summary()
            record("churn_soak_progress",
                   elapsed_s=now - t0, rows_published=rows,
                   created=s["churn"]["created"],
                   deleted=s["churn"]["deleted"],
                   live_rules=s["live_rules"],
                   shed_rows=sum(
                       int(v) for v in (s.get("shed_totals") or {})
                       .values()),
                   unexplained=len(s["unexplained_drops"]))
            next_progress = now + 10.0
        time.sleep(0.03)
    # structured-admission probe: under a tight fold budget a fat device
    # rule must come back 429 with reason + price, not an exception
    os.environ["KUIPER_ADMISSION_FOLD_BUDGET_US_PER_S"] = "1"
    try:
        code, out = api.dispatch("POST", "/rules", {
            "id": "chaos_fat",
            "sql": ("SELECT deviceId, avg(v) AS a, min(v) AS mn, "
                    "max(v) AS mx FROM chaos GROUP BY deviceId, "
                    "TUMBLINGWINDOW(ss, 5)"),
            "actions": [{"nop": {}}],
            "options": {"sharedFold": False}}, {})
        adm = (out or {}).get("admission") or {}
        admission_structured = (code == 429 and bool(adm.get("reason"))
                               and "fold_us_per_s" in (adm.get("price")
                                                       or {}))
    finally:
        del os.environ["KUIPER_ADMISSION_FOLD_BUDGET_US_PER_S"]
    elapsed = time.time() - t0
    # settle, then judge
    time.sleep(1.0)
    s = h.summary()
    p99 = h.e2e_p99_ms(work)
    victim_shed = sum(n for (rid, qos), n
                      in (api.qos_controller.shed_totals().items())
                      if rid == victim and qos == "low")
    soak_p99 = max(p99.values()) if p99 else float("nan")
    workload_ok = bool(p99) and all(v <= 5000.0 for v in p99.values())
    mrep = _meshwatch.observe()
    msplit = _meshwatch.collective_split()
    soak_skew = max((e["skew_ratio"] or 0.0 for e in mrep.values()),
                    default=0.0)
    mcoll = sorted(v["collective_us"] / 1000.0
                   for (op, _), v in msplit.items() if "fold" in str(op))
    print(f"# churn_soak: {rows:,} rows over {elapsed:.1f}s; "
          f"churn {s['churn']}; live={s['live_rules']}; "
          f"workload p99 {p99}; victim shed {victim_shed} rows; "
          f"shed totals {s.get('shed_totals')}; "
          f"victim health "
          f"{(api.health_evaluator.verdicts().get(victim) or {}).get('state')}; "
          f"admission {s.get('admission')}; "
          f"unexplained drops {s['unexplained_drops']}; "
          f"recover {recover_stats}", file=sys.stderr)
    record("churn_soak",
           soak_p99_ms=soak_p99,
           rows_published=rows,
           rules_created=s["churn"]["created"],
           rules_updated=s["churn"]["updated"],
           rules_deleted=s["churn"]["deleted"],
           admission_rejects=(s.get("admission") or {}).get("reject", 0),
           admission_queued=(s.get("admission") or {}).get("queue", 0),
           victim_shed_rows=victim_shed,
           victim_shed_ok=victim_shed > 0,
           workload_slo_ok=workload_ok,
           unexplained_drop_rules=len(s["unexplained_drops"]),
           zero_unexplained=not s["unexplained_drops"],
           admission_structured=admission_structured,
           skew_ratio=soak_skew,
           collective_ms_p50=(mcoll[len(mcoll) // 2] if mcoll else 0.0),
           observatory_overhead_pct=(100.0 * obs_s / elapsed
                                     if elapsed > 0 else 0.0),
           recovered=recover_stats.get("recovered", 0),
           recover_expected=recover_stats.get("expected", 0),
           pooled_sources=True,
           autosize_events=s.get("autosize_events", 0),
           # the actions themselves (node, grow/shrink, applied sizes):
           # the evidence the autosize path actually ran end-to-end
           autosize_actions=[
               {k: v for k, v in a.items() if k != "ts_ms"}
               for a in ((api.qos_controller.diagnostics()
                          .get("autosize") or {}).get("recent") or [])
           ][-8:],
           # churn keeps re-planning rules over the same certified
           # signature set: compile_total staying flat (vs rules_created
           # growing) is the AOT cache's zero-compile-churn claim
           compile_total=_compile_total(),
           aot=_aot_fields())
    dog.disarm()
    # daemon node threads + live jax state can segfault interpreter
    # teardown; the records are flushed — exit hard (kuiperdiag
    # --smoke precedent)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)


def bench_multichip_full_pipe() -> None:
    _run_isolated("_multichip_full_pipe_main", "multichip_full_pipe",
                  timeout=600)


def _multichip_full_pipe_main() -> None:
    """Multi-chip sharded serving phase (ISSUE 15): the saturated
    tumbling full pipe (json bytes → decode pool → fused window) run
    twice through the REAL planned topo — single-chip, then key-range
    sharded across an N-device mesh (`KUIPER_MESH`, planner
    `shards=auto`) — recording rows/s for both, the scaling ratio,
    per-shard fold rows, emit p99, a direct-kernel window-parity check,
    and jitcert.clean. `phases.multichip_full_pipe.rows_per_sec` gates
    in benchdiff's HEADLINE every round, replacing the dryrun.

    Devices: the CPU host-device emulation CI uses
    (`--xla_force_host_platform_device_count`), always — this child runs
    while the parent holds the chip. Scaling is a HARDWARE criterion that
    virtual CPU devices sharing the host's cores cannot judge; the sharded
    plan on real chips is `chip_smoke.py --chips 4`."""
    import json as _json

    n_dev = int(os.environ.get("BENCH_MULTICHIP_DEVICES", "8") or 8)
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={n_dev}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    import jax

    jax.config.update("jax_platforms", "cpu")
    n_dev = min(n_dev, len(jax.devices()))
    child_budget = float(os.environ.get("BENCH_CHILD_BUDGET_S", "0") or 0)
    dog = PhaseWatchdog()
    if child_budget > 0:
        dog.arm("multichip_child", child_budget)
    from ekuiper_tpu.io import memory as mem
    from ekuiper_tpu.observability import jitcert
    from ekuiper_tpu.planner.planner import RuleDef, plan_rule
    from ekuiper_tpu.server.processors import StreamProcessor
    from ekuiper_tpu.store import kv

    # CPU host-device emulation pays every shard's fold on the same
    # shared cores, so the full-size workload cannot finish two legs +
    # parity inside the phase floor: rows, key universe and per-fold
    # state are sized for the emulated run (universe ~85% of the slot
    # table so the key-range partition still engages nearly every shard
    # of the virtual mesh)
    key_universe = 3_500
    drain_rows = 1024
    mb_rows = 8192
    slots = 4096
    rng = np.random.default_rng(29)
    drains = []
    for _ in range(8):
        drains.append([
            _json.dumps({
                "deviceId": f"dev_{rng.integers(0, key_universe)}",
                "temperature": round(float(rng.normal(20, 5)), 2),
            }).encode()
            for _ in range(drain_rows)
        ])

    seg_s = 8.0
    if child_budget > 0:
        seg_s = min(seg_s, max((child_budget - 60.0) / 2.0, 3.0))
    # per-leg deadline: each leg (plan + compile + warm + timed segment)
    # gets its share of the child budget; a leg that cannot start in
    # time is dropped with the partial record already emitted
    leg_deadline = (time.time() + child_budget - 20.0
                    if child_budget > 0 else float("inf"))

    def run_leg(shards: str, tag: str):
        """Plan + open one rule, saturate it for seg_s, return metrics."""
        mem.reset()
        store = kv.get_store()
        try:
            StreamProcessor(store).exec_stmt(
                'CREATE STREAM pipe_mc (deviceId STRING, temperature '
                'FLOAT) WITH (DATASOURCE="topic/pipe_mc", TYPE="memory", '
                'FORMAT="JSON")')
        except Exception:
            pass
        rule = RuleDef(
            id=f"mc_{tag}", sql=(
                "SELECT deviceId, avg(temperature) AS a, count(*) AS c "
                "FROM pipe_mc GROUP BY deviceId, TUMBLINGWINDOW(ss, 5)"),
            actions=[{"nop": {}}],
            options={"bufferLength": 64, "micro_batch_rows": mb_rows,
                     "micro_batch_linger_ms": 50, "key_slots": slots,
                     "decodePoolSize": 2, "ingestRingDepth": 2,
                     "sharedFold": False,
                     "planOptimizeStrategy": {"shards": shards}})
        topo = plan_rule(rule, store)
        fused = next(n for n in topo.ops
                     if type(n).__name__ == "FusedWindowAggNode")
        topo.open()
        src = (topo.sources[0] if topo.sources
               else topo._live_shared[0][0].source)
        # fleet observatory duty cycle rides the sharded leg: observe()
        # + timeline snapshot at a 1s cadence inside the timed segment,
        # so observatory_overhead is the measured fraction of fold wall
        # time the observatory costs (budget: <1%)
        fleetobs = None
        if shards != "off":
            import shutil as _shutil
            import tempfile as _tempfile

            from ekuiper_tpu.observability import meshwatch
            from ekuiper_tpu.observability import timeline as _tl_mod

            def _scrape() -> str:
                fam: list = []
                meshwatch.render_prometheus(fam, lambda s: s)
                return "\n".join(fam) + "\n"

            _tl_dir = _tempfile.mkdtemp(prefix="bench_mc_timeline_")
            fleetobs = (meshwatch,
                        _tl_mod.Timeline(scrape_fn=_scrape,
                                         base_dir=_tl_dir,
                                         interval_ms=0),
                        _tl_dir, _shutil)
            meshwatch.observe()  # baseline the skew window
        obs_s = 0.0
        try:
            # warm: compile the fold executables before the timed segment
            for d in drains:
                src.ingest(d)
            topo.wait_idle(30.0)
            topo.e2e_hist.snapshot_and_decay(0.0)
            rows = 0
            t0 = time.time()
            next_obs = t0 + 1.0
            n = 0
            while time.time() - t0 < seg_s:
                src.ingest(drains[n % len(drains)])
                rows += drain_rows
                n += 1
                if fleetobs is not None and time.time() >= next_obs:
                    # thread CPU time: wall would mostly count GIL
                    # waits behind the fold workers, not the observatory
                    ot = time.thread_time()
                    fleetobs[0].observe()
                    fleetobs[1].snapshot()
                    obs_s += time.thread_time() - ot
                    next_obs = time.time() + 1.0
                bp_deadline = time.time() + 60
                while fused.inq.qsize() > 8:
                    time.sleep(0.002)
                    if time.time() > bp_deadline:
                        raise RuntimeError(
                            "multichip: fused queue stuck >60s")
            topo.wait_idle(timeout=30.0)
            elapsed = time.time() - t0
            e2e = _e2e_fields(topo)
            shard_stats = (fused.gb.shard_stats(fused.state)
                           if hasattr(fused.gb, "shard_stats") else [])
            skew_ratio = 0.0
            coll_p50 = 0.0
            if fleetobs is not None:
                ot = time.thread_time()
                rep = fleetobs[0].observe()
                split = fleetobs[0].collective_split()
                fleetobs[1].snapshot()
                obs_s += time.thread_time() - ot
                skew_ratio = max(
                    (e["skew_ratio"] or 0.0 for e in rep.values()),
                    default=0.0)
                coll = sorted(v["collective_us"] / 1000.0
                              for (op, _), v in split.items()
                              if "fold" in str(op))
                if coll:
                    coll_p50 = coll[len(coll) // 2]
            return {
                "rows_per_sec": rows / elapsed,
                "rows": rows,
                "elapsed_s": elapsed,
                "shard_info": getattr(fused, "shard_info", {}),
                "per_shard_rows": [s["rows"] for s in shard_stats],
                "mesh": getattr(fused.gb, "mesh_tag", ""),
                "skew_ratio": skew_ratio,
                "collective_ms_p50": coll_p50,
                "observatory_overhead_pct": (100.0 * obs_s / elapsed
                                             if elapsed > 0 else 0.0),
                **e2e,
            }
        finally:
            topo.close()
            mem.reset()
            if fleetobs is not None:
                fleetobs[3].rmtree(fleetobs[2], ignore_errors=True)

    os.environ["KUIPER_MESH"] = f"1x{n_dev}"
    try:
        single = run_leg("off", "single")
        # partial record NOW: if the sharded leg dies to the watchdog or
        # the parent's kill, the artifact still carries the single-shard
        # leg instead of a bare timeout (the r05 parsed-null class)
        record("multichip_full_pipe",
               single_shard_rows_per_sec=single["rows_per_sec"],
               n_devices=n_dev, partial="single leg only")
        if time.time() + 25.0 > leg_deadline:
            print("# multichip_full_pipe: sharded leg dropped — "
                  "per-leg budget exhausted after the single leg",
                  file=sys.stderr)
            dog.disarm()
            sys.stderr.flush()
            os._exit(0)
        sharded = run_leg("auto", "sharded")
    finally:
        os.environ.pop("KUIPER_MESH", None)

    # direct-kernel window parity (byte-identical emitted groups):
    # the cheap in-process twin of tools/probe_multichip.py's full check
    parity_ok = True
    try:
        from ekuiper_tpu.ops.aggspec import extract_kernel_plan
        from ekuiper_tpu.ops.groupby import DeviceGroupBy
        from ekuiper_tpu.ops.keytable import KeyTable
        from ekuiper_tpu.parallel.mesh import make_mesh
        from ekuiper_tpu.parallel.sharded import ShardedGroupBy
        from ekuiper_tpu.sql.parser import parse_select

        pstmt = parse_select(
            "SELECT deviceId, avg(v) AS a, count(*) AS c, min(v) AS mn "
            "FROM s GROUP BY deviceId, TUMBLINGWINDOW(ss, 5)")
        pplan = extract_kernel_plan(pstmt)
        mesh = make_mesh(rows=1, keys=n_dev)
        sgb = ShardedGroupBy(pplan, mesh, capacity=256, micro_batch=512)
        ggb = DeviceGroupBy(extract_kernel_plan(pstmt), capacity=256,
                            micro_batch=512)
        kt = KeyTable(256)
        keys = np.array([f"d{rng.integers(200)}" for _ in range(5000)],
                        dtype=np.object_)
        vals = rng.normal(10, 3, 5000).astype(np.float32)
        slots, _ = kt.encode_column(keys)
        ss = sgb.fold(sgb.init_state(), {"v": vals}, slots)
        ds = ggb.fold(ggb.init_state(), {"v": vals}, slots)
        souts, sact = sgb.finalize(ss, kt.n_keys)
        douts, dact = ggb.finalize(ds, kt.n_keys)
        parity_ok = bool(np.array_equal(sact, dact) and all(
            np.allclose(souts[i], douts[i], rtol=1e-5, atol=1e-5,
                        equal_nan=True)
            for i in range(len(souts))))
    except Exception as exc:
        parity_ok = False
        print(f"# multichip parity check failed: {exc}", file=sys.stderr)

    scaling = (sharded["rows_per_sec"] / single["rows_per_sec"]
               if single["rows_per_sec"] else 0.0)
    print(
        f"# multichip_full_pipe ({n_dev} devices, mesh {sharded['mesh']}): "
        f"single {single['rows_per_sec']:,.0f} rows/s -> sharded "
        f"{sharded['rows_per_sec']:,.0f} rows/s ({scaling:.2f}x); "
        f"per-shard {sharded['per_shard_rows']}; emit p99 "
        f"{sharded['e2e_p99_ms']}ms; parity={'ok' if parity_ok else 'FAIL'}; "
        f"skew {sharded.get('skew_ratio', 0.0):.2f}; observatory "
        f"{sharded.get('observatory_overhead_pct', 0.0):.3f}%",
        file=sys.stderr,
    )
    record("multichip_full_pipe",
           rows_per_sec=sharded["rows_per_sec"],
           single_shard_rows_per_sec=single["rows_per_sec"],
           scaling_x=scaling,
           n_devices=n_dev,
           mesh=sharded["mesh"],
           per_shard_rows=sharded["per_shard_rows"],
           shard_info=sharded["shard_info"],
           skew_ratio=sharded.get("skew_ratio", 0.0),
           collective_ms_p50=sharded.get("collective_ms_p50", 0.0),
           observatory_overhead_pct=sharded.get(
               "observatory_overhead_pct", 0.0),
           parity_ok=parity_ok,
           platform=str(jax.devices()[0].platform),
           jitcert=_jitcert_fields(),
           emit_p99_ms=sharded["e2e_p99_ms"],
           e2e_p50_ms=sharded["e2e_p50_ms"])
    dog.disarm()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)


def bench_cold_start() -> None:
    _run_isolated("_cold_start_main", "cold_start", timeout=180)


def _cold_start_main() -> None:
    """Zero-compile serving phase (ISSUE 16): boot→first-emit and
    rule-create→first-emit for the SAME planned rule, cold (empty AOT
    executable cache — warmup lowers + compiles every fused-window
    executable) then warm (in-process restart against the disk cache the
    cold leg just baked — warmup is a deserialization sweep). The warm
    leg must show ZERO XLA traces and zero AOT misses: that pair is the
    cache's zero-compile-restart claim, and `speedup_first_fold_x` is
    its headline (seconds cold vs tens of ms warm).

    Runs on CPU jax in its own subprocess: the phase measures compile
    amortization, not device throughput."""
    import json as _json
    import shutil
    import tempfile

    os.environ["JAX_PLATFORMS"] = "cpu"
    # a throwaway directory on purpose: this CPU-only phase measures cold
    # against warm for the hand-built AOT cache, so it must start empty.
    # JAX's own persistent cache (utils/jaxcache.py) is not involved; what
    # becomes of this phase is ROADMAP S1/D8's call
    cache_dir = tempfile.mkdtemp(prefix="bench-aot-")
    os.environ["KUIPER_AOT_CACHE_DIR"] = cache_dir
    child_budget = float(os.environ.get("BENCH_CHILD_BUDGET_S", "0") or 0)
    dog = PhaseWatchdog()
    if child_budget > 0:
        dog.arm("cold_start_child", child_budget)
    from ekuiper_tpu.io import memory as mem
    from ekuiper_tpu.observability import devwatch, jitcert
    from ekuiper_tpu.planner.planner import RuleDef, plan_rule
    from ekuiper_tpu.runtime import aotcache
    from ekuiper_tpu.server.processors import StreamProcessor
    from ekuiper_tpu.store import kv

    rng = np.random.default_rng(31)
    rows = [
        _json.dumps({
            "deviceId": f"dev_{rng.integers(0, 500)}",
            "temperature": round(float(rng.normal(20, 5)), 2),
        }).encode()
        for _ in range(2048)
    ]

    def leg(tag: str) -> dict:
        t_boot = time.time()
        mem.reset()
        store = kv.get_store()
        try:
            StreamProcessor(store).exec_stmt(
                'CREATE STREAM pipe_cs (deviceId STRING, temperature '
                'FLOAT) WITH (DATASOURCE="topic/pipe_cs", TYPE="memory", '
                'FORMAT="JSON")')
        except Exception:
            pass
        t_rule = time.time()
        # ONE rule id + no shared-fold grouping: the warm leg must plan
        # the byte-identical kernel config (a store still holding the
        # cold leg's rule would otherwise vmap-group the warm plan into
        # different state shapes, and nothing would hit the cache)
        rule = RuleDef(
            id="cs_restart",
            sql=("SELECT deviceId, avg(temperature) AS a, count(*) AS c "
                 "FROM pipe_cs GROUP BY deviceId, TUMBLINGWINDOW(ss, 1)"),
            actions=[{"nop": {}}],
            options={"bufferLength": 64, "micro_batch_rows": 2048,
                     "micro_batch_linger_ms": 20, "key_slots": 1024,
                     "sharedFold": False})
        topo = plan_rule(rule, store)
        topo.open()  # <- warmup: compile sweep cold, cache probe warm
        src = (topo.sources[0] if topo.sources
               else topo._live_shared[0][0].source)
        try:
            src.ingest(rows)
            topo.wait_idle(60.0)
            t_fold = time.time()
            # first EMIT additionally waits for the 1s tumbling window
            # to close — the user-visible latency, window wait included
            emit_deadline = time.time() + 30.0
            while (topo.e2e_hist.count == 0
                   and time.time() < emit_deadline):
                time.sleep(0.01)
            t_emit = time.time()
            return {
                "boot_to_first_fold_ms": (t_fold - t_boot) * 1000.0,
                "rule_create_to_first_fold_ms":
                    (t_fold - t_rule) * 1000.0,
                "boot_to_first_emit_ms": (t_emit - t_boot) * 1000.0,
                "rule_create_to_first_emit_ms":
                    (t_emit - t_rule) * 1000.0,
                "emitted": bool(topo.e2e_hist.count > 0),
                "compile_total": _compile_total(),
                "aot": _aot_fields(),
            }
        finally:
            topo.close()
            mem.reset()

    try:
        cold = leg("cold")
        # partial record NOW so a watchdog kill still leaves the cold
        # numbers in the artifact (the r05 parsed-null class)
        record("cold_start", cold=cold, partial="cold leg only")
        # in-process restart: kernels + every registry die; only the
        # disk cache the cold leg baked survives — what a real process
        # restart on the same image sees
        devwatch.registry().clear()
        jitcert.reset()
        aotcache.reset()
        warm = leg("warm")
        zero_compile = (warm["compile_total"] == 0
                        and warm["aot"]["misses"] == 0)
        record("cold_start",
               cold=cold, warm=warm,
               zero_compile_restart=zero_compile,
               warm_disk_loads=warm["aot"]["disk_loads"],
               speedup_first_fold_x=round(
                   cold["rule_create_to_first_fold_ms"]
                   / max(warm["rule_create_to_first_fold_ms"], 1e-3), 1),
               jitcert=_jitcert_fields())
        print(
            "# cold_start: rule-create→first-fold "
            f"{cold['rule_create_to_first_fold_ms']:.0f}ms cold -> "
            f"{warm['rule_create_to_first_fold_ms']:.0f}ms warm; "
            f"first-emit {cold['rule_create_to_first_emit_ms']:.0f}ms "
            f"cold -> {warm['rule_create_to_first_emit_ms']:.0f}ms warm; "
            f"warm compiles {warm['compile_total']}, aot misses "
            f"{warm['aot']['misses']} (zero_compile_restart="
            f"{zero_compile})", file=sys.stderr)
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
        os.environ.pop("KUIPER_AOT_CACHE_DIR", None)
    dog.disarm()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)


def bench_full_pipe_ingest() -> None:
    _run_isolated("_full_pipe_main", "full-pipe")


def bench_full_pipe_contended() -> None:
    _run_isolated("_full_pipe_contended_main", "full-pipe-contended",
                  timeout=1200)


def bench_hetero_rules() -> None:
    _run_isolated("_hetero_main", "hetero 256-rule", timeout=1800)


def _hetero_main() -> None:
    """256 HETEROGENEOUS rules sharing one source on one chip (the
    reference's 300-rules-shared-stream benchmark, README.md:144-156, but
    with rules that do NOT all share a statement shape):

    - 4 rule FAMILIES with different aggregates/columns/comparators; rules
      within a family differ only in WHERE literals. Each family plans as
      ONE vmapped device program (plan_rule_group / parallel/multirule.py) —
      vmapped grouping applies WITHIN a family, never across families.
    - 4 fully-individual rules plan as their own fused nodes.
    - All 8 topologies ride ONE shared source+decode subtopo.

    Prints a stderr metric line with rule-rows/s and device state bytes."""
    import jax

    _require_tpu()
    from ekuiper_tpu.data.batch import ColumnBatch
    from ekuiper_tpu.io import memory as mem
    from ekuiper_tpu.planner.planner import RuleDef, plan_rule, plan_rule_group
    from ekuiper_tpu.server.processors import StreamProcessor
    from ekuiper_tpu.store import kv

    mem.reset()
    store = kv.get_store()
    StreamProcessor(store).exec_stmt(
        'CREATE STREAM sensors (deviceId STRING, temperature FLOAT, '
        'pressure FLOAT, humidity FLOAT) '
        'WITH (DATASOURCE="topic/sensors", TYPE="memory", FORMAT="JSON")')
    families = [
        ("fa", "SELECT deviceId, avg(temperature) AS a, count(*) AS c "
               "FROM sensors WHERE temperature > {x} "
               "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)", 14.0, 0.05),
        ("fb", "SELECT deviceId, min(pressure) AS mn, max(pressure) AS mx "
               "FROM sensors WHERE pressure > {x} "
               "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)", 0.4, 0.002),
        ("fc", "SELECT deviceId, sum(humidity) AS s, stddev(humidity) AS sd "
               "FROM sensors WHERE humidity > {x} "
               "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)", 30.0, 0.1),
        ("fd", "SELECT deviceId, count(*) AS c, avg(pressure) AS ap "
               "FROM sensors WHERE temperature < {x} "
               "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)", 26.0, 0.05),
    ]
    topos = []
    n_rules = 0
    for name, sql, base, step in families:
        rules = [
            RuleDef(id=f"{name}{i}", sql=sql.format(x=base + step * i),
                    actions=[{"nop": {}}],
                    options={"micro_batch_rows": 32768, "bufferLength": 96})
            for i in range(63)
        ]
        topos.append(plan_rule_group(name, rules, store))
        n_rules += 63
    singles = [
        "SELECT deviceId, stddev(temperature) AS sd, percentile_approx"
        "(temperature, 0.9) AS p90 FROM sensors "
        "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)",
        "SELECT deviceId, hll(humidity) AS u FROM sensors "
        "GROUP BY deviceId, HOPPINGWINDOW(ss, 10, 5)",
        "SELECT deviceId, max(temperature) AS m, count(*) AS c "
        "FROM sensors GROUP BY deviceId, COUNTWINDOW(262144)",
        "SELECT deviceId, avg(humidity) AS ah, min(temperature) AS mt "
        "FROM sensors GROUP BY deviceId, TUMBLINGWINDOW(ss, 5)",
    ]
    for i, sql in enumerate(singles):
        topos.append(plan_rule(
            RuleDef(id=f"solo{i}", sql=sql, actions=[{"nop": {}}],
                    options={"micro_batch_rows": 32768, "bufferLength": 96}),
            store))
        n_rules += 1
    assert n_rules == 256
    for t in topos:
        t.open()
    try:
        import json as _json

        # ONE physical source is shared by all 8 topologies (subtopo pool)
        srcs = {id(t._live_shared[0][0]) for t in topos if t._live_shared}
        assert len(srcs) == 1, f"expected 1 shared subtopo, got {len(srcs)}"
        src = topos[0]._live_shared[0][0].source
        rng = np.random.default_rng(31)
        n_dev = 4096
        ids = np.array([f"dev_{i}" for i in range(n_dev)], dtype=np.object_)
        drains = []
        for _ in range(8):
            k = 16384
            # raw JSON bytes, like the reference's MQTT fan-out benchmark
            # (README.md:144-156 rides a real broker) — decoded once by the
            # shared pipeline's native decoder, then key-encoded + uploaded
            # once per batch for all 256 riders (SharedPrepCtx)
            drains.append([
                _json.dumps({"deviceId": d, "temperature": t, "pressure": p,
                             "humidity": h}).encode()
                for d, t, p, h in zip(
                    ids[rng.integers(0, n_dev, k)],
                    rng.normal(20, 5, k).round(2),
                    rng.random(k).round(3),
                    rng.normal(50, 15, k).round(2))
            ])
        deadline = time.time() + 900
        warm_ok = False
        for _ in range(2):  # two full-coverage rounds, flush inline
            for d in drains:
                src.ingest(d)
            warm_ok = False
            while time.time() < deadline:  # all 8 programs compile
                if all(t.wait_idle(5.0) for t in topos):
                    warm_ok = True
                    break
        if not warm_ok:
            print("# hetero warm-up INCOMPLETE — number includes compiles",
                  file=sys.stderr)
        fused = [n for t in topos for n in t.ops
                 if "Fused" in type(n).__name__]
        rows = 0
        n = 0
        stall = 0.0
        t0 = time.time()
        while time.time() - t0 < 20.0:
            src.ingest(drains[n % len(drains)])
            rows += len(drains[0])
            n += 1
            ts = time.time()
            # queue-depth-aware dispatch: boundary instants put ~256 rules'
            # finalize+reset work on the link at once — let queues absorb
            # the spike (depth << bufferLength so drop-oldest NEVER fires;
            # asserted below) and only stall when a node falls genuinely
            # behind for a sustained stretch
            bp_deadline = time.time() + 120
            while max(f.inq.qsize() for f in fused) > 48:
                time.sleep(0.002)
                if time.time() > bp_deadline:
                    raise RuntimeError(
                        "hetero: queues stuck >120s (device link wedged?) "
                        "— aborting phase")
            stall += time.time() - ts
        for t in topos:
            t.wait_idle(timeout=30.0)
        elapsed = time.time() - t0
        drop_nodes = [
            n_.name for t_ in topos
            for n_ in (t_.sources + t_.ops + t_.sinks)
            if "dropped oldest" in getattr(n_.stats, "last_exception", "")]
        assert not drop_nodes, \
            f"queue depth rode into drop-oldest on {drop_nodes} — stall% " \
            "would be fake; raise bufferLength or lower the threshold"
        state_mb = sum(
            float(np.prod(v.shape)) * 4 for f in fused
            for v in (f.state or {}).values()) / 1e6
        print(
            f"# hetero 256-rule fan-out (4 vmapped families x63 + 4 solo, "
            f"one shared source): {rows:,} rows x {n_rules} rules in "
            f"{elapsed:.2f}s = {rows * n_rules / elapsed:,.0f} rule-rows/s "
            f"({stall:.1f}s backpressure-stalled), device state "
            f"{state_mb:.0f}MB across {len(fused)} fused nodes "
            f"(reference fan-out baseline: 150,000 rule-msg/s)",
            file=sys.stderr,
        )
        record("hetero_256", rule_rows_per_sec=rows * n_rules / elapsed,
               stalled_s=stall, stalled_pct=100.0 * stall / elapsed,
               state_mb=state_mb)
    finally:
        for t in topos:
            t.close()
        mem.reset()


def _require_tpu() -> None:
    """Every chip phase measures the chip or fails — never "whatever
    jax.devices() provides"."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"chip phase needs a TPU; jax.devices()[0].platform is "
            f"{platform!r}")


def _stage_summary(node) -> dict:
    """Per-stage StatManager timings for the bench artifact: the ingest
    pipeline balance (source decode/upload vs fused upload/fold) is an
    acceptance number, not just an operator dashboard."""
    out = {}
    for stage, st in node.stats.snapshot()["stage_timings"].items():
        calls = max(st["calls"], 1)
        out[stage] = {"calls": st["calls"], "rows": st["rows"],
                      "us_per_call": round(st["total_us"] / calls, 1)}
    return out


def _full_pipe_session(measure) -> None:
    """Shared full-pipe harness: raw JSON bytes → native columnar decode
    (jsoncol.cpp, shard-parallel on the decode pool) → fused device window,
    through the REAL planned topo (source node + decode pool + channels +
    fused node worker). Opens + warms the topo, then hands control to
    `measure(run_segment, src, dec)` where `run_segment(seconds)` returns
    (rows, bytes, elapsed) for one timed ingest segment."""
    import json as _json

    _require_tpu()
    from ekuiper_tpu.io import memory as mem
    from ekuiper_tpu.planner.planner import RuleDef, plan_rule
    from ekuiper_tpu.server.processors import StreamProcessor
    from ekuiper_tpu.store import kv

    # child-side watchdog (r05 fix): the parent kills us silently at its
    # subprocess timeout — die a little earlier WITH the partial records
    # and a final JSON flushed, so the artifact always carries this phase
    child_budget = float(os.environ.get("BENCH_CHILD_BUDGET_S", "0") or 0)
    dog = PhaseWatchdog()
    if child_budget > 0:
        dog.arm("full_pipe_child", child_budget)

    mem.reset()
    from ekuiper_tpu.io import fastjson

    fastjson.ensure_native(background=False)  # build the C decoder now
    store = kv.get_store()
    try:
        StreamProcessor(store).exec_stmt(
            'CREATE STREAM pipe (deviceId STRING, temperature FLOAT) '
            'WITH (DATASOURCE="topic/pipe", TYPE="memory", FORMAT="JSON")')
    except Exception:
        pass  # stream exists from a prior phase
    rule = RuleDef(
        id="pipe1", sql=(
            "SELECT deviceId, avg(temperature) AS a, count(*) AS c "
            "FROM pipe GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)"),
        actions=[{"nop": {}}],
        # ingest-rate shapes: bigger micro-batches amortize per-item node
        # overhead and per-fold upload latency; key_slots pinned (= the
        # default) so the measured config is explicit about cardinality;
        # decode pool explicit so the measured ingest pipeline is too
        options={"bufferLength": 64, "micro_batch_rows": 32768,
                 "micro_batch_linger_ms": 50, "key_slots": 16384,
                 "decodePoolSize": 3, "ingestRingDepth": 3})
    topo = plan_rule(rule, store)
    fused = next(n for n in topo.ops
                 if type(n).__name__ == "FusedWindowAggNode")
    topo.open()
    # memory streams plan as a shared subtopo; the physical SourceNode
    # lives in the pool, resolved at open()
    src = (topo.sources[0] if topo.sources
           else topo._live_shared[0][0].source)
    try:
        # pregenerate raw JSON payload batches (768 msgs per broker drain)
        rng = np.random.default_rng(23)
        drain_rows = 3072
        drains = []
        for _ in range(12):
            drain = [
                _json.dumps({
                    "deviceId": f"dev_{rng.integers(0, N_DEVICES)}",
                    "temperature": round(float(rng.normal(20, 5)), 2),
                }).encode()
                for _ in range(drain_rows)
            ]
            drains.append(drain)
        n_bytes_per = sum(len(p) for p in drains[0])
        # warm: the node worker compiles fold/finalize/prefinalize
        # executables first.
        # Feed a full micro-batch so the flush happens INLINE in ingest —
        # rows sitting in the source's pending buffer would let wait_idle
        # return before the pipe ever ran (queues look empty), leaving
        # every compile inside the measured window. Two rounds: all 12
        # drains cover ~97% of the 10k keys, so steady-state capacity and
        # executables are reached before timing starts. The warm window is
        # capped HARD below the child budget (no floor that could swallow
        # it): the measured segment must start before the watchdog fires,
        # even if that means measuring with compiles still warm.
        warm_s = 600.0
        if child_budget > 0:
            warm_s = min(warm_s, max(child_budget - 45.0, 5.0))
        warm_deadline = time.time() + warm_s
        for _ in range(2):
            for d in drains:
                src.ingest(d)
            while time.time() < warm_deadline and not topo.wait_idle(5.0):
                pass

        from ekuiper_tpu.observability import devwatch, memwatch

        def run_segment(seconds: float):
            rows = 0
            byts = 0
            n = 0
            # warm-vs-cold attribution (BENCH_r06): a steady-state segment
            # must run on cached executables — compile_count says whether
            # this number paid XLA compiles mid-measurement
            compiles0 = devwatch.registry().totals()["compiles"]
            peak = 0
            t0 = time.time()
            while time.time() - t0 < seconds:
                src.ingest(drains[n % len(drains)])
                rows += drain_rows
                byts += n_bytes_per
                n += 1
                # registered-component HBM/host footprint, sampled per
                # drain (probe walk is a handful of attribute reads)
                b = memwatch.registry().total_bytes()
                if b > peak:
                    peak = b
                # backpressure: keep the fused node's input queue shallow so
                # drop-oldest never fires (dropped batches would fake the
                # rate). Deadline-bounded: a wedged device link must fail
                # the phase loudly, not hang into the subprocess timeout
                bp_deadline = time.time() + 120
                while fused.inq.qsize() > 8:
                    time.sleep(0.002)
                    if time.time() > bp_deadline:
                        raise RuntimeError(
                            "full-pipe: fused queue stuck >120s (device "
                            "link wedged?) — aborting phase")
            # drain: all queued batches consumed (state is owned by the
            # node's worker thread — donated buffers, don't touch it here)
            topo.wait_idle(timeout=30.0)
            b = memwatch.registry().total_bytes()
            run_segment.device_bytes_peak = max(peak, b)
            run_segment.compile_count = (
                devwatch.registry().totals()["compiles"] - compiles0)
            return rows, byts, time.time() - t0

        run_segment.device_bytes_peak = 0
        run_segment.compile_count = 0

        dec = ("native" if src._fast_spec is not None
               and fastjson._load() is not None else "python")
        measure(run_segment, src, dec, fused, topo)
    finally:
        dog.disarm()
        topo.close()
        mem.reset()


def _devwatch_overhead(fused) -> dict:
    """Measured cost of the compile-watcher wrapper (observability/
    devwatch.py) on the CACHE-HIT path — the acceptance number behind
    'instrumentation ≤1% of fold time'. Each watched call adds exactly:
    one rule-context check, one flag write, one perf_counter read and two
    counter bumps; measured here as (watched − raw) jit dispatch time on
    an identity kernel, scaled against the fused fold stage."""
    import jax

    from ekuiper_tpu.observability.devwatch import watched_jit

    x = np.zeros(8, dtype=np.float32)
    raw = jax.jit(lambda v: v)
    watched = watched_jit(lambda v: v, op="bench.overhead_probe")
    raw(x)
    watched(x)  # both compiled before timing
    n = 3000

    def per_call_us(fn):
        t0 = time.perf_counter()
        for _ in range(n):
            fn(x)
        return (time.perf_counter() - t0) * 1e6 / n

    raw_us = per_call_us(raw)
    watched_us = per_call_us(watched)
    per_call = max(watched_us - raw_us, 0.0)
    st = fused.stats.snapshot()["stage_timings"].get("fold")
    fold_us = (st["total_us"] / max(st["calls"], 1)) if st else 0.0
    pct = (100.0 * per_call / fold_us) if fold_us else None
    return {"wrapper_us_per_call": round(per_call, 3),
            "fold_us_per_call": round(fold_us, 1),
            "pct_of_fold": round(pct, 3) if pct is not None else None}


def _kernwatch_overhead(fused) -> dict:
    """Measured cost of the kernel observatory (observability/
    kernwatch.py) against the fused fold — the acceptance number behind
    'device-time sampling ≤1% of fold', same bar as devwatch_overhead.
    Every watched call pays one cadence check (`KernelRecord.tick`);
    every Nth call additionally pays a device sync (`block_until_ready`
    on the outputs) plus the dispatch/device split math. Amortized
    per-call cost at the hot cadence = tick + sample / N."""
    import jax

    from ekuiper_tpu.observability import kernwatch
    from ekuiper_tpu.observability.kernwatch import KernelRecord

    rec = KernelRecord("bench.kern_probe")
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        rec.tick()
    tick_us = (time.perf_counter() - t0) * 1e6 / n
    # sample cost = (dispatch + block + split math) − bare dispatch, on a
    # compiled identity kernel: what a sampled call pays BEYOND the call
    x = np.zeros(8, dtype=np.float32)
    f = jax.jit(lambda v: v)
    jax.block_until_ready(f(x))
    m = 500
    t0 = time.perf_counter()
    for _ in range(m):
        f(x)
    bare_us = (time.perf_counter() - t0) * 1e6 / m
    t0 = time.perf_counter()
    for _ in range(m):
        ta = time.perf_counter()
        out = f(x)
        tb = time.perf_counter()
        rec.sample(out, ta, tb, (x,), {})
    sample_us = max((time.perf_counter() - t0) * 1e6 / m - bare_us, 0.0)
    # cadence 0 = hot sampling disabled: only the tick cost remains
    every = kernwatch.DEFAULT_SAMPLING["hot"]
    per_call = tick_us + (sample_us / every if every > 0 else 0.0)
    st = fused.stats.snapshot()["stage_timings"].get("fold")
    fold_us = (st["total_us"] / max(st["calls"], 1)) if st else 0.0
    pct = (100.0 * per_call / fold_us) if fold_us else None
    return {"tick_us": round(tick_us, 3),
            "sample_us": round(sample_us, 1),
            "sample_every": every,
            "per_call_us": round(per_call, 3),
            "fold_us_per_call": round(fold_us, 1),
            "pct_of_fold": round(pct, 3) if pct is not None else None}


def _kernel_fields() -> dict:
    """The kernel observatory's per-kernel device-time summary for the
    bench artifact (observability/kernwatch.py): top sites by sampled
    device time with FLOPs/bytes cost and roofline utilization — the
    numbers a ROADMAP re-anchor can cite for headroom claims."""
    from ekuiper_tpu.observability import kernwatch

    return kernwatch.bench_summary()


def _jitcert_fields() -> dict:
    """The compile-contract verdict for the phase (observability/
    jitcert.py): every devwatch-observed signature must sit inside the
    registered certificates. `clean=False` names the escapees — the
    acceptance gate for new jit sites (ISSUE 10) is zero observed
    signatures outside the certified set on full_pipe and
    multi_rule_shared."""
    from ekuiper_tpu.observability import jitcert

    d = jitcert.diff_live()
    return {
        "clean": d["clean"],
        "observed_signatures": d["observed_signatures"],
        "certified_signatures": d["certified_signatures"],
        "sites_observed": d["sites_observed"],
        "sites_open": d["sites_open"],
        "uncertified": [
            {"op": u["op"], "rule": u["rule"],
             "signature": u["signature"][:300]}
            for u in d["uncertified"][:16]],
    }


def _kernel_split_probe():
    """Device-time decomposition over the jit registry: returns
    `finish() -> dict` computing per-op deltas of sampled dispatch /
    device / transfer time plus devwatch compile time since the probe
    started — the sliding phase's answer to WHERE its trigger stalls go
    (the 865ms fold stalls of BENCH_r04 were one opaque host number)."""
    from ekuiper_tpu.observability import devwatch, kernwatch

    def totals():
        t = {}
        for w in devwatch.registry().watches():
            k = w.kern
            t[w.op] = (k.samples, k.dispatch_us, k.device_us,
                       k.transfer_us, w.compile_hist.sum, w.traces)
        return t

    before = totals()

    def finish(top: int = 8) -> dict:
        after = totals()
        ops = {}
        agg = {"samples": 0, "dispatch_us": 0.0, "device_us": 0.0,
               "transfer_us": 0.0, "compile_us": 0.0, "compiles": 0}
        for op, a in after.items():
            b = before.get(op, (0, 0.0, 0.0, 0.0, 0, 0))
            samples, disp, dev, xfer, comp_us, traces = (
                x - y for x, y in zip(a, b))
            if samples <= 0 and traces <= 0:
                continue
            agg["samples"] += samples
            agg["dispatch_us"] += disp
            agg["device_us"] += dev
            agg["transfer_us"] += xfer
            agg["compile_us"] += comp_us
            agg["compiles"] += traces
            ops[op] = {"samples": samples,
                       "dispatch_ms": round(disp / 1e3, 2),
                       "device_ms": round(dev / 1e3, 2),
                       "transfer_est_ms": round(xfer / 1e3, 2),
                       **({"compile_ms": round(comp_us / 1e3, 1),
                           "compiles": traces} if traces else {})}
        hot = sorted(ops, key=lambda o: -ops[o]["device_ms"])[:top]
        return {
            "device": kernwatch.device_spec().get("kind"),
            "sampling": dict(kernwatch.DEFAULT_SAMPLING),
            "samples": agg["samples"],
            "dispatch_ms": round(agg["dispatch_us"] / 1e3, 2),
            "compile_ms": round(agg["compile_us"] / 1e3, 1),
            "device_compute_ms": round(
                (agg["device_us"] - agg["transfer_us"]) / 1e3, 2),
            "transfer_est_ms": round(agg["transfer_us"] / 1e3, 2),
            "compiles": agg["compiles"],
            "ops": {o: ops[o] for o in hot},
        }

    return finish


def _hist_overhead(fused) -> dict:
    """Measured cost of the histogram hot path against the fused fold —
    the acceptance number behind 'histograms add <1% to the fold'. The
    fold path gained exactly: one queue-wait record + one process-latency
    record per dispatched batch (observability/histogram.py O(1) record),
    so overhead = 2 x record cost / per-batch fold time."""
    from ekuiper_tpu.observability.histogram import LatencyHistogram

    h = LatencyHistogram()
    n = 100_000
    t0 = time.perf_counter()
    for i in range(n):
        h.record(i & 0xFFFFF)
    per_record_us = (time.perf_counter() - t0) * 1e6 / n
    st = fused.stats.snapshot()["stage_timings"].get("fold")
    fold_us = (st["total_us"] / max(st["calls"], 1)) if st else 0.0
    pct = (100.0 * 2 * per_record_us / fold_us) if fold_us else None
    return {"record_us": round(per_record_us, 3),
            "fold_us_per_call": round(fold_us, 1),
            "pct_of_fold": round(pct, 3) if pct is not None else None}


def _compile_total() -> int:
    """Engine-wide XLA trace count (devwatch): the number the AOT cache
    exists to hold flat across rule churn and restarts."""
    from ekuiper_tpu.observability import devwatch

    return int(devwatch.registry().totals()["compiles"])


def _aot_fields() -> dict:
    """AOT executable-cache counters for the artifact (runtime/
    aotcache.py): hits serve from prebuilt executables, misses paid a
    serve-path lower+compile, disk_loads deserialized a baked entry."""
    from ekuiper_tpu.runtime import aotcache

    s = aotcache.stats().snapshot()
    return {"hits": s["hits"], "misses": s["misses"],
            "serve_misses": s["serve_misses"],
            "disk_loads": s["disk_loads"], "builds": s["builds"],
            "build_seconds": s["build_seconds"],
            "executables": s["executables"]}


def _e2e_fields(topo) -> dict:
    """SLO fields for the artifact: the rule's ingest→emit distribution
    (runtime/topo.py e2e_hist, fed by the sink) as p50/p99 ms."""
    h = topo.e2e_hist
    if h.count == 0:
        return {"e2e_p50_ms": None, "e2e_p99_ms": None, "e2e_samples": 0}
    return {"e2e_p50_ms": float(h.percentile(50)),
            "e2e_p99_ms": float(h.percentile(99)),
            "e2e_samples": h.count}


class _HealthTopoShim:
    """Just enough Topo surface for the health evaluator when a bench
    phase drives nodes directly (no planned Topo): all_nodes + no shared
    list, no e2e histogram (the evaluator skips absent surfaces)."""

    def __init__(self, nodes):
        self._nodes = nodes

    def all_nodes(self):
        return self._nodes

    def live_shared(self):
        return []


def _health_fields(topo, fused, elapsed_s, rule_id="pipe1") -> dict:
    """Final health verdict + peak burn rate + measured evaluator
    overhead (observability/health.py) for a bench phase. Same
    methodology as devwatch_overhead — measured cost scaled against the
    fold stage: the evaluator ticks once per DEFAULT_INTERVAL_MS, so
    overhead = mean tick cost x the ticks this segment would have seen
    at the default cadence, over the fold time the segment actually
    spent (acceptance target <1% of fold)."""
    from ekuiper_tpu.observability import health

    ev = health.HealthEvaluator(lambda: [(rule_id, topo, {})])
    # seed tick: first delta is the whole segment, so ITS verdict carries
    # the segment-wide burn/bottleneck/watermark attribution; later ticks
    # see empty deltas (traffic stopped) and only advance the FSM
    ev.tick()
    seed = ev.verdicts().get(rule_id) or {}
    tick_us = []  # warm ticks only — the seed paid the lazy imports
    for _ in range(5):
        ev.tick()
        tick_us.append(ev.last_tick_us)
    v = ev.verdicts().get(rule_id) or seed
    mean_us = sum(tick_us) / len(tick_us)
    st = (fused.stats.snapshot()["stage_timings"].get("fold")
          if fused is not None else None)
    fold_us = st["total_us"] if st else 0
    ticks = max(elapsed_s * 1000.0 / health.DEFAULT_INTERVAL_MS, 1.0)
    pct = (100.0 * mean_us * ticks / fold_us) if fold_us else None
    burn = seed.get("burn_rate") or {}
    return {
        "health_verdict": v.get("state"),
        "peak_burn_rate": ev.peak_burn(rule_id),
        "burn_rate_fast": burn.get("fast"),
        "burn_rate_slow": burn.get("slow"),
        "bottleneck_stage": (seed.get("bottleneck") or {}).get("stage"),
        "watermark_lag_ms": (seed.get("watermark") or {}).get("lag_ms"),
        "health_overhead": {
            "tick_us": round(mean_us, 1),
            "interval_ms": health.DEFAULT_INTERVAL_MS,
            "pct_of_fold": round(pct, 3) if pct is not None else None,
        },
    }


def _full_pipe_main() -> None:
    """Full-pipe ingest throughput (the reference measures through its
    MQTT+decode pipeline, README.md:98; kernel-fed numbers skip ingest,
    this line does not). Prints a stderr metric line."""

    def measure(run_segment, src, dec, fused, topo):
        # warm-up emissions (jit-stall dwells) must not pollute the SLO
        # fields: the measured segment starts from an empty distribution
        topo.e2e_hist.snapshot_and_decay(0.0)
        rows, byts, elapsed = run_segment(10.0)
        e2e = _e2e_fields(topo)
        print(
            f"# full-pipe ingest (json bytes → decode[{dec}] → coerce → "
            f"fused window, real topo): {rows:,} rows / {byts / 1e6:.0f}MB "
            f"in {elapsed:.2f}s ({rows / elapsed:,.0f} rows/s, "
            f"{byts / elapsed / 1e6:.1f}MB/s bytes-in); ingest→emit "
            f"p50={e2e['e2e_p50_ms']}ms p99={e2e['e2e_p99_ms']}ms over "
            f"{e2e['e2e_samples']} window emits",
            file=sys.stderr,
        )
        prep = src.prep_ctx
        record("full_pipe", rows_per_sec=rows / elapsed,
               mb_per_sec=byts / elapsed / 1e6, decoder=dec,
               pool=src.decode_pool_size, shards=src._decode_shards,
               prep_batches=(prep.n_precomputed if prep else 0),
               hist_overhead=_hist_overhead(fused),
               devwatch_overhead=_devwatch_overhead(fused),
               kernwatch_overhead=_kernwatch_overhead(fused),
               kernels=_kernel_fields(),
               jitcert=_jitcert_fields(),
               compile_count=run_segment.compile_count,
               device_bytes_peak=run_segment.device_bytes_peak,
               stages={"source": _stage_summary(src),
                       "fused": _stage_summary(fused)},
               **e2e, **_health_fields(topo, fused, elapsed))

    _full_pipe_session(measure)


def _burn_cpu(stop_path: str) -> None:
    """Background CPU load for the contention phase: spin until the stop
    file appears. A subprocess, not a thread — the point is stealing CPU
    from the engine the way a co-tenant process would, not GIL contention."""
    import os as _os

    x = 1.0
    while not _os.path.exists(stop_path):
        for _ in range(100_000):
            x = x * 1.0000001 + 1e-9
    _ = x


def _full_pipe_contended_main() -> None:
    """Full-pipe ingest under concurrent CPU load (VERDICT r5 weak #3:
    1.14M rows/s idle collapsed to 554k under load — the decode was
    GIL-bound on one thread). Measures an idle segment, then the same
    segment with cpu_count/2 busy subprocesses, and records both plus the
    degradation — the number that must stop halving under load."""
    import multiprocessing
    import os as _os
    import tempfile

    def measure(run_segment, src, dec, fused, topo):
        rows, byts, elapsed = run_segment(10.0)
        idle = rows / elapsed
        n_burn = max(2, (_os.cpu_count() or 4) // 2)
        stop_path = tempfile.mktemp(prefix="ek_burn_stop_")
        burners = [
            multiprocessing.Process(target=_burn_cpu, args=(stop_path,),
                                    daemon=True)
            for _ in range(n_burn)
        ]
        for b in burners:
            b.start()
        try:
            time.sleep(0.5)  # burners reach steady spin before the segment
            # e2e fields report the LOADED segment only (the phase's claim)
            topo.e2e_hist.snapshot_and_decay(0.0)
            rows, byts, elapsed = run_segment(10.0)
        finally:
            with open(stop_path, "w"):
                pass
            for b in burners:
                b.join(timeout=5)
                if b.is_alive():
                    b.terminate()
            _os.unlink(stop_path)
        loaded = rows / elapsed
        degr = 100.0 * (1.0 - loaded / idle) if idle else 0.0
        print(
            f"# full-pipe-contended ingest (decode[{dec}], {n_burn} cpu "
            f"burners): idle {idle:,.0f} rows/s → loaded {loaded:,.0f} "
            f"rows/s ({degr:.0f}% degradation)",
            file=sys.stderr,
        )
        prep = src.prep_ctx
        record("full_pipe_contended", idle_rows_per_sec=idle,
               loaded_rows_per_sec=loaded, degradation_pct=degr,
               burners=n_burn, decoder=dec,
               pool=src.decode_pool_size, shards=src._decode_shards,
               prep_batches=(prep.n_precomputed if prep else 0),
               kernels=_kernel_fields(),
               jitcert=_jitcert_fields(),
               compile_count=run_segment.compile_count,
               device_bytes_peak=run_segment.device_bytes_peak,
               stages={"source": _stage_summary(src),
                       "fused": _stage_summary(fused)},
               **_e2e_fields(topo),
               **_health_fields(topo, fused, elapsed))

    _full_pipe_session(measure)


def bench_multi_rule_shared(batches, kt_slots) -> None:
    """ISSUE 4 acceptance phase: 8 correlated rules, one stream, 10k keys —
    shared pane fold (one device fold per batch + per-rule pane combine)
    vs 8 independent folds. Records aggregate rule-rows/s for both plans,
    the fold-dedup ratio, and a deterministic byte-parity check of the
    emitted windows (integer-valued measurements so pane-sum association
    is exact — docs/SHARING.md)."""
    import jax

    from ekuiper_tpu.data.batch import ColumnBatch
    from ekuiper_tpu.data.rows import WindowRange
    from ekuiper_tpu.ops.aggspec import extract_kernel_plan
    from ekuiper_tpu.ops.emit import build_direct_emit
    from ekuiper_tpu.ops.panestore import pane_gcd, union_plan
    from ekuiper_tpu.runtime.events import Trigger
    from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode
    from ekuiper_tpu.runtime.nodes_sharedfold import (
        MemberSpec, SharedEmitNode, SharedFoldNode)
    from ekuiper_tpu.sql import ast
    from ekuiper_tpu.sql.parser import parse_select

    n_rules = 8
    sqls = [
        "SELECT deviceId, avg(temperature) AS a, count(*) AS c FROM demo "
        "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)",
        "SELECT deviceId, min(temperature) AS mn, max(temperature) AS mx "
        "FROM demo GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)",
        "SELECT deviceId, sum(temperature) AS s FROM demo "
        "GROUP BY deviceId, HOPPINGWINDOW(ss, 10, 5)",
        "SELECT deviceId, count(*) AS c, max(temperature) AS mx FROM demo "
        "GROUP BY deviceId, HOPPINGWINDOW(ss, 20, 5)",
        "SELECT deviceId, avg(temperature) AS a, min(temperature) AS mn "
        "FROM demo GROUP BY deviceId, TUMBLINGWINDOW(ss, 20)",
        "SELECT deviceId, avg(temperature) AS a, count(*) AS c FROM demo "
        "GROUP BY deviceId, TUMBLINGWINDOW(ss, 15)",
        "SELECT deviceId, sum(temperature) AS s, count(*) AS c FROM demo "
        "GROUP BY deviceId, TUMBLINGWINDOW(ss, 5)",
        "SELECT deviceId, avg(temperature) AS a FROM demo "
        "GROUP BY deviceId, HOPPINGWINDOW(ss, 15, 5)",
    ]
    stmts = [parse_select(s) for s in sqls]
    plans = [extract_kernel_plan(s) for s in stmts]
    assert all(p is not None for p in plans)
    union, _ = union_plan(plans)
    windows = []
    for s in stmts:
        w = s.window
        windows += [w.length_ms(), w.interval_ms() or w.length_ms()]
    pane = pane_gcd(windows)
    max_span = max(s.window.length_ms() // pane for s in stmts)

    # integer-valued temperatures: pane-sum association is exact, so the
    # shared-vs-private comparison below is BYTE-identical, not approximate
    int_batches = [
        ColumnBatch(n=b.n,
                    columns={"deviceId": b.columns["deviceId"],
                             "temperature": np.rint(
                                 b.columns["temperature"]).astype(
                                     np.float32)},
                    timestamps=b.timestamps, emitter=b.emitter)
        for b in batches
    ]

    def mk_shared():
        node = SharedFoldNode(
            "bench", "shared_fold[demo]", union, pane, max_span + 2,
            subtopo_ref=None, capacity=kt_slots, micro_batch=BATCH_ROWS)
        node._cur_bucket = 0
        entries = []
        for i, (stmt, plan) in enumerate(zip(stmts, plans)):
            w = stmt.window
            spec = MemberSpec(
                rule_id=f"r{i}", length_ms=w.length_ms(),
                interval_ms=w.interval_ms() or w.length_ms(), plan=plan,
                direct_emit=build_direct_emit(stmt, plan, ["deviceId"]),
                dims=["deviceId"], emit_columnar=True)
            e = SharedEmitNode(f"r{i}_emit", buffer_length=4096)
            node.attach_rule(spec, e, None)
            entries.append(e)
        return node, entries

    def mk_private():
        nodes, caps = [], []
        for stmt, plan in zip(stmts, plans):
            n = FusedWindowAggNode(
                "priv", stmt.window, plan,
                dims=[d.expr for d in stmt.dimensions],
                capacity=kt_slots, micro_batch=BATCH_ROWS,
                direct_emit=build_direct_emit(stmt, plan, ["deviceId"]),
                emit_columnar=True, prefinalize_lead_ms=0)
            n.state = n.gb.init_state()
            got = []
            n.broadcast = lambda item, g=got: g.append(item)
            nodes.append(n)
            caps.append(got)
        return nodes, caps

    def private_boundary(p, end):
        iv = p.interval_ms or p.length_ms
        if end % iv:
            return
        p._emit(WindowRange(end - p.length_ms, end))
        if p.wt == ast.WindowType.TUMBLING_WINDOW:
            p.state = p.gb.reset_pane(p.state, 0)
        else:
            p.cur_pane = (p.cur_pane + 1) % p.n_panes
            p.state = p.gb.reset_pane(p.state, p.cur_pane)

    # ---- parity: identical batches + boundaries through both plans ----
    shared, entries = mk_shared()
    privs, caps = mk_private()
    for end_i in range(1, 5):
        end = end_i * pane
        shared.process(int_batches[end_i % len(int_batches)])
        for p in privs:
            p.process(int_batches[end_i % len(int_batches)])
        shared.on_trigger(Trigger(ts=end))
        for p in privs:
            private_boundary(p, end)
    jax.block_until_ready(shared.store.state)
    n_windows = 0
    for i, e in enumerate(entries):
        got = []
        while not e.inq.empty():
            item = e.inq.get_nowait()
            if isinstance(item, ColumnBatch):
                got.append(item)
        ref = [x for x in caps[i] if isinstance(x, ColumnBatch)]
        assert len(got) == len(ref), f"rule {i}: {len(got)} vs {len(ref)}"
        for a, b in zip(got, ref):
            for c in a.columns:
                assert np.array_equal(a.columns[c], b.columns[c]), \
                    f"rule {i} col {c} diverged"
        n_windows += len(got)
    parity_windows = n_windows

    # ---- throughput: aggregate rule-rows/s shared vs independent ----
    def run(fold_fn, boundary_fn, state_ref, seconds=6.0):
        rows = 0
        n = 0
        t0 = time.time()
        while time.time() - t0 < seconds:
            fold_fn(int_batches[n % len(int_batches)])
            rows += BATCH_ROWS
            n += 1
            if n % T_BLOCK_EVERY == 0:
                # bound the dispatch queue: block on the CURRENT state
                # before the boundary donates it (a held older marker
                # would reference donated buffers). Same pipeline bubble
                # for both arms — the comparison stays fair.
                jax.block_until_ready(state_ref()["act"])
            if n % 16 == 0:
                boundary_fn((n // 16) * pane)
        jax.block_until_ready(state_ref())
        return rows, time.time() - t0

    shared, entries = mk_shared()
    shared.process(int_batches[0])
    shared.on_trigger(Trigger(ts=pane))  # warm fold + combine
    jax.block_until_ready(shared.store.state)
    for e in entries:
        while not e.inq.empty():
            e.inq.get_nowait()
    shared.folds_did = shared.folds_would = 0
    s_rows, s_el = run(shared.process,
                       lambda end: shared.on_trigger(Trigger(ts=end)),
                       lambda: shared.store.state)
    dedup = shared.fold_dedup_ratio()

    privs, caps = mk_private()
    for p in privs:
        p.process(int_batches[0])
        private_boundary(p, p.interval_ms or p.length_ms)
    jax.block_until_ready(privs[0].state)

    def priv_fold(b):
        for p in privs:
            p.process(b)

    def priv_boundary(end):
        for p in privs:
            private_boundary(p, end)

    p_rows, p_el = run(priv_fold, priv_boundary, lambda: privs[0].state)
    shared_agg = s_rows * n_rules / s_el
    priv_agg = p_rows * n_rules / p_el
    speedup = shared_agg / max(priv_agg, 1e-9)
    print(
        f"# multi-rule shared fold ({n_rules} correlated rules, "
        f"{N_DEVICES} keys, pane {pane}ms x {max_span + 2} panes): shared "
        f"{shared_agg:,.0f} rule-rows/s vs independent {priv_agg:,.0f} "
        f"rule-rows/s = {speedup:.1f}x; fold-dedup ratio {dedup:.3f}; "
        f"parity: {parity_windows} windows byte-identical",
        file=sys.stderr,
    )
    record("multi_rule_shared",
           shared_rule_rows_per_sec=shared_agg,
           independent_rule_rows_per_sec=priv_agg,
           speedup=speedup, fold_dedup_ratio=dedup,
           parity_windows=parity_windows, n_rules=n_rules,
           pane_ms=pane,
           jitcert=_jitcert_fields(),
           **_health_fields(
               _HealthTopoShim(shared.pipeline_nodes() + entries),
               shared, s_el, rule_id="r0"))


def bench_join_heavy(kt_slots) -> None:
    """ISSUE 19 acceptance phase: interval stream-stream join through
    the device join ring (ops/joinring.py). Two legs:

    - columnar throughput: 2048-rows-per-side windows through the
      certified match kernel (key equality + event-time band + residual)
      — rows/s counts both sides, acceptance floor 500k rows/s on the
      CPU smoke;
    - emission tail: full DeviceJoinNode._join_step windows (mask +
      host-order emission reconstruction) at 256 rows/side — the
      per-window latency p99 is the join analogue of the emit p99.

    Every window must take the device mask: a single runtime fallback
    (fallback_windows_total != 0) fails the phase."""
    import jax

    from ekuiper_tpu.data.rows import JoinTuple, Tuple
    from ekuiper_tpu.ops.joinring import SideBatch
    from ekuiper_tpu.planner import relational
    from ekuiper_tpu.runtime.nodes_relational import DeviceJoinNode
    from ekuiper_tpu.sql.parser import parse_select

    sql = ("SELECT l.v, r.w FROM l INNER JOIN r ON l.k = r.k "
           "AND l.ts - r.ts >= -5000 AND l.ts - r.ts <= 5000 "
           "AND l.v > r.w GROUP BY TUMBLINGWINDOW(ss, 10)")
    stmt = parse_select(sql)
    lowering = relational.lower_join(stmt, stmt.joins)
    ring = lowering.build_ring(capacity=kt_slots)
    rng = np.random.default_rng(19)
    n_keys = 512

    def side(n, left):
        b = SideBatch(n=n)
        b.key_cols.append([f"k{i}" for i in rng.integers(0, n_keys, n)])
        b.band = rng.integers(0, 60_000, n).tolist()
        col = "__jl_v" if left else "__jr_w"
        b.cols[col] = rng.uniform(0.0, 100.0, n).tolist()
        return b

    per_side = 2048
    windows = [(side(per_side, True), side(per_side, False))
               for _ in range(4)]
    mask = ring.match(*windows[0])  # warm: compile the (2048, 2048) pad
    matches = 0
    rows = 0
    n = 0
    t0 = time.time()
    while time.time() - t0 < 6.0:
        left, right = windows[n % len(windows)]
        mask = ring.match(left, right)
        rows += left.n + right.n
        n += 1
    matches = int(mask.sum())
    elapsed = time.time() - t0
    rows_per_sec = rows / elapsed

    # emission-order reconstruction leg: host rows through the full node
    node = DeviceJoinNode("join", stmt.joins, left_name="l",
                          lowering=lowering)
    node.ring = ring

    def mk_rows(n, left):
        out = []
        for i in range(n):
            ts = int(rng.integers(0, 60_000))
            msg = {"k": f"k{int(rng.integers(0, n_keys))}", "ts": ts}
            if left:
                msg["v"] = float(rng.uniform(0.0, 100.0))
            else:
                msg["w"] = float(rng.uniform(0.0, 100.0))
            out.append(Tuple(emitter="l" if left else "r", message=msg,
                             timestamp=ts))
        return out

    lat_ms = []
    emitted = 0
    for _ in range(40):
        left = [JoinTuple(tuples=[t]) for t in mk_rows(256, True)]
        right = mk_rows(256, False)
        w0 = time.perf_counter()
        out = node._join_step(left, right, stmt.joins[0])
        lat_ms.append((time.perf_counter() - w0) * 1e3)
        emitted += len(out)
    p99 = float(np.percentile(lat_ms, 99))
    fallbacks = int(ring.fallback_windows_total)
    print(f"# join_heavy: match {rows_per_sec:,.0f} rows/s "
          f"({matches:,} pairs/window at {per_side}/side), emission "
          f"window p99 {p99:.1f}ms ({emitted:,} tuples over 40 windows), "
          f"fallback windows {fallbacks} (must be 0); device="
          f"{jax.devices()[0].device_kind}", file=sys.stderr)
    record("join_heavy", rows_per_sec=rows_per_sec,
           emit_p99_ms=p99, matches_per_window=matches,
           emitted_tuples=emitted, fallback_windows=fallbacks)
    assert fallbacks == 0, \
        f"join_heavy: {fallbacks} windows fell back to the host loop"


def bench_filter_heavy(batches, kt_slots) -> None:
    """ISSUE 12 acceptance phase: a rule with a non-trivial WHERE
    (string-dict IN + numeric predicate) and a CASE agg projection at
    10k keys, fully device-compiled by the expression IR
    (sql/expr_ir.py) — vs the same aggregates with NO WHERE. Acceptance:
    the compiled-WHERE rule runs fold-limited (within 15% of the
    no-WHERE tumbling throughput) with zero FilterNode / row-interpreter
    samples in kernel_split (the plan IS the fused kernel; there is no
    filter hop to sample)."""
    import jax

    from ekuiper_tpu.data.batch import ColumnBatch
    from ekuiper_tpu.data.rows import WindowRange
    from ekuiper_tpu.ops.aggspec import extract_kernel_plan
    from ekuiper_tpu.ops.emit import build_direct_emit
    from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode
    from ekuiper_tpu.sql.parser import parse_select

    sql_where = (
        "SELECT deviceId, count(*) AS c, "
        "sum(CASE WHEN status = 'ok' THEN temperature ELSE 0.0 END) AS s, "
        "avg(temperature) AS a FROM demo "
        "WHERE status IN ('ok', 'warn') AND temperature > 15 "
        "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)")
    sql_plain = (
        "SELECT deviceId, count(*) AS c, sum(temperature) AS s, "
        "avg(temperature) AS a FROM demo "
        "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)")

    # status column riding the shared bench batches: ~70% pass the IN
    rng = np.random.default_rng(12)
    statuses = np.array(["ok", "warn", "err"], dtype=np.object_)
    f_batches = []
    for b in batches:
        st = statuses[rng.integers(0, 3, b.n)]
        f_batches.append(ColumnBatch(
            n=b.n, columns={**b.columns, "status": st},
            timestamps=b.timestamps, emitter=b.emitter))

    def mk(sql):
        stmt = parse_select(sql)
        plan = extract_kernel_plan(stmt)
        assert plan is not None, f"not device-eligible: {sql}"
        node = FusedWindowAggNode(
            "fh", stmt.window, plan,
            dims=[d.expr for d in stmt.dimensions],
            capacity=kt_slots, micro_batch=BATCH_ROWS,
            direct_emit=build_direct_emit(stmt, plan, ["deviceId"]),
            emit_columnar=True, prefinalize_lead_ms=0)
        node.state = node.gb.init_state()
        node.broadcast = lambda item: None
        return node, plan

    node_w, plan_w = mk(sql_where)
    assert plan_w.filter is not None and plan_w.derived, \
        "WHERE must compile into the fused kernel (expression IR)"
    node_p, _ = mk(sql_plain)

    def run(node, seconds=6.0):
        # warm
        node.process(f_batches[0])
        node._emit(WindowRange(0, 10_000))
        node.state = node.gb.reset_pane(node.state, 0)
        jax.block_until_ready(node.state)
        split = _kernel_split_probe()
        rows = 0
        n = 0
        t0 = time.time()
        while time.time() - t0 < seconds:
            node.process(f_batches[n % len(f_batches)])
            rows += BATCH_ROWS
            n += 1
            if n % T_BLOCK_EVERY == 0:
                jax.block_until_ready(node.state["act"])
            if n % 16 == 0:
                node._emit(WindowRange(0, (n // 16) * 10_000))
                node.state = node.gb.reset_pane(node.state, 0)
        jax.block_until_ready(node.state)
        return rows / (time.time() - t0), split()

    w_rows, w_split = run(node_w)
    p_rows, _ = run(node_p)
    ratio = w_rows / max(p_rows, 1e-9)
    # device-path contract: every sampled op is a fused-kernel site —
    # a FilterNode hop or row-interpreter loop has no jit site and would
    # show up as a throughput collapse (the ratio floor), never here
    host_ops = [op for op in w_split.get("ops", {})
                if not op.startswith(("groupby.", "sharded.",
                                      "slidingring.", "multirule.",
                                      "sketch."))]
    print(
        f"# filter_heavy: compiled WHERE+CASE {w_rows:,.0f} rows/s vs "
        f"no-WHERE {p_rows:,.0f} rows/s = {ratio:.3f}x "
        f"(fold-limited target >= 0.85); kernel_split ops "
        f"{sorted(w_split.get('ops', {}))}; device="
        f"{jax.devices()[0].device_kind}",
        file=sys.stderr,
    )
    record("filter_heavy",
           rows_per_sec=w_rows,
           nowhere_rows_per_sec=p_rows,
           where_throughput_ratio=ratio,
           fold_limited=ratio >= 0.85,
           derived_cols=len(plan_w.derived),
           host_expr_ops=host_ops,
           kernel_split=w_split,
           jitcert=_jitcert_fields())


def bench_multi_rule_shared_mixed(batches, kt_slots) -> None:
    """Mixed-WHERE twin of multi_rule_shared: 6 rules, same stream /
    GROUP BY / window grid, WHERE clauses all DIFFERENT — the shape that
    planned 6 private folds before predicate lifting. Records the
    predicate-lifted fold-dedup ratio and byte-parity of every member's
    emissions vs its private plan."""
    import jax

    from ekuiper_tpu.data.batch import ColumnBatch
    from ekuiper_tpu.data.rows import WindowRange
    from ekuiper_tpu.ops.aggspec import extract_kernel_plan, lift_predicate
    from ekuiper_tpu.ops.emit import build_direct_emit
    from ekuiper_tpu.ops.panestore import union_plan
    from ekuiper_tpu.runtime.events import Trigger
    from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode
    from ekuiper_tpu.runtime.nodes_sharedfold import (
        MemberSpec, SharedEmitNode, SharedFoldNode)
    from ekuiper_tpu.sql.parser import parse_select

    sqls = [
        "SELECT deviceId, count(*) AS c, sum(temperature) AS s FROM demo "
        f"WHERE temperature > {t} GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)"
        for t in (10, 15, 20, 25)
    ] + [
        "SELECT deviceId, count(*) AS c, max(temperature) AS mx FROM demo "
        "WHERE status = 'ok' GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)",
        "SELECT deviceId, count(*) AS c FROM demo "
        "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)",
    ]
    stmts = [parse_select(s) for s in sqls]
    plans = [extract_kernel_plan(s) for s in stmts]
    assert all(p is not None for p in plans)
    lifted = [lift_predicate(p, s.condition)
              for p, s in zip(plans, stmts)]
    union, _ = union_plan(lifted)
    n_rules = len(sqls)

    rng = np.random.default_rng(13)
    statuses = np.array(["ok", "warn", "err"], dtype=np.object_)
    int_batches = []
    for b in batches:
        st = statuses[rng.integers(0, 3, b.n)]
        int_batches.append(ColumnBatch(
            n=b.n,
            columns={"deviceId": b.columns["deviceId"],
                     "temperature": np.rint(
                         b.columns["temperature"]).astype(np.float32),
                     "status": st},
            timestamps=b.timestamps, emitter=b.emitter))

    def mk_shared():
        node = SharedFoldNode(
            "bench_mixed", "shared_fold[demo:mixed]", union, 10_000, 3,
            subtopo_ref=None, capacity=kt_slots, micro_batch=BATCH_ROWS)
        node._cur_bucket = 0
        entries = []
        for i, (stmt, plan, lp) in enumerate(zip(stmts, plans, lifted)):
            spec = MemberSpec(
                rule_id=f"m{i}", length_ms=10_000, interval_ms=10_000,
                plan=lp, dims=["deviceId"],
                direct_emit=build_direct_emit(stmt, plan, ["deviceId"]),
                emit_columnar=True, act_idx=lp.act_idx)
            e = SharedEmitNode(f"m{i}_emit", buffer_length=4096)
            node.attach_rule(spec, e, None)
            entries.append(e)
        return node, entries

    def mk_private():
        nodes, caps = [], []
        for stmt, plan in zip(stmts, plans):
            n = FusedWindowAggNode(
                "privm", stmt.window, plan,
                dims=[d.expr for d in stmt.dimensions],
                capacity=kt_slots, micro_batch=BATCH_ROWS,
                direct_emit=build_direct_emit(stmt, plan, ["deviceId"]),
                emit_columnar=True, prefinalize_lead_ms=0)
            n.state = n.gb.init_state()
            got = []
            n.broadcast = lambda item, g=got: g.append(item)
            nodes.append(n)
            caps.append(got)
        return nodes, caps

    # ---- byte parity: same batches + boundaries through both plans ----
    shared, entries = mk_shared()
    privs, caps = mk_private()
    for end_i in range(1, 4):
        end = end_i * 10_000
        shared.process(int_batches[end_i % len(int_batches)])
        for p in privs:
            p.process(int_batches[end_i % len(int_batches)])
        shared.on_trigger(Trigger(ts=end))
        for p in privs:
            p._emit(WindowRange(end - 10_000, end))
            p.state = p.gb.reset_pane(p.state, 0)
    jax.block_until_ready(shared.store.state)
    parity_windows = 0
    for i, e in enumerate(entries):
        got = []
        while not e.inq.empty():
            item = e.inq.get_nowait()
            if isinstance(item, ColumnBatch):
                got.append(item)
        ref = [x for x in caps[i] if isinstance(x, ColumnBatch)]
        assert len(got) == len(ref), f"rule {i}: {len(got)} vs {len(ref)}"
        for a, b in zip(got, ref):
            for c in a.columns:
                assert np.array_equal(a.columns[c], b.columns[c]), \
                    f"mixed rule {i} col {c} diverged"
        parity_windows += len(got)

    # ---- throughput + dedup: shared (lifted) vs 6 private folds ----
    def run(fold_fn, boundary_fn, state_ref, seconds=5.0):
        rows = 0
        n = 0
        t0 = time.time()
        while time.time() - t0 < seconds:
            fold_fn(int_batches[n % len(int_batches)])
            rows += BATCH_ROWS
            n += 1
            if n % T_BLOCK_EVERY == 0:
                jax.block_until_ready(state_ref()["act"])
            if n % 16 == 0:
                boundary_fn((n // 16) * 10_000)
        jax.block_until_ready(state_ref())
        return rows, time.time() - t0

    shared, entries = mk_shared()
    shared.process(int_batches[0])
    shared.on_trigger(Trigger(ts=10_000))
    jax.block_until_ready(shared.store.state)
    for e in entries:
        while not e.inq.empty():
            e.inq.get_nowait()
    shared.folds_did = shared.folds_would = 0
    s_rows, s_el = run(shared.process,
                       lambda end: shared.on_trigger(Trigger(ts=end)),
                       lambda: shared.store.state)
    dedup = shared.fold_dedup_ratio()

    privs, caps = mk_private()
    for p in privs:
        p.process(int_batches[0])
        p._emit(WindowRange(0, 10_000))
        p.state = p.gb.reset_pane(p.state, 0)
    jax.block_until_ready(privs[0].state)

    def priv_fold(b):
        for p in privs:
            p.process(b)

    def priv_boundary(end):
        for p in privs:
            p._emit(WindowRange(end - 10_000, end))
            p.state = p.gb.reset_pane(p.state, 0)

    p_rows, p_el = run(priv_fold, priv_boundary, lambda: privs[0].state)
    shared_agg = s_rows * n_rules / s_el
    priv_agg = p_rows * n_rules / p_el
    speedup = shared_agg / max(priv_agg, 1e-9)
    # identical-WHERE-only baseline: these 6 mixed-WHERE rules shared
    # NOTHING before predicate lifting (6 distinct store keys) — the
    # lifted dedup ratio improves on a flat 0.0
    print(
        f"# multi-rule shared MIXED-WHERE ({n_rules} rules, predicate-"
        f"lifted): shared {shared_agg:,.0f} rule-rows/s vs independent "
        f"{priv_agg:,.0f} rule-rows/s = {speedup:.1f}x; lifted fold-dedup "
        f"ratio {dedup:.3f} (identical-WHERE-only baseline: 0.000); "
        f"union specs {len(union.specs)}; parity: {parity_windows} "
        "windows byte-identical",
        file=sys.stderr,
    )
    record("multi_rule_shared_mixed",
           shared_rule_rows_per_sec=shared_agg,
           independent_rule_rows_per_sec=priv_agg,
           speedup=speedup,
           mixed_where_dedup_ratio=dedup,
           identical_where_baseline_dedup=0.0,
           union_specs=len(union.specs),
           parity_windows=parity_windows, n_rules=n_rules,
           jitcert=_jitcert_fields())


def bench_event_time(batches, kt_slots) -> None:
    """Event-time device path: per-row pane routing + watermark-driven
    emission. Prints a stderr metric line."""
    import jax
    from ekuiper_tpu.ops.aggspec import extract_kernel_plan
    from ekuiper_tpu.ops.emit import build_direct_emit
    from ekuiper_tpu.runtime.events import Watermark
    from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode
    from ekuiper_tpu.sql.parser import parse_select

    stmt = parse_select(SQL)
    plan = extract_kernel_plan(stmt)
    node = FusedWindowAggNode(
        "ev", stmt.window, plan, dims=[d.expr for d in stmt.dimensions],
        capacity=kt_slots, micro_batch=BATCH_ROWS,
        direct_emit=build_direct_emit(stmt, plan, ["deviceId"]),
        emit_columnar=True, is_event_time=True, late_tolerance_ms=1000)
    from ekuiper_tpu.data.batch import ColumnBatch

    node.state = node.gb.init_state()
    emitted = []
    node.broadcast = lambda item: emitted.append(item)

    def stamped(i):  # event timestamps advance ~1s/batch -> window per ~10
        b = batches[i % 4]
        return ColumnBatch(n=b.n, columns=b.columns,
                           timestamps=np.full(b.n, i * 1000, dtype=np.int64),
                           emitter=b.emitter)

    node.process(stamped(0))
    node.on_watermark(Watermark(ts=0))
    jax.block_until_ready(node.state)
    n = 1
    t0 = time.time()
    while time.time() - t0 < 3.0:  # untimed warm: steady link + executables
        node.process(stamped(n))
        node.on_watermark(Watermark(ts=n * 1000 - 1000))
        n += 1
    jax.block_until_ready(node.state)
    emitted.clear()
    rows = 0
    t0 = time.time()
    while time.time() - t0 < 10.0:
        node.process(stamped(n))
        node.on_watermark(Watermark(ts=n * 1000 - 1000))
        rows += BATCH_ROWS
        n += 1
    jax.block_until_ready(node.state)
    elapsed = time.time() - t0
    n_windows = sum(1 for i in emitted if not isinstance(i, Watermark))
    print(
        f"# event-time device path: {rows:,} rows in {elapsed:.2f}s "
        f"({rows / elapsed:,.0f} rows/s), {n_windows} watermark-driven "
        f"window emits", file=sys.stderr,
    )
    record("event_time", rows_per_sec=rows / elapsed, windows=n_windows)


def make_node():
    from ekuiper_tpu.ops.aggspec import extract_kernel_plan
    from ekuiper_tpu.ops.emit import build_direct_emit
    from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode
    from ekuiper_tpu.sql.parser import parse_select

    stmt = parse_select(SQL)
    plan = extract_kernel_plan(stmt)
    assert plan is not None, "bench rule must be device-eligible"
    direct = build_direct_emit(stmt, plan, ["deviceId"])
    assert direct is not None, "bench rule must take the direct-emit tail"
    node = FusedWindowAggNode(
        "bench", stmt.window, plan, dims=[d.expr for d in stmt.dimensions],
        capacity=KEY_SLOTS, micro_batch=BATCH_ROWS, direct_emit=direct,
        emit_columnar=True,
    )
    node.state = node.gb.init_state()
    node.broadcast = lambda item: None
    return node


def make_batches():
    from ekuiper_tpu.data.batch import ColumnBatch

    rng = np.random.default_rng(0)
    device_ids = np.array(
        [f"dev_{i}" for i in range(N_DEVICES)], dtype=np.object_)
    # a few distinct pre-built batches so host-side caching can't fake it
    batches = []
    for _ in range(4):
        idx = rng.integers(0, N_DEVICES, BATCH_ROWS)
        cols = {
            "deviceId": device_ids[idx],
            "temperature": rng.normal(20, 5, BATCH_ROWS).astype(np.float32),
        }
        batches.append(
            ColumnBatch(n=BATCH_ROWS, columns=cols,
                        timestamps=np.zeros(BATCH_ROWS, dtype=np.int64),
                        emitter="demo")
        )
    return batches


def warmup(node, batches) -> None:
    """Compile fold + sync finalize + components before measuring."""
    import jax

    from ekuiper_tpu.data.rows import WindowRange
    from ekuiper_tpu.runtime.events import PreTrigger

    assert node._prefinalize_ok, "bench rule must take the latency-hiding emit"
    for i in range(WARMUP_BATCHES):
        node.process(batches[i % len(batches)])
    node._emit(WindowRange(0, 10_000))  # sync path (compiles finalize)
    node.on_pre_trigger(PreTrigger(ts=10_000))
    node.process(batches[3])
    node._emit(WindowRange(0, 10_000))  # merged path (compiles components)
    node.state = node.gb.reset_pane(node.state, 0)
    jax.block_until_ready(node.state)


class WindowStats:
    """Per-boundary bookkeeping shared by both phases."""

    def __init__(self) -> None:
        self.latencies: list = []
        self.device_latencies: list = []
        self.fetch_ms: list = []
        self.sources = {"device": 0, "sync": 0}

    def boundary(self, node, emit_fn) -> None:
        from ekuiper_tpu.data.rows import WindowRange

        t = time.time()
        emit_fn(WindowRange(0, 10_000))
        lat = (time.time() - t) * 1000
        self.latencies.append(lat)
        node.state = node.gb.reset_pane(node.state, 0)
        info = node.last_emit_info
        if info is None:  # empty window: no emit, no source to attribute
            return
        self.sources[info.get("source", "sync")] += 1
        if info.get("source") == "device":
            self.device_latencies.append(lat)
            self.fetch_ms.append(info.get("fetch_ms", -1.0))

    def line(self) -> str:
        def pct(xs, q):
            return float(np.percentile(xs, q)) if xs else float("nan")

        s = self.sources
        return (
            f"emit p50={pct(self.latencies, 50):.1f}ms "
            f"p99={pct(self.latencies, 99):.1f}ms over "
            f"{len(self.latencies)} samples; sources device/sync="
            f"{s['device']}/{s['sync']}; "
            f"device-served p50={pct(self.device_latencies, 50):.1f}ms "
            f"p99={pct(self.device_latencies, 99):.1f}ms "
            f"(fetch issue→landed p50={pct(self.fetch_ms, 50):.0f}ms)"
        )


def phase_throughput(batches) -> float:
    """Saturate the ingest path; boundaries WAIT on the pre-issued device
    fetch, so throughput includes device-served emission."""
    import jax

    from ekuiper_tpu.runtime.events import PreTrigger

    node = make_node()
    warmup(node, batches)
    stats = WindowStats()
    rows = 0
    n = 0
    marker = None
    t0 = time.time()
    while len(stats.latencies) < T_WINDOWS:
        node.process(batches[n % len(batches)])
        rows += BATCH_ROWS
        n += 1
        if n % T_BLOCK_EVERY == 0:
            # bound the dispatch queue WITHOUT stalling the pipeline: wait
            # for the state as of one mark AGO (usually already done), so
            # at most ~2*T_BLOCK_EVERY batches are ever in flight. An
            # unbounded loop would measure client RAM, not the pipeline.
            _block_marker(marker)
            marker = node.state["act"][:1]  # non-donated slice
        m = n % T_WINDOW_BATCHES
        if m in T_PRE_ISSUE_AT:
            node.on_pre_trigger(PreTrigger(ts=0))
        elif m == 0:
            stats.boundary(node, node._emit)
    jax.block_until_ready(node.state)
    elapsed = time.time() - t0
    rows_per_sec = rows / elapsed
    print(
        f"# phase T (saturated): {rows:,} rows in {elapsed:.2f}s "
        f"({rows_per_sec:,.0f} rows/s); {stats.line()}; "
        f"groups/window={N_DEVICES}; device={jax.devices()[0].device_kind}",
        file=sys.stderr,
    )
    assert stats.sources["device"] == len(stats.latencies), \
        "phase T emits must all be device-served"
    record("tumbling_saturated", rows_per_sec=rows_per_sec,
           emit_p50_ms=float(np.percentile(stats.latencies, 50)),
           emit_p99_ms=float(np.percentile(stats.latencies, 99)),
           windows=len(stats.latencies))
    return rows_per_sec


def _final_json(rows_per_sec: float = 0.0, error: str = "") -> None:
    """The self-contained artifact line: the LAST stdout line carries every
    recorded phase metric under "phases", so the driver's record survives
    any tail truncation AND any mid-run death (the watchdog prints this
    before force-exiting)."""
    out = {
        "metric": "tumbling_groupby_rows_per_sec_10k_devices",
        "value": round(rows_per_sec),
        "unit": "rows/s",
        "vs_baseline": round(rows_per_sec / BASELINE_MSG_S, 2),
        # shallow copy: the watchdog dumps this from a timer thread while
        # the main thread may still be record()-ing
        "phases": dict(RESULTS),
    }
    if error:
        out["error"] = error
    print(json.dumps(out), flush=True)


class PhaseWatchdog:
    """Hard wall-clock bound around each in-process phase. A wedged device
    call cannot be interrupted from Python, so on
    expiry the watchdog prints the final self-contained JSON — everything
    recorded so far — and force-exits with rc=3 instead of letting the
    driver's global timeout produce rc=124 with no artifact."""

    def __init__(self) -> None:
        self._timer = None

    def arm(self, phase: str, seconds: float) -> None:
        import threading

        self.disarm()
        self._timer = threading.Timer(seconds, self._fire, (phase, seconds))
        self._timer.daemon = True
        self._timer.start()

    def disarm(self) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None

    def _fire(self, phase: str, seconds: float) -> None:
        # exception-safe: os._exit MUST run even if the artifact dump
        # races a record() on the wedged main thread — dying here would
        # recreate the rc=124-no-artifact failure this class prevents
        try:
            RESULTS[f"{phase}_error"] = f"watchdog: exceeded {seconds:.0f}s"
            print(f"# WATCHDOG: {phase} exceeded {seconds:.0f}s — emitting "
                  "final JSON and exiting", file=sys.stderr, flush=True)
            _flush_record_dump()
            _final_json(error=f"{phase} exceeded {seconds:.0f}s watchdog")
        except BaseException:
            pass
        finally:
            os._exit(3)


def main() -> None:
    # global budget: the driver hard-kills `python bench.py` (rc=124, no
    # artifact) — phase budgets are carved from TOTAL_BUDGET_S and a
    # last-resort watchdog emits the final JSON with whatever was recorded
    # just before that outer timeout would hit
    _DEADLINE.clear()
    _DEADLINE.append(time.time() + TOTAL_BUDGET_S)
    global_dog = PhaseWatchdog()
    global_dog.arm("total_budget", TOTAL_BUDGET_S - 10.0)
    # subprocess-isolated phases FIRST: they need the chip to themselves —
    # once this process initializes its own TPU client (first jax use), a
    # concurrent child client is starved to ~1% of its standalone rate
    bench_full_pipe_ingest()
    bench_full_pipe_contended()
    bench_hetero_rules()
    _require_tpu()
    batches = make_batches()
    # one phase failing must not orphan the headline + phases JSON — the
    # driver records the LAST stdout line; log the failure and keep going.
    # The watchdog bounds each phase: a wedged device call prints the
    # artifact with whatever was recorded and exits rc=3.
    rows_per_sec = 0.0
    dog = PhaseWatchdog()
    for name, budget_s, fn in (
        ("phase_throughput", 900.0, lambda: phase_throughput(batches)),
        ("sliding", 600.0,
         lambda: bench_sliding_percentile(batches, KEY_SLOTS)),
        ("heavy_hitters", 600.0,
         lambda: bench_hopping_heavy_hitters(batches, KEY_SLOTS)),
        ("hll_1m", 900.0, lambda: bench_countwindow_hll_1m(KEY_SLOTS)),
        ("event_time", 600.0, lambda: bench_event_time(batches, KEY_SLOTS)),
        ("rule_group", 600.0, lambda: bench_rule_group(batches, KEY_SLOTS)),
        ("filter_heavy", 600.0,
         lambda: bench_filter_heavy(batches, KEY_SLOTS)),
        ("join_heavy", 600.0, lambda: bench_join_heavy(KEY_SLOTS)),
        ("multi_rule_shared", 600.0,
         lambda: bench_multi_rule_shared(batches, KEY_SLOTS)),
        ("multi_rule_shared_mixed", 600.0,
         lambda: bench_multi_rule_shared_mixed(batches, KEY_SLOTS)),
        ("key_cardinality", 600.0,
         lambda: bench_key_cardinality(
             KEY_SLOTS,
             budget_s=max(phase_budget(
                 240.0, later_floor_s=later_floor("key_cardinality"))
                 - 30.0, 30.0))),
    ):
        budget_s = phase_budget(budget_s, later_floor_s=later_floor(name))
        if budget_s < 20.0:
            print(f"# {name}: skipped — global budget exhausted",
                  file=sys.stderr)
            RESULTS[f"{name}_error"] = "skipped: global budget exhausted"
            continue
        dog.arm(name, budget_s)
        try:
            out = fn()
            if name == "phase_throughput":
                rows_per_sec = out
        except Exception as exc:
            print(f"# {name} FAILED: {exc}", file=sys.stderr)
            RESULTS[f"{name}_error"] = str(exc)
        finally:
            dog.disarm()

    # subprocess phases with their own (virtual) device fleets run after
    # the in-process chip phases: multichip forces CPU host-device
    # emulation, so it never needs the chip the parent holds
    bench_multichip_full_pipe()
    # cold vs warm boot on CPU jax in its own subprocess: the AOT
    # executable cache's zero-compile-restart claim, measured
    bench_cold_start()
    # the churn soak runs LAST (its floor is reserved by every earlier
    # phase): it needs no chip to itself — it measures the QoS control
    # plane on CPU jax in its own subprocess
    bench_churn_soak()

    global_dog.disarm()
    _final_json(rows_per_sec)


if __name__ == "__main__":
    main()
