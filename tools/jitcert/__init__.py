"""jitcert CLI — certify + diff the engine's compile contracts headlessly.

Two subcommands, both tier-1-safe on CPU jax (tools/ci_gate.py runs
them; tests/test_jitcert.py asserts on them):

  python -m tools.jitcert certify [--json]
      Derive certificates for a canonical battery of kernel shapes
      (tumbling / hopping / multirule / heavy-hitters / sketch) and
      verify each one is MACHINE-CHECKABLE: re-deriving from the
      recorded params reproduces the signature set bit-for-bit, the set
      is closed (not truncated), and every SITE_DERIVATIONS op is
      exercised by at least one battery kernel. Exit 1 on any failure.

  python -m tools.jitcert diff [--json]
      Drive the same battery through real folds/finalizes on CPU jax,
      then diff devwatch's OBSERVED signatures against the registered
      certificates (observability/jitcert.py diff_live). Exit 1 when
      any observed signature falls outside its certificate — the same
      gate bench rounds and /diagnostics/xla apply to live engines.

The battery intentionally exercises the signature axes the derivations
encode: capacity growth across the slot-dtype boundary, validity-mask
presence flips, event-time pane vectors, masked edge refolds, dynamic
pane masks, and the sketch's pow-2 value pad ladder.
"""
from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))  # repo root


def _battery():
    """Construct the canonical kernel battery. Imports jax lazily so
    `certify --help` works anywhere."""
    import numpy as np  # noqa: F401

    from ekuiper_tpu.ops.aggspec import extract_kernel_plan
    from ekuiper_tpu.ops.groupby import DeviceGroupBy
    from ekuiper_tpu.ops.sketches import CountMinSketch
    from ekuiper_tpu.parallel.multirule import (BatchedGroupBy,
                                                build_rule_batch)
    from ekuiper_tpu.sql.parser import parse_select

    def plan(sql):
        p = extract_kernel_plan(parse_select(sql))
        assert p is not None, sql
        return p

    from ekuiper_tpu.ops.slidingring import RingLayout, SlidingRing

    tumbling = plan("SELECT deviceId, avg(v) AS a, count(*) AS c "
                    "FROM s GROUP BY deviceId, TUMBLINGWINDOW(ss, 1)")
    hopping = plan("SELECT deviceId, min(v) AS mn, max(v) AS mx FROM s "
                   "GROUP BY deviceId, HOPPINGWINDOW(ss, 4, 1)")
    # sliding ring battery kernel: additive (count/hist) + two-stack
    # (min) components over a small plan-time ring geometry
    sliding = plan("SELECT deviceId, count(*) AS c, min(v) AS mn, "
                   "percentile_approx(v, 0.5) AS p FROM s GROUP BY "
                   "deviceId, SLIDINGWINDOW(ss, 2) OVER (WHEN v > 90)")
    sliding_gb = DeviceGroupBy(sliding, capacity=32, n_panes=5,
                               micro_batch=16)
    sliding_ring = SlidingRing(
        sliding_gb,
        RingLayout(bucket_ms=500, n_ring_panes=4, n_panes=5,
                   span_buckets=3, scratch_pane=4))
    hh = plan("SELECT deviceId, heavy_hitters(tag, 2) AS hh FROM s "
              "GROUP BY deviceId, TUMBLINGWINDOW(ss, 1)")
    # expression-IR kernel: device-compiled CASE + string-dict IN +
    # temporal WHERE — the fold signature family gains int32 derived
    # columns (__sd_*/__ts32_*, KernelPlan.col_dtypes), which the
    # _derive_fold dtype axis must close over
    expr = plan("SELECT deviceId, sum(CASE WHEN status = 'ok' THEN v "
                "ELSE 0.0 END) AS s, count(*) AS c FROM s "
                "WHERE status IN ('ok', 'warn') AND hour(ets) < 23 "
                "GROUP BY deviceId, TUMBLINGWINDOW(ss, 1)")
    mr_sqls = [
        f"SELECT deviceId, count(*) AS c FROM s WHERE v > {t} "
        "GROUP BY deviceId, TUMBLINGWINDOW(ss, 1)" for t in (1.0, 2.0)]
    mr_spec = build_rule_batch(
        ["jc_r1", "jc_r2"],
        [parse_select(q) for q in mr_sqls])
    # tiered kernel (ops/tierstore.py): the touch column changes EVERY
    # groupby site's state signature, and the demote/promote gather/
    # scatter sites get their own certificates — both derive here and
    # drive in the diff battery (incl. a grow across a doubling)
    from ekuiper_tpu.ops.tierstore import TierLayout, TierStore

    tiered = plan("SELECT deviceId, avg(v) AS a, min(v) AS mn FROM s "
                  "GROUP BY deviceId, HOPPINGWINDOW(ss, 2, 1)")
    tiered_gb = DeviceGroupBy(tiered, capacity=32, n_panes=2,
                              micro_batch=16, track_touch=True)
    tier_store = TierStore(
        tiered_gb, TierLayout(hot_slots=16, demote_batch=4,
                              scan_interval_ms=500, min_idle_scans=1))
    kernels = {
        "groupby_tumbling": DeviceGroupBy(tumbling, capacity=32,
                                          n_panes=1, micro_batch=16),
        "groupby_hopping": DeviceGroupBy(hopping, capacity=32, n_panes=4,
                                         micro_batch=16),
        "groupby_hh": DeviceGroupBy(hh, capacity=32, n_panes=1,
                                    micro_batch=16),
        "groupby_expr": DeviceGroupBy(expr, capacity=32, n_panes=1,
                                      micro_batch=16),
        "multirule": BatchedGroupBy(mr_spec, capacity=32, n_panes=1,
                                    micro_batch=16),
        "sketch": CountMinSketch(depth=2, width=64, max_candidates=16),
        "sliding_ring": sliding_ring,
        "groupby_tiered": tiered_gb,
        "tier_store": tier_store,
    }
    # relational tier (ops/joinring.py, ops/segscan.py): interval join
    # with an ON residual (the residual column dtypes enter the match
    # signature) and the analytic scan pair, driven across a capacity
    # doubling in the diff battery
    from ekuiper_tpu.planner import relational
    from ekuiper_tpu.ops.segscan import SegScan

    jstmt = parse_select(
        "SELECT l.v, r.w FROM l INNER JOIN r ON l.k = r.k "
        "AND l.ts - r.ts >= -5 AND l.ts - r.ts <= 5 AND l.v > r.w "
        "GROUP BY TUMBLINGWINDOW(ss, 1)")
    kernels["join_ring"] = relational.lower_join(
        jstmt, jstmt.joins).build_ring(capacity=32)
    kernels["segscan"] = SegScan(capacity=32)
    # sharded battery kernel (multi-chip serving, parallel/sharded.py):
    # the shard_map fold/finalize family driven across a capacity
    # doubling — needs >= 4 devices (2x2 mesh); the CLI forces 8 virtual
    # CPU devices (main() below) so CI always has them, and certify's
    # exemption stays honest on a 1-device box
    try:
        import jax

        from ekuiper_tpu.parallel.mesh import make_mesh
        from ekuiper_tpu.parallel.sharded import ShardedGroupBy

        devs = jax.devices()
        if len(devs) >= 4:
            mesh = make_mesh(rows=2, keys=2, devices=devs[:4])
            sharded_plan = plan(
                "SELECT deviceId, avg(v) AS a, min(v) AS mn, "
                "count(*) AS c FROM s GROUP BY deviceId, "
                "HOPPINGWINDOW(ss, 2, 1)")
            kernels["sharded_fold"] = ShardedGroupBy(
                sharded_plan, mesh, capacity=32, n_panes=2,
                micro_batch=16)
    except Exception as exc:
        # recorded, not swallowed: certify() fails when a >=4-device
        # host cannot construct the sharded kernel — silently re-opening
        # the sharded exemption would hide exactly the regression class
        # the battery exists to catch
        _SHARDED_BATTERY_ERROR.append(str(exc))
    return kernels


#: last sharded-battery construction failure (certify surfaces it)
_SHARDED_BATTERY_ERROR: list = []


def certify(as_json: bool = False) -> int:
    from ekuiper_tpu.observability import jitcert

    kernels = _battery()
    report: Dict[str, Any] = {"kernels": {}, "problems": []}
    ops_seen: set = set()
    for name, kernel in kernels.items():
        certs = jitcert.certificates_for(kernel)
        recheck = jitcert.certificates_for(kernel)
        entries: List[Dict[str, Any]] = []
        for c, c2 in zip(certs, recheck):
            ops_seen.add(c.op)
            entry = c.to_json()
            if c.truncated:
                report["problems"].append(
                    f"{name}:{c.op} certificate is truncated (open set)")
            if c.signatures != c2.signatures:
                report["problems"].append(
                    f"{name}:{c.op} derivation is not deterministic")
            if not c.signatures:
                report["problems"].append(
                    f"{name}:{c.op} derived an empty signature set")
            entries.append(entry)
        report["kernels"][name] = entries
    # the sharded battery kernel needs a >= 4-device ("rows","keys")
    # mesh (the CLI forces 8 virtual CPU devices); only when even that
    # is absent do the sharded ops fall back to the shared _derive_*
    # builder coverage above
    have_sharded = any(getattr(k, "watch_prefix", "") == "sharded"
                       for k in kernels.values())
    if not have_sharded:
        try:
            import jax

            if len(jax.devices()) >= 4:
                report["problems"].append(
                    "sharded battery kernel failed to construct on a "
                    ">=4-device host: "
                    + (_SHARDED_BATTERY_ERROR[-1]
                       if _SHARDED_BATTERY_ERROR else "unknown"))
        except Exception:
            pass
    unexercised = {
        op for op in jitcert.SITE_DERIVATIONS
        if op not in ops_seen
        and not (op.startswith("sharded.") and not have_sharded)}
    for op in sorted(unexercised):
        report["problems"].append(
            f"SITE_DERIVATIONS op {op} not exercised by the battery")
    report["ok"] = not report["problems"]
    report["ops_certified"] = sorted(ops_seen)
    report["total_signatures"] = sum(
        e["n_signatures"] for entries in report["kernels"].values()
        for e in entries)
    if as_json:
        print(json.dumps(report, indent=2))
    else:
        state = "OK" if report["ok"] else "FAILED"
        print(f"jitcert certify: {state} — {len(ops_seen)} site "
              f"families, {report['total_signatures']} certified "
              f"signatures across {len(kernels)} battery kernels"
              + ("" if report["ok"]
                 else "\n  " + "\n  ".join(report["problems"])))
    return 0 if report["ok"] else 1


def _drive(kernels) -> None:
    """Exercise every battery kernel's jit sites across the signature
    axes the certificates promise to close."""
    import numpy as np

    from ekuiper_tpu.ops.groupby import DeviceGroupBy

    def feed(gb: DeviceGroupBy, with_masks: bool, pane_vec: bool,
             n_keys: int = 8):
        from ekuiper_tpu.ops.groupby import col_np_dtype

        cols = {}
        valid = {}
        n = 10
        for name in gb.plan.columns:
            if name.startswith("__hhc__"):
                cols[name] = np.arange(n, dtype=np.float32) % 3
            else:
                dt = col_np_dtype(gb.plan, name)
                cols[name] = np.arange(n).astype(
                    dt if dt != np.dtype(np.float32) else np.float64)
            if with_masks:
                valid[name] = np.ones(n, dtype=np.bool_)
        slots = (np.arange(n, dtype=np.int32) % n_keys)
        pane = (np.zeros(n, dtype=np.int64) if pane_vec else 0)
        return cols, valid, slots, pane

    for name, gb in kernels.items():
        if name == "tier_store":
            # demote/promote across a capacity doubling: the gather/
            # scatter re-specialization must stay inside the certified
            # ladder (the paired groupby_tiered kernel drives the
            # touch-bearing fold/finalize family via the generic loop)
            gb2 = gb.gb
            state = gb2.init_state()
            cols, valid, slots, pane = feed(gb2, with_masks=False,
                                            pane_vec=False)
            state = gb2.fold(state, cols, slots, pane_idx=pane)
            state, packed = gb.demote(state, np.array([1, 2], np.int32))
            state = gb.promote(state, np.asarray(packed)[:2],
                               np.array([1, 2], np.int32))
            state = gb2.grow(state, gb2.capacity * 2)
            state, packed = gb.demote(state, np.array([1], np.int32))
            state = gb.promote(state, np.asarray(packed)[:1],
                               np.array([1], np.int32))
            continue
        if name == "join_ring":
            from ekuiper_tpu.ops.joinring import SideBatch

            def side(n, prefix, base):
                b = SideBatch(n=n)
                b.key_cols.append([f"k{i % 5}" for i in range(n)])
                b.band = [base + i for i in range(n)]
                col = "__jl_v" if prefix == "l" else "__jr_w"
                b.cols[col] = [float(i) for i in range(n)]
                return b

            # two pad-pair steps of the certified (PL, PR) ladder, plus
            # a key-table doubling (capacity is not a match leaf — the
            # signature must NOT change across the grow)
            gb.match(side(10, "l", 0), side(10, "r", 0))
            gb.match(side(300, "l", 0), side(10, "r", 0))
            gb.match(side(40, "l", 0), side(300, "r", 0))
            continue
        if name == "segscan":
            # micro-batch pad ladder + a carry-capacity doubling (slot
            # beyond capacity forces grow; the shift signature's carry
            # dims step one rung)
            slots = (np.arange(10) % 8).astype(np.int32)
            vals = np.arange(10, dtype=np.float32)
            gb.shift(slots, vals, 10)
            gb.ranks(slots, vals, 10)
            big = (np.arange(300) % 40).astype(np.int32)
            gb.shift(big, np.arange(300, dtype=np.float32), 300)
            gb.ranks(big, np.arange(300, dtype=np.float32), 300)
            continue
        if name == "sketch":
            gb.update(np.arange(10, dtype=np.float32))
            gb.update(np.arange(300, dtype=np.float32))  # next pad bucket
            gb.heavy_hitters(3)
            continue
        if name == "sliding_ring":
            ring_kernel = gb
            gb2 = ring_kernel.gb
            state = gb2.init_state()
            cols, valid, slots, pane = feed(gb2, with_masks=False,
                                            pane_vec=False)
            state = gb2.fold(state, cols, slots, pane_idx=pane)
            ring = ring_kernel.init_state()
            ring = ring_kernel.advance(ring, state, 0, True, 1, False)
            ring = ring_kernel.flip(
                ring, state, 0,
                np.ones(ring_kernel.n_ring_panes, dtype=np.bool_))
            from ekuiper_tpu.ops.slidingring import QUERY_ADJ

            def tail(segs):
                """A query and the tail over its result + `segs`."""
                body = ring_kernel.query(
                    ring, state, body_on=True, f_on=True, f_slot=0,
                    adj_slots=np.zeros(QUERY_ADJ, dtype=np.int32),
                    adj_weights=np.zeros(QUERY_ADJ, dtype=np.float32),
                    adj_mm=np.zeros(QUERY_ADJ, dtype=np.bool_))
                np.asarray(ring_kernel.tail_begin(
                    body, ring_kernel.edge_buffers(segs)))

            # an edge segment without masks and one with
            masks = {c: np.ones(len(slots), dtype=np.bool_) for c in cols}
            tail([(cols, {}, slots, None), (cols, masks, slots, None)])
            gb2.components_begin_dyn(
                state, np.zeros(gb2.n_panes, dtype=np.bool_)).get()
            # capacity growth across a doubling: ring re-specialization
            # must stay inside the certified ladder
            state = gb2.grow(state, gb2.capacity * 2)
            ring = ring_kernel.grow(ring, gb2.capacity)
            ring = ring_kernel.advance(ring, state, 0, True, 1, False)
            tail([])
            continue
        state = gb.init_state()
        cols, valid, slots, pane = feed(gb, with_masks=False,
                                        pane_vec=False)
        state = gb.fold(state, cols, slots, pane_idx=pane)
        cols, valid, slots, pane = feed(gb, with_masks=True,
                                        pane_vec=gb.n_panes > 1)
        state = gb.fold(state, cols, slots, valid=valid, pane_idx=pane)
        outs, act = gb.finalize(state, 8)
        if gb.n_panes > 1:
            outs, act = gb.finalize(state, 8, panes=[0, 1])
        state = gb.reset_pane(state, 0)
        # capacity growth across a doubling: re-specialization must stay
        # inside the certified ladder
        state = gb.grow(state, gb.capacity * 2)
        cols, valid, slots, pane = feed(gb, with_masks=False,
                                        pane_vec=False)
        state = gb.fold(state, cols, slots, pane_idx=pane)
        outs, act = gb.finalize(state, 8)


def diff(as_json: bool = False) -> int:
    from ekuiper_tpu.observability import jitcert

    kernels = _battery()
    _drive(kernels)
    report = jitcert.diff_live()
    if as_json:
        print(json.dumps(report, indent=2))
    else:
        state = "OK" if report["clean"] else "FAILED"
        print(f"jitcert diff: {state} — {report['observed_signatures']} "
              f"observed signatures over {report['sites_observed']} live "
              f"sites, {report['certified_signatures']} certified"
              + ("" if report["clean"] else "\n  " + "\n  ".join(
                  f"{u['op']} [{u['rule'] or '__engine__'}]: "
                  f"{u['signature'][:140]}"
                  for u in report["uncertified"])))
    return 0 if report["clean"] else 1


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="tools.jitcert", description=__doc__.splitlines()[0])
    ap.add_argument("command", choices=["certify", "diff"])
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # 8 virtual CPU devices so the sharded battery kernel constructs
    # (must land before the first jax import initializes the backend)
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append("--xla_force_host_platform_device_count=8")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    if args.command == "certify":
        return certify(as_json=args.json)
    return diff(as_json=args.json)
