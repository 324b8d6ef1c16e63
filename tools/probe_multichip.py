#!/usr/bin/env python
"""probe_multichip — tier-1 smoke for multi-chip sharded serving
(parallel/sharded.py, docs/DISTRIBUTED.md).

Runs the full-pipe parity check on an 8-virtual-device CPU mesh (the
same `--xla_force_host_platform_device_count` recipe as
tests/conftest.py) and asserts:

  1. planner selection: `shards=auto` under KUIPER_MESH plans the rule
     onto the sharded kernel, and explain() carries the "shards"
     section naming the mesh;
  2. full-pipe parity: the sharded plan's emitted windows (hopping
     panes, capacity growth mid-stream) are byte-identical to the
     single-chip plan on the same data;
  3. cross-mesh checkpoint restore: a snapshot taken on the 8-device
     mesh restores single-chip (8->1) and back onto the mesh (1->8)
     with KeyTable slots, pane cursor, and window output byte-identical;
  4. placement-aware admission: a rule the single-chip HBM budget would
     429 is ACCEPTED with a sharded placement when the mesh is up;
  5. jitcert: every traced sharded signature is inside its certificate
     (diff_live clean).

Run directly or through tools/ci_gate.py (gate name `probe_multichip`).
Exit 0 on success.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # repo root

SQL = ("SELECT deviceId, sum(v) AS s, count(*) AS c, min(v) AS mn "
       "FROM demo GROUP BY deviceId, HOPPINGWINDOW(ss, 4, 2)")


def _force_devices(n: int = 8) -> None:
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main() -> int:
    _force_devices(8)
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from ekuiper_tpu.data.batch import ColumnBatch
    from ekuiper_tpu.observability import jitcert
    from ekuiper_tpu.ops.aggspec import extract_kernel_plan
    from ekuiper_tpu.ops.emit import build_direct_emit
    from ekuiper_tpu.parallel.mesh import make_mesh
    from ekuiper_tpu.planner.planner import (RuleDef, merged_options,
                                             mesh_request)
    from ekuiper_tpu.runtime.events import Trigger
    from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode
    from ekuiper_tpu.sql.parser import parse_select
    from ekuiper_tpu.utils import timex

    timex.set_mock_clock(0)
    problems = []
    stmt = parse_select(SQL)
    plan = extract_kernel_plan(stmt)
    assert plan is not None
    if len(jax.devices()) < 8:
        problems.append(f"only {len(jax.devices())} devices — the "
                        "virtual-device recipe did not engage")
        print(json.dumps({"ok": False, "problems": problems}))
        return 1

    # ---- 1. planner selection (shards=auto / KUIPER_MESH)
    os.environ["KUIPER_MESH"] = "2x4"
    try:
        rule = RuleDef(id="probe_mc", sql=SQL,
                       options={"planOptimizeStrategy": {"shards": "auto"}})
        req = mesh_request(merged_options(rule), plan)
        if req["mode"] != "sharded" or req["cfg"] != {"rows": 2, "keys": 4}:
            problems.append(f"planner did not select the mesh: {req}")
        off = RuleDef(id="probe_off", sql=SQL,
                      options={"planOptimizeStrategy": {"shards": "off"}})
        if mesh_request(merged_options(off), plan)["mode"] != "single-chip":
            problems.append("shards=off did not pin single-chip")
    finally:
        del os.environ["KUIPER_MESH"]

    # ---- 2. full-pipe parity: sharded vs single-chip fused node
    def mk(mesh):
        n = FusedWindowAggNode(
            "probe_mc", stmt.window, extract_kernel_plan(stmt),
            [d.expr for d in stmt.dimensions],
            capacity=64, micro_batch=128, prefinalize_lead_ms=0,
            direct_emit=build_direct_emit(stmt, plan, ["deviceId"]),
            emit_columnar=False, mesh=mesh)
        n.state = n.gb.init_state()
        out = []
        n.emit = lambda item, count=None, _o=out: _o.append(item)
        return n, out

    mesh = make_mesh(rows=2, keys=4)
    sharded, out_s = mk(mesh)
    plain, out_p = mk(None)
    if getattr(sharded.gb, "watch_prefix", "") != "sharded":
        problems.append("mesh node did not build a ShardedGroupBy")

    rng = np.random.default_rng(11)

    def batch(ids, vals):
        ids = np.array(ids, dtype=np.object_)
        return ColumnBatch(
            n=len(ids),
            columns={"deviceId": ids,
                     "v": np.asarray(vals, np.float64)},
            timestamps=np.zeros(len(ids), np.int64), emitter="demo")

    def feed(nodes, ids):
        vals = np.rint(rng.normal(50, 10, len(ids))).astype(np.float64)
        for n in nodes:
            n.process(batch(list(ids), vals))

    def boundary(nodes, ts):
        for n in nodes:
            n.on_trigger(Trigger(ts=ts))
            n._drain_async_emits()

    both = [sharded, plain]
    feed(both, [f"dev{i}" for i in range(40)])          # within capacity
    boundary(both, 2000)
    feed(both, [f"dev{i}" for i in range(40, 150)])     # forces a grow
    boundary(both, 4000)
    feed(both, [f"dev{i}" for i in range(0, 150, 3)])
    boundary(both, 6000)

    def flat(msgs):
        rows = {}
        for m in msgs:
            for r in (m if isinstance(m, list) else [m]):
                k = tuple(sorted(r.items()))
                rows[k] = rows.get(k, 0) + 1
        return rows

    if flat(out_s) != flat(out_p):
        diff = set(flat(out_s).items()) ^ set(flat(out_p).items())
        problems.append(f"sharded != single-chip windows: {list(diff)[:4]}")
    shard_rows = sharded.gb.shard_stats(sharded.state)
    if sum(s["rows"] for s in shard_rows) == 0:
        problems.append("per-shard row accounting recorded nothing")

    # ---- 3. cross-mesh checkpoint restore (8 -> 1 -> 8)
    snap8 = sharded.snapshot_state()
    single, out_1 = mk(None)
    single.restore_state(snap8)
    if single.kt.decode_all() != sharded.kt.decode_all():
        problems.append("8->1 restore changed the KeyTable slot order")
    if single.cur_pane != sharded.cur_pane:
        problems.append("8->1 restore changed the pane cursor")
    tail = [f"dev{i}" for i in range(10, 60)]
    vals = np.ones(len(tail), np.float64)
    for n in (single, sharded):
        n.process(batch(tail, vals))
    boundary([single, sharded], 8000)
    out_s_tail = flat(out_s[-1:])
    if flat(out_1) != out_s_tail:
        problems.append("8->1 restored windows diverged")
    snap1 = single.snapshot_state()
    remesh, out_8 = mk(make_mesh(rows=2, keys=4))
    remesh.restore_state(snap1)
    if remesh.kt.decode_all() != single.kt.decode_all():
        problems.append("1->8 restore changed the KeyTable slot order")
    for n in (remesh, single):
        n.process(batch(tail, vals))
    out_1.clear()
    boundary([remesh, single], 10000)
    if flat(out_8) != flat(out_1):
        problems.append("1->8 restored windows diverged")

    # ---- 4. placement-aware admission (per-chip ledger)
    from ekuiper_tpu.runtime import control
    from ekuiper_tpu.store import kv

    store = kv.get_store()
    # tierStore=off: the cold tier would otherwise absorb the footprint
    # (hot-set pricing) — this leg probes the PLACEMENT path
    fat = RuleDef(id="probe_fat", sql=SQL,
                  options={"key_slots": 262144, "sharedFold": False,
                           "tierStore": "off"})
    os.environ["KUIPER_HBM_BUDGET_MB"] = "8"
    ctl = control.install(lambda: [], start=False)
    try:
        single_chip = control.admit_rule(fat, store)
        if single_chip["decision"] != "reject":
            problems.append("single-chip HBM budget did not 429 the fat "
                            f"rule: {single_chip['decision']}")
        os.environ["KUIPER_MESH"] = "1x8"
        placed = control.admit_rule(fat, store)
        placement = (placed.get("price") or {}).get("placement") or {}
        if placed["decision"] != "accept" or \
                placement.get("mode") != "sharded":
            problems.append(
                "placement-aware admission did not accept the sharded "
                f"rule: {placed['decision']} / {placement}")
    finally:
        del os.environ["KUIPER_HBM_BUDGET_MB"]
        os.environ.pop("KUIPER_MESH", None)
        control.reset()

    # ---- 5. compile contracts
    d = jitcert.diff_live()
    if not d["clean"]:
        problems.append(
            "jitcert diff not clean: "
            + "; ".join(f"{u['op']}: {u['signature'][:80]}"
                        for u in d["uncertified"][:3]))

    report = {
        "ok": not problems,
        "problems": problems,
        "devices": len(jax.devices()),
        "mesh": getattr(sharded.gb, "mesh_tag", ""),
        "capacity": int(sharded.gb.capacity),
        "shard_rows": [s["rows"] for s in shard_rows],
        "jitcert_clean": d["clean"],
    }
    print(json.dumps(report, indent=2))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
