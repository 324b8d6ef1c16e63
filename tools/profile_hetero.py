"""Mini heterogeneous fan-out profile: a scaled-down _hetero_main (2 vmapped
families x8 + 2 solo rules over one shared source) run twice — shared
ingest prep ON vs OFF — to measure what one-encode/one-upload-per-batch
buys on the real chip without the full 256-rule compile bill.

Run: python tools/profile_hetero.py
"""
import sys
import time

import numpy as np

sys.path.insert(0, ".")


def run(shared: bool, seconds: float = 8.0):
    from ekuiper_tpu.io import memory as mem
    from ekuiper_tpu.planner.planner import RuleDef, plan_rule, plan_rule_group
    from ekuiper_tpu.runtime import subtopo as subtopo_mod
    from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode
    from ekuiper_tpu.server.processors import StreamProcessor
    from ekuiper_tpu.store import kv

    if not shared:
        orig_enc = FusedWindowAggNode._shared_encode
        orig_dev = FusedWindowAggNode._shared_device_inputs
        FusedWindowAggNode._shared_encode = lambda self, sub: None
        FusedWindowAggNode._shared_device_inputs = \
            lambda self, sub, cols, valid, slots: None
    try:
        mem.reset()
        store = kv.get_store()
        try:
            StreamProcessor(store).exec_stmt(
                'CREATE STREAM sensors (deviceId STRING, temperature FLOAT, '
                'pressure FLOAT, humidity FLOAT) '
                'WITH (DATASOURCE="topic/sensors", TYPE="memory", '
                'FORMAT="JSON")')
        except Exception:
            pass
        tag = "s" if shared else "u"
        families = [
            (f"fa{tag}", "SELECT deviceId, avg(temperature) AS a, count(*) "
             "AS c FROM sensors WHERE temperature > {x} "
             "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)", 14.0, 0.05),
            (f"fb{tag}", "SELECT deviceId, min(pressure) AS mn, max(pressure)"
             " AS mx FROM sensors WHERE pressure > {x} "
             "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)", 0.4, 0.002),
        ]
        topos = []
        for name, sql, base, step in families:
            rules = [RuleDef(id=f"{name}{i}", sql=sql.format(x=base + step * i),
                             actions=[{"nop": {}}],
                             options={"micro_batch_rows": 16384})
                     for i in range(8)]
            topos.append(plan_rule_group(name, rules, store))
        solos = [
            "SELECT deviceId, sum(humidity) AS s, stddev(humidity) AS sd "
            "FROM sensors GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)",
            "SELECT deviceId, avg(humidity) AS ah, min(temperature) AS mt "
            "FROM sensors GROUP BY deviceId, TUMBLINGWINDOW(ss, 5)",
        ]
        for i, sql in enumerate(solos):
            topos.append(plan_rule(
                RuleDef(id=f"solo{tag}{i}", sql=sql, actions=[{"nop": {}}],
                        options={"micro_batch_rows": 16384}), store))
        for t in topos:
            t.open()
        try:
            import json as _json

            src = topos[0]._live_shared[0][0].source
            rng = np.random.default_rng(31)
            n_dev = 4096
            ids = np.array([f"dev_{i}" for i in range(n_dev)],
                           dtype=np.object_)
            drains = []
            for _ in range(8):
                k = 16384
                drains.append([
                    _json.dumps(
                        {"deviceId": d, "temperature": t_, "pressure": p,
                         "humidity": h}).encode()
                    for d, t_, p, h in zip(
                        ids[rng.integers(0, n_dev, k)],
                        rng.normal(20, 5, k).round(2),
                        rng.random(k).round(3),
                        rng.normal(50, 15, k).round(2))
                ])
            deadline = time.time() + 600
            for _ in range(2):
                for d in drains:
                    src.ingest(d)
                while time.time() < deadline and \
                        not all(t.wait_idle(5.0) for t in topos):
                    pass
            fused = [n for t in topos for n in t.ops
                     if "Fused" in type(n).__name__]
            n_rules = 18
            rows = 0
            n = 0
            stall = 0.0
            t0 = time.time()
            while time.time() - t0 < seconds:
                src.ingest(drains[n % len(drains)])
                rows += len(drains[0])
                n += 1
                ts = time.time()
                while max(f.inq.qsize() for f in fused) > 6:
                    time.sleep(0.002)
                stall += time.time() - ts
            for t in topos:
                t.wait_idle(timeout=30.0)
            elapsed = time.time() - t0
            print(f"shared={shared}: {rows:,} rows x {n_rules} rules in "
                  f"{elapsed:.2f}s = {rows * n_rules / elapsed:,.0f} "
                  f"rule-rows/s, {rows/elapsed:,.0f} rows/s "
                  f"({stall:.1f}s stalled, {100*stall/elapsed:.0f}%)")
        finally:
            for t in topos:
                t.close()
            mem.reset()
    finally:
        if not shared:
            FusedWindowAggNode._shared_encode = orig_enc
            FusedWindowAggNode._shared_device_inputs = orig_dev


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "both"
    if which in ("both", "off"):
        run(False)
    if which in ("both", "on"):
        run(True)
