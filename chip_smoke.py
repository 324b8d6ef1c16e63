#!/usr/bin/env python3
"""chip_smoke.py — the served path, once, on the attached TPU.

Starts the engine the way a deployment does (`server.main.start_up`), creates
streams and rules over REST, publishes raw JSON byte payloads to the memory
source and reads the memory sink, then holds what came out to a plain numpy
reference computed from the same rows. It is the quickest proof that the
system still starts and answers correctly on the chip; it measures no speed.

    python chip_smoke.py             one chip: P1 tumbling, P2 HLL state
    python chip_smoke.py --chips 4   the sharded P1 plan against the one-chip
                                     plan, and nothing else

Every line but the last is one JSON object per phase (seconds, of which
compiling). The last line is `{"ok": true, "device": {...}}`; any failed
phase ends the run with `"ok": false` and a non-zero exit code. There is no
CPU mode: without a TPU the run fails (exit 2). The phase functions take
their sizes as arguments so a rehearsal script can call them small.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import traceback
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

P1_SQL = ("SELECT deviceId, avg(temperature) AS a, count(*) AS c, "
          "min(temperature) AS mn, max(temperature) AS mx FROM {stream} "
          "GROUP BY deviceId, TUMBLINGWINDOW(ss, {window_s})")
P1_OPTIONS = {"key_slots": 16384, "micro_batch_rows": 32768,
              "micro_batch_linger_ms": 50, "bufferLength": 64,
              "decodePoolSize": 3, "ingestRingDepth": 3}
P2_SQL = ("SELECT deviceId, hll(uid) AS uniq FROM {stream} "
          "GROUP BY deviceId, COUNTWINDOW({window_rows})")
P2_OPTIONS = {"micro_batch_rows": 65536, "micro_batch_linger_ms": 50,
              "bufferLength": 64, "decodePoolSize": 3, "ingestRingDepth": 3}
HLL_STD_ERR = 0.065  # ops/sketches.py: m=256 registers
DRAIN_ROWS = 4096  # rows per publish, one broker drain


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


class NoChip(Exception):
    """JAX found no TPU."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(**kv) -> None:
    print(json.dumps(kv, default=str), flush=True)


# ------------------------------------------------------------------ engine
class Engine:
    """The server, started through its own start-up on an ephemeral port
    with its store under `out_dir`; everything else goes over REST."""

    def __init__(self, out_dir: str) -> None:
        from ekuiper_tpu.server.main import start_up

        # a fresh store each run: streams and rules of an earlier smoke in
        # this output directory are not this run's
        shutil.rmtree(os.path.join(out_dir, "store"), ignore_errors=True)
        os.makedirs(out_dir, exist_ok=True)
        cfg_path = os.path.join(out_dir, "smoke_config.json")
        with open(cfg_path, "w") as fh:
            json.dump({
                "basic": {"rest_ip": "127.0.0.1", "rest_port": 0,
                          "log_level": "warning"},
                "store": {"type": "sqlite",
                          "path": os.path.join(out_dir, "store")},
            }, fh)
        self.api, self.server = start_up(cfg_path, block=False)
        self.port = self.server.server_address[1]

    def rest(self, method: str, path: str, body=None, raw: bool = False):
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}", method=method,
            data=None if body is None else json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=120) as resp:
                data = resp.read()
        except urllib.error.HTTPError as exc:
            raise SmokeFailure(
                f"{method} {path} -> {exc.code}: {exc.read()[:400]!r}")
        return data.decode() if raw else json.loads(data)

    def create_stream(self, name: str, fields: str, topic: str) -> None:
        self.rest("POST", "/streams", {"sql": (
            f"CREATE STREAM {name} ({fields}) WITH (DATASOURCE=\"{topic}\", "
            "TYPE=\"memory\", FORMAT=\"JSON\")")})

    def create_rule(self, rule_id: str, sql: str, sink_topic: str,
                    options: dict) -> "RuleHandle":
        self.rest("POST", "/rules", {
            "id": rule_id, "sql": sql, "options": options,
            "actions": [{"memory": {"topic": sink_topic}}]})
        deadline = time.time() + 600  # the start compiles the kernels
        while time.time() < deadline:
            st = self.rest("GET", f"/rules/{rule_id}/status")
            if st.get("status") == "running":
                return RuleHandle(self, rule_id)
            check(not str(st.get("status", "")).startswith("stopped"),
                  f"rule {rule_id} did not start: {st}")
            time.sleep(0.1)
        raise SmokeFailure(f"rule {rule_id} not running after 600 s")

    def drop_rule(self, rule_id: str) -> None:
        self.rest("POST", f"/rules/{rule_id}/stop")
        self.rest("DELETE", f"/rules/{rule_id}")

    def close(self) -> None:
        from ekuiper_tpu.observability import health
        from ekuiper_tpu.runtime import control

        control.reset()
        health.reset()
        self.api.rules.stop_all()
        self.server.shutdown()


class RuleHandle:
    """A running rule: its live topo (for flow control and the checks no
    REST route answers) and the REST views of it."""

    def __init__(self, engine: Engine, rule_id: str) -> None:
        self.engine = engine
        self.id = rule_id
        self.topo = engine.api.rules.state(rule_id).topo
        self.fused = next(n for n in self.topo.ops
                          if type(n).__name__ == "FusedWindowAggNode")
        # memory streams plan onto a shared source subtopo
        self.src = (self.topo.sources[0] if self.topo.sources
                    else self.topo._live_shared[0][0].source)

    def status(self) -> dict:
        return self.engine.rest("GET", f"/rules/{self.id}/status")

    def emit_sources(self) -> dict:
        st = self.status()
        key = next((k for k in st if k.endswith("_emit_sources")), None)
        return dict(st[key]) if key else {}

    def wait_shallow(self) -> None:
        """Keep the fused node's input queue shallow so drop-oldest never
        fires (a dropped batch would break row conservation)."""
        deadline = time.time() + 120
        while self.fused.inq.qsize() > 8:
            time.sleep(0.002)
            check(time.time() < deadline,
                  "fused input queue stuck for 120 s")


def compile_marks() -> dict:
    """Cumulative compile accounting: events at watched jit sites
    (devwatch) and seconds spent lowering+compiling them (aotcache)."""
    from ekuiper_tpu.observability import devwatch
    from ekuiper_tpu.runtime import aotcache

    tot = devwatch.registry().totals()
    return {"compiles": tot["compiles"], "storms": tot["storms"],
            "compile_s": aotcache.stats().snapshot()["build_seconds"]}


def _metric_total(text: str, family: str) -> float:
    return sum(float(line.rsplit(" ", 1)[1])
               for line in text.splitlines() if line.startswith(family))


def check_no_hidden_fallback(engine: Engine, rule: RuleHandle) -> dict:
    """What must hold in every phase: the device path served, natively
    decoded, with nothing degraded, dropped or answered from the host."""
    from ekuiper_tpu.io import fastjson

    explain = engine.rest("GET", f"/rules/{rule.id}/explain")
    check(explain.get("path") == "device-fused",
          f"{rule.id} planned as {explain.get('path')!r}, not device-fused")
    check(rule.src._fast_spec is not None and fastjson._load() is not None,
          f"{rule.id}: the Python JSON decoder served, not the native one")
    metrics = engine.rest("GET", "/metrics", raw=True)
    check(_metric_total(metrics, "kuiper_expr_host_fallback_total") == 0,
          "kuiper_expr_host_fallback_total > 0")
    dropped = _metric_total(metrics, "kuiper_node_dropped_total")
    check(dropped == 0, f"kuiper_node_dropped_total = {dropped}")
    events = engine.rest("GET", "/diagnostics/events")["events"]
    bad = [e for e in events if e.get("kind") in (
        "aot_degraded", "sliding_impl_fallback", "compile_storm",
        "warmup_failure")]
    check(not bad, f"flight recorder: {bad[:3]}")
    check(compile_marks()["storms"] == 0, "recompile storm flagged")
    sources = rule.emit_sources()
    check(sources.get("backstop", 0) == 0,
          f"{rule.id}: host backstop served windows: {sources}")
    check(sum(sources.values()) > 0, f"{rule.id}: no window was emitted")
    status = rule.status()
    errs = {k: v for k, v in status.items()
            if k.endswith("_exceptions_total") and v}
    check(not errs, f"{rule.id}: node exceptions {errs}")
    return {"emit_sources": sources, "explain": explain}


# ------------------------------------------------------------- P1: tumbling
def make_tumbling_rows(seed: int, n_devices: int, n_rows: int):
    """Seeded sensor readings: (drains of JSON payloads, device index per
    row, float32 temperature per row as the JSON text reads back)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    n_rows = -(-n_rows // DRAIN_ROWS) * DRAIN_ROWS
    ids = rng.integers(0, n_devices, n_rows)
    ids[:n_devices] = rng.permutation(n_devices)  # every device reports
    temps = np.rint(rng.normal(20.0, 5.0, n_rows) * 100.0) / 100.0
    rows = [b'{"deviceId":"dev_%d","temperature":%.2f}' % it
            for it in zip(ids.tolist(), temps.tolist())]
    drains = [rows[i:i + DRAIN_ROWS] for i in range(0, n_rows, DRAIN_ROWS)]
    return drains, ids.reshape(-1, DRAIN_ROWS), \
        temps.astype(np.float32).reshape(-1, DRAIN_ROWS)


def feed_tumbling(rule: RuleHandle, topic: str, drains, emits: list,
                  min_rows: int, min_windows: int, window_s: float) -> dict:
    """Publish drains (cycling the pool when the clock asks for more than
    it holds) until `min_rows` went in and `min_windows` whole windows
    closed after warm-up; returns what was sent and the compile marks."""
    from ekuiper_tpu.io import memory as mem

    sent = []  # drain index per publish, in order

    def publish() -> None:
        i = len(sent) % len(drains)
        mem.publish(topic, drains[i])
        sent.append(i)
        rule.wait_shallow()

    # warm-up: until one boundary has emitted — every executable of the
    # steady path (fold, pre-issue, merge, reset) has then run once
    deadline = time.time() + 600
    while not emits:
        publish()
        check(time.time() < deadline, "no window emitted within 600 s")
    warm = compile_marks()
    warm_rows = len(sent) * DRAIN_ROWS
    emits_at_warm = len(emits)
    # the first boundary after the mark closes a window that began before
    # it; the next `min_windows` are whole
    deadline = time.time() + 120 + 4 * min_windows * window_s
    while (len(sent) * DRAIN_ROWS < min_rows
           or len(emits) - emits_at_warm < min_windows + 1):
        publish()
        check(time.time() < deadline, "fed windows did not close in time")
    fed = compile_marks()
    return {"sent": sent, "rows": len(sent) * DRAIN_ROWS,
            "warm_rows": warm_rows,
            "whole_windows": len(emits) - emits_at_warm - 1,
            "compiles_in_fed_windows": fed["compiles"] - warm["compiles"]}


def fold_tumbling_emits(emits: list, n_devices: int):
    """Per device over every emitted window: Σc, Σ a·c, min mn, max mx."""
    import numpy as np

    cnt = np.zeros(n_devices, dtype=np.int64)
    tot = np.zeros(n_devices, dtype=np.float64)
    mn = np.full(n_devices, np.inf)
    mx = np.full(n_devices, -np.inf)
    for payload in emits:
        msgs = payload if isinstance(payload, list) else [payload]
        k = np.fromiter((int(m["deviceId"][4:]) for m in msgs), np.int64,
                        len(msgs))
        c = np.fromiter((m["c"] for m in msgs), np.int64, len(msgs))
        a = np.fromiter((m["a"] for m in msgs), np.float64, len(msgs))
        check(len(np.unique(k)) == len(k), "a device twice in one window")
        cnt[k] += c
        tot[k] += a * c
        mn[k] = np.minimum(mn[k], np.fromiter(
            (m["mn"] for m in msgs), np.float64, len(msgs)))
        mx[k] = np.maximum(mx[k], np.fromiter(
            (m["mx"] for m in msgs), np.float64, len(msgs)))
    return cnt, tot, mn, mx


def reference_tumbling(sent, ids, temps, n_devices: int):
    """The same four numbers from the rows themselves — numpy only."""
    import numpy as np

    order = np.asarray(sent)
    k = ids[order].ravel()
    t = temps[order].ravel()
    cnt = np.bincount(k, minlength=n_devices)
    tot = np.bincount(k, weights=t.astype(np.float64), minlength=n_devices)
    mn = np.full(n_devices, np.inf)
    mx = np.full(n_devices, -np.inf)
    np.minimum.at(mn, k, t)
    np.maximum.at(mx, k, t)
    return cnt, tot, mn, mx


def compare_tumbling(got, want, label: str) -> None:
    """Row conservation across windows: counts exact, sums and extrema to
    float32 accumulation tolerance."""
    import numpy as np

    cnt, tot, mn, mx = got
    rcnt, rtot, rmn, rmx = want
    bad = np.nonzero(cnt != rcnt)[0]
    check(len(bad) == 0,
          f"{label}: {len(bad)} devices miscounted, e.g. dev_{bad[:1]}: "
          f"{cnt[bad[:1]]} vs {rcnt[bad[:1]]} (Σ {cnt.sum()} vs "
          f"{rcnt.sum()})")
    check(bool(np.allclose(tot, rtot, rtol=1e-4, atol=1e-3)),
          f"{label}: Σ a·c off by up to {np.abs(tot - rtot).max()}")
    check(bool(np.allclose(mn, rmn, rtol=1e-6)), f"{label}: min differs")
    check(bool(np.allclose(mx, rmx, rtol=1e-6)), f"{label}: max differs")


def run_tumbling(engine: Engine, tag: str, rows, n_devices: int,
                 min_rows: int, min_windows: int, window_s: int,
                 extra_options=None, replay=None, inspect=None):
    """One served tumbling rule from create to drop, checked against the
    numpy reference. `replay` re-sends exactly the drains an earlier run
    sent; `inspect(rule)` adds facts read off the live rule before it is
    dropped. Returns (per-device numbers out of the sink, the drain order
    sent, facts for the phase line)."""
    from ekuiper_tpu.io import memory as mem

    drains, ids, temps = rows
    stream, topic, out = f"pipe_{tag}", f"smoke/{tag}/in", f"smoke/{tag}/out"
    engine.create_stream(stream, "deviceId STRING, temperature FLOAT", topic)
    emits: list = []
    unsub = mem.subscribe(out, lambda _t, payload: emits.append(payload))
    rule = engine.create_rule(
        f"rule_{tag}", P1_SQL.format(stream=stream, window_s=window_s), out,
        {**P1_OPTIONS, **(extra_options or {})})
    try:
        if replay is None:
            fed = feed_tumbling(rule, topic, drains, emits, min_rows,
                                min_windows, window_s)
        else:
            for i in replay:
                mem.publish(topic, drains[i])
                rule.wait_shallow()
            fed = {"sent": list(replay), "rows": len(replay) * DRAIN_ROWS}
        # stop feeding; the tail flushes at the next boundaries
        check(rule.topo.wait_idle(60.0), "topo never went idle")
        deadline = time.time() + 5 * window_s + 30
        while time.time() < deadline:
            seen = sum(m["c"] for p in list(emits)
                       for m in (p if isinstance(p, list) else [p]))
            if seen >= fed["rows"]:
                break
            time.sleep(0.2)
        facts = check_no_hidden_fallback(engine, rule)
        if inspect is not None:
            facts.update(inspect(rule))
    finally:
        unsub()
        engine.drop_rule(rule.id)
    facts.update({k: v for k, v in fed.items() if k != "sent"})
    facts["windows_emitted"] = len(emits)
    got = fold_tumbling_emits(emits, n_devices)
    compare_tumbling(got, reference_tumbling(fed["sent"], ids, temps,
                                             n_devices), f"rule_{tag}")
    check(fed.get("compiles_in_fed_windows", 0) == 0,
          f"{fed.get('compiles_in_fed_windows')} compiles inside the fed "
          "windows after warm-up")
    return got, fed["sent"], facts


def phase_tumbling(engine: Engine, seed: int, n_devices: int, min_rows: int,
                   min_windows: int, window_s: int) -> None:
    t0, m0 = time.time(), compile_marks()
    rows = make_tumbling_rows(seed, n_devices, min_rows)
    t_rows = time.time() - t0
    _, _, facts = run_tumbling(
        engine, "p1", rows, n_devices, min_rows, min_windows, window_s)
    # "device": the pre-issued fetch had landed at the boundary;
    # "device-async*": the emit worker waited for it
    check(any(k.startswith("device") and v > 0
              for k, v in facts["emit_sources"].items()),
          f"no boundary was served by a device fetch: "
          f"{facts['emit_sources']}")
    m1 = compile_marks()
    say(phase="P1 served tumbling", seconds=round(time.time() - t0, 2),
        make_rows_seconds=round(t_rows, 2),
        compile_seconds=round(m1["compile_s"] - m0["compile_s"], 2),
        compiles=m1["compiles"] - m0["compiles"], devices=n_devices,
        rows=facts["rows"], warm_rows=facts["warm_rows"],
        whole_windows=facts["whole_windows"],
        windows_emitted=facts["windows_emitted"],
        emit_sources=facts["emit_sources"],
        compiles_in_fed_windows=facts["compiles_in_fed_windows"],
        decoder="native", path=facts["explain"]["path"])


# ----------------------------------------------------------- P2: HLL state
def make_hll_rows(seed: int, n_keys: int, window_rows: int, n_windows: int):
    """Each count window holds every one of `n_keys` devices at least
    once, the rest drawn uniformly; uid uniform in [0, 5M)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    check(window_rows >= n_keys and window_rows % DRAIN_ROWS == 0,
          "window must hold every key and whole drains")
    ids = np.empty((n_windows, window_rows), dtype=np.int64)
    for w in range(n_windows):
        ids[w, :n_keys] = np.arange(n_keys)
        ids[w, n_keys:] = rng.integers(0, n_keys, window_rows - n_keys)
        rng.shuffle(ids[w])
    uids = rng.integers(0, 5_000_000, ids.shape)
    rows = [b'{"deviceId":"dev_%d","uid":%d}' % it
            for it in zip(ids.ravel().tolist(), uids.ravel().tolist())]
    drains = [rows[i:i + DRAIN_ROWS]
              for i in range(0, len(rows), DRAIN_ROWS)]
    return drains, ids, uids


def phase_hll(engine: Engine, seed: int, n_keys: int, window_rows: int,
              n_windows: int, sample: int):
    """The HLL count-window rule at deployment state size; returns the
    device's peak bytes in use (None where the backend reports none)."""
    import numpy as np

    from ekuiper_tpu.io import memory as mem

    t0, m0 = time.time(), compile_marks()
    drains, ids, uids = make_hll_rows(seed, n_keys, window_rows, n_windows)
    t_rows = time.time() - t0
    engine.create_stream("pipe_p2", "deviceId STRING, uid BIGINT",
                         "smoke/p2/in")
    emits: list = []
    unsub = mem.subscribe("smoke/p2/out", lambda _t, p: emits.append(p))
    rule = engine.create_rule(
        "rule_p2", P2_SQL.format(stream="pipe_p2", window_rows=window_rows),
        "smoke/p2/out", P2_OPTIONS)
    per_window = window_rows // DRAIN_ROWS
    marks = []
    for w in range(n_windows):
        for d in drains[w * per_window:(w + 1) * per_window]:
            mem.publish("smoke/p2/in", d)
            rule.wait_shallow()
        deadline = time.time() + 600
        while len(emits) <= w:  # count windows close on the row count
            time.sleep(0.05)
            check(time.time() < deadline,
                  f"count window {w} never emitted")
        check(rule.topo.wait_idle(60.0), "topo never went idle")
        marks.append(compile_marks())
    check(len(emits) == n_windows,
          f"{len(emits)} windows emitted for {n_windows} fed")
    # window 0 climbs the capacity ladder (one compile set per doubling);
    # after it every key is known and nothing may compile
    late = marks[-1]["compiles"] - marks[0]["compiles"]
    check(n_windows < 2 or late == 0,
          f"{late} compiles after the first count window")
    check(rule.fused.gb.capacity >= n_keys,
          f"capacity {rule.fused.gb.capacity} < {n_keys} keys")
    facts = check_no_hidden_fallback(engine, rule)
    rng = np.random.default_rng(seed + 1)
    keys = rng.choice(n_keys, size=min(sample, n_keys), replace=False)
    worst = 0.0
    exact_hits = 0
    for w, payload in enumerate(emits):
        check(len(payload) == n_keys,
              f"window {w}: {len(payload)} groups for {n_keys} keys")
        uniq = np.zeros(n_keys, dtype=np.int64)
        for m in payload:
            uniq[int(m["deviceId"][4:])] = m["uniq"]
        sel = np.isin(ids[w], keys)
        pairs = np.unique(np.stack([ids[w][sel], uids[w][sel]]), axis=1)
        exact = np.bincount(pairs[0], minlength=n_keys)[keys]
        err = np.abs(uniq[keys] - exact)
        tol = np.maximum(1.0, np.ceil(3 * HLL_STD_ERR * exact))
        bad = np.nonzero(err > tol)[0]
        check(len(bad) == 0,
              f"window {w}: {len(bad)} of {len(keys)} sampled keys outside "
              f"3σ of HLL's {HLL_STD_ERR:.1%}: dev_{keys[bad[:1]]} "
              f"{uniq[keys][bad[:1]]} vs {exact[bad[:1]]}")
        worst = max(worst, float((err / np.maximum(exact, 1)).max()))
        exact_hits += int((err == 0).sum())
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    state_bytes = sum(int(getattr(a, "nbytes", 0))
                      for a in rule.fused.state.values())
    unsub()
    engine.drop_rule(rule.id)
    m1 = compile_marks()
    say(phase="P2 HLL state", seconds=round(time.time() - t0, 2),
        make_rows_seconds=round(t_rows, 2),
        compile_seconds=round(m1["compile_s"] - m0["compile_s"], 2),
        compiles=m1["compiles"] - m0["compiles"], keys=n_keys,
        windows=n_windows, rows=n_windows * window_rows,
        capacity=rule.fused.gb.capacity, state_bytes=state_bytes,
        peak_bytes_in_use=peak, sampled_keys=len(keys),
        sampled_exact=exact_hits, worst_rel_err=round(worst, 4),
        compiles_after_first_window=late,
        emit_sources=facts["emit_sources"], decoder="native",
        path=facts["explain"]["path"])
    return peak


# -------------------------------------------------------- --chips 4: mesh
def phase_sharded(engine: Engine, seed: int, n_devices: int, min_rows: int,
                  min_windows: int, window_s: int, keys_axis: int) -> None:
    """The P1 rule sharded over a 1 x `keys_axis` mesh against the same
    rule on one chip, fed the same drains in the same order."""
    import jax

    def placement(rule: RuleHandle) -> dict:
        return {
            "placed": {
                leaf: sorted(str(s.device) for s in arr.addressable_shards)
                for leaf, arr in rule.fused.state.items()},
            "shard_stats": rule.fused.gb.shard_stats()}

    t0, m0 = time.time(), compile_marks()
    rows = make_tumbling_rows(seed, n_devices, min_rows)
    one, sent, f1 = run_tumbling(
        engine, "one", rows, n_devices, min_rows, min_windows, window_s)
    mesh = {"planOptimizeStrategy": {
        "mesh": {"rows": 1, "keys": keys_axis}}}
    many, _, f4 = run_tumbling(
        engine, "mesh", rows, n_devices, min_rows, min_windows, window_s,
        extra_options=mesh, replay=sent, inspect=placement)
    shards = f4["explain"].get("shards") or {}
    check(shards.get("mode") == "sharded", f"explain shards: {shards}")
    compare_tumbling(many, one, "sharded vs one-chip")
    for leaf, devs in f4["placed"].items():
        check(len(set(devs)) == keys_axis,
              f"state leaf {leaf} sits on {sorted(set(devs))}, not on "
              f"{keys_axis} distinct devices")
    stats = f4["shard_stats"]
    check(len(stats) == keys_axis and all(s["rows"] > 0 for s in stats),
          f"a shard folded no rows: {stats}")
    m1 = compile_marks()
    say(phase=f"sharded 1x{keys_axis} vs one chip",
        seconds=round(time.time() - t0, 2),
        compile_seconds=round(m1["compile_s"] - m0["compile_s"], 2),
        compiles=m1["compiles"] - m0["compiles"], devices=n_devices,
        rows=f4["rows"], windows_one_chip=f1["windows_emitted"],
        windows_sharded=f4["windows_emitted"], shards=shards,
        state_devices=f4["placed"]["act"],
        shard_rows=[s["rows"] for s in stats],
        emit_sources_one_chip=f1["emit_sources"],
        emit_sources_sharded=f4["emit_sources"],
        visible_devices=len(jax.devices()))


# ------------------------------------------------------------------- main
def phase_device(chips: int) -> dict:
    """P0: a TPU or nothing; the compile cache placed before any compile;
    the device known to the peaks table."""
    t0 = time.time()
    import jax

    from ekuiper_tpu.utils import jaxcache

    cache_dir = jaxcache.setup()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"jax found platform {devs[0].platform!r}, not a TPU")
    check(len(devs) >= chips, f"{len(devs)} chips visible, {chips} needed")
    from ekuiper_tpu.io import fastjson
    from ekuiper_tpu.observability import kernwatch

    spec = kernwatch.device_spec()
    check(spec.get("spec") is not None,
          f"device kind {spec.get('kind')!r} is not in kernwatch.PEAK_SPECS")
    check(fastjson.ensure_native(background=False),
          "the native JSON decoder did not build")
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(phase="P0 device", seconds=round(time.time() - t0, 2),
        platform=devs[0].platform, kind=devs[0].device_kind,
        visible=len(devs), peaks=spec["spec"], compile_cache=cache_dir,
        cache_entries_at_start=cached)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "chip_smoke"),
        help="directory for the server's store and config")
    args = ap.parse_args(argv)
    t0 = time.time()
    engine = None
    try:
        device = phase_device(args.chips)
        engine = Engine(os.path.join(args.out, f"run_{args.chips}chip"))
        if args.chips == 4:
            phase_sharded(engine, args.seed, n_devices=16_000,
                          min_rows=2_000_000, min_windows=3, window_s=2,
                          keys_axis=4)
        else:
            phase_tumbling(engine, args.seed, n_devices=10_000,
                           min_rows=2_000_000, min_windows=3, window_s=2)
            peak = phase_hll(engine, args.seed, n_keys=1_000_000,
                             window_rows=2_097_152, n_windows=2,
                             sample=2_000)
            check(peak is not None and peak >= 1.0e9,
                  f"peak device bytes {peak} < 1.0 GB")
        engine.close()
    except NoChip as exc:
        print(json.dumps({"ok": False, "error": str(exc)}), flush=True)
        return 2
    except Exception as exc:  # any failed phase fails the run
        traceback.print_exc()
        print(json.dumps({"ok": False,
                          "error": f"{type(exc).__name__}: {exc}"[:2000]}),
              flush=True)
        return 1
    say(phase="total", seconds=round(time.time() - t0, 2),
        **{k: v for k, v in compile_marks().items() if k != "storms"})
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # engine worker threads and jax state outlive main(); leave without
    # running interpreter teardown over them
    os._exit(code)
