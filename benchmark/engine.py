"""The system under test, as the benchmark holds it.

A copy of `chip_smoke.py`'s `Engine`, `RuleHandle`, `compile_marks` and
`check_no_hidden_fallback` (PR 22), kept here so that later PRs may change the
smoke but not the yardstick. The server starts through its own start-up on an
ephemeral port; streams and rules go over REST; the benchmark reaches inside
only for flow control (the fused node's input queue) and for the two facts no
REST route answers (native decoder, compile totals).
"""
from __future__ import annotations

import json
import os
import shutil
import time
import urllib.error
import urllib.request


class EngineFailure(Exception):
    """The engine refused a request or did not reach a state in time."""


class Engine:
    """The server, started through `server.main.start_up` with its store
    under `out_dir`; everything else goes over REST."""

    def __init__(self, out_dir: str) -> None:
        from ekuiper_tpu.server.main import start_up

        # a fresh store each run: streams and rules of an earlier run in
        # this directory are not this run's
        shutil.rmtree(os.path.join(out_dir, "store"), ignore_errors=True)
        os.makedirs(out_dir, exist_ok=True)
        cfg_path = os.path.join(out_dir, "server_config.json")
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
            raise EngineFailure(
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
            if str(st.get("status", "")).startswith("stopped"):
                raise EngineFailure(f"rule {rule_id} did not start: {st}")
            time.sleep(0.1)
        raise EngineFailure(f"rule {rule_id} not running after 600 s")

    def close(self) -> None:
        """Stop every rule and the server (the self-tests start several
        engines in one process; a run of the benchmark just exits)."""
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

    def wait_shallow(self, depth: int) -> None:
        """Block while the fused node's input queue holds more than `depth`
        batches, so drop-oldest never fires in a closed loop."""
        deadline = time.time() + 120
        while self.fused.inq.qsize() > depth:
            time.sleep(0.002)
            if time.time() > deadline:
                raise EngineFailure("fused input queue stuck for 120 s")


def compile_marks() -> dict:
    """Cumulative compile accounting: events at watched jit sites
    (devwatch) and seconds spent lowering+compiling them (aotcache)."""
    from ekuiper_tpu.observability import devwatch
    from ekuiper_tpu.runtime import aotcache

    tot = devwatch.registry().totals()
    return {"compiles": tot["compiles"], "storms": tot["storms"],
            "compile_s": aotcache.stats().snapshot()["build_seconds"]}


def metric_total(text: str, family: str, **labels: str) -> float:
    """Sum of a Prometheus family's samples in `/metrics` text, over the
    lines that carry every given label value."""
    wants = [f'{k}="{v}"' for k, v in labels.items()]
    return sum(float(line.rsplit(" ", 1)[1])
               for line in text.splitlines()
               if line.startswith(family) and all(w in line for w in wants))


def metric_growth(marks0: dict, marks1: dict, family: str,
                  **labels: str) -> float:
    """Growth of a counter family between two `/metrics` snapshots."""
    return (metric_total(marks1["metrics"], family, **labels)
            - metric_total(marks0["metrics"], family, **labels))


BAD_EVENTS = ("aot_degraded", "sliding_impl_fallback", "compile_storm",
              "warmup_failure")


def fallback_facts(engine: Engine, rule: RuleHandle) -> dict:
    """What must hold in every run for it to be this system and not a slower
    cousin: the device path planned, the native decoder serving, nothing
    degraded, dropped, or answered from the host. Each fact is a count that
    has to be 0 (chip_smoke.py `check_no_hidden_fallback`, as numbers)."""
    from ekuiper_tpu.io import fastjson

    explain = engine.rest("GET", f"/rules/{rule.id}/explain")
    metrics = engine.rest("GET", "/metrics", raw=True)
    events = engine.rest("GET", "/diagnostics/events")["events"]
    sources = rule.emit_sources()
    status = rule.status()
    native = rule.src._fast_spec is not None and fastjson._load() is not None
    return {
        "not_device_fused": int(explain.get("path") != "device-fused"),
        "python_decoder": int(not native),
        "expr_host_fallback": metric_total(
            metrics, "kuiper_expr_host_fallback_total"),
        "dropped_items": metric_total(metrics, "kuiper_node_dropped_total"),
        "bad_flight_events": sum(
            1 for e in events if e.get("kind") in BAD_EVENTS),
        "compile_storms": compile_marks()["storms"],
        "backstop_windows": sources.get("backstop", 0),
        "no_window_emitted": int(sum(sources.values()) == 0),
        "node_exceptions": sum(
            v for k, v in status.items()
            if k.endswith("_exceptions_total") and v),
    }
