"""Prometheus exposition, span tracing, metrics dump."""
import json
import time
import urllib.request

import pytest

from ekuiper_tpu.observability import prometheus
from ekuiper_tpu.observability.tracer import Tracer
from ekuiper_tpu.server.processors import StreamProcessor
from ekuiper_tpu.server.rest import RestApi, serve
from ekuiper_tpu.store import kv
import ekuiper_tpu.io.memory as mem


@pytest.fixture
def fresh_tracer():
    old = Tracer._instance
    Tracer._instance = Tracer()
    yield Tracer._instance
    Tracer._instance = old


@pytest.fixture
def api_server(mock_clock):
    store = kv.get_store()
    StreamProcessor(store).exec_stmt(
        'CREATE STREAM demo (deviceId STRING, temperature FLOAT) '
        'WITH (DATASOURCE="obs/demo", TYPE="memory", FORMAT="JSON")')
    api = RestApi(store)
    srv = serve(api, "127.0.0.1", 0)
    port = srv.server_address[1]

    def req(method, path, body=None, raw=False):
        data = json.dumps(body).encode() if body is not None else None
        r = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", data=data, method=method,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(r, timeout=5) as resp:
            payload = resp.read()
            return payload.decode() if raw else json.loads(payload or b"null")

    yield api, req
    api.rules.stop_all()
    srv.shutdown()


class TestPrometheus:
    def test_metrics_endpoint(self, api_server, mock_clock, fresh_tracer):
        api, req = api_server
        req("POST", "/rules", {
            "id": "obs1",
            "sql": "SELECT deviceId, temperature FROM demo",
            "actions": [{"memory": {"topic": "obs/out"}}]})
        api.rules.start("obs1")
        time.sleep(0.3)
        mem.publish("obs/demo", {"deviceId": "a", "temperature": 1.0})
        mock_clock.advance(20)
        time.sleep(0.3)
        text = req("GET", "/metrics", raw=True)
        assert "# TYPE kuiper_rule_status gauge" in text
        assert 'kuiper_rule_status{rule="obs1"} 1' in text
        assert 'kuiper_op_records_in_total{rule="obs1"' in text
        assert "kuiper_uptime_seconds" in text
        # shared-source subtopo nodes are scraped too
        assert 'op="demo"' in text

    def test_dump(self, api_server):
        api, req = api_server
        req("POST", "/rules", {
            "id": "obs2", "sql": "SELECT deviceId FROM demo",
            "actions": [{"log": {}}]})
        out = req("GET", "/metrics/dump")
        assert out["rules"] >= 1
        with open(out["file"]) as f:
            lines = [json.loads(ln) for ln in f.read().splitlines()]
        assert any(ln["rule"] == "obs2" for ln in lines)


class TestTracing:
    def test_trace_rule_spans(self, api_server, mock_clock, fresh_tracer):
        api, req = api_server
        req("POST", "/rules", {
            "id": "tr1",
            "sql": "SELECT deviceId, temperature FROM demo "
                   "WHERE temperature > 0",
            "actions": [{"memory": {"topic": "tr/out"}}]})
        api.rules.start("tr1")
        time.sleep(0.3)
        assert req("POST", "/rules/tr1/trace/start") == \
            "Tracing enabled for rule tr1."
        mem.publish("obs/demo", {"deviceId": "a", "temperature": 5.0})
        mock_clock.advance(20)
        deadline = time.time() + 5
        while time.time() < deadline and not fresh_tracer.rule_traces("tr1"):
            time.sleep(0.05)
        traces = req("GET", "/trace/rule/tr1")
        assert traces
        # the trace follows the ColumnBatch through the rule chain (plain
        # list/dict items ride the tracer's bounded fallback map since the
        # non-weakref-able fix, so the sink hop keeps the trace too)
        by_trace = {t: req("GET", f"/trace/{t}") for t in traces}
        chain = next(
            (spans for spans in by_trace.values()
             if {"filter", "project"} <= {s["op"] for s in spans}), None)
        assert chain is not None, {
            t: [s["op"] for s in spans] for t, spans in by_trace.items()}
        assert len({s["traceId"] for s in chain}) == 1
        # the shared source's spans (rule="__shared__") open the trace the
        # rule's own nodes continue: one root, every other span names a
        # parent that is in the trace
        assert {s["rule"] for s in chain} == {"tr1", "__shared__"}
        assert all(s["rows"] == 1 for s in chain if s["rule"] == "tr1")
        ids = {s["spanId"] for s in chain}
        assert [s["op"] for s in chain if not s["parentSpanId"]] == ["demo"]
        assert all(s["parentSpanId"] in ids
                   for s in chain if s["parentSpanId"])
        assert req("POST", "/rules/tr1/trace/stop") == \
            "Tracing disabled for rule tr1."
        assert not fresh_tracer.is_enabled("tr1")

    def test_disabled_rules_record_nothing(self, fresh_tracer):
        fresh_tracer.enable("other")
        fresh_tracer.begin("other", "op1", "Tuple", 1).end()
        fresh_tracer.begin("not_enabled", "op1", "Tuple", 1).end()
        assert fresh_tracer.rule_spans("other")
        assert fresh_tracer.rule_spans("not_enabled") == []

    def test_non_weakrefable_items_keep_trace(self, fresh_tracer):
        """Regression: plain lists/dicts (multi-row project output) used to
        silently drop trace propagation at the queue hop — they now ride
        the bounded fallback map."""
        t = fresh_tracer
        t.enable("r")
        span = t.begin("r", "project", "list", 3)
        item = {"deviceId": "a", "temperature": 1.0}
        t.tag(item)
        rows = [1, 2, 3]
        t.tag(rows)
        span.end()  # the receiving node's worker has a fresh context
        assert t.current() is None
        assert t.lookup(item) == (span.trace_id, span.span_id)
        assert t.lookup(rows) == (span.trace_id, span.span_id)

    def test_fallback_map_bounded_eviction(self, fresh_tracer):
        t = fresh_tracer
        t.enable("r")
        span = t.begin("r", "project", "list", 0)
        first = {"k": 0}
        t.tag(first)
        keep_alive = [{"k": i} for i in range(t.FALLBACK_CAP)]
        for d in keep_alive:
            t.tag(d)
        span.end()
        assert len(t._fallback_traces) <= t.FALLBACK_CAP
        assert t.lookup(first) is None  # oldest evicted, newest retained
        assert t.lookup(keep_alive[-1]) is not None

    def test_span_attributes_surface_in_dict_and_otlp(self, fresh_tracer):
        from ekuiper_tpu.observability.otlp import encode_span

        t = fresh_tracer
        t.enable("r")
        t.begin("r", "sink", "list", 2).end({"e2e_ms": 17})
        span = [s for s in t.rule_spans("r") if s["op"] == "sink"][0]
        assert span["attributes"] == {"e2e_ms": 17}
        plain = t.rule_spans("r")
        # attribute-less spans omit the key (legacy dict/bytes unchanged)
        t.begin("r", "op", "Tuple", 1).end()
        plain = [s for s in t.rule_spans("r") if s["op"] == "op"][0]
        assert "attributes" not in plain

        class S:  # minimal span shape for the encoder
            trace_id, span_id, parent_id = "t1", "s1", ""
            rule_id, op, start_ns, duration_us = "r", "sink", 5, 100
            kind, rows = "list", 2
            attrs = None

        base = encode_span(S())
        S.attrs = {"e2e_ms": 17}
        with_attr = encode_span(S())
        assert len(with_attr) > len(base)  # extra KeyValue appended
        assert b"e2e_ms" in with_attr and b"e2e_ms" not in base


class TestE2ELatency:
    """The tentpole: ingest→emit latency measured at the sink under the
    deterministic mock clock, exported through status JSON and the
    Prometheus histogram."""

    @staticmethod
    def _wait_topo(api, rid, timeout=10.0):
        """Poll until the rule's topo is live (start is async; a fixed
        sleep flakes on cold-compile runs)."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            rs = api.rules.state(rid)
            if rs is not None and rs.topo is not None:
                return rs.topo
            time.sleep(0.05)
        raise AssertionError(f"rule {rid} topo never came up")

    def _make_rule(self, api, req, rid="sle1"):
        req("POST", "/rules", {
            "id": rid,
            "sql": "SELECT deviceId, temperature FROM demo",
            "actions": [{"memory": {"topic": f"{rid}/out"}}]})
        api.rules.start(rid)
        return self._wait_topo(api, rid)

    def _wait_count(self, topo, n=1, timeout=5.0):
        deadline = time.time() + timeout
        while time.time() < deadline and topo.e2e_hist.count < n:
            time.sleep(0.05)
        return topo.e2e_hist.count

    def test_mock_clock_rule_reports_sane_p99(self, api_server, mock_clock,
                                              fresh_tracer):
        api, req = api_server
        topo = self._make_rule(api, req)
        for i in range(5):
            mem.publish("obs/demo", {"deviceId": f"d{i}", "temperature": 1.0})
        mock_clock.advance(20)  # one linger flush covers every row
        assert self._wait_count(topo, n=1) >= 1
        snap = topo.e2e_hist.snapshot()
        # every row ingested at mock t=0, linger-flushed at t=10, delivered
        # with the clock parked at t=20: samples are deterministically
        # 0..20ms — a sane p99 under the mock clock
        assert 0 <= snap["p50"] <= 20
        assert 0 <= snap["p99"] <= 20
        assert snap["max"] <= 20
        # rule status JSON carries the SLO summary
        status = req("GET", "/rules/sle1/status")
        assert status["e2e_latency_ms"]["count"] >= 1
        assert 0 <= status["e2e_latency_ms"]["p99"] <= 20
        # per-op histogram summaries ride the same status payload
        hist_keys = [k for k in status if k.endswith("process_latency_us_hist")]
        assert hist_keys and all(
            set(status[k]) == {"count", "p50", "p90", "p99", "max"}
            for k in hist_keys)
        # fleet-wide SLO view (sibling of /rules/usage/cpu)
        usage = req("GET", "/rules/usage/latency")
        assert usage["sle1"]["count"] >= 1
        assert 0 <= usage["sle1"]["p99"] <= 20

    def test_windowed_rule_records_e2e_at_boundary(self, api_server,
                                                   mock_clock, fresh_tracer):
        """The fused window path: emission happens on a TRIGGER dispatch
        (not the data dispatch), so the stamp must survive through the
        node's last-seen provenance. Under the mock clock the single batch
        is 10s old at the boundary — the sample is its true dwell."""
        api, req = api_server
        req("POST", "/rules", {
            "id": "slw1",
            "sql": "SELECT deviceId, avg(temperature) AS a FROM demo "
                   "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)",
            "actions": [{"memory": {"topic": "slw1/out"}}]})
        api.rules.start("slw1")
        topo = self._wait_topo(api, "slw1")
        for i in range(8):
            mem.publish("obs/demo", {"deviceId": f"d{i % 2}",
                                     "temperature": float(i)})
        mock_clock.advance(50)  # linger flush into the fused fold
        time.sleep(0.3)
        mock_clock.advance(10_000)  # boundary fires, window emits
        assert self._wait_count(topo, n=1, timeout=8.0) >= 1
        snap = topo.e2e_hist.snapshot()
        assert 10_000 <= snap["p99"] <= 11_000, snap  # dwell, ≤6.25% bucket

    def test_metrics_exposes_e2e_histogram(self, api_server, mock_clock,
                                           fresh_tracer):
        api, req = api_server
        topo = self._make_rule(api, req, rid="sle2")
        mem.publish("obs/demo", {"deviceId": "a", "temperature": 2.0})
        mock_clock.advance(20)
        assert self._wait_count(topo, n=1) >= 1
        text = req("GET", "/metrics", raw=True)
        assert "# TYPE kuiper_rule_e2e_latency_ms histogram" in text
        assert "# HELP kuiper_rule_e2e_latency_ms" in text
        bucket_lines = [ln for ln in text.splitlines()
                        if ln.startswith("kuiper_rule_e2e_latency_ms_bucket"
                                         '{rule="sle2"')]
        les = [ln.rsplit('le="', 1)[1].split('"')[0] for ln in bucket_lines]
        assert les[-1] == "+Inf"
        nums = [float(x) for x in les[:-1]]
        assert nums == sorted(nums)
        counts = [int(ln.split()[-1]) for ln in bucket_lines]
        assert counts == sorted(counts)  # cumulative
        count_line = [ln for ln in text.splitlines()
                      if ln.startswith("kuiper_rule_e2e_latency_ms_count"
                                       '{rule="sle2"')][0]
        assert int(count_line.split()[-1]) == counts[-1]
        assert f'kuiper_rule_e2e_latency_ms_sum{{rule="sle2"}}' in text
        # per-op latency quantiles render too
        assert 'kuiper_op_process_latency_quantile_us{' in text
        assert 'q="0.99"' in text
        assert 'kuiper_op_queue_wait_quantile_us{' in text

    def test_shared_subtopo_metrics_emitted_once(self, api_server,
                                                 mock_clock, fresh_tracer):
        """Regression: nodes reached via a shared subtopo were emitted once
        per referencing rule, double-counting records_*_total in any PromQL
        sum — they now render exactly once, under rule="__shared__"."""
        api, req = api_server
        self._make_rule(api, req, rid="shd1")
        self._make_rule(api, req, rid="shd2")
        mem.publish("obs/demo", {"deviceId": "a", "temperature": 1.0})
        mock_clock.advance(20)
        time.sleep(0.3)
        text = req("GET", "/metrics", raw=True)
        demo_in = [ln for ln in text.splitlines()
                   if ln.startswith("kuiper_op_records_in_total")
                   and 'op="demo"' in ln]
        assert len(demo_in) == 1, demo_in
        assert 'rule="__shared__"' in demo_in[0]
        # both rules' OWN nodes still render per rule
        for rid in ("shd1", "shd2"):
            assert any(f'rule="{rid}"' in ln for ln in text.splitlines()
                       if ln.startswith("kuiper_op_records_in_total"))
