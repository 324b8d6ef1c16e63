"""OTLP span export: the hand-encoded wire bytes (observability/otlp.py)
are decoded with protoc + google.protobuf against a schema derived from the
official opentelemetry-proto field numbers — an independent decoder, so an
encoding bug can't validate itself. Plus the HTTP batching exporter and the
tracer tee (reference pkg/tracer/manager.go:28-76)."""
import threading
import time
from http.server import BaseHTTPRequestHandler, HTTPServer

import pytest

from ekuiper_tpu.observability.otlp import (OtlpExporter,
                                            encode_export_request,
                                            from_config)
from ekuiper_tpu.observability.tracer import Span, Tracer

# Official opentelemetry-proto subset (field numbers from trace/v1/
# trace.proto, common/v1/common.proto, resource/v1/resource.proto,
# collector/trace/v1/trace_service.proto) — used ONLY as the decode schema.
OTLP_PROTO = """
syntax = "proto3";
package otlptest;

message AnyValue {
  oneof value {
    string string_value = 1;
    bool bool_value = 2;
    int64 int_value = 3;
    double double_value = 4;
  }
}
message KeyValue { string key = 1; AnyValue value = 2; }
message Resource { repeated KeyValue attributes = 1; }
message InstrumentationScope { string name = 1; string version = 2; }
message Span {
  bytes trace_id = 1;
  bytes span_id = 2;
  string trace_state = 3;
  bytes parent_span_id = 4;
  string name = 5;
  int32 kind = 6;
  fixed64 start_time_unix_nano = 7;
  fixed64 end_time_unix_nano = 8;
  repeated KeyValue attributes = 9;
}
message ScopeSpans {
  InstrumentationScope scope = 1;
  repeated Span spans = 2;
  string schema_url = 3;
}
message ResourceSpans {
  Resource resource = 1;
  repeated ScopeSpans scope_spans = 2;
  string schema_url = 3;
}
message ExportTraceServiceRequest { repeated ResourceSpans resource_spans = 1; }

service Noop { rpc Export(ExportTraceServiceRequest) returns (ExportTraceServiceRequest); }
"""


@pytest.fixture(scope="module")
def decoder():
    """protoc-compiled ExportTraceServiceRequest class."""
    from ekuiper_tpu.services.schema import ProtoServiceSchema

    schema = ProtoServiceSchema(OTLP_PROTO)
    cls, _ = schema.methods["Export"][1], schema.methods["Export"][2]
    return cls


def _spans():
    return [
        Span("t0000002a", "s00000001", "", "r1", "source", 1_000_000_123, 250,
             "ColumnBatch", 16),
        Span("t0000002a", "s00000002", "s00000001", "r1", "window_agg",
             1_001_000_000, 1250, "stage", 3, stage="fold"),
    ]


class TestEncoding:
    def test_decodes_with_official_schema(self, decoder):
        body = encode_export_request(_spans(), service_name="svc-x")
        req = decoder.FromString(body)
        assert len(req.resource_spans) == 1
        rs = req.resource_spans[0]
        res_attrs = {kv.key: kv.value.string_value
                     for kv in rs.resource.attributes}
        assert res_attrs == {"service.name": "svc-x"}
        assert rs.scope_spans[0].scope.name == "ekuiper_tpu.tracer"
        spans = rs.scope_spans[0].spans
        assert len(spans) == 2
        s0, s1 = spans
        assert len(s0.trace_id) == 16 and len(s0.span_id) == 8
        assert s0.trace_id == s1.trace_id  # same engine trace
        assert s0.span_id != s1.span_id
        assert s1.parent_span_id == s0.span_id  # deterministic id mapping
        assert s0.name == "r1/source" and s1.name == "r1/window_agg:fold"
        assert s0.kind == 1  # INTERNAL
        # the nanoseconds the span has, not a millisecond times 1e6
        assert s0.start_time_unix_nano == 1_000_000_123
        assert s0.end_time_unix_nano == s0.start_time_unix_nano + 250_000
        attrs = {kv.key: kv.value for kv in s1.attributes}
        assert attrs["op"].string_value == "window_agg"
        assert attrs["item.rows"].int_value == 3
        assert attrs["item.kind"].string_value == "stage"


class _Collector:
    """Minimal in-process OTLP/HTTP collector."""

    def __init__(self):
        self.bodies = []
        self.headers = []
        outer = self

        class H(BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                outer.bodies.append((self.path, self.rfile.read(n)))
                outer.headers.append(dict(self.headers))
                self.send_response(200)
                self.end_headers()

            def log_message(self, *a):
                pass

        self.srv = HTTPServer(("127.0.0.1", 0), H)
        self.port = self.srv.server_address[1]
        threading.Thread(target=self.srv.serve_forever, daemon=True).start()

    def close(self):
        self.srv.shutdown()


@pytest.fixture
def collector():
    c = _Collector()
    yield c
    c.close()


class TestExporter:
    def test_http_post_batch(self, collector, decoder):
        exp = OtlpExporter(f"127.0.0.1:{collector.port}",
                           batch_interval_ms=50)
        for s in _spans():
            exp.on_span(s)
        deadline = time.time() + 5
        while time.time() < deadline and not collector.bodies:
            time.sleep(0.02)
        exp.close()
        assert collector.bodies, "no export arrived"
        path, body = collector.bodies[0]
        assert path == "/v1/traces"
        assert collector.headers[0]["Content-Type"] == "application/x-protobuf"
        req = decoder.FromString(body)
        got = [s.name for rs in req.resource_spans
               for ss in rs.scope_spans for s in ss.spans]
        assert got == ["r1/source", "r1/window_agg:fold"]
        assert exp.stats()["exported"] == 2

    def test_collector_down_bounds_memory(self):
        exp = OtlpExporter("127.0.0.1:1", batch_max_spans=4,
                           batch_interval_ms=50)
        for _ in range(100):
            for s in _spans():
                exp.on_span(s)
        time.sleep(0.3)
        exp.close()
        st = exp.stats()
        assert st["exported"] == 0 and st["errors"] >= 1
        assert st["dropped"] > 0  # bounded, never blocked

    def test_tracer_tee(self, collector, decoder):
        tracer = Tracer()
        exp = OtlpExporter(f"127.0.0.1:{collector.port}",
                           batch_interval_ms=50)
        tracer.exporter = exp
        tracer.enable("r9")
        tracer.begin("r9", "decode", "dict", 1).end()
        tracer.begin("other_rule_not_traced", "decode", "dict", 1).end()
        deadline = time.time() + 5
        while time.time() < deadline and not collector.bodies:
            time.sleep(0.02)
        tracer.set_exporter(None)  # closes the exporter
        names = [s.name for _, b in collector.bodies
                 for rs in decoder.FromString(b).resource_spans
                 for ss in rs.scope_spans for s in ss.spans]
        assert names == ["r9/decode"]  # only traced rules tee to OTLP

    def test_config_gate_default_off(self):
        from ekuiper_tpu.utils.config import Config

        assert from_config(Config()) is None
        cfg = Config()
        cfg.open_telemetry.enable_remote_collector = True
        cfg.open_telemetry.remote_endpoint = "127.0.0.1:9"
        exp = from_config(cfg)
        assert exp is not None and exp.url == "http://127.0.0.1:9/v1/traces"
        exp.close()


def test_config_service_name_plumbs():
    from ekuiper_tpu.utils.config import Config

    cfg = Config()
    cfg.open_telemetry.enable_remote_collector = True
    cfg.open_telemetry.remote_endpoint = "127.0.0.1:9"
    cfg.open_telemetry.service_name = "edge-7"
    exp = from_config(cfg)
    assert exp.service_name == "edge-7"
    exp.close()
