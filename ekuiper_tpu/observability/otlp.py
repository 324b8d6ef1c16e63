"""OTLP/HTTP span export — the remote-collector tee of the tracer
(reference pkg/tracer/manager.go:28-45: otlptracehttp.New + WithInsecure;
every exported span ALSO stays in the local store, manager.go:62-76).

Spans are serialized as an OTLP `ExportTraceServiceRequest` protobuf and
POSTed to `http://<endpoint>/v1/traces` with content-type
application/x-protobuf. The message is hand-encoded against the official
opentelemetry-proto field numbers (trace/v1/trace.proto, common/v1/
common.proto, resource/v1/resource.proto) — protobuf wire bytes carry only
field numbers and wire types, so no schema compilation is needed at
runtime; tests/test_otlp.py cross-validates the bytes by decoding them
with protoc + google.protobuf against a spec-derived schema.

Export is config-gated OFF (utils/config.py OpenTelemetryConfig): zero
egress unless the operator points the engine at a collector.
"""
from __future__ import annotations

import hashlib
import struct
import threading
import urllib.request
from typing import Any, Dict, List, Optional

from ..utils.infra import logger

# ------------------------------------------------------ protobuf wire encode
_LEN = 2  # wire types
_VARINT = 0
_I64 = 1


def _varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _tag(field: int, wire: int) -> bytes:
    return _varint(field << 3 | wire)


def _ld(field: int, payload: bytes) -> bytes:
    """Length-delimited field (submessage / string / bytes)."""
    return _tag(field, _LEN) + _varint(len(payload)) + payload


def _str(field: int, s: str) -> bytes:
    return _ld(field, s.encode())


def _u64(field: int, v: int) -> bytes:
    """fixed64 (OTLP timestamps)."""
    return _tag(field, _I64) + struct.pack("<Q", v)


def _vint(field: int, v: int) -> bytes:
    return _tag(field, _VARINT) + _varint(v)


def _any_value(v: Any) -> bytes:
    # AnyValue: string_value=1 | bool_value=2 | int_value=3 | double_value=4
    if isinstance(v, bool):
        return _vint(2, 1 if v else 0)
    if isinstance(v, int):
        return _vint(3, v & 0xFFFFFFFFFFFFFFFF)
    if isinstance(v, float):
        return _tag(4, _I64) + struct.pack("<d", v)
    return _str(1, str(v))


def _kv(key: str, v: Any) -> bytes:
    # KeyValue: key=1, value=2
    return _str(1, key) + _ld(2, _any_value(v))


def _trace_id_bytes(tid: str) -> bytes:
    """Engine trace ids are short strings ("t0000002a"); OTLP requires 16
    opaque bytes — a deterministic digest keeps one engine trace one OTLP
    trace across batches and restarts."""
    return hashlib.md5(tid.encode()).digest()


def _span_id_bytes(sid: str) -> bytes:
    return hashlib.md5(sid.encode()).digest()[:8]


#: OTLP SpanKind: the engine's operator spans are INTERNAL(1)
_KIND_INTERNAL = 1


def encode_span(span) -> bytes:
    """observability.tracer.Span -> opentelemetry.proto.trace.v1.Span bytes.
    Field numbers: trace_id=1, span_id=2, parent_span_id=4, name=5, kind=6,
    start_time_unix_nano=7, end_time_unix_nano=8, attributes=9."""
    start_ns = span.start_ns
    end_ns = start_ns + span.duration_us * 1_000
    stage = getattr(span, "stage", "")
    out = _ld(1, _trace_id_bytes(span.trace_id))
    out += _ld(2, _span_id_bytes(span.span_id))
    if span.parent_id:
        out += _ld(4, _span_id_bytes(span.parent_id))
    out += _str(5, f"{span.rule_id}/{span.op}" + (f":{stage}" if stage
                                                  else ""))
    out += _vint(6, _KIND_INTERNAL)
    out += _u64(7, start_ns)
    out += _u64(8, end_ns)
    for k, v in (("rule", span.rule_id), ("op", span.op),
                 ("item.kind", span.kind), ("item.rows", span.rows)):
        out += _ld(9, _kv(k, v))
    # extra span attributes (e.g. the sink's end-to-end e2e_ms latency) —
    # absent on the common span, so legacy encodings are byte-identical
    for k, v in (getattr(span, "attrs", None) or {}).items():
        out += _ld(9, _kv(str(k), v))
    return out


def encode_export_request(spans: List[Any],
                          service_name: str = "ekuiper_tpu") -> bytes:
    """-> ExportTraceServiceRequest{resource_spans=1} bytes.
    ResourceSpans: resource=1, scope_spans=2; Resource: attributes=1;
    ScopeSpans: scope=1, spans=2; InstrumentationScope: name=1."""
    resource = _ld(1, _kv("service.name", service_name))
    scope = _str(1, "ekuiper_tpu.tracer")
    scope_spans = _ld(1, scope) + b"".join(_ld(2, encode_span(s))
                                           for s in spans)
    resource_spans = _ld(1, resource) + _ld(2, scope_spans)
    return _ld(1, resource_spans)


# ------------------------------------------------------------------ exporter
class OtlpExporter:
    """Batching background exporter. on_span() is called from dispatch hot
    paths — it only appends under a lock; serialization + HTTP happen on
    the flusher thread."""

    def __init__(self, endpoint: str, batch_max_spans: int = 512,
                 batch_interval_ms: int = 2000,
                 service_name: str = "ekuiper_tpu") -> None:
        if "://" not in endpoint:
            endpoint = "http://" + endpoint  # WithInsecure analogue
        self.url = endpoint.rstrip("/") + "/v1/traces"
        self.batch_max = batch_max_spans
        self.interval = batch_interval_ms / 1000.0
        self.service_name = service_name
        self._buf: List[Any] = []
        self._mu = threading.Lock()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self.dropped = 0
        self.exported = 0
        self.errors = 0
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="otlp-export")
        self._thread.start()

    def on_span(self, span) -> None:
        with self._mu:
            if len(self._buf) >= 4 * self.batch_max:
                self.dropped += 1  # collector down — bound memory, not block
                return
            self._buf.append(span)
            full = len(self._buf) >= self.batch_max
        if full:
            self._wake.set()

    def _run(self) -> None:
        while not self._stop.is_set():
            self._wake.wait(self.interval)
            self._wake.clear()
            self.flush()

    def flush(self) -> None:
        with self._mu:
            batch, self._buf = self._buf, []
        if not batch:
            return
        body = encode_export_request(batch, self.service_name)
        req = urllib.request.Request(
            self.url, data=body,
            headers={"Content-Type": "application/x-protobuf"})
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                resp.read()
            self.exported += len(batch)
        except Exception as e:
            self.errors += 1
            if self.errors in (1, 10) or self.errors % 100 == 0:
                logger.warning("otlp export to %s failed (%d so far): %s",
                               self.url, self.errors, e)

    def stats(self) -> Dict[str, int]:
        return {"exported": self.exported, "dropped": self.dropped,
                "errors": self.errors}

    def close(self) -> None:
        self._stop.set()
        self._wake.set()
        self._thread.join(timeout=5)
        self.flush()


def from_config(cfg) -> Optional[OtlpExporter]:
    """Build the exporter the boot sequence installs on the tracer when
    open_telemetry.enable_remote_collector is on (server/main.py)."""
    ot = cfg.open_telemetry
    if not ot.enable_remote_collector:
        return None
    return OtlpExporter(ot.remote_endpoint,
                        batch_max_spans=ot.batch_max_spans,
                        batch_interval_ms=ot.batch_interval_ms,
                        service_name=ot.service_name)
