"""InfluxDB sinks — line-protocol over plain HTTP, no client library.

Analogue of the reference's influx/influx2 extensions
(`extensions/impl/influx/influx.go:30-43` v1 conf {addr, username,
password, database, measurement} and `extensions/impl/influx2/
influx2.go:38-50` v2 conf {addr, token, org, bucket, precision,
measurement}, both sharing WriteOptions {precision, tags, tsFieldName}
from `extensions/impl/tspoint/transform.go:29-32`). The reference links
the vendor clients; the wire format is just line protocol over HTTP
POST, so this implementation speaks it directly:

    measurement,tag=v field1=1.5,field2="s",n=3i 1700000000000

v1 posts to /write?db=<database>&precision=<p> (basic auth), v2 to
/api/v2/write?org=<org>&bucket=<bucket>&precision=<p> (Token auth).
"""
from __future__ import annotations

import json
import re
import urllib.parse
import urllib.request
from typing import Any, Dict, List, Optional

from ..utils import timex
from ..utils.infra import EngineError, logger
from .contract import Sink

_TMPL_RE = re.compile(r"{{\s*\.(\w+)\s*}}")


def _escape(s: str, *, quoted: bool = False) -> str:
    if quoted:  # string field value
        return s.replace("\\", "\\\\").replace('"', '\\"')
    # measurement/tag/field keys and tag values
    return (s.replace("\\", "\\\\").replace(",", "\\,")
            .replace("=", "\\=").replace(" ", "\\ "))


def _field_value(v: Any) -> Optional[str]:
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, int):
        return f"{v}i"
    if isinstance(v, float):
        return json.dumps(v)
    if isinstance(v, str):
        return f'"{_escape(v, quoted=True)}"'
    return None  # arrays/objects are not line-protocol fields


def _render_tag(template: str, row: Dict[str, Any]) -> str:
    """Tags may be static strings or '{{.field}}' templates
    (tspoint WriteOptions.Tags)."""
    return _TMPL_RE.sub(lambda m: str(row.get(m.group(1), "")), template)


_MS_TO_PRECISION = {"ns": 1_000_000, "us": 1_000, "ms": 1, "s": 1 / 1000}


def to_lines(rows: List[Dict[str, Any]], measurement: str,
             tags: Dict[str, str], ts_field: str, precision: str) -> bytes:
    lines = []
    for row in rows:
        tag_parts = []
        for k, tmpl in tags.items():
            v = _render_tag(str(tmpl), row)
            if v:
                tag_parts.append(f"{_escape(k)}={_escape(v)}")
        # like the reference, ALL row fields (including tag-source ones)
        # stay fields; only the ts field is excluded
        # (tspoint/transform.go:112-117 Fields: mm)
        fields = []
        for k, v in row.items():
            if k == ts_field or v is None:
                continue
            fv = _field_value(v)
            if fv is not None:
                fields.append(f"{_escape(k)}={fv}")
        if not fields:
            continue
        line = _escape(measurement)
        if tag_parts:
            line += "," + ",".join(tag_parts)
        line += " " + ",".join(fields)
        if ts_field:
            ts = row.get(ts_field)
            if not isinstance(ts, (int, float)):
                continue  # ref errors the row; we drop it (counted upstream)
            # ref getTime: the field value is ALREADY in the precision unit
            line += f" {int(ts)}"
        else:
            # ref uses now() when no ts field is configured
            line += f" {int(timex.now_ms() * _MS_TO_PRECISION[precision])}"
        lines.append(line)
    return "\n".join(lines).encode()


class _BaseInfluxSink(Sink):
    def __init__(self) -> None:
        self.measurement = ""
        self.tags: Dict[str, str] = {}
        self.ts_field = ""
        self.precision = "ms"
        self._url = ""
        self._headers: Dict[str, str] = {}

    def _common(self, props: Dict[str, Any]) -> None:
        self.measurement = str(props.get("measurement", ""))
        if not self.measurement:
            raise EngineError("influx sink requires measurement")
        self.tags = dict(props.get("tags") or {})
        self.ts_field = str(props.get("tsFieldName", ""))
        self.precision = str(props.get("precision", "ms"))
        if self.precision not in _MS_TO_PRECISION:
            raise EngineError(f"bad precision {self.precision!r} "
                              "(want ns/us/ms/s)")

    def collect(self, item: Any) -> None:
        if isinstance(item, dict):
            rows = [item]
        elif isinstance(item, list):
            rows = [r for r in item if isinstance(r, dict)]
        else:
            try:  # columnar emissions flatten to rows
                rows = item.to_messages()
            except AttributeError:
                raise EngineError(f"influx sink: invalid data {item!r}")
        body = to_lines(rows, self.measurement, self.tags, self.ts_field,
                        self.precision)
        if not body:
            return
        req = urllib.request.Request(self._url, data=body, method="POST",
                                     headers=self._headers)
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                resp.read()
        except urllib.error.HTTPError as exc:
            detail = exc.read().decode(errors="replace")[:300]
            raise EngineError(
                f"influx write failed: {exc.code} {detail}") from exc


class InfluxSink(_BaseInfluxSink):
    """InfluxDB v1: POST /write?db=...&precision=... with basic auth."""

    def configure(self, props: Dict[str, Any]) -> None:
        self._common(props)
        addr = str(props.get("addr", "http://127.0.0.1:8086")).rstrip("/")
        database = str(props.get("database", ""))
        if not database:
            raise EngineError("influx sink requires database")
        q = urllib.parse.urlencode({"db": database,
                                    "precision": self.precision})
        self._url = f"{addr}/write?{q}"
        self._headers = {"Content-Type": "text/plain; charset=utf-8"}
        user = str(props.get("username", ""))
        if user:
            import base64

            cred = base64.b64encode(
                f"{user}:{props.get('password', '')}".encode()).decode()
            self._headers["Authorization"] = f"Basic {cred}"


class Influx2Sink(_BaseInfluxSink):
    """InfluxDB v2: POST /api/v2/write?org=...&bucket=... with Token auth."""

    def configure(self, props: Dict[str, Any]) -> None:
        self._common(props)
        addr = str(props.get("addr", "http://127.0.0.1:8086")).rstrip("/")
        org, bucket = str(props.get("org", "")), str(props.get("bucket", ""))
        if not (org and bucket):
            raise EngineError("influx2 sink requires org and bucket")
        q = urllib.parse.urlencode({"org": org, "bucket": bucket,
                                    "precision": self.precision})
        self._url = f"{addr}/api/v2/write?{q}"
        self._headers = {"Content-Type": "text/plain; charset=utf-8"}
        token = str(props.get("token", ""))
        if token:
            self._headers["Authorization"] = f"Token {token}"
