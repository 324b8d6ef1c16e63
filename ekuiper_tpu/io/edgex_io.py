"""EdgeX Foundry message-bus source & sink.

Analogue of the reference's edgex connector
(`internal/io/edgex/source.go:34-316`, `sink.go:35-392`): events ride the
EdgeX message bus as JSON `MessageEnvelope`s whose payload is an Event DTO
(or an AddEventRequest wrapper when messageType="request"); readings carry
their value as a STRING plus a `valueType` tag, and the source maps them
back to typed values (`source.go:203-280` getValue). The reference links
the official go-mod-messaging client; this image bundles no EdgeX client
library, so the bus rides the repo's OWN transport clients instead — the
native MQTT 3.1.1 client (io/mqtt_native.py) or the RESP redis client
(io/redis_io.py), the same two brokers EdgeX itself deploys on.

Envelope shape (go-mod-messaging types.MessageEnvelope, JSON-marshaled:
[]byte payload encodes as base64):

    {"apiVersion": "v3", "receivedTopic": ..., "correlationID": ...,
     "contentType": "application/json", "payload": "<base64>"}

A raw (non-enveloped) Event JSON payload is also accepted on the source
side — some EdgeX deployments publish bare events on MQTT.
"""
from __future__ import annotations

import base64
import json
import threading
import uuid
from typing import Any, Callable, Dict, List, Optional

from ..utils.infra import EngineError, logger
from .contract import Sink, Source

API_VERSION = "v3"

# EdgeX value types (go-mod-core-contracts v4/common/constants.go)
VT_BOOL = "Bool"
VT_STRING = "String"
VT_UINT8, VT_UINT16, VT_UINT32, VT_UINT64 = ("Uint8", "Uint16", "Uint32",
                                             "Uint64")
VT_INT8, VT_INT16, VT_INT32, VT_INT64 = "Int8", "Int16", "Int32", "Int64"
VT_FLOAT32, VT_FLOAT64 = "Float32", "Float64"
VT_BINARY = "Binary"
VT_OBJECT = "Object"

_INT_TYPES = {VT_INT8, VT_INT16, VT_INT32, VT_INT64,
              VT_UINT8, VT_UINT16, VT_UINT32}
_INT_ARRAY_TYPES = {t + "Array" for t in _INT_TYPES} | {"Uint64Array"}


def decode_reading_value(reading: Dict[str, Any]):
    """Typed value of one BaseReading (ref source.go:203-280 getValue).
    Raises ValueError on an unparsable value (caller logs + skips, like
    the reference's warn-and-continue)."""
    vt = reading.get("valueType", VT_STRING)
    v = reading.get("value", "")
    if vt == VT_BOOL:
        low = str(v).strip().lower()
        if low in ("true", "1"):
            return True
        if low in ("false", "0"):
            return False
        raise ValueError(f"bad bool {v!r}")
    if vt in _INT_TYPES or vt == VT_UINT64:
        return int(str(v), 10)
    if vt in (VT_FLOAT32, VT_FLOAT64):
        return float(v)
    if vt == VT_STRING:
        return v
    if vt == VT_BINARY:
        raw = reading.get("binaryValue", "")
        return base64.b64decode(raw) if isinstance(raw, str) else bytes(raw)
    if vt == VT_OBJECT:
        return reading.get("objectValue")
    if vt.endswith("Array"):
        val = json.loads(v) if isinstance(v, str) else v
        if not isinstance(val, list):
            raise ValueError(f"bad array {v!r}")
        if vt == "BoolArray":
            return [bool(x) for x in val]
        if vt in _INT_ARRAY_TYPES:
            return [int(x) for x in val]
        if vt in ("Float32Array", "Float64Array"):
            # ref convertFloatArray: accepts ["1.2", ...] or [1.2, ...]
            return [float(x) for x in val]
        if vt == "StringArray":
            return [str(x) for x in val]
    # ref: "Not supported type, processed as string value"
    logger.warning("edgex: unsupported valueType %s treated as string", vt)
    return v


def infer_value_type(v: Any):
    """(valueType, formatted) for a result value (ref sink.go:195-292
    getValueType — Python has no sized ints, so ints map to Int64 and
    floats to Float64, matching the reference's reflect.Int/Float64)."""
    if v is None:
        raise ValueError("unsupported value nil")
    if isinstance(v, bool):
        return VT_BOOL, "true" if v else "false"
    if isinstance(v, int):
        return VT_INT64, str(v)
    if isinstance(v, float):
        return VT_FLOAT64, json.dumps(v)
    if isinstance(v, str):
        return VT_STRING, v
    if isinstance(v, (bytes, bytearray)):
        return VT_BINARY, bytes(v)
    if isinstance(v, (list, tuple)):
        vals = list(v)
        if vals and all(isinstance(x, bool) for x in vals):
            return "BoolArray", json.dumps(vals)
        if vals and all(isinstance(x, int) and not isinstance(x, bool)
                        for x in vals):
            return "Int64Array", json.dumps(vals)
        if vals and all(isinstance(x, (int, float))
                        and not isinstance(x, bool) for x in vals):
            return "Float64Array", json.dumps([float(x) for x in vals])
        if all(isinstance(x, str) for x in vals):
            return "StringArray", json.dumps(vals)
        return VT_OBJECT, vals
    if isinstance(v, dict):
        return VT_OBJECT, v
    raise ValueError(f"unsupported value {v!r} ({type(v).__name__})")


# --------------------------------------------------------------- transports
class _Bus:
    """Minimal pub/sub transport facade over the in-repo clients."""

    def subscribe(self, topic: str, on_msg: Callable[[str, bytes], None]) -> None:
        raise NotImplementedError

    def publish(self, topic: str, payload: bytes) -> None:
        raise NotImplementedError

    def close(self) -> None:
        pass


class _MqttBus(_Bus):
    def __init__(self, props: Dict[str, Any]) -> None:
        from . import mqtt as mqtt_mod

        self._server = str(props.get("server",
                                     props.get("mqttServer",
                                               "tcp://127.0.0.1:1883")))
        self._client_id = str(props.get("clientid",
                                        f"ekuiper-edgex-{uuid.uuid4().hex[:8]}"))
        self._cli = mqtt_mod._acquire(
            self._server, self._client_id,
            str(props.get("username", "")), str(props.get("password", "")))
        self._mqtt_mod = mqtt_mod
        self._topics: List[str] = []

    def subscribe(self, topic: str, on_msg) -> None:
        def cb(_client, _userdata, msg):
            on_msg(msg.topic, bytes(msg.payload))

        self._cli.message_callback_add(topic, cb)
        self._cli.subscribe(topic)
        self._topics.append(topic)

    def publish(self, topic: str, payload: bytes) -> None:
        self._cli.publish(topic, payload)

    def close(self) -> None:
        # the pooled client may outlive this bus (shared clientid): drop
        # our callbacks + subscriptions so a closed source stops ingesting
        for topic in self._topics:
            try:
                self._cli.message_callback_remove(topic)
                self._cli.unsubscribe(topic)
            except Exception:
                pass
        self._topics = []
        self._mqtt_mod._release(self._server, self._client_id)


class _RedisBus(_Bus):
    """EdgeX redis message bus: topics are pub/sub channels; EdgeX maps
    topic separators '/' to '.' on redis (go-mod-messaging redis impl)."""

    def __init__(self, props: Dict[str, Any]) -> None:
        from .redis_io import _client_from_props

        self._props = dict(props)
        self._make = lambda: _client_from_props(self._props)
        self._pub = None
        self._sub_threads: List[threading.Thread] = []
        self._sub_clients: List[Any] = []
        self._stop = threading.Event()
        self._lock = threading.Lock()

    @staticmethod
    def _chan(topic: str) -> str:
        return topic.replace("/", ".").replace("#", "*").replace("+", "*")

    def subscribe(self, topic: str, on_msg) -> None:
        chan = self._chan(topic)
        pattern = "*" in chan

        def loop() -> None:
            from ..utils.backoff import Backoff

            bo = Backoff(base_s=0.5, cap_s=30.0)
            while not self._stop.is_set():
                cli = None
                try:
                    cli = self._make()
                    cli.connect()
                    cli._sock.settimeout(None)
                    with self._lock:
                        self._sub_clients.append(cli)
                    cli.send("PSUBSCRIBE" if pattern else "SUBSCRIBE", chan)
                    bo.reset()
                    while not self._stop.is_set():
                        reply = cli.read_reply()
                        if not isinstance(reply, list) or len(reply) < 3:
                            continue
                        kind = reply[0]
                        kind = kind.decode() if isinstance(kind, bytes) else kind
                        if kind == "message":
                            t, payload = reply[1], reply[2]
                        elif kind == "pmessage" and len(reply) >= 4:
                            t, payload = reply[2], reply[3]
                        else:
                            continue
                        t = t.decode() if isinstance(t, bytes) else str(t)
                        if isinstance(payload, str):
                            payload = payload.encode()
                        on_msg(t.replace(".", "/"), bytes(payload))
                except Exception as exc:
                    if cli is not None:  # close + forget the dead client
                        with self._lock:
                            if cli in self._sub_clients:
                                self._sub_clients.remove(cli)
                        try:
                            cli.close()
                        except Exception:
                            pass
                    if self._stop.is_set():
                        return
                    logger.warning("edgex redis bus reconnect: %s", exc)
                    if bo.wait(self._stop):
                        return

        th = threading.Thread(target=loop, daemon=True, name="edgex-redis-sub")
        th.start()
        self._sub_threads.append(th)

    def publish(self, topic: str, payload: bytes) -> None:
        with self._lock:
            if self._pub is None:
                self._pub = self._make()
                self._pub.connect()
            self._pub.command("PUBLISH", self._chan(topic), payload)

    def close(self) -> None:
        self._stop.set()
        with self._lock:
            clients = list(self._sub_clients)
            self._sub_clients.clear()
            pub, self._pub = self._pub, None
        for c in clients:
            try:
                c.close()
            except Exception:
                pass
        if pub is not None:
            pub.close()


def _make_bus(props: Dict[str, Any]) -> _Bus:
    proto = str(props.get("protocol", props.get("type", "redis"))).lower()
    if proto in ("mqtt", "tcp"):
        return _MqttBus(props)
    if proto in ("redis", "redis-pubsub"):
        return _RedisBus(props)
    raise EngineError(f"edgex: unsupported message bus protocol {proto!r}")


# ------------------------------------------------------------------- source
class EdgexSource(Source):
    """Subscribe to an EdgeX bus topic and ingest one message per event:
    {resourceName: typed value} plus reading/event metadata (ref
    source.go:107-201 Subscribe)."""

    def __init__(self) -> None:
        self.topic = ""
        self.message_type = "event"
        self.props: Dict[str, Any] = {}
        self._bus: Optional[_Bus] = None

    def configure(self, datasource: str, props: Dict[str, Any]) -> None:
        self.topic = (datasource or str(props.get("topic", ""))
                      or "rules-events")
        mt = str(props.get("messageType", "event"))
        if mt not in ("event", "request"):
            raise EngineError(f"edgex: bad messageType {mt!r}")
        self.message_type = mt
        self.props = props

    def open(self, ingest) -> None:
        self._bus = _make_bus(self.props)

        def on_msg(topic: str, payload: bytes) -> None:
            try:
                result, meta = self._decode(payload)
            except Exception as exc:
                logger.error("edgex source: bad payload on %s: %s", topic, exc)
                return
            if result:
                ingest(result, meta)
            else:
                logger.warning("edgex source: event with no readings ignored")

        self._bus.subscribe(self.topic, on_msg)

    def _decode(self, payload: bytes):
        doc = json.loads(payload)
        correlation = ""
        if isinstance(doc, dict) and "payload" in doc and "event" not in doc \
                and "readings" not in doc:
            # MessageEnvelope: payload is base64 of the event JSON
            correlation = str(doc.get("correlationID", ""))
            inner = doc.get("payload", "")
            raw = (base64.b64decode(inner) if isinstance(inner, str)
                   else bytes(inner))
            doc = json.loads(raw)
        event = doc.get("event", doc) if self.message_type == "request" \
            else (doc.get("event") or doc)
        readings = event.get("readings") or []
        result: Dict[str, Any] = {}
        meta: Dict[str, Any] = {}
        for r in readings:
            name = r.get("resourceName", "")
            if not name:
                logger.warning("edgex: reading without resourceName skipped")
                continue
            try:
                result[name] = decode_reading_value(r)
            except Exception as exc:
                logger.warning("edgex: fail to get value for %s: %s",
                               name, exc)
                continue
            rmeta = {"id": r.get("id"), "origin": r.get("origin"),
                     "deviceName": r.get("deviceName"),
                     "profileName": r.get("profileName"),
                     "valueType": r.get("valueType")}
            if r.get("mediaType"):
                rmeta["mediaType"] = r["mediaType"]
            meta[name] = rmeta
        if result:
            meta.update({
                "id": event.get("id"),
                "deviceName": event.get("deviceName"),
                "profileName": event.get("profileName"),
                "sourceName": event.get("sourceName"),
                "origin": event.get("origin"),
                "tags": event.get("tags"),
                "correlationid": correlation,
            })
        return result, meta

    def close(self) -> None:
        if self._bus is not None:
            self._bus.close()


# --------------------------------------------------------------------- sink
class EdgexSink(Sink):
    """Publish result rows as EdgeX events (ref sink.go EdgexMsgBusSink).
    One event per collect(): every row's fields become readings, with
    value types inferred from the Python values, or overridden per
    reading through the `metadata` field (ref getMeta/readingMeta)."""

    def __init__(self) -> None:
        self.props: Dict[str, Any] = {}
        self.topic = ""
        self.topic_prefix = ""
        self.message_type = "event"
        self.content_type = "application/json"
        self.device_name = "ekuiper"
        self.profile_name = "ekuiperProfile"
        self.source_name = ""
        self.metadata_field = ""
        self.fields: List[str] = []
        self.data_field = ""
        self._bus: Optional[_Bus] = None

    def configure(self, props: Dict[str, Any]) -> None:
        self.props = props
        self.topic = str(props.get("topic", ""))
        self.topic_prefix = str(props.get("topicPrefix", ""))
        if self.topic and self.topic_prefix:
            raise EngineError(
                "not allow to specify both topic and topicPrefix, "
                "please set one only")
        mt = str(props.get("messageType", "event"))
        if mt not in ("event", "request"):
            raise EngineError(f"specified wrong messageType value {mt}")
        self.message_type = mt
        self.content_type = str(props.get("contentType", "application/json"))
        if mt == "event" and self.content_type != "application/json":
            raise EngineError(
                f"specified wrong contentType value {self.content_type}: "
                "only 'application/json' is supported if messageType is "
                "event")
        self.device_name = str(props.get("deviceName", "ekuiper"))
        self.profile_name = str(props.get("profileName", "ekuiperProfile"))
        self.source_name = str(props.get("sourceName", ""))
        self.metadata_field = str(props.get("metadata", ""))
        self.fields = list(props.get("fields") or [])
        self.data_field = str(props.get("dataField", ""))

    def connect(self) -> None:
        self._bus = _make_bus(self.props)

    # -------------------------------------------------------------- events
    def _rows(self, item: Any) -> List[Dict[str, Any]]:
        if isinstance(item, dict):
            rows = [item]
        elif isinstance(item, list):
            rows = [r for r in item if isinstance(r, dict)]
        else:
            try:  # columnar emissions (ColumnBatch) flatten to rows
                rows = item.to_messages()
            except AttributeError:
                raise EngineError(f"edgex sink: invalid data {item!r}")
        if self.data_field:
            out = []
            for r in rows:
                v = r.get(self.data_field)
                if isinstance(v, dict):
                    out.append(v)
                elif isinstance(v, list):
                    out.extend(x for x in v if isinstance(x, dict))
            rows = out
        if self.fields:
            rows = [{k: r[k] for k in self.fields if k in r} for r in rows]
        return rows

    def _event_meta(self, rows: List[Dict[str, Any]]):
        """Event-level + per-reading overrides from the metadata field
        (ref sink.go getMeta: the row's `metadata` entry may carry event
        fields and {reading: {...}} decorations)."""
        ev: Dict[str, Any] = {}
        readings_meta: Dict[str, Dict[str, Any]] = {}
        if self.metadata_field:
            for row in rows:
                md = row.get(self.metadata_field)
                if not isinstance(md, dict):
                    continue
                for k in ("id", "deviceName", "profileName", "sourceName",
                          "origin"):
                    if k in md and md[k] is not None:
                        ev.setdefault(k, md[k])
                for k, v in md.items():
                    if isinstance(v, dict):
                        readings_meta.setdefault(k, {}).update(v)
        return ev, readings_meta

    def produce_event(self, item: Any) -> Dict[str, Any]:
        from ..utils import timex

        rows = self._rows(item)
        ev_meta, readings_meta = self._event_meta(rows)
        origin = int(ev_meta.get("origin") or timex.now_ms() * 1_000_000)
        event = {
            "apiVersion": API_VERSION,
            "id": str(ev_meta.get("id") or uuid.uuid4()),
            "deviceName": str(ev_meta.get("deviceName") or self.device_name),
            "profileName": str(ev_meta.get("profileName")
                               or self.profile_name),
            "sourceName": str(ev_meta.get("sourceName") or self.source_name),
            "origin": origin,
            "readings": [],
        }
        for row in rows:
            for k, v in row.items():
                if k == self.metadata_field or v is None:
                    continue
                rmeta = readings_meta.get(k) or {}
                try:
                    if rmeta.get("valueType"):
                        vt = str(rmeta["valueType"])
                        _, formatted = infer_value_type(v)
                        if vt == VT_OBJECT:
                            formatted = v
                        elif vt == VT_BINARY and not isinstance(
                                formatted, (bytes, bytearray)):
                            formatted = str(formatted).encode()
                    else:
                        vt, formatted = infer_value_type(v)
                except (ValueError, TypeError) as exc:
                    # ref logs and continues on a bad reading (sink.go:181)
                    logger.error("edgex sink: %s", exc)
                    continue
                reading = {
                    "id": str(rmeta.get("id") or uuid.uuid4()),
                    "origin": int(rmeta.get("origin") or origin),
                    "deviceName": str(rmeta.get("deviceName")
                                      or event["deviceName"]),
                    "profileName": str(rmeta.get("profileName")
                                       or event["profileName"]),
                    "resourceName": k,
                    "valueType": vt,
                }
                if vt == VT_BINARY:
                    reading["binaryValue"] = base64.b64encode(
                        formatted).decode()
                    reading["mediaType"] = str(rmeta.get("mediaType")
                                               or "application/text")
                    reading["value"] = ""
                elif vt == VT_OBJECT:
                    reading["objectValue"] = formatted
                    reading["value"] = ""
                else:
                    reading["value"] = formatted
                event["readings"].append(reading)
        return event

    def _topic_for(self, event: Dict[str, Any]) -> str:
        if self.topic:
            return self.topic
        if self.topic_prefix:
            return "/".join([self.topic_prefix, event["profileName"],
                             event["deviceName"],
                             event["sourceName"] or "ekuiper"])
        return "application"

    def collect(self, item: Any) -> None:
        event = self.produce_event(item)
        if not event["readings"]:
            return
        if self.message_type == "request":
            payload = {"apiVersion": API_VERSION,
                       "requestId": str(uuid.uuid4()), "event": event}
        else:
            payload = event
        raw = json.dumps(payload, default=str).encode()
        envelope = {
            "apiVersion": API_VERSION,
            "correlationID": str(uuid.uuid4()),
            "contentType": self.content_type,
            "payload": base64.b64encode(raw).decode(),
        }
        self._bus.publish(self._topic_for(event),
                          json.dumps(envelope).encode())

    def close(self) -> None:
        if self._bus is not None:
            self._bus.close()
