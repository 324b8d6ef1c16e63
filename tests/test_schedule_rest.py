"""Cron/duration rule scheduling + the REST surface additions (tags,
uploads, config patch, data import/export, JWT auth)."""
import base64
import hashlib
import hmac
import json
import time
import urllib.error
import urllib.request

import pytest

from ekuiper_tpu.planner.planner import RuleDef
from ekuiper_tpu.runtime.rule import RuleState, RunState
from ekuiper_tpu.server.processors import StreamProcessor
from ekuiper_tpu.server.rest import RestApi, serve
from ekuiper_tpu.store import kv
from ekuiper_tpu.utils import cron as cronlib
from ekuiper_tpu.utils.config import get_config
import ekuiper_tpu.io.memory as mem


class TestCronParser:
    def test_next_fire(self):
        c = cronlib.Cron("*/15 * * * *")
        # from 00:07 local on a fixed minute boundary
        base = (int(time.time()) // 3600) * 3600 * 1000  # top of an hour
        nxt = c.next_fire_ms(base + 7 * 60_000)
        assert nxt == base + 15 * 60_000

    def test_fields(self):
        c = cronlib.Cron("0 9-17 * * mon-fri")
        assert c.minutes == {0}
        assert c.hours == set(range(9, 18))
        assert c.dow == {1, 2, 3, 4, 5}

    def test_six_field_seconds_dropped(self):
        c = cronlib.Cron("30 */5 * * * *")
        assert c.minutes == {0, 5, 10, 15, 20, 25, 30, 35, 40, 45, 50, 55}

    def test_bad_exprs(self):
        for bad in ("* * *", "61 * * * *", "* 25 * * *"):
            with pytest.raises(Exception):
                cronlib.Cron(bad)

    def test_duration(self):
        assert cronlib.parse_duration_ms("10s") == 10_000
        assert cronlib.parse_duration_ms("1h30m") == 5_400_000
        assert cronlib.parse_duration_ms("500ms") == 500
        assert cronlib.parse_duration_ms(250) == 250
        with pytest.raises(Exception):
            cronlib.parse_duration_ms("10 parsecs")

    def test_ranges(self):
        assert cronlib.in_ranges(5, None)
        assert cronlib.in_ranges(
            5_000, [{"beginTimestamp": 1_000, "endTimestamp": 10_000}])
        assert not cronlib.in_ranges(
            50_000, [{"beginTimestamp": 1_000, "endTimestamp": 10_000}])


class TestScheduledRule:
    def _mk(self, store, options):
        StreamProcessor(store).exec_stmt(
            'CREATE STREAM demo (deviceId STRING, temperature FLOAT) '
            'WITH (DATASOURCE="sch/demo", TYPE="memory", FORMAT="JSON")')
        return RuleState(RuleDef(
            id="sch1", sql="SELECT deviceId FROM demo",
            actions=[{"memory": {"topic": "sch/out"}}],
            options=options), store)

    def _wait_state(self, rs, state, timeout=5.0):
        """Until the rule rests in `state`, its transition's work done (the
        next schedule timer armed): advancing the clock on the state alone
        raced the timer and lost the fire."""
        return rs.wait_state(state, timeout)

    def test_cron_cycle(self, mock_clock):
        store = kv.get_store()
        # fire every minute, run for 10s
        rs = self._mk(store, {"cron": "* * * * *", "duration": "10s"})
        rs.start()
        assert self._wait_state(rs, RunState.SCHEDULED)
        assert rs.topo is None
        mock_clock.advance(60_000)  # next minute boundary -> fire
        assert self._wait_state(rs, RunState.RUNNING)
        assert rs.topo is not None
        mock_clock.advance(10_000)  # duration elapses -> back to waiting
        assert self._wait_state(rs, RunState.SCHEDULED)
        assert rs.topo is None
        mock_clock.advance(50_000)  # next boundary -> runs again
        assert self._wait_state(rs, RunState.RUNNING)
        rs.stop()
        assert self._wait_state(rs, RunState.STOPPED)

    def test_duration_only_runs_once(self, mock_clock):
        store = kv.get_store()
        rs = self._mk(store, {"duration": "5s"})
        rs.start()
        assert self._wait_state(rs, RunState.RUNNING)
        mock_clock.advance(5_000)
        assert self._wait_state(rs, RunState.STOPPED)

    def test_cron_requires_duration(self):
        store = kv.get_store()
        with pytest.raises(ValueError, match="duration"):
            self._mk(store, {"cron": "* * * * *"})

    def test_out_of_range_skips_activation(self, mock_clock):
        store = kv.get_store()
        rs = self._mk(store, {
            "cron": "* * * * *", "duration": "10s",
            "cronDatetimeRange": [
                {"beginTimestamp": 10_000_000, "endTimestamp": 20_000_000}],
        })
        rs.start()
        assert self._wait_state(rs, RunState.SCHEDULED)
        armed = rs._sched_timer
        mock_clock.advance(60_000)  # fires, but now (60s) is out of range
        assert self._wait_state(rs, RunState.SCHEDULED)
        assert rs._sched_timer is not armed  # fired, and armed the next
        assert rs.topo is None
        rs.stop()


@pytest.fixture
def api_server():
    store = kv.get_store()
    api = RestApi(store)
    srv = serve(api, "127.0.0.1", 0)
    port = srv.server_address[1]

    def req(method, path, body=None, headers=None):
        data = json.dumps(body).encode() if body is not None else None
        r = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", data=data, method=method,
            headers={"Content-Type": "application/json", **(headers or {})})
        with urllib.request.urlopen(r, timeout=5) as resp:
            return json.loads(resp.read() or b"null")

    yield api, req
    api.rules.stop_all()
    srv.shutdown()


class TestRestGaps:
    def test_tags_filter(self, api_server):
        api, req = api_server
        StreamProcessor(api.store).exec_stmt(
            'CREATE STREAM demo (a STRING) '
            'WITH (DATASOURCE="t/x", TYPE="memory", FORMAT="JSON")')
        req("POST", "/rules", {"id": "tag1", "sql": "SELECT a FROM demo",
                               "actions": [{"log": {}}], "tags": ["edge"]})
        req("POST", "/rules", {"id": "tag2", "sql": "SELECT a FROM demo",
                               "actions": [{"log": {}}]})
        all_rules = {r["id"] for r in req("GET", "/rules")}
        assert {"tag1", "tag2"} <= all_rules
        tagged = [r["id"] for r in req("GET", "/rules?tags=edge")]
        assert tagged == ["tag1"]
        req("PUT", "/rules/tag2/tags", {"tags": ["edge", "prod"]})
        assert {r["id"] for r in req("GET", "/rules?tags=edge")} == \
            {"tag1", "tag2"}
        req("DELETE", "/rules/tag2/tags", {"tags": ["edge"]})
        assert [r["id"] for r in req("GET", "/rules?tags=edge")] == ["tag1"]

    def test_uploads(self, api_server):
        api, req = api_server
        path = req("POST", "/config/uploads",
                   {"name": "cert.pem", "content": "hello"})
        assert path.endswith("cert.pem")
        assert "cert.pem" in req("GET", "/config/uploads")
        with open(path) as f:
            assert f.read() == "hello"
        req("POST", "/config/uploads", {
            "name": "bin.dat",
            "base64": base64.b64encode(b"\x00\x01").decode()})
        assert req("DELETE", "/config/uploads/cert.pem") == \
            "Upload cert.pem is deleted."
        assert "cert.pem" not in req("GET", "/config/uploads")
        with pytest.raises(urllib.error.HTTPError):
            req("POST", "/config/uploads", {"name": "../evil", "content": "x"})

    def test_config_patch(self, api_server):
        api, req = api_server
        out = req("PATCH", "/configs", {"basic": {"log_level": "debug"}})
        assert "log_level" in out
        assert req("GET", "/configs")["basic"]["log_level"] == "debug"
        with pytest.raises(urllib.error.HTTPError):
            req("PATCH", "/configs", {"basic": {"rest_port": 1}})

    def test_data_import_export(self, api_server):
        api, req = api_server
        StreamProcessor(api.store).exec_stmt(
            'CREATE STREAM exp (a STRING) '
            'WITH (DATASOURCE="t/e", TYPE="memory", FORMAT="JSON")')
        req("POST", "/rules", {"id": "expr1", "sql": "SELECT a FROM exp",
                               "actions": [{"log": {}}]})
        doc = req("GET", "/data/export")
        assert "expr1" in doc["rules"] and "exp" in doc["streams"]
        # async import into the same store (idempotent overwrite semantics)
        req("POST", "/data/import?async=true", {"content": doc})
        deadline = time.time() + 5
        while time.time() < deadline:
            st = req("GET", "/data/import/status")
            if st["status"] in ("done", "error"):
                break
            time.sleep(0.05)
        assert st["status"] == "done", st

    def test_jwt_auth(self, api_server):
        api, req = api_server
        cfg = get_config()
        cfg.basic.authentication = True
        cfg.basic.jwt_secret = "s3cret"
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                req("GET", "/rules")
            assert e.value.code == 401

            def b64u(b):
                return base64.urlsafe_b64encode(b).rstrip(b"=").decode()

            head = b64u(json.dumps({"alg": "HS256", "typ": "JWT"}).encode())
            payload = b64u(json.dumps(
                {"iss": "test", "exp": time.time() + 60}).encode())
            sig = b64u(hmac.new(b"s3cret", f"{head}.{payload}".encode(),
                                hashlib.sha256).digest())
            token = f"{head}.{payload}.{sig}"
            assert isinstance(
                req("GET", "/rules",
                    headers={"Authorization": f"Bearer {token}"}), list)
            bad = f"{head}.{payload}.{b64u(b'nope')}"
            with pytest.raises(urllib.error.HTTPError) as e:
                req("GET", "/rules",
                    headers={"Authorization": f"Bearer {bad}"})
            assert e.value.code == 401
        finally:
            cfg.basic.authentication = False
            cfg.basic.jwt_secret = ""
