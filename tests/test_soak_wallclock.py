"""Wall-clock soak (reference analogue: fvt/ suites): a minutes-scale run
with REAL time — continuous file-source traffic, short checkpoint
intervals, repeated kill/restore cycles, and a flapping sink buffered by
the CacheNode — asserting the at-least-once contract (no loss) and
bounded memory. Marked slow.
"""
import json
import os
import threading
import time

import pytest

from ekuiper_tpu.io.memory import MemorySink
from ekuiper_tpu.planner.planner import RuleDef, plan_rule
from ekuiper_tpu.server.processors import StreamProcessor
from ekuiper_tpu.store import kv
import ekuiper_tpu.io.memory as mem

N_ROWS = 120_000
WINDOW = 1000


def _rss_mb() -> float:
    with open("/proc/self/statm") as f:
        pages = int(f.read().split()[1])
    return pages * os.sysconf("SC_PAGE_SIZE") / 1e6


@pytest.fixture
def real_clock():
    """This soak runs on the REAL clock (timers, checkpoint intervals,
    resend backoff all at wall-clock pace)."""
    from ekuiper_tpu.utils import timex

    timex.use_real_clock()
    yield
    timex.use_real_clock()


@pytest.mark.slow
class TestWallClockSoak:
    def test_kill_restore_flapping_sink_no_loss(self, real_clock, tmp_path):
        """qos1 rule over a rewindable file source with a short checkpoint
        interval; the topo is closed and re-planned repeatedly mid-stream
        while the sink flaps up/down (CacheNode spill + resend). Contract:
        every uid is delivered AT LEAST once; memory growth stays bounded."""
        mem.reset()
        store = kv.get_store()
        path = tmp_path / "soak.jsonl"
        with open(path, "w") as f:
            for i in range(N_ROWS):
                f.write(json.dumps(
                    {"uid": i, "deviceId": f"d{i % 50}",
                     "v": float(i % 7)}) + "\n")
        store.kv("source_conf").set("file:soaklines", {"fileType": "lines"})
        StreamProcessor(store).exec_stmt(
            f'CREATE STREAM soakf (uid BIGINT, deviceId STRING, v FLOAT) '
            f'WITH (DATASOURCE="{path}", TYPE="file", FORMAT="JSON", '
            f'CONF_KEY="soaklines")')

        got_uids = set()
        got_count = [0]
        flap = {"down": False}
        orig_collect = MemorySink.collect

        def flaky_collect(self, item):
            if flap["down"]:
                raise ConnectionError("sink flapping (soak)")
            orig_collect(self, item)

        MemorySink.collect = flaky_collect

        def on_msg(_t, payload):
            msgs = payload if isinstance(payload, list) else [payload]
            for m in msgs:
                if isinstance(m, dict) and "uid" in m:
                    got_uids.add(m["uid"])
                    got_count[0] += 1

        mem.subscribe("soak/out", on_msg)

        def make_topo():
            return plan_rule(RuleDef(
                id="soakrule",
                sql="SELECT uid, deviceId FROM soakf WHERE v >= 0",
                actions=[{"memory": {
                    "topic": "soak/out", "enableCache": True,
                    "memoryCacheThreshold": 256,
                    "resendInterval": 50}}],
                options={"qos": 1, "checkpointInterval": 800}), store)

        rss_start = _rss_mb()
        try:
            deadline = time.time() + 90
            cycles = 0
            while len(got_uids) < N_ROWS and time.time() < deadline:
                topo = make_topo()
                topo.open()
                t0 = time.time()
                if cycles < 2:
                    # early lives: sink goes DOWN mid-life and STAYS down
                    # through the kill — the backlog must survive via the
                    # cache spill and resend in a later life
                    while time.time() - t0 < 2.5:
                        flap["down"] = time.time() - t0 >= 0.8
                        time.sleep(0.05)
                else:
                    flap["down"] = False
                    while (time.time() - t0 < 4.0
                           and len(got_uids) < N_ROWS):
                        time.sleep(0.05)
                topo.close()  # kill this life; next cycle restores
                flap["down"] = False
                cycles += 1
            assert cycles >= 3, "soak must span multiple kill/restore cycles"
            missing = set(range(N_ROWS)) - got_uids
            assert not missing, (
                f"lost {len(missing)} uids (first: {sorted(missing)[:5]}) "
                f"after {cycles} cycles — at-least-once violated")
            # duplicates are allowed (at-least-once), but must be bounded by
            # the replay spans, not systemic re-delivery
            assert got_count[0] < N_ROWS * 3, got_count[0]
            growth = _rss_mb() - rss_start
            assert growth < 600, f"RSS grew {growth:.0f}MB during soak"
        finally:
            MemorySink.collect = orig_collect
            mem.reset()

    def test_count_window_state_survives_kills(self, real_clock, tmp_path):
        """Device-path COUNTWINDOW partials + _rows_in_window ride
        checkpoints across kill/restore: the sum of emitted window counts
        covers every complete window at least once."""
        mem.reset()
        store = kv.get_store()
        n = 60_000
        path = tmp_path / "soakc.jsonl"
        with open(path, "w") as f:
            for i in range(n):
                f.write(json.dumps(
                    {"uid": i, "deviceId": f"d{i % 20}",
                     "v": float(i % 5)}) + "\n")
        store.kv("source_conf").set("file:soaklines", {"fileType": "lines"})
        StreamProcessor(store).exec_stmt(
            f'CREATE STREAM soakc (uid BIGINT, deviceId STRING, v FLOAT) '
            f'WITH (DATASOURCE="{path}", TYPE="file", FORMAT="JSON", '
            f'CONF_KEY="soaklines")')
        counts = []
        mem.subscribe("soak/cnt", lambda _t, p: counts.extend(
            m["c"] for m in (p if isinstance(p, list) else [p])
            if isinstance(m, dict) and "c" in m))

        def make_topo():
            # end-to-end at-least-once for window EMISSIONS needs the sink
            # cache (reference SyncCache): without it, a kill can cut an
            # in-flight emission after the window state already reset
            return plan_rule(RuleDef(
                id="soakcw",
                sql=(f"SELECT deviceId, count(*) AS c FROM soakc "
                     f"GROUP BY deviceId, COUNTWINDOW({WINDOW})"),
                actions=[{"memory": {"topic": "soak/cnt",
                                     "enableCache": True,
                                     "resendInterval": 30}}],
                options={"qos": 1, "checkpointInterval": 700}), store)

        deadline = time.time() + 60
        target = (n // WINDOW) * WINDOW
        lives = 0
        try:
            while time.time() < deadline:
                topo = make_topo()
                topo.open()
                t0 = time.time()
                if lives < 2:
                    # first lives are ALWAYS killed mid-stream, regardless
                    # of progress — the restore path must carry the rest
                    time.sleep(1.5)
                else:
                    while time.time() - t0 < 3.0 and sum(counts) < target:
                        time.sleep(0.05)
                topo.close()
                lives += 1
                if lives >= 2 and sum(counts) >= target:
                    break
            assert lives >= 2
            assert sum(counts) >= target, (
                f"window counts {sum(counts)} < {target} after {lives} "
                "lives — rows lost beyond the QoS contract")
        finally:
            mem.reset()
