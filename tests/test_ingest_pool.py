"""Sharded ingest pipeline (native shard parse + decode pool + ring):
shard-boundary correctness of the C decoder, pool ordering/backpressure,
and decode-pool determinism vs the single-thread source path.
"""
import json
import threading
import time

import numpy as np
import pytest

from ekuiper_tpu.data.types import DataType, Field, Schema
from ekuiper_tpu.io import fastjson
from ekuiper_tpu.io.converters import JsonConverter
from ekuiper_tpu.runtime.ingest import DecodePool
from ekuiper_tpu.runtime.nodes_source import SourceNode

SCHEMA = Schema(fields=[
    Field("deviceId", DataType.STRING),
    Field("temperature", DataType.FLOAT),
    Field("count", DataType.BIGINT),
    Field("ok", DataType.BOOLEAN),
])


@pytest.fixture(scope="module")
def native():
    fastjson.ensure_native(background=False)
    mod = fastjson._load()
    if mod is None:
        pytest.skip("native decoder unavailable (no toolchain)")
    return mod


def mixed_payloads(n=4000, seed=3):
    """string/float/bool/null/missing fixtures spread across any shard cut."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        m = {"deviceId": f"dev_{int(rng.integers(0, 97))}"}
        if i % 3 != 0:
            m["temperature"] = round(float(rng.normal(20, 5)), 3)
        if i % 4 != 0:
            m["count"] = int(rng.integers(-5000, 5000))
        if i % 5 == 0:
            m["ok"] = bool(i % 2)
        if i % 11 == 0:
            m["deviceId"] = None  # null string -> invalid, row stays good
        out.append(json.dumps(m).encode())
    return out


class TestShardBoundaries:
    def test_parity_across_shard_counts(self, native):
        spec = fastjson.schema_field_spec(SCHEMA)
        payloads = mixed_payloads()
        ref = fastjson.decode_columns(payloads, spec, shards=1)
        for shards in (2, 3, 5, 8):
            got = fastjson.decode_columns(payloads, spec, shards=shards)
            for k in ref[0]:
                if ref[0][k].dtype == object:
                    assert got[0][k].tolist() == ref[0][k].tolist(), k
                else:
                    np.testing.assert_array_equal(got[0][k], ref[0][k], k)
                np.testing.assert_array_equal(got[1][k], ref[1][k], k)
            np.testing.assert_array_equal(got[2], ref[2])

    def test_interning_shared_across_shards(self, native):
        # the same device id decoded by different shards must still intern
        # to ONE object (the intern pass is a single GIL'd merge)
        payloads = [b'{"deviceId": "only_one"}'] * 2048
        spec = fastjson.schema_field_spec(SCHEMA)
        cols, _, _ = fastjson.decode_columns(payloads, spec, shards=4)
        first = cols["deviceId"][0]
        assert all(v is first for v in cols["deviceId"])

    def test_int64_overflow_in_any_shard_falls_back(self, native):
        spec = fastjson.schema_field_spec(SCHEMA)
        good = [b'{"count": 1}'] * 1500
        big = b'{"count": 99999999999999999999999}'
        for pos in (0, 700, 1499):  # first, middle, last shard
            payloads = list(good)
            payloads[pos] = big
            assert fastjson.decode_columns(payloads, spec, shards=3) is None

    def test_malformed_payload_isolated_per_shard(self, native):
        spec = fastjson.schema_field_spec(SCHEMA)
        payloads = mixed_payloads(3000)
        bad_at = [5, 777, 1500, 1501, 2999]
        for i in bad_at:
            payloads[i] = b"not json at all"
        cols, valid, bad = fastjson.decode_columns(payloads, spec, shards=4)
        assert sorted(np.nonzero(bad)[0].tolist()) == bad_at
        # neighbors of bad rows decode normally
        ref = fastjson.decode_columns(payloads, spec, shards=1)
        np.testing.assert_array_equal(bad, ref[2])
        np.testing.assert_array_equal(cols["count"], ref[0]["count"])

    def test_shard_count_clamped_for_tiny_batches(self, native):
        # far fewer rows than shards*256: must still decode correctly
        spec = fastjson.schema_field_spec(SCHEMA)
        cols, valid, bad = fastjson.decode_columns(
            [b'{"count": 7}'] * 10, spec, shards=8)
        assert cols["count"].tolist() == [7] * 10
        assert not bad.any()


class TestDecodePool:
    def test_ordered_emission_under_reordered_completion(self):
        # job 0 decodes SLOWEST; emission must still be 0, 1, 2, ...
        done = []
        delays = {0: 0.15, 1: 0.0, 2: 0.05, 3: 0.0}

        def decode(job):
            time.sleep(delays.get(job, 0))
            return job

        pool = DecodePool(4, 8, decode, done.append, name="t")
        for i in range(8):
            pool.submit(i)
        assert pool.drain(timeout=5)
        assert done == list(range(8))
        pool.close()

    def test_none_results_skip_emit_but_keep_order(self):
        done = []
        pool = DecodePool(2, 4, lambda j: None if j % 2 else j,
                          done.append, name="t")
        for i in range(6):
            pool.submit(i)
        assert pool.drain(timeout=5)
        assert done == [0, 2, 4]
        pool.close()

    def test_ring_depth_backpressures_submit(self):
        gate = threading.Event()
        done = []

        def decode(job):
            gate.wait(timeout=5)
            return job

        pool = DecodePool(1, 2, decode, done.append, name="t")
        pool.submit(0)
        pool.submit(1)  # ring full: 2 in flight
        t0 = time.monotonic()
        blocker = threading.Thread(target=pool.submit, args=(2,))
        blocker.start()
        time.sleep(0.1)
        assert blocker.is_alive()  # submit is blocked on the full ring
        gate.set()
        blocker.join(timeout=5)
        assert not blocker.is_alive()
        assert pool.drain(timeout=5)
        assert done == [0, 1, 2]
        assert time.monotonic() - t0 < 5
        pool.close()

    def test_decode_error_skips_job(self):
        done = []

        def decode(job):
            if job == 1:
                raise ValueError("boom")
            return job

        pool = DecodePool(2, 4, decode, done.append, name="t")
        for i in range(4):
            pool.submit(i)
        assert pool.drain(timeout=5)
        assert done == [0, 2, 3]
        pool.close()

    def test_submit_after_close_raises(self):
        pool = DecodePool(1, 2, lambda j: j, lambda r: None, name="t")
        pool.close()
        with pytest.raises(RuntimeError):
            pool.submit(0)


def make_source(pool_size, native_ok=True, micro_batch_rows=512):
    src = SourceNode(
        "s", connector=type("C", (), {
            "open": lambda self, cb: None,
            "close": lambda self: None})(),
        schema=SCHEMA, converter=JsonConverter(),
        micro_batch_rows=micro_batch_rows,
        decode_pool_size=pool_size, decode_shards=0, ring_depth=2)
    got = []
    src.broadcast = lambda item: got.append(item)
    return src, got


class TestSourceDeterminism:
    def test_pool_path_matches_inline_path(self, native):
        payloads = mixed_payloads(2100, seed=9)
        outs = []
        for pool_size in (0, 3):
            src, got = make_source(pool_size)
            # several drains -> several flush jobs through the ring
            for i in range(0, len(payloads), 300):
                src.ingest(payloads[i:i + 300])
            src._flush()  # final=True drains the pool
            src.on_close()
            outs.append(got)
        inline, pooled = outs
        assert [b.n for b in inline] == [b.n for b in pooled]
        for bi, bp in zip(inline, pooled):
            for k in bi.columns:
                if bi.columns[k].dtype == object:
                    assert bi.columns[k].tolist() == bp.columns[k].tolist()
                else:
                    np.testing.assert_array_equal(
                        bi.columns[k], bp.columns[k])
            np.testing.assert_array_equal(bi.timestamps, bp.timestamps)

    def test_pool_source_records_decode_stage(self, native):
        src, got = make_source(2)
        src.ingest([json.dumps({"count": i}).encode() for i in range(600)])
        src._flush()
        src.on_close()
        stages = src.stats.snapshot()["stage_timings"]
        assert "decode" in stages
        assert stages["decode"]["calls"] >= 1
        assert stages["decode"]["rows"] == 600

    def test_eof_never_precedes_pooled_batches(self, native):
        from ekuiper_tpu.runtime.events import EOF

        src, got = make_source(2)
        src.ingest([json.dumps({"count": i}).encode() for i in range(900)])
        src.on_eof(EOF(source_id="s"))
        kinds = [type(x).__name__ for x in got]
        assert kinds[-1] == "EOF"
        assert sum(1 for x in got if not isinstance(x, EOF)) >= 1
        total = sum(b.n for b in got if hasattr(b, "n"))
        assert total == 900
        src.on_close()

    def test_eof_drains_ring_even_with_empty_pending(self, native):
        """Exactly micro_batch_rows rows: the threshold flush submits the
        job and empties pending, so the EOF-time _flush sees nothing
        pending — it must STILL drain the ring or EOF overtakes the batch
        (review regression: got order was ['EOF', 'ColumnBatch'])."""
        from ekuiper_tpu.runtime.events import EOF

        # slow decode so the job is reliably still in flight at EOF time
        src, got = make_source(1, micro_batch_rows=512)
        inner = src._decode_job

        def slow(job):
            time.sleep(0.1)
            return inner(job)

        src._ensure_pool()._decode = slow
        src.ingest([json.dumps({"count": i}).encode() for i in range(512)])
        src.on_eof(EOF(source_id="s"))
        kinds = [type(x).__name__ for x in got]
        assert kinds == ["ColumnBatch", "EOF"]
        assert got[0].n == 512
        src.on_close()

    def test_barrier_drains_pending_and_ring(self, native):
        """A checkpoint barrier must not pass rows still buffered or
        decoding: the connector offset already covers them, so rows
        emitted after the barrier would be lost on restore (behind the
        offset, outside the snapshot)."""
        from ekuiper_tpu.runtime.events import Barrier

        src, got = make_source(1, micro_batch_rows=512)
        inner = src._decode_job

        def slow(job):
            time.sleep(0.1)
            return inner(job)

        src._ensure_pool()._decode = slow
        # 512 rows: threshold flush submits the job (pending empties);
        # +100 rows stay PENDING — the barrier must flush both
        src.ingest([json.dumps({"count": i}).encode() for i in range(612)])
        src.on_barrier(Barrier(checkpoint_id=1, qos=1))
        kinds = [type(x).__name__ for x in got]
        assert kinds == ["ColumnBatch", "ColumnBatch", "Barrier"]
        assert sum(b.n for b in got[:2]) == 612
        src.on_close()

    def test_msg_batch_cannot_overtake_raw_batch_in_ring(self, native):
        """Mixed ingestion shapes share the ordered ring: a dict payload
        flushed after a raw drain must emit after it, even when the raw
        decode is slow."""
        src, got = make_source(2, micro_batch_rows=256)
        inner = src._decode_job

        def slow(job):
            if job[0] == "raw":
                time.sleep(0.1)
            return inner(job)

        src._ensure_pool()._decode = slow
        src.ingest([json.dumps({"count": i}).encode() for i in range(256)])
        src.ingest([{"count": 999}] * 256)  # dict payloads -> msgs job
        src._flush()
        assert [b.n for b in got] == [256, 256]
        assert got[0].columns["count"][0] == 0  # raw batch first
        assert got[1].columns["count"][0] == 999
        src.on_close()


def obj_col(vals):
    col = np.empty(len(vals), dtype=object)
    col[:] = vals
    return col


def make_fused(sql="SELECT count(*) AS c, avg(temperature) AS a FROM s "
                   "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)",
               micro_batch=256, capacity=64):
    from ekuiper_tpu.ops.aggspec import extract_kernel_plan
    from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode
    from ekuiper_tpu.sql.parser import parse_select

    stmt = parse_select(sql)
    plan = extract_kernel_plan(stmt)
    assert plan is not None
    node = FusedWindowAggNode(
        "f", stmt.window, plan, dims=[d.expr for d in stmt.dimensions],
        capacity=capacity, micro_batch=micro_batch)
    node.state = node.gb.init_state()
    return node


class TestPrepUploadStage:
    def test_pool0_default_path_unchanged(self):
        src, got = make_source(0)
        assert src.prep_ctx is None
        src.ingest([{"count": 1}] * 10)
        src._flush()
        src.on_close()
        assert got and all(b.shared_ctx is None for b in got)

    def test_prep_ctx_rides_pooled_batches(self, native):
        src, got = make_source(2)
        assert src.prep_ctx is not None
        src.ingest([json.dumps({"count": i}).encode() for i in range(600)])
        src._flush()
        src.on_close()
        assert got and all(b.shared_ctx is src.prep_ctx for b in got)

    def test_prep_upload_opt_out(self, native):
        from ekuiper_tpu.runtime.nodes_source import SourceNode

        src = SourceNode(
            "s", connector=type("C", (), {
                "open": lambda self, cb: None,
                "close": lambda self: None})(),
            schema=SCHEMA, converter=JsonConverter(),
            decode_pool_size=2, prep_upload=False)
        assert src.prep_ctx is None

    def test_precompute_builds_fused_share_keys(self, native):
        import jax.numpy as jnp

        src, got = make_source(2, micro_batch_rows=256)
        src.prep_ctx.register_upload("deviceId", ["temperature", "count"],
                                     256)
        payloads = mixed_payloads(512, seed=21)
        src.ingest(payloads[:256])
        src.ingest(payloads[256:])
        src._flush()
        src.on_close()
        assert len(got) == 2
        for b in got:
            st = b.share_state
            assert ("slots", "deviceId") in st
            assert ("dslots", "deviceId", 256, True) in st
            assert ("dcol", "temperature", 256) in st
            dev, dm = st[("dcol", "temperature", 256)]
            assert isinstance(dev, jnp.ndarray) and dev.shape == (256,)
            dslots = st[("dslots", "deviceId", 256, True)]
            assert dslots.dtype == jnp.uint16
        # the upload stage accrued on the SOURCE node
        stages = src.stats.snapshot()["stage_timings"]
        assert "upload" in stages and stages["upload"]["calls"] >= 2
        # slots match an independent python encode of the same columns
        from ekuiper_tpu.ops.keytable import KeyTable

        ref = KeyTable()
        ref._native_ok = False
        for b in got:
            slots, n_keys, _ = b.share_state[("slots", "deviceId")]
            ref_slots, _ = ref.encode_column(b.columns["deviceId"])
            np.testing.assert_array_equal(slots, ref_slots)

    def test_fused_node_consumes_pre_uploaded_inputs(self, native):
        """Parity: a fused node fed prep-uploaded pooled batches computes
        the same window state as one fed the inline (pool=0) batches, and
        actually hits the pre-built share entries."""
        outs = []
        for pool in (0, 2):
            src, got = make_source(pool, micro_batch_rows=256)
            if src.prep_ctx is not None:
                src.prep_ctx.register_upload(
                    "deviceId", ["temperature", "count"], 256)
            payloads = mixed_payloads(1024, seed=33)
            for i in range(0, 1024, 256):  # aligned drains: 256-row batches
                src.ingest(payloads[i:i + 256])
            src._flush()
            src.on_close()
            node = make_fused()
            for b in got:
                prebuilt = (b.share_state is not None
                            and ("dslots", "deviceId", 256, True)
                            in b.share_state)
                node.process(b)
                if pool and b.n == 256:
                    assert prebuilt  # the pool built it BEFORE the fold
            assert node._shared_slots_ok is not False
            res, act = node.gb.finalize(node.state, max(node.kt.n_keys, 1))
            outs.append((node.kt.decode_all(),
                         [np.asarray(r) for r in res], np.asarray(act)))
        keys_a, res_a, act_a = outs[0]
        keys_b, res_b, act_b = outs[1]
        assert keys_a == keys_b
        for ra, rb in zip(res_a, res_b):
            np.testing.assert_array_equal(ra, rb)  # NaN-positions equal too
        np.testing.assert_array_equal(act_a, act_b)

    def test_out_of_order_pool_encode_tolerated(self):
        """Pool workers may key-encode batch k+1 before batch k's snapshot
        is consumed; the fused sync must tolerate its table running ahead
        of an older snapshot instead of poisoning slot reuse."""
        from ekuiper_tpu.data.batch import ColumnBatch
        from ekuiper_tpu.runtime.ingest import IngestPrepCtx

        ctx = IngestPrepCtx()
        a = ColumnBatch(n=3, columns={
            "deviceId": obj_col(["a", "b", "a"]),
            "temperature": np.array([1, 2, 3], dtype=np.float32)},
            emitter="s")
        b = ColumnBatch(n=3, columns={
            "deviceId": obj_col(["c", "a", "d"]),
            "temperature": np.array([4, 5, 6], dtype=np.float32)},
            emitter="s")
        for batch in (a, b):
            batch.ensure_share_state()
            batch.shared_ctx = ctx
        ctx.encode(b, "deviceId")  # pool finished the LATER batch first
        ctx.encode(a, "deviceId")
        node = make_fused()
        node.process(a)  # emission order: a then b
        node.process(b)
        assert node._shared_slots_ok is True
        assert node.kt.decode_all() == ["c", "a", "d", "b"]
        res, act = node.gb.finalize(node.state, node.kt.n_keys)
        counts = {node.kt.decode(i): int(res[0][i])
                  for i in range(node.kt.n_keys)}
        assert counts == {"a": 3, "b": 1, "c": 1, "d": 1}

    def test_capacity_grow_flips_slot_share_key(self, monkeypatch):
        """The grow round-trip: once the neutral table's capacity crosses
        the slot-dtype boundary, precompute keys new uploads under
        u16=False — in-flight uint16 pre-uploads simply miss the fused
        lookup and are rebuilt there (never folded with a stale dtype)."""
        import ekuiper_tpu.ops.groupby as groupby_mod
        from ekuiper_tpu.data.batch import ColumnBatch
        from ekuiper_tpu.ops.keytable import KeyTable
        from ekuiper_tpu.runtime.ingest import IngestPrepCtx

        monkeypatch.setattr(
            groupby_mod, "slot_dtype",
            lambda cap: np.uint16 if cap <= 16 else np.int32)
        ctx = IngestPrepCtx()
        kt = KeyTable(initial_capacity=16)
        kt._native_ok = False
        ctx.key_tables["deviceId"] = kt
        ctx.register_upload("deviceId", ["temperature"], 32)

        def mk(keys):
            b = ColumnBatch(n=len(keys), columns={
                "deviceId": obj_col(keys),
                "temperature": np.arange(len(keys), dtype=np.float32)},
                emitter="s")
            b.ensure_share_state()
            b.shared_ctx = ctx
            return b

        b1 = mk([f"k{i}" for i in range(10)])
        ctx.precompute(b1)
        assert ("dslots", "deviceId", 32, True) in b1.share_state
        b2 = mk([f"n{i}" for i in range(20)])  # 30 keys > 16: capacity 32
        ctx.precompute(b2)
        assert kt.capacity == 32
        assert ("dslots", "deviceId", 32, False) in b2.share_state
        assert ("dslots", "deviceId", 32, True) not in b2.share_state

    def test_pool_depth_gauges(self, native):
        src, got = make_source(1, micro_batch_rows=256)
        assert src.pool_depths() is None  # pool starts lazily
        gate = threading.Event()
        inner = src._decode_job

        def slow(job):
            gate.wait(timeout=5)
            return inner(job)

        src._ensure_pool()._decode = slow
        src.ingest([json.dumps({"count": i}).encode() for i in range(512)])
        time.sleep(0.05)
        ring, queue = src.pool_depths()
        assert ring >= 1  # submitted, not yet emitted
        gate.set()
        src._flush()
        src.on_close()
        ring, queue = src.pool_depths()
        assert ring == 0 and queue == 0

    def test_pool_gauges_render_in_prometheus(self, native):
        from ekuiper_tpu.observability.prometheus import render

        src, got = make_source(2)
        src.ingest([json.dumps({"count": i}).encode() for i in range(600)])
        src._flush()

        class FakeTopo:
            from ekuiper_tpu.observability.histogram import LatencyHistogram
            e2e_hist = LatencyHistogram()
            boundary_hists = {}

            def live_shared(self):
                return []

            def all_nodes(self):
                return [src]

        class FakeState:
            topo = FakeTopo()

        class FakeReg:
            def list(self):
                return [{"id": "r1", "status": "running"}]

            def state(self, rid):
                return FakeState()

        text = render(FakeReg())
        assert 'kuiper_ingest_ring_depth{rule="r1",op="s"}' in text
        assert 'kuiper_decode_pool_queue{rule="r1",op="s"}' in text
        src.on_close()


class TestStagePrometheus:
    def test_stage_lines_render(self):
        from ekuiper_tpu.observability.prometheus import render

        class FakeReg:
            def list(self):
                return [{"id": "r1", "status": "running"}]

            def state(self, rid):
                class S:
                    topo = None
                return S()

        # no rules with topos -> no stage rows, but the section must render
        text = render(FakeReg())
        assert "kuiper_rule_status" in text
        # direct StatManager path: stages flow into the snapshot
        from ekuiper_tpu.utils.metrics import StatManager

        sm = StatManager("source", "s1")
        sm.observe_stage("decode", 1500, rows=100)
        sm.observe_stage("decode", 500, rows=50)
        snap = sm.snapshot()["stage_timings"]["decode"]
        assert snap == {"calls": 2, "total_us": 2000, "rows": 150,
                        "cpu_us": 0}
