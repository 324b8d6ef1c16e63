"""DABA sliding rings (ISSUE 11): parity of the constant-time sliding
implementation (ops/slidingring.py, `slidingImpl=daba`) against the
legacy refold-on-trigger path (`slidingImpl=refold`) — same batches, same
triggers, same emitted windows, across window shapes, aggregate classes,
clock modes, eviction pressure, and kill/restore.

The refold path is the exactness baseline (tests/test_sliding_device.py
proves it against ground truth); this suite proves the DABA rings match
it, so the default swap cannot silently change semantics."""
import json

import numpy as np
import pytest

from ekuiper_tpu.data.batch import ColumnBatch
from ekuiper_tpu.ops.aggspec import extract_kernel_plan
from ekuiper_tpu.ops.emit import build_direct_emit
from ekuiper_tpu.ops.slidingring import (ADD_COMBINE, MAX_COMBINE,
                                         MIN_COMBINE, SlidingRing,
                                         plan_ring_layout)
from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode
from ekuiper_tpu.sql.parser import parse_select

SQL_INV = ("SELECT deviceId, count(*) AS c, sum(temp) AS s, "
           "avg(temp) AS a, stddev(temp) AS sd FROM s GROUP BY deviceId, "
           "SLIDINGWINDOW(ss, 2) OVER (WHEN temp > 90)")
SQL_MM = ("SELECT deviceId, min(temp) AS mn, max(temp) AS mx, "
          "count(*) AS c FROM s GROUP BY deviceId, "
          "SLIDINGWINDOW(ss, 2) OVER (WHEN temp > 90)")
SQL_SKETCH = ("SELECT deviceId, percentile_approx(temp, 0.9) AS p90, "
              "distinct_count_approx(temp) AS dc FROM s GROUP BY deviceId, "
              "SLIDINGWINDOW(ss, 2) OVER (WHEN temp > 90)")

# identical fold inputs -> identical integer counts and min/max picks;
# float accumulations (sum/avg/stddev) compare loose, sketch FINAL values
# looser still (the refold path finalizes on device f32, the ring path
# in the numpy twins — same bins/registers, ±ulp value math)
EXACT_FIELDS = {"c", "mn", "mx"}


def mknode(sql, impl, capacity=64, micro_batch=128):
    stmt = parse_select(sql)
    plan = extract_kernel_plan(stmt)
    assert plan is not None, sql
    node = FusedWindowAggNode(
        f"sr_{impl}", stmt.window, plan,
        dims=[d.expr for d in stmt.dimensions],
        capacity=capacity, micro_batch=micro_batch,
        direct_emit=build_direct_emit(stmt, plan, ["deviceId"]),
        sliding_impl=impl)
    node.state = node.gb.init_state()
    got = []
    node.broadcast = lambda item: got.append(item)
    return node, got


def flat(items):
    msgs = []
    for item in items:
        if isinstance(item, ColumnBatch):
            msgs.extend(item.to_messages())
        elif isinstance(item, list):
            msgs.extend(item)
        else:
            msgs.append(item.message if hasattr(item, "message") else item)
    return msgs


def per_trigger(items):
    return [{m["deviceId"]: m for m in flat([item])} for item in items]


def run_pair(sql, batches, **kw):
    """Drive the SAME batches through both impls; returns per-trigger
    emission lists (daba, refold) plus the daba node."""
    node_d, got_d = mknode(sql, "daba", **kw)
    node_r, got_r = mknode(sql, "refold", **kw)
    assert node_d.sliding_impl == "daba"
    assert node_r.sliding_impl == "refold"
    for b in batches:
        node_d.process(b)
        node_r.process(b)
    node_d._drain_async_emits()
    node_r._drain_async_emits()
    return per_trigger(got_d), per_trigger(got_r), node_d


def assert_parity(trig_d, trig_r):
    assert len(trig_d) == len(trig_r) >= 1
    for td, tr in zip(trig_d, trig_r):
        assert set(td) == set(tr)
        for key, mr in tr.items():
            md = td[key]
            for f, vr in mr.items():
                vd = md[f]
                if vr is None or vd is None or isinstance(vr, str):
                    assert vd == vr, (key, f, vd, vr)
                elif f in EXACT_FIELDS:
                    assert vd == vr, (key, f, vd, vr)
                elif f == "dc":  # hll estimate rounds to an integer
                    assert abs(vd - vr) <= 1, (key, f, vd, vr)
                else:
                    np.testing.assert_allclose(
                        vd, vr, rtol=1e-4, atol=1e-4,
                        err_msg=f"{key}.{f}")


def trigger_batches(trigger_ts, keys=5, rows=48, t0=10_000, step=100,
                    n_batches=12, seed=3):
    """Monotone timestamped batches; for each requested trigger time the
    row closest to it (within its batch span) carries the trigger temp
    (>90), everything else stays below it — deterministic cadences."""
    rng = np.random.default_rng(seed)
    out = []
    t = t0
    for _ in range(n_batches):
        ids = np.array([f"d{i}" for i in rng.integers(0, keys, rows)],
                       dtype=np.object_)
        temp = rng.uniform(0, 88, rows).astype(np.float32)
        ts = t + np.sort(rng.integers(0, step, rows)).astype(np.int64)
        for tv in trigger_ts:
            if t <= tv < t + step:
                temp[int(np.argmin(np.abs(ts - tv)))] = 95.0
        out.append(ColumnBatch(
            n=rows, columns={"deviceId": ids, "temp": temp},
            timestamps=ts, emitter="s"))
        t += step
    return out


def endspike_batches(n_batches=3, rows=32, keys=4, t0=10_000, step=100,
                     seed=2):
    """Batches whose LAST row of the LAST batch is the trigger — the
    trigger lands in the head bucket (the ring's fast-path shape)."""
    rng = np.random.default_rng(seed)
    out = []
    t = t0
    for i in range(n_batches):
        ids = np.array([f"d{j}" for j in rng.integers(0, keys, rows)],
                       dtype=np.object_)
        temp = rng.uniform(0, 88, rows).astype(np.float32)
        ts = t + np.sort(rng.integers(0, step, rows)).astype(np.int64)
        if i == n_batches - 1:
            temp[-1] = 99.0
            ts[-1] = max(int(ts[-1]), int(ts.max()))
        out.append(ColumnBatch(
            n=rows, columns={"deviceId": ids, "temp": temp},
            timestamps=ts, emitter="s"))
        t += step
    return out


def spike_batches(at_end: bool, n_batches=24, rows=32, keys=4, seed=9):
    """Batches 205 ms apart whose rows lie within 5 ms, inside one ring
    bucket at either bucket width (25 ms; 41 ms for a wide sketch): every
    third batch holds a trigger — its last row (`at_end`: nothing received
    is newer than the trigger), or one in its middle with later rows of
    the same bucket behind it."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_batches):
        ids = np.array([f"d{j}" for j in rng.integers(0, keys, rows)],
                       dtype=np.object_)
        temp = rng.uniform(0, 88, rows).astype(np.float32)
        ts = 10_250 + 205 * i + np.sort(
            rng.integers(0, 5, rows)).astype(np.int64)
        ts[-1] = ts[0] + 4
        if i % 3 == 2:
            at = rows - 1 if at_end else int(np.argmax(ts > ts[0] + 1))
            temp[at] = 95.0
            assert at_end or ts[at] < ts[-1]
        out.append(ColumnBatch(
            n=rows, columns={"deviceId": ids, "temp": temp},
            timestamps=ts, emitter="s"))
    return out


def random_trigger_batches(seed=7, n_batches=12, rows=48, keys=5,
                           t0=10_000, step=100, spike_every=17):
    rng = np.random.default_rng(seed)
    out = []
    t = t0
    k = 0
    for _ in range(n_batches):
        ids = np.array([f"d{i}" for i in rng.integers(0, keys, rows)],
                       dtype=np.object_)
        temp = rng.uniform(0, 88, rows).astype(np.float32)
        ts = t + np.sort(rng.integers(0, step, rows)).astype(np.int64)
        for i in range(rows):
            k += 1
            if k % spike_every == 0:
                temp[i] = 99.0
        out.append(ColumnBatch(
            n=rows, columns={"deviceId": ids, "temp": temp},
            timestamps=ts, emitter="s"))
        t += step
    return out


class TestWindowShapes:
    """DABA vs refold across the three trigger cadences: tumbling-
    degenerate (disjoint windows), hopping (regular overlap), and true
    sliding (arbitrary trigger times)."""

    def test_tumbling_degenerate(self):
        # one trigger every window length: windows tile without overlap
        trig = [12_000, 14_000, 16_000, 18_000]
        batches = trigger_batches(trig, n_batches=85, step=100)
        trig_d, trig_r, _ = run_pair(SQL_INV, batches)
        assert_parity(trig_d, trig_r)

    def test_hopping_shape(self):
        # trigger every 500ms on a 2s window: 4x overlap, hopping-like
        trig = list(range(12_000, 18_001, 500))
        batches = trigger_batches(trig, n_batches=85, step=100)
        trig_d, trig_r, _ = run_pair(SQL_INV, batches)
        assert_parity(trig_d, trig_r)

    def test_true_sliding_invertible(self):
        trig_d, trig_r, node = run_pair(
            SQL_INV, random_trigger_batches(seed=7, n_batches=30))
        assert_parity(trig_d, trig_r)
        # the DABA node kept NO device batch cache: the refold-era
        # _dev_ring stays empty (the stall class it carried is gone)
        assert node._dev_ring_bytes == 0
        assert not any(e is not None
                       for lst in node._dev_ring.values() for e in lst)

    def test_true_sliding_min_max(self):
        trig_d, trig_r, node = run_pair(
            SQL_MM, random_trigger_batches(seed=11, n_batches=30))
        assert_parity(trig_d, trig_r)
        assert node.ring is not None and node.ring.mm_comps == ["mn", "mx"]

    def test_true_sliding_sketches(self):
        trig_d, trig_r, _ = run_pair(
            SQL_SKETCH, random_trigger_batches(seed=13, n_batches=30))
        assert_parity(trig_d, trig_r)

    def test_delay_windows(self):
        """SLIDINGWINDOW(ss, 2, 1): delayed emission takes the exact
        fallback on the DABA path — parity must hold regardless."""
        from ekuiper_tpu.utils import timex

        sql = ("SELECT deviceId, count(*) AS c, max(temp) AS mx FROM s "
               "GROUP BY deviceId, SLIDINGWINDOW(ss, 2, 1) "
               "OVER (WHEN temp > 90)")
        batches = random_trigger_batches(seed=5, n_batches=20)
        node_d, got_d = mknode(sql, "daba")
        node_r, got_r = mknode(sql, "refold")
        clock = timex.get_clock()
        for b in batches:
            clock.set(int(b.timestamps[-1]))
            node_d.process(b)
            node_r.process(b)
        # fire every pending delayed emission on both nodes
        clock.advance(5_000)
        for node in (node_d, node_r):
            for t in sorted(node._pending_slides):
                node._pending_slides.pop(t, None)
                node._emit_sliding(t)
            node._drain_async_emits()
        assert_parity(per_trigger(got_d), per_trigger(got_r))


# ------------------------------------------------------- the device tail
SQL_PCT = ("SELECT deviceId, percentile_approx(temp, 0.99) AS p99, "
           "count(*) AS c FROM s GROUP BY deviceId, "
           "SLIDINGWINDOW(ss, 2) OVER (WHEN temp > 90)")
TAIL_MB = 16  # the tail's static edge shape is then 256 rows


def tail_kernel(sql, capacity=64):
    from ekuiper_tpu.ops.groupby import DeviceGroupBy
    from ekuiper_tpu.ops.slidingring import ring_layout_for

    stmt = parse_select(sql)
    plan = extract_kernel_plan(stmt)
    layout = ring_layout_for(stmt.window, plan)
    gb = DeviceGroupBy(plan, capacity=capacity, n_panes=layout.n_panes,
                       micro_batch=TAIL_MB)
    return gb, SlidingRing(gb, layout)


def tail_rows(gb, rng, n, key_lo, key_hi, nulls=False):
    """(cols, valid, slots) of n rows over the slots [key_lo, key_hi): the
    plan's columns as the node's row ring holds them."""
    from ekuiper_tpu.ops.aggspec import materialize_hll_columns

    temp = np.round(rng.normal(20.0, 15.0, n), 2).astype(np.float32)
    valid = {}
    if nulls:
        mask = rng.random(n) > 0.3
        valid = {name: mask for name in gb.plan.columns}
        temp[rng.random(n) > 0.8] = np.nan
    return (materialize_hll_columns(gb.plan.columns, {"temp": temp}, n),
            valid, rng.integers(key_lo, key_hi, n).astype(np.int32))


class _Landed:
    """A fetched components array, as `prefinalize_merge` takes it."""

    def __init__(self, arr, layout):
        from ekuiper_tpu.ops.prefinalize import unpack_components

        self._comps = unpack_components(arr, layout)

    def get(self):
        return self._comps


class TestDeviceTail:
    """A trigger the ring's running partials served is finished on the
    device (`slidingring.tail`): against the host tail it replaced —
    HostShadow + merge_components + numpy final values — on the same
    query result and the same edge rows."""

    @pytest.mark.parametrize("edge", ["no_rows", "one_buffer", "two_buffers",
                                      "new_key", "nulls", "cut_rows"])
    @pytest.mark.parametrize("sql", [SQL_INV, SQL_MM, SQL_SKETCH, SQL_PCT],
                             ids=["sums", "minmax", "sketches", "pct"])
    def test_device_tail_equals_host_tail(self, sql, edge):
        from ekuiper_tpu.ops.groupby import apply_int_semantics
        from ekuiper_tpu.ops.prefinalize import HostShadow, hist_bin_np
        from ekuiper_tpu.ops.slidingring import QUERY_ADJ

        gb, ring = tail_kernel(sql)
        assert ring.edge_rows == 16 * TAIL_MB
        rng = np.random.default_rng(len(sql) * 31 + len(edge))
        n_keys = 12
        # the body: three closed panes of keys 0..7, the partials rebuilt
        # from them, one combine of the partials
        state = gb.init_state()
        for pane in range(3):
            cols, valid, slots = tail_rows(gb, rng, 300, 0, 8)
            state = gb.fold(state, cols, slots, valid, pane)
        rs = ring.flip(ring.init_state(), state, 0,
                       np.arange(ring.n_ring_panes) < 3)
        body = ring.query(
            rs, state, body_on=True, f_on=True, f_slot=0,
            adj_slots=np.zeros(QUERY_ADJ, dtype=np.int32),
            adj_weights=np.zeros(QUERY_ADJ, dtype=np.float32),
            adj_mm=np.zeros(QUERY_ADJ, dtype=np.bool_))
        body_np = np.array(body)  # the tail overwrites its input
        assert body_np[:8, -1].min() > 0 and body_np[8:, -1].max() == 0
        # the edge rows, as the node's row ring holds them
        segs = {
            "no_rows": [],
            "one_buffer": [tail_rows(gb, rng, 90, 0, 8), tail_rows(gb, rng, 70, 0, 8)],
            # 600 rows: three calls of the program at 256 rows each
            "two_buffers": [tail_rows(gb, rng, 250, 0, 8),
                            tail_rows(gb, rng, 350, 0, 8)],
            # keys 8..11 have no row in the body
            "new_key": [tail_rows(gb, rng, 120, 4, n_keys)],
            "nulls": [tail_rows(gb, rng, 150, 0, n_keys, nulls=True),
                      tail_rows(gb, rng, 40, 0, 8)],
            "cut_rows": [tail_rows(gb, rng, 200, 0, n_keys)],
        }[edge]
        segs = [seg + (None,) for seg in segs]
        if edge == "cut_rows":  # a stamp cut keeps part of a segment
            segs[0] = segs[0][:3] + (np.nonzero(rng.random(200) > 0.5)[0],)
        buffers = ring.edge_buffers(segs)
        n_edge = sum(len(s) if sel is None else len(sel)
                     for _c, _v, s, sel in segs)
        assert sum(n for _c, _v, _s, n in buffers) == n_edge
        assert len(buffers) == max(-(-n_edge // ring.edge_rows), 1)
        for cols, valid, slots, _n in buffers:  # one static shape
            assert {a.shape for a in (*cols.values(), *valid.values(),
                                      slots)} == {(ring.edge_rows,)}
            assert set(valid) == set(cols) == set(gb.plan.columns)
        fin = np.asarray(ring.tail_begin(body, buffers))
        assert fin.shape == (len(gb.plan.specs) + 1, gb.capacity)
        dev = apply_int_semantics(
            gb.plan.specs, [fin[i][:n_keys] for i in range(len(fin) - 1)])
        # the host tail
        shadow = HostShadow(gb.plan, gb.comp_specs, n_keys)
        for cols, valid, slots, sel in segs:
            pick = (lambda a: a) if sel is None else (lambda a: a[sel])
            shadow.fold({k: pick(v) for k, v in cols.items()}, pick(slots),
                        {k: pick(v) for k, v in valid.items()})
        assert shadow.n_rows == n_edge
        host, act = gb.prefinalize_merge(
            _Landed(body_np, gb._components_layout()), shadow, n_keys)
        assert np.array_equal(fin[-1][:n_keys], act)
        seen = 8 if edge in ("no_rows", "one_buffer", "two_buffers") \
            else n_keys
        assert (act[:seen] > 0).all() and (act[seen:] == 0).all()
        for spec, d, h in zip(gb.plan.specs, dev, host):
            if spec.kind in ("count", "hll", "min", "max"):
                assert d.dtype == h.dtype
                assert np.array_equal(d, h, equal_nan=True), spec.kind
            elif spec.kind == "percentile_approx":
                # the same bin for every key (bin centres lie 10 % apart);
                # the value may differ in the last ulps of exp
                assert np.array_equal(np.isnan(d), np.isnan(h))
                ok = ~np.isnan(h)
                assert np.array_equal(hist_bin_np(d[ok].astype(np.float32)),
                                      hist_bin_np(h[ok].astype(np.float32)))
                np.testing.assert_allclose(d[ok], h[ok], rtol=1e-5)
            else:
                np.testing.assert_allclose(d, h, rtol=1e-4, atol=1e-4,
                                           err_msg=spec.kind)

    @pytest.mark.parametrize("head", ["live_pane", "edge_rows"])
    @pytest.mark.parametrize("sql", [SQL_INV, SQL_MM, SQL_PCT],
                             ids=["sums", "minmax", "pct"])
    def test_node_device_tail_against_host_tail(self, sql, head, monkeypatch):
        """Two DABA nodes over the same batches, one held to the host tail
        (its ring path answered `dyn`: shadow + pane merge + numpy, the
        exact fallback). `live_pane`: each trigger is the last row
        received, so the high edge is the live pane (`include_head`);
        `edge_rows`: rows of its micro-batch follow it in its bucket, so
        the high edge goes up as rows too. No shadow is built for a
        trigger the device finished, and what it fetches is
        (n_specs + 1) x capacity floats."""
        import ekuiper_tpu.ops.prefinalize as pf

        shadows = []

        class CountedShadow(pf.HostShadow):
            def __init__(self, *a, **kw):
                shadows.append(1)
                super().__init__(*a, **kw)

        monkeypatch.setattr(pf, "HostShadow", CountedShadow)
        node_d, got_d = mknode(sql, "daba")
        node_h, got_h = mknode(sql, "daba")
        node_h._ring_body_query = lambda *a: (None, "dyn")
        heads, fetched = [], []
        body_query = node_d._ring_body_query
        node_d._ring_body_query = lambda body, include_head, b_hi: (
            heads.append(include_head) or body_query(body, include_head,
                                                     b_hi))
        deliver = node_d._deliver_async

        def record(kind, payload, *rest):
            fetched.append((getattr(payload[0], "shape", None),
                            getattr(payload[0], "dtype", None), payload[1]))
            return deliver(kind, payload, *rest)
        node_d._deliver_async = record
        batches = spike_batches(at_end=head == "live_pane")
        for b in batches:
            node_d.process(b)
        n_shadows_device = len(shadows)
        for b in batches:
            node_h.process(b)
        node_d._drain_async_emits()
        node_h._drain_async_emits()
        trig_d, trig_h = per_trigger(got_d), per_trigger(got_h)
        assert len(trig_d) >= 6
        assert_parity(trig_d, trig_h)
        n = len(trig_d)
        assert node_h.sliding_tails == {"host": n}
        assert node_d.sliding_tails["device"] >= n - 1, node_d.sliding_tails
        assert sum(node_d.sliding_tails.values()) == n \
            == sum(node_d.sliding_triggers.values())
        assert node_d.sliding_tails["device"] == \
            node_d.sliding_triggers.get("fast", 0) \
            + node_d.sliding_triggers.get("flip", 0)
        if head == "live_pane":
            assert all(heads)
        else:
            assert not any(heads)
        # no shadow but for the triggers the host finished
        assert n_shadows_device == node_d.sliding_tails.get("host", 0)
        assert len(shadows) == n_shadows_device + n
        shape = (len(node_d.plan.specs) + 1, node_d.gb.capacity)
        on_device = [f for f in fetched if f[2] is None]
        assert len(on_device) == node_d.sliding_tails["device"]
        assert all(f[:2] == (shape, np.float32) for f in on_device)


class TestClockModes:
    def test_processing_time_mock_clock(self, mock_clock):
        """Batches WITHOUT timestamps stamp at now_ms — drive the mock
        clock so both impls bucket identically."""
        rng = np.random.default_rng(23)
        node_d, got_d = mknode(SQL_INV, "daba")
        node_r, got_r = mknode(SQL_INV, "refold")
        mock_clock.set(50_000)
        for i in range(40):
            rows = 32
            ids = np.array([f"d{j}" for j in rng.integers(0, 4, rows)],
                           dtype=np.object_)
            temp = rng.uniform(0, 88, rows).astype(np.float32)
            if i % 7 == 6:
                temp[-1] = 97.0
            b = ColumnBatch(n=rows,
                            columns={"deviceId": ids, "temp": temp},
                            emitter="s")
            node_d.process(b)
            node_r.process(b)
            mock_clock.advance(100)
        node_d._drain_async_emits()
        node_r._drain_async_emits()
        assert_parity(per_trigger(got_d), per_trigger(got_r))


class TestEviction:
    def test_evict_past_capacity(self):
        """A stream longer than the pane ring retention: old buckets
        recycle, the running totals evict in lockstep, and every emitted
        window still matches the refold path (which refolds from its row
        ring). 100+ buckets on a ~83-slot ring."""
        batches = random_trigger_batches(seed=31, n_batches=90, rows=24,
                                         spike_every=29)
        trig_d, trig_r, node = run_pair(SQL_INV, batches)
        span_ms = 90 * 100
        assert span_ms // node.bucket_ms > node.n_ring_panes
        assert_parity(trig_d, trig_r)

    def test_gap_jump_rebuilds(self):
        """A time gap far wider than the advance hysteresis marks the
        ring dirty; the next trigger rebuilds from the panes (flip) and
        stays exact."""
        b1 = trigger_batches([10_250], n_batches=3, t0=10_000)
        b2 = trigger_batches([28_250], n_batches=3, t0=28_000, seed=9)
        trig_d, trig_r, _ = run_pair(SQL_INV, b1 + b2)
        assert len(trig_d) == 2
        assert_parity(trig_d, trig_r)

    def test_late_rows_mark_dirty_and_stay_exact(self):
        """Rows folding into already-absorbed buckets taint the running
        partials; the next trigger must rebuild rather than serve them."""
        def b(ts_list, temps):
            k = len(ts_list)
            return ColumnBatch(
                n=k,
                columns={"deviceId": np.array(["d0"] * k, dtype=np.object_),
                         "temp": np.asarray(temps, dtype=np.float32)},
                timestamps=np.asarray(ts_list, dtype=np.int64), emitter="s")

        node_d, got_d = mknode(SQL_INV, "daba")
        node_r, got_r = mknode(SQL_INV, "refold")
        for node in (node_d, node_r):
            node.process(b([10_000, 10_100, 10_200], [50.0, 50.0, 50.0]))
            # 8 buckets behind the head: folds into a closed bucket
            node.process(b([10_150], [50.0]))
            node.process(b([10_400], [95.0]))  # trigger
            node._drain_async_emits()
        td, tr = per_trigger(got_d), per_trigger(got_r)
        assert_parity(td, tr)
        assert td[0]["d0"]["c"] == 5  # the late row counted


class TestKillRestore:
    @pytest.mark.parametrize("impl", ["daba", "refold"])
    def test_snapshot_roundtrip_within_impl(self, impl):
        batches = random_trigger_batches(seed=17, n_batches=16)
        # uninterrupted reference
        ref_node, ref_got = mknode(SQL_INV, impl)
        for b in batches:
            ref_node.process(b)
        ref_node._drain_async_emits()
        # kill after batch 8, restore, continue
        n1, got1 = mknode(SQL_INV, impl)
        for b in batches[:8]:
            n1.process(b)
        n1._drain_async_emits()
        snap = json.loads(json.dumps(n1.snapshot_state()))
        n2, got2 = mknode(SQL_INV, impl)
        n2.restore_state(snap)
        for b in batches[8:]:
            n2.process(b)
        n2._drain_async_emits()
        ref = per_trigger(ref_got)
        after = per_trigger(got2)
        assert len(after) >= 1
        assert len(ref) == len(per_trigger(got1)) + len(after)
        # post-restore windows (some straddle the checkpoint) match the
        # uninterrupted run
        assert_parity(after, ref[-len(after):])

    def test_cross_impl_restore(self):
        """A refold-era checkpoint restores into a DABA node (and back):
        the pane state layout is shared, the ring partials rebuild from
        the restored panes on the first trigger."""
        batches = random_trigger_batches(seed=19, n_batches=16)
        for src, dst in (("refold", "daba"), ("daba", "refold")):
            n1, _ = mknode(SQL_INV, src)
            for b in batches[:8]:
                n1.process(b)
            n1._drain_async_emits()
            snap = json.loads(json.dumps(n1.snapshot_state()))
            n2, got2 = mknode(SQL_INV, dst)
            n2.restore_state(snap)
            nr, gotr = mknode(SQL_INV, "refold")
            nr.restore_state(json.loads(json.dumps(snap)))
            for b in batches[8:]:
                n2.process(b)
                nr.process(b)
            n2._drain_async_emits()
            nr._drain_async_emits()
            assert_parity(per_trigger(got2), per_trigger(gotr))


class TestRingGuardrails:
    def test_budget_fallback_to_refold(self):
        """A ring whose static footprint exceeds slidingDevRingMb must
        refuse the DABA allocation and keep the refold path."""
        stmt = parse_select(SQL_SKETCH)
        plan = extract_kernel_plan(stmt)
        node = FusedWindowAggNode(
            "tiny", stmt.window, plan,
            dims=[d.expr for d in stmt.dimensions],
            capacity=64, micro_batch=128,
            direct_emit=build_direct_emit(stmt, plan, ["deviceId"]),
            dev_ring_budget_mb=0, sliding_impl="daba")
        assert node.sliding_impl == "refold"
        assert node.ring is None

    def test_memwatch_probe_registered(self):
        from ekuiper_tpu.observability import memwatch

        node, _ = mknode(SQL_INV, "daba")
        node.on_open()
        comps = {r["component"]
                 for r in memwatch.registry().snapshot()
                 if r["component"].startswith(("sliding", "dev_ring"))}
        assert "sliding_ring" in comps and "dev_ring" in comps
        # bytes appear once the ring allocates (first served trigger)
        for b in endspike_batches():
            node.process(b)
        node._drain_async_emits()
        assert node.ring_dev_bytes() > 0
        rows = {r["component"]: r["bytes"]
                for r in memwatch.registry().snapshot()
                if r["component"] == "sliding_ring"}
        assert rows.get("sliding_ring", 0) > 0
        # and the refold-era cache stays unbudgeted/empty under daba
        assert node._dev_ring_bytes == 0

    def test_estimate_matches_allocation(self):
        node, _ = mknode(SQL_INV, "daba")
        for b in endspike_batches():
            node.process(b)
        node._drain_async_emits()
        est = node.ring.estimate_bytes(node.gb.capacity)
        assert node.ring_dev_bytes() == est

    def test_combine_classes_are_total(self):
        """Every device component must have a ring combine class —
        a new component without one must fail loudly at plan time."""
        from ekuiper_tpu.ops.groupby import _INIT

        for comp in _INIT:
            assert (comp in ADD_COMBINE or comp in MIN_COMBINE
                    or comp in MAX_COMBINE), comp

    def test_admission_prices_ring_sites(self):
        """QoS admission must price a DABA sliding rule's extra compile
        surface (4 ring sites + components_dyn), not just the shared
        group-by sites — the signature budget would otherwise invert."""
        from ekuiper_tpu.observability import jitcert

        plan = extract_kernel_plan(parse_select(SQL_INV))
        base = jitcert.estimate_plan_signatures(plan, 1, 128, 64)
        ring = jitcert.estimate_plan_signatures(plan, 1, 128, 64,
                                                sliding_ring_slots=83)
        assert ring == base + 5

    def test_rule_option_plumbs(self):
        from ekuiper_tpu.planner.planner import RuleDef, merged_options

        opts = merged_options(RuleDef(id="r", sql="",
                                      options={"slidingImpl": "refold"}))
        assert opts.sliding_impl == "refold"
        assert merged_options(RuleDef(id="r", sql="")).sliding_impl == "daba"

    def test_layout_is_plan_time(self):
        layout = plan_ring_layout(2_000, 0, wide=False)
        assert layout.n_panes == layout.n_ring_panes + 1
        assert layout.span_buckets == -(-2_000 // layout.bucket_ms)
        node, _ = mknode(SQL_INV, "daba")
        assert node.bucket_ms == layout.bucket_ms
        assert node.n_ring_panes == layout.n_ring_panes


class TestBudgetAwareLayout:
    """ROADMAP item-2 remnant: wide-hll sliding rules must take the DABA
    ring inside the slidingDevRingMb budget by coarsening their ring
    geometry, instead of silently falling back to refold; and the
    budget check must price exactly what init_state allocates."""

    WIDE_SQL = ("SELECT deviceId, distinct_count_approx(temp) AS dc, "
                "percentile_approx(temp, 0.9) AS p90, count(*) AS c "
                "FROM s GROUP BY deviceId, "
                "SLIDINGWINDOW(ss, 30) OVER (WHEN temp > 90)")

    def test_estimate_matches_allocation(self):
        stmt = parse_select(SQL_MM)
        plan = extract_kernel_plan(stmt)
        from ekuiper_tpu.ops.groupby import DeviceGroupBy
        from ekuiper_tpu.ops.slidingring import ring_layout_for

        layout = ring_layout_for(stmt.window, plan)
        gb = DeviceGroupBy(plan, capacity=32, n_panes=layout.n_panes,
                           micro_batch=16)
        ring = SlidingRing(gb, layout)
        state = ring.init_state()
        assert ring.state_nbytes(state) == ring.estimate_bytes(32)

    def test_plan_time_estimate_matches_kernel_estimate(self):
        """The planner's no-kernel estimate (_plan_ring_bytes) must
        price the same bytes SlidingRing.estimate_bytes reports."""
        from ekuiper_tpu.ops.groupby import DeviceGroupBy
        from ekuiper_tpu.ops.slidingring import (_plan_ring_bytes,
                                                 ring_layout_for)

        stmt = parse_select(self.WIDE_SQL)
        plan = extract_kernel_plan(stmt)
        layout = ring_layout_for(stmt.window, plan)
        gb = DeviceGroupBy(plan, capacity=64, n_panes=layout.n_panes,
                           micro_batch=16)
        ring = SlidingRing(gb, layout)
        mm_slot, fixed = _plan_ring_bytes(plan, 64)
        assert fixed + (1 + layout.n_ring_panes) * mm_slot == \
            ring.estimate_bytes(64)

    def test_wide_hll_coarsens_into_budget(self):
        """A wide-hll sliding rule whose default geometry would blow the
        budget coarsens its buckets until the ring fits — and takes the
        DABA ring, not the refold fallback."""
        from ekuiper_tpu.ops.slidingring import (_plan_ring_bytes,
                                                 ring_layout_for)

        stmt = parse_select(self.WIDE_SQL)
        plan = extract_kernel_plan(stmt)
        capacity = 2048
        default = ring_layout_for(stmt.window, plan)
        mm_slot, fixed = _plan_ring_bytes(plan, capacity)
        default_bytes = fixed + (1 + default.n_ring_panes) * mm_slot
        # pick a budget the default layout misses but a coarser fits
        budget_mb = max(int(default_bytes * 0.6) >> 20, 1)
        fitted = ring_layout_for(stmt.window, plan, capacity=capacity,
                                 budget_mb=budget_mb)
        assert fitted.n_ring_panes < default.n_ring_panes
        fitted_bytes = fixed + (1 + fitted.n_ring_panes) * mm_slot
        assert fitted_bytes <= budget_mb << 20
        node = FusedWindowAggNode(
            "wide", stmt.window, plan,
            dims=[d.expr for d in stmt.dimensions],
            capacity=capacity, micro_batch=128,
            direct_emit=build_direct_emit(stmt, plan, ["deviceId"]),
            dev_ring_budget_mb=budget_mb, sliding_impl="daba")
        assert node.sliding_impl == "daba", "wide-hll rule must ride DABA"
        assert node.ring.estimate_bytes(capacity) <= budget_mb << 20

    def test_impossible_budget_still_refolds(self):
        stmt = parse_select(self.WIDE_SQL)
        plan = extract_kernel_plan(stmt)
        node = FusedWindowAggNode(
            "none", stmt.window, plan,
            dims=[d.expr for d in stmt.dimensions],
            capacity=2048, micro_batch=128,
            direct_emit=build_direct_emit(stmt, plan, ["deviceId"]),
            dev_ring_budget_mb=0, sliding_impl="daba")
        assert node.sliding_impl == "refold"
