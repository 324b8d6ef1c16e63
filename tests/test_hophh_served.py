"""`BASELINE.json` configs[1] on the served path (PR 29): a hopping-window
`heavy_hitters` rule created over REST lands on the device-fused plan, its
windows hold what a plain reference says they hold, its boundary work is
seen (stages `hh_encode`, `hh_finalize`, `hh_assemble`; the boundary's
phases; the `kuiper/hh_finalize/*` scopes), and the two routes from a sketch
to a top list agree."""
import json
import math
import time

import numpy as np
import pytest

import ekuiper_tpu.io.memory as mem
from ekuiper_tpu.observability.tracer import Tracer
from ekuiper_tpu.ops.sketches import HH_WIDTH
from ekuiper_tpu.server.processors import StreamProcessor
from ekuiper_tpu.server.rest import RestApi
from ekuiper_tpu.store import kv

N_KEYS = 64
HOP_ROWS = 4096
TOPK = 3
HEAVY = ((7, 0.35), (13, 0.20), (99, 0.15))


# ------------------------------------------------- the plain reference
# (the semantics of benchmark/references/hopping_topk.py, for hops whose
# rows the test knows because it drives the clock)
def seeded_hops(seed: int, n_hops: int):
    """Per hop: (key index, code) per row; codes 7 / 13 / 99 heavy, the
    rest uniform over 100..2099 (bench.py's mixture)."""
    rng = np.random.default_rng(seed)
    hops = []
    for _ in range(n_hops):
        keys = rng.integers(0, N_KEYS, HOP_ROWS)
        codes = rng.integers(100, 2100, HOP_ROWS)
        p = rng.random(HOP_ROWS)
        edge = 0.0
        for value, share in HEAVY:
            codes[(p >= edge) & (p < edge + share)] = value
            edge += share
        hops.append((keys, codes))
    return hops


def exact_window(hops, j: int, span: int):
    """{key: {code: count}} over hops j-span+1 .. j (those that exist)."""
    out = {}
    for keys, codes in hops[max(j - span + 1, 0):j + 1]:
        for k, c in zip(keys.tolist(), codes.tolist()):
            per = out.setdefault(k, {})
            per[c] = per.get(c, 0) + 1
    return out


def held_to_reference(windows, hops, span: int):
    """Every emitted window against the reference: `c` exact, every row in
    exactly `span` windows, top-3 values the exact ones where separated by
    more than count-min's bound, every estimate in [exact, exact + bound]."""
    assert len(windows) == len(hops) + span - 1
    total_c = 0
    n_separated = 0
    for j, msgs in enumerate(windows):
        want = exact_window(hops, j, span)
        assert sorted(m["deviceId"] for m in msgs) == \
            sorted(f"dev_{k}" for k in want)  # no key twice, none missing
        for m in msgs:
            per = want[int(m["deviceId"][4:])]
            n = sum(per.values())
            assert m["c"] == n
            total_c += n
            bound = math.ceil(math.e / HH_WIDTH * n)
            ranked = sorted(per.values(), reverse=True) + [0] * (TOPK + 1)
            top = m["top"]
            assert len(top) <= TOPK
            for pair in top:
                exact = per.get(pair["value"], 0)
                assert exact <= pair["count"] <= exact + bound, (j, m, exact)
            if ranked[TOPK - 1] - ranked[TOPK] > bound:
                n_separated += 1
                assert {p["value"] for p in top} == \
                    {c for c, x in per.items() if x >= ranked[TOPK - 1]}
    assert total_c == span * len(hops) * HOP_ROWS
    assert n_separated > 0.9 * N_KEYS * (len(windows) - 2 * (span - 1))


# ------------------------------------------------------- the served rule
def _start_rule(rule_id: str, span: int, options=None):
    store = kv.get_store()
    StreamProcessor(store).exec_stmt(
        f'CREATE STREAM {rule_id}_in (deviceId STRING, code BIGINT) WITH '
        f'(DATASOURCE="{rule_id}/in", TYPE="memory", FORMAT="JSON")')
    api = RestApi(store)
    got = []
    mem.subscribe(f"{rule_id}/out", lambda _t, payload: got.append(payload))
    code, _ = api.dispatch("POST", "/rules", {
        "id": rule_id,
        "sql": "SELECT deviceId, heavy_hitters(code, 3) AS top, "
               f"count(*) AS c FROM {rule_id}_in GROUP BY deviceId, "
               f"HOPPINGWINDOW(ss, {span}, 1)",
        "options": {"key_slots": 16384, "micro_batch_rows": HOP_ROWS,
                    "micro_batch_linger_ms": 50, "decodePoolSize": 2,
                    **(options or {})},
        "actions": [{"memory": {"topic": f"{rule_id}/out"}}]}, {})
    assert code in (200, 201)
    deadline = time.time() + 20
    while time.time() < deadline:
        rs = api.rules.state(rule_id)
        if rs is not None and rs.topo is not None and rs.topo._open:
            break
        time.sleep(0.05)
    return api, got


def _drive_hop(mock_clock, topic: str, hop, got, expect_window=True):
    """One hop of rows, then its boundary; waits for the window."""
    n_before = len(got)
    if hop is not None:
        keys, codes = hop
        mem.publish(topic, [
            json.dumps({"deviceId": f"dev_{k}", "code": c}).encode()
            for k, c in zip(keys.tolist(), codes.tolist())])
        mock_clock.advance(60)  # the linger flush (a full batch cut itself)
        time.sleep(0.4)  # decode pool -> fused worker, in real threads
        mock_clock.advance(940)  # the boundary
    else:
        # let the node's threads settle after the last window: an advance
        # right behind it lost this boundary in 3 of 128 runs on a loaded
        # machine (parent and change alike), in none of 96 with the pause
        time.sleep(0.3)
        mock_clock.advance(1000)
    deadline = time.time() + 20
    while expect_window and time.time() < deadline and len(got) <= n_before:
        time.sleep(0.02)
    if expect_window:
        assert len(got) > n_before, "the window never reached the sink"


@pytest.mark.parametrize("span", [2, 3])
@pytest.mark.parametrize("seed", [11, 2 ** 31 + 5, 977])
def test_served_hopping_heavy_hitters_against_the_reference(
        mock_clock, seed, span):
    rule_id = f"hophh_{span}_{seed % 1000}"
    api, got = _start_rule(rule_id, span)
    try:
        code, explain = api.dispatch(
            "GET", f"/rules/{rule_id}/explain", None, {})
        assert code == 200 and explain["path"] == "device-fused", explain
        fused = next(n for n in api.rules.state(rule_id).topo.ops
                     if type(n).__name__ == "FusedWindowAggNode")
        assert fused.n_panes == span and fused._async_hh
        hops = seeded_hops(seed, 4)
        for hop in hops:
            _drive_hop(mock_clock, f"{rule_id}/in", hop, got)
        for _ in range(span - 1):  # the windows that still hold old hops
            _drive_hop(mock_clock, f"{rule_id}/in", None, got)
        _drive_hop(mock_clock, f"{rule_id}/in", None, got,
                   expect_window=False)  # an empty window emits nothing
        fused._drain_async_emits()
        held_to_reference(got, hops, span)
        status = api.rules.state(rule_id).topo.status()
        assert not any(v for k, v in status.items()
                       if k.endswith("_exceptions_total"))
        sources = next(v for k, v in status.items()
                       if k.endswith("_emit_sources"))
        assert sources.get("backstop", 0) == 0
        assert sum(sources.values()) == len(got)
    finally:
        api.rules.stop_all()


# ------------------------------------ the boundary's host tail (PR 33)
def test_served_window_makes_no_call_per_key_or_per_candidate(
        mock_clock, monkeypatch):
    """The hot path assembles a window's lists with array operations: the
    scalar `hh_dedupe_topk` and `ValueDict.decode` are never called, and
    the windows still hold what the reference says."""
    from ekuiper_tpu.ops import prefinalize
    from ekuiper_tpu.ops.aggspec import ValueDict

    def scalar_call(*_a, **_k):
        raise AssertionError("a per-key or per-candidate call on the "
                             "served boundary")

    monkeypatch.setattr(prefinalize, "hh_dedupe_topk", scalar_call)
    monkeypatch.setattr(ValueDict, "decode", scalar_call)
    api, got = _start_rule("hophh_flat", 2)
    try:
        hops = seeded_hops(33, 3)
        for hop in hops:
            _drive_hop(mock_clock, "hophh_flat/in", hop, got)
        _drive_hop(mock_clock, "hophh_flat/in", None, got)
        topo = api.rules.state("hophh_flat").topo
        fused = next(n for n in topo.ops
                     if type(n).__name__ == "FusedWindowAggNode")
        fused._drain_async_emits()
        held_to_reference(got, hops, 2)
        status = topo.status()
        assert not any(v for k, v in status.items()
                       if k.endswith("_exceptions_total"))
        sources = next(v for k, v in status.items()
                       if k.endswith("_emit_sources"))
        assert sources == {"device-async": len(got)}
        for msgs in got:  # the emitted types: Python's own
            for m in msgs:
                assert type(m["top"]) is list
                for pair in m["top"]:
                    assert list(pair) == ["value", "count"]
                    assert type(pair["value"]) is int
                    assert type(pair["count"]) is int
    finally:
        api.rules.stop_all()


def test_values_learnt_after_the_decode_table_was_built(mock_clock):
    """The emit worker decodes from an array of the dictionary's values,
    built again only when the dictionary has grown: two hops of known
    values decode from the same array, and values the fused worker learns
    afterwards are decoded at the next hop."""
    api, got = _start_rule("hophh_grow", 2)
    try:
        rng = np.random.default_rng(33)
        known = np.array([7, 13, 99])

        def hop(values):
            return (rng.integers(0, N_KEYS, HOP_ROWS),
                    rng.choice(values, HOP_ROWS))

        def tops(msgs):
            return {p["value"] for m in msgs for p in m["top"]}

        topo = api.rules.state("hophh_grow").topo
        fused = next(n for n in topo.ops
                     if type(n).__name__ == "FusedWindowAggNode")
        _drive_hop(mock_clock, "hophh_grow/in", hop(known), got)
        fused._drain_async_emits()
        vd = fused._hh_dicts["code"]
        table = vd._decode_table
        assert len(table) == 4 and tops(got[0]) == {7, 13, 99}
        _drive_hop(mock_clock, "hophh_grow/in", hop(known), got)
        _drive_hop(mock_clock, "hophh_grow/in", hop(known), got)
        fused._drain_async_emits()
        assert vd._decode_table is table  # known values: not rebuilt
        assert tops(got[1]) == tops(got[2]) == {7, 13, 99}
        late = np.array([5000, 6000, 7000])
        for _ in range(2):  # both panes of a window hold the late values
            _drive_hop(mock_clock, "hophh_grow/in", hop(late), got)
        fused._drain_async_emits()
        assert vd._decode_table is not table
        assert len(vd._decode_table) == 7
        assert tops(got[3]) <= {7, 13, 99, 5000, 6000, 7000}
        assert tops(got[4]) == {5000, 6000, 7000}
        assert not any(v for k, v in topo.status().items()
                       if k.endswith("_exceptions_total"))
    finally:
        api.rules.stop_all()


# --------------------------------------------- what the boundary is seen by
@pytest.fixture
def fresh_tracer():
    old = Tracer._instance
    Tracer._instance = Tracer()
    yield Tracer._instance
    Tracer._instance = old


def _host_event_names(trace_dir: str) -> set:
    import glob

    from jax.profiler import ProfileData

    path = glob.glob(trace_dir + "/plugins/profile/*/*.xplane.pb")[0]
    return {ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("kuiper:")}


def test_hh_stages_in_metrics_trace_and_profile(mock_clock, fresh_tracer,
                                                tmp_path):
    import jax

    api, got = _start_rule("hophh_seen", 2)
    try:
        hops = seeded_hops(3, 4)
        _drive_hop(mock_clock, "hophh_seen/in", hops[0], got)  # compiles
        fresh_tracer.enable("hophh_seen")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            _drive_hop(mock_clock, "hophh_seen/in", hops[1], got)
        finally:
            jax.profiler.stop_trace()
        _drive_hop(mock_clock, "hophh_seen/in", hops[2], got)
        topo = api.rules.state("hophh_seen").topo
        fused = next(n for n in topo.ops
                     if type(n).__name__ == "FusedWindowAggNode")
        fused._drain_async_emits()
        deadline = time.time() + 5
        while time.time() < deadline and \
                topo.boundary_hists["sink"].count < len(got):
            time.sleep(0.02)

        # ---- counters: one hh_finalize and one hh_assemble a window, one
        # hh_encode a micro-batch, each inside the stage that holds it
        st = fused.stats.snapshot()["stage_timings"]
        assert st["hh_finalize"]["calls"] == st["hh_assemble"]["calls"] \
            == len(got) == 3
        assert st["hh_encode"]["calls"] == st["upload"]["calls"] == 3
        assert st["hh_encode"]["rows"] == 3 * HOP_ROWS
        assert st["hh_assemble"]["rows"] == 3 * N_KEYS
        assert st["hh_encode"]["total_us"] <= st["upload"]["total_us"]
        assert st["hh_assemble"]["total_us"] <= st["emit"]["total_us"]
        assert 0 < st["hh_assemble"]["cpu_us"] <= \
            st["hh_assemble"]["total_us"]
        # dispatch -> landed: longer than the worker's wait for it alone
        assert st["hh_finalize"]["total_us"] >= st["hh_finalize"]["cpu_us"]
        # nested stages are counted, but not summed into the node's busy
        # time twice: `health_sample` is what the health plane's covered
        # time and `kuiper_bottleneck_stage` are computed from
        assert fused.stats.nested_stages == {
            "hh_encode", "hh_encode_new", "hh_finalize", "hh_assemble",
            "key_encode",  # (the mirror of new keys, PR 37)
            "fold_h2d"}  # (the fold's staging, PR 40)
        sample = fused.stats.health_sample()["stages"]
        assert set(sample) == {"upload", "fold", "emit",
                               "boundary_reset", "release"}  # (PR 40)
        code, text = api.dispatch("GET", "/metrics", None, {})
        for stage in ("hh_encode", "hh_finalize", "hh_assemble"):
            for fam in ("us", "cpu_us", "calls", "rows"):
                assert any(
                    ln.startswith(f"kuiper_op_stage_{fam}_total{{")
                    and f'stage="{stage}"' in ln
                    for ln in text.splitlines()), (stage, fam)
        for phase in ("trigger_delay", "emit", "sink"):
            line = (f'kuiper_boundary_ms_count{{rule="hophh_seen",'
                    f'phase="{phase}"}} ')
            count = next(float(ln[len(line):]) for ln in text.splitlines()
                         if ln.startswith(line))
            assert count == len(got)

        # ---- the rule's trace: the three stages as spans under theirs
        spans = [s for tid in fresh_tracer.rule_traces("hophh_seen")
                 for s in fresh_tracer.trace(tid)]
        by_id = {s["spanId"]: s for s in spans}
        for stage, parent in (("hh_encode", "upload"),
                              ("hh_finalize", "emit"),
                              ("hh_assemble", "emit")):
            mine = [s for s in spans if s.get("stage") == stage]
            assert mine, stage
            for s in mine:
                assert by_id[s["parentSpanId"]].get("stage") == parent
                assert s["attributes"]["within"] == parent

        # ---- the profiler's host plane
        names = _host_event_names(str(tmp_path))
        assert {"kuiper:hh_encode", "kuiper:hh_finalize",
                "kuiper:hh_assemble", "kuiper:jit:hh_finalize",
                "kuiper:emit", "kuiper:upload"} <= names, sorted(names)
    finally:
        api.rules.stop_all()


def _stage_total(text: str, fam: str, stage: str) -> float:
    """Sum of `kuiper_op_stage_<fam>_total{...stage="<stage>"...}`."""
    return sum(float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
               if ln.startswith(f"kuiper_op_stage_{fam}_total{{")
               and f'stage="{stage}"' in ln)


def test_hh_encode_new_counts_the_rows_the_table_lacked(mock_clock,
                                                        fresh_tracer):
    """`hh_encode_new` (PR 30) is opened for a micro-batch that has values
    no table knows, with those rows: all of a cold dictionary's first
    batch, none of a batch of known values, the first-seen rows of a later
    one. Over `hh_encode`'s rows it is the miss share."""
    api, got = _start_rule("hophh_new", 2)
    try:
        fresh_tracer.enable("hophh_new")
        rng = np.random.default_rng(30)
        known = np.array([7, 13, 99] + list(range(100, 150)))

        def hop(first_seen=()):
            codes = rng.choice(known, HOP_ROWS)
            codes[:len(first_seen)] = first_seen
            return rng.integers(0, N_KEYS, HOP_ROWS), codes

        topo = api.rules.state("hophh_new").topo
        fused = next(n for n in topo.ops
                     if type(n).__name__ == "FusedWindowAggNode")

        def marks():
            st = fused.stats.snapshot()["stage_timings"]
            new = st.get("hh_encode_new", {"calls": 0, "rows": 0})
            return (st["hh_encode"]["calls"], st["hh_encode"]["rows"],
                    new["calls"], new["rows"])

        _drive_hop(mock_clock, "hophh_new/in", hop(), got)
        assert marks() == (1, HOP_ROWS, 1, HOP_ROWS)  # a cold dictionary
        for _ in range(3):  # known values only: the stage is not opened
            _drive_hop(mock_clock, "hophh_new/in", hop(), got)
        assert marks() == (4, 4 * HOP_ROWS, 1, HOP_ROWS)
        _drive_hop(mock_clock, "hophh_new/in",
                   hop(first_seen=[5000, 6000, 5000]), got)
        assert marks() == (5, 5 * HOP_ROWS, 2, HOP_ROWS + 3)
        _drive_hop(mock_clock, "hophh_new/in", hop(), got)
        assert marks() == (6, 6 * HOP_ROWS, 2, HOP_ROWS + 3)
        fused._drain_async_emits()
        # the windows say the same: the three rows are counted as 5000 / 6000
        late = {p["value"] for msgs in got[4:6] for m in msgs
                for p in m["top"]}
        assert late <= set(known.tolist()) | {5000, 6000}

        # ---- /metrics: the four families; rows over rows = the miss share
        code, text = api.dispatch("GET", "/metrics", None, {})
        assert _stage_total(text, "calls", "hh_encode_new") == 2
        assert _stage_total(text, "rows", "hh_encode_new") == HOP_ROWS + 3
        assert _stage_total(text, "rows", "hh_encode") == 6 * HOP_ROWS
        assert 0 < _stage_total(text, "cpu_us", "hh_encode_new") <= \
            _stage_total(text, "us", "hh_encode_new") <= \
            _stage_total(text, "us", "hh_encode")
        # ---- a nested stage: counted, and left out of the health plane
        assert "hh_encode_new" in fused.stats.nested_stages
        assert set(fused.stats.health_sample()["stages"]) >= {
            "upload", "fold", "emit"}
        assert "hh_encode_new" not in fused.stats.health_sample()["stages"]
        # ---- the rule's trace: a span under hh_encode, two in six batches
        spans = [s for tid in fresh_tracer.rule_traces("hophh_new")
                 for s in fresh_tracer.trace(tid)]
        by_id = {s["spanId"]: s for s in spans}
        mine = [s for s in spans if s.get("stage") == "hh_encode_new"]
        assert sorted(s["rows"] for s in mine) == [3, HOP_ROWS]
        for s in mine:
            assert by_id[s["parentSpanId"]].get("stage") == "hh_encode"
            assert s["attributes"]["within"] == "hh_encode"
        assert len([s for s in spans if s.get("stage") == "hh_encode"]) == 6
    finally:
        api.rules.stop_all()


def _hh_groupby(capacity: int, n_panes: int = 2):
    from ekuiper_tpu.ops.aggspec import extract_kernel_plan
    from ekuiper_tpu.ops.groupby import DeviceGroupBy
    from ekuiper_tpu.sql.parser import parse_select

    stmt = parse_select(
        "SELECT deviceId, heavy_hitters(code, 3) AS top, count(*) AS c "
        "FROM s GROUP BY deviceId, HOPPINGWINDOW(ss, 2, 1)")
    return DeviceGroupBy(extract_kernel_plan(stmt), capacity=capacity,
                         n_panes=n_panes, micro_batch=64)


def test_hh_finalize_program_name_and_scopes():
    """`trace_call_roofline` finds the program by `hh_finalize` in its
    name; the scopes name the ops inside it."""
    import jax

    gb = _hh_groupby(64)
    text = jax.jit(gb._hh_finalize_impl).lower(
        gb.init_state(), np.ones(2, dtype=np.bool_)
    ).as_text(debug_info=True)
    head = next(ln for ln in text.splitlines() if ln.startswith("module @"))
    assert "module @jit__hh_finalize_impl" in head, head
    for scope in ("kuiper/hh_finalize/pane_merge",
                  "kuiper/hh_finalize/candidates",
                  "kuiper/hh_finalize/values", "kuiper/hh_finalize/stack"):
        assert scope in text, scope
    assert gb._hh_fin.rec.trace_name == "kuiper:jit:hh_finalize"


# ------------------------------------- two routes from a sketch to a list
@pytest.mark.parametrize("seed", [1, 2, 3, 2 ** 31 + 9])
def test_hh_assemble_equals_hh_topk_np_on_seeded_sketches(seed):
    """`hh_dedupe_topk`'s docstring: the device route (candidates on the
    device, `hh_assemble` on the host) and the numpy components route
    (`hh_topk_np`) give identical top lists."""
    from ekuiper_tpu.ops.prefinalize import hh_topk_np, hh_update_parts_np
    from ekuiper_tpu.ops.sketches import HH_SIZE

    rng = np.random.default_rng(seed)
    cap, n_live = 32, 24
    hh = np.zeros((2, cap, 1, HH_SIZE), dtype=np.float32)
    n = np.zeros((2, cap, 1), dtype=np.float32)
    for key in range(n_live):
        # four heavy codes with counts that cannot tie, a light tail
        heavy = rng.choice(50, size=4, replace=False)
        for pane in range(2):
            codes = np.concatenate(
                [np.repeat(heavy, [40 + 3 * key, 29, 17, 9])]
                + [rng.integers(50, 1500, 30)]).astype(np.float32)
            idx, wts = hh_update_parts_np(codes, np.ones(len(codes),
                                                         np.float32))
            np.add.at(hh[pane, key, 0], idx.ravel(), wts.ravel())
            n[pane, key, 0] += len(codes)
    gb = _hh_groupby(cap)
    state = {"hh": hh, "n": n, "act": n[..., 0].copy()}
    stacked = np.asarray(gb._hh_fin(state, np.ones(2, dtype=np.bool_)))
    outs, act = gb.hh_assemble(stacked, n_live)
    want = hh_topk_np(hh.sum(axis=0)[:, 0], TOPK)
    assert len(outs[0]) == n_live and (act > 0).all()
    for key in range(n_live):
        assert outs[0][key] == want[key], key
        assert len(outs[0][key]) == TOPK
    assert (outs[1] == n.sum(axis=0)[:n_live, 0]).all()
    assert all(want[key] == [] for key in range(n_live, cap))
