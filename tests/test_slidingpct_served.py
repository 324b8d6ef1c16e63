"""`BASELINE.json` configs[2] on the served path (PR 34): a trigger-gated
`SLIDINGWINDOW(ss, 10)` of per-device `percentile_approx` created over REST
lands on the device-fused plan with the DABA ring; every trigger's window
holds what a plain reference says it holds (the semantics of
`benchmark/references/sliding_quantile.py`: exact `c` per key over the rows
stamped in (t - L, t], the percentile within the sketch's stated error of the
exact order statistic) and what the host operator path answers; and the
trigger's work is seen (stages `slide_edge`, `slide_advance`, `slide_query`,
`slide_merge`; `kuiper_sliding_triggers_total`, `kuiper_sliding_tail_total`;
the `kuiper/slide_query/*` and `kuiper/slide_tail/*` scopes). Since PR 35 a
trigger the ring's running partials served is finished on the device."""
import json
import math
import time

import numpy as np
import pytest

import ekuiper_tpu.io.memory as mem
from ekuiper_tpu.observability.tracer import Tracer
from ekuiper_tpu.server.processors import StreamProcessor
from ekuiper_tpu.server.rest import RestApi
from ekuiper_tpu.store import kv

N_KEYS = 64
DRAIN_ROWS = 64
LENGTH_MS = 10_000
FRAC = 0.99
THRESHOLD = 44.5
# the sketch as the configuration states it, not as the program has it:
# 1,024 signed log bins over [1e-9, 1e12), so 510 ratios a half
_GAMMA = (1e12 / 1e-9) ** (1.0 / 510)
ROOT_GAMMA = math.sqrt(_GAMMA)  # the stated relative error: 4.9 %
SQL = ("SELECT deviceId, percentile_approx(temperature, 0.99) AS p99, "
       "count(*) AS c, window_end() AS we FROM {stream} GROUP BY deviceId, "
       "SLIDINGWINDOW(ss, 10) OVER (WHEN temperature > 44.5)")


# ------------------------------------------------- the plain reference
def seeded_drains(seed: int, n_drains: int, trigger_drains):
    """Per drain (key index, value) per row: normal(20, 5) to 2 decimals
    capped under the threshold; the LAST row of each drain in
    `trigger_drains` is 99.0 (the host operator fires on the trigger row
    itself, the device path after its drain: the same rows only where no
    row follows it in its drain)."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, N_KEYS, (n_drains, DRAIN_ROWS))
    vals = np.minimum(np.round(rng.normal(20.0, 5.0, keys.shape), 2), 44.49)
    vals[list(trigger_drains), -1] = 99.0
    return keys, vals.astype(np.float32)


def exact_window(keys, vals, stamps, batch_of, d: int):
    """{key: sorted values} of the window of the trigger in drain d: the
    rows stamped in (t - L, t] that were folded when it fired — those of
    its micro-batch and the ones before."""
    t = stamps[d]
    inside = [i for i in range(len(stamps))
              if t - LENGTH_MS < stamps[i] <= t and batch_of[i] <= batch_of[d]]
    out = {}
    for i in inside:
        for k, v in zip(keys[i].tolist(), vals[i].tolist()):
            out.setdefault(k, []).append(v)
    return {k: sorted(v) for k, v in out.items()}


def rank_interval(values):
    """The exact order statistic at the sketch's rank convention (the
    first bin whose cumulative count reaches frac x n), as an interval:
    one rank wide but where frac x n is a whole number, which float32
    arithmetic may round up."""
    n = len(values)
    r = max(math.ceil(FRAC * n - 1e-4), 1)
    r_hi = min(math.floor(FRAC * n + 1e-4) + 1, n)
    return values[r - 1], values[max(r_hi, r) - 1]


def held_to_reference(windows, keys, vals, stamps, batch_of, trigger_drains,
                      root=ROOT_GAMMA, host=False):
    """Every emitted window against the reference: the stamp, no key twice
    and none missing, `c` exact, p99 within the stated error of the exact
    order statistic (the host path's percentile is exact and
    interpolates: between the neighbours of that rank)."""
    assert len(windows) == len(trigger_drains)
    for msgs, d in zip(windows, trigger_drains):
        want = exact_window(keys, vals, stamps, batch_of, d)
        assert sorted(m["deviceId"] for m in msgs) == \
            sorted(f"dev_{k}" for k in want)
        for m in msgs:
            rows = want[int(m["deviceId"][4:])]
            assert m["c"] == len(rows), (d, m)
            assert m["we"] == stamps[d]
            if host:
                n = len(rows)
                at = (n - 1) * FRAC
                lo, hi = rows[math.floor(at)], rows[math.ceil(at)]
                assert lo - 1e-4 <= m["p99"] <= hi + 1e-4, (d, m, lo, hi)
            else:
                lo, hi = rank_interval(rows)
                assert lo / root * (1 - 1e-4) <= m["p99"] \
                    <= hi * root * (1 + 1e-4), (d, m, lo, hi)


# ------------------------------------------------------- the served rule
def _start(rule_id: str, micro_batch_rows: int, with_host: bool = False):
    store = kv.get_store()
    StreamProcessor(store).exec_stmt(
        f'CREATE STREAM {rule_id}_in (deviceId STRING, temperature FLOAT) '
        f'WITH (DATASOURCE="{rule_id}/in", TYPE="memory", FORMAT="JSON")')
    api = RestApi(store)
    got, got_host = [], []
    mem.subscribe(f"{rule_id}/out", lambda _t, p: got.append(p))
    mem.subscribe(f"{rule_id}/host", lambda _t, p: got_host.append(p))
    rules = [(rule_id, f"{rule_id}/out", {})]
    if with_host:
        rules.append((rule_id + "_host", f"{rule_id}/host",
                      {"use_device_kernel": False}))
    for rid, topic, extra in rules:
        code, _ = api.dispatch("POST", "/rules", {
            "id": rid, "sql": SQL.format(stream=f"{rule_id}_in"),
            "options": {"key_slots": 128,
                        "micro_batch_rows": micro_batch_rows,
                        "micro_batch_linger_ms": 100000,
                        "decodePoolSize": 2, **extra},
            "actions": [{"memory": {"topic": topic}}]}, {})
        assert code in (200, 201)
    deadline = time.time() + 20
    for rid, _topic, _extra in rules:
        while time.time() < deadline:
            rs = api.rules.state(rid)
            if rs is not None and rs.topo is not None and rs.topo._open:
                break
            time.sleep(0.05)
    return api, got, got_host


def _fused(api, rule_id: str):
    return next(n for n in api.rules.state(rule_id).topo.ops
                if type(n).__name__ == "FusedWindowAggNode")


def _publish(topic: str, keys_d, vals_d) -> None:
    mem.publish(topic, [
        json.dumps({"deviceId": f"dev_{k}", "temperature": v}).encode()
        for k, v in zip(keys_d.tolist(), np.round(
            vals_d.astype(np.float64), 2).tolist())])


def _wait_for(got, n: int, what: str) -> None:
    deadline = time.time() + 60
    while time.time() < deadline and len(got) < n:
        time.sleep(0.02)
    assert len(got) == n, f"{len(got)} of {n} {what} reached the sink"


def _drive(mock_clock, topic: str, keys, vals, steps_ms):
    """Publish every drain at the mock clock's now, advancing it by the
    drain's step afterwards; returns the stamps. Stamps are taken at the
    publish (one per drain), so the engine's threads may lag behind."""
    stamps = []
    for i in range(len(keys)):
        stamps.append(mock_clock.now_ms())
        _publish(topic, keys[i], vals[i])
        if steps_ms[i]:
            mock_clock.advance(int(steps_ms[i]))
    return stamps


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 5, 977])
def test_served_sliding_percentiles_against_the_reference(mock_clock, seed):
    """Micro-batches of two drains, a drain every 60 ms over 3.4 window
    lengths: a micro-batch straddles a 208 ms bucket edge more often than
    not (the per-row pane-vector fold), the first trigger falls in the
    stream's first bucket, two triggers come in drains that share their
    stamp with the next drain (in the trigger's micro-batch: in the
    window; in the next one: not yet folded, so not)."""
    rule_id = f"slide_{seed % 1000}"
    api, got, _ = _start(rule_id, 2 * DRAIN_ROWS)
    try:
        code, explain = api.dispatch(
            "GET", f"/rules/{rule_id}/explain", None, {})
        assert code == 200 and explain["path"] == "device-fused", explain
        assert explain["sliding"]["impl"] == "daba", explain["sliding"]
        fused = _fused(api, rule_id)
        assert fused.sliding_impl == "daba"
        assert (fused.bucket_ms, fused.n_ring_panes, fused.gb.n_panes) \
            == (208, 52, 53)
        n_drains = 568  # 284 whole micro-batches
        triggers = [1, 57, 130, 203, 258, 333, 391, 466, 531, 567]
        keys, vals = seeded_drains(seed, n_drains, triggers)
        steps = np.full(n_drains, 60)
        steps[130] = 0  # drain 131 shares 130's stamp and micro-batch
        steps[203] = 0  # drain 204 shares 203's stamp, in the next one
        stamps = _drive(mock_clock, f"{rule_id}/in", keys, vals, steps)
        assert stamps[-1] > 3 * LENGTH_MS
        _wait_for(got, len(triggers), "windows")
        fused._drain_async_emits()
        batch_of = [i // 2 for i in range(n_drains)]
        held_to_reference(got, keys, vals, stamps, batch_of, triggers)
        # the cases were cases: the first window lies in the stream's first
        # bucket; drain 131 is counted with 130 (its stamp, its
        # micro-batch), 204 not with 203 (its stamp, the next micro-batch)
        assert stamps[1] < 208
        assert sum(m["c"] for m in got[0]) == 2 * DRAIN_ROWS
        assert stamps[131] == stamps[130] and stamps[204] == stamps[203]
        assert sum(m["c"] for m in got[2]) == 132 * DRAIN_ROWS
        in_203 = sum(stamps[203] - LENGTH_MS < s for s in stamps[:204])
        assert sum(m["c"] for m in got[3]) == in_203 * DRAIN_ROWS
        # ... both fold forms ran: a micro-batch whose two drains lie in
        # two buckets takes the per-row pane vector
        straddling = sum(stamps[i] // 208 != stamps[i + 1] // 208
                         for i in range(0, n_drains, 2))
        assert 50 < straddling < 284
        status = api.rules.state(rule_id).topo.status()
        assert not any(v for k, v in status.items()
                       if k.endswith("_exceptions_total"))
        assert not any(v for k, v in status.items()
                       if k.endswith("_dropped_total"))
        sources = next(v for k, v in status.items()
                       if k.endswith("_emit_sources"))
        assert sources == {"device-ring": len(triggers)}
        # triggers by path: the first finds the partials cold (flip); one
        # whose micro-batch ends in a later bucket than its own stamp's
        # finds the head moved on and takes the exact pane merge (dyn);
        # the rest are one combine of the running partials (fast)
        moved_on = sum(stamps[d | 1] // 208 > stamps[d] // 208
                       for d in triggers)
        assert 0 < moved_on < len(triggers) - 1
        assert fused.sliding_triggers == {
            "flip": 1, "dyn": moved_on,
            "fast": len(triggers) - 1 - moved_on}
        # ... and by where the trigger was finished: the device for what
        # the ring's program served, the host for the exact fallback
        assert fused.sliding_tails == {
            "device": len(triggers) - moved_on, "host": moved_on}
        code, text = api.dispatch("GET", "/metrics", None, {})
        by_tail = {
            ln.split('tail="')[1].split('"')[0]: float(ln.rsplit(" ", 1)[1])
            for ln in text.splitlines()
            if ln.startswith("kuiper_sliding_tail_total{")
            and f'rule="{rule_id}"' in ln}
        assert by_tail == {k: float(v)
                           for k, v in fused.sliding_tails.items()}
        by_path = {
            ln.split('path="')[1].split('"')[0]: float(ln.rsplit(" ", 1)[1])
            for ln in text.splitlines()
            if ln.startswith("kuiper_sliding_triggers_total{")
            and f'rule="{rule_id}"' in ln}
        assert sum(by_tail.values()) == sum(by_path.values()) \
            == len(triggers)
        assert by_tail["device"] == by_path["fast"] + by_path["flip"]
        assert by_tail["host"] == by_path["dyn"] + by_path.get("edge", 0)
    finally:
        api.rules.stop_all()


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 77])
def test_served_path_reference_and_host_operator_agree(mock_clock, seed):
    """The host window operator stamps a trigger with the clock at which
    it *processes* the row, so this case runs in lockstep: one drain a
    micro-batch, the clock advanced once both rules have taken it. Both
    paths then answer for the same rows: `c` equal key by key, each
    percentile held to the same exact order statistic."""
    rule_id = f"slideh_{seed % 1000}"
    api, got, got_host = _start(rule_id, DRAIN_ROWS, with_host=True)
    try:
        code, explain = api.dispatch(
            "GET", f"/rules/{rule_id}_host/explain", None, {})
        assert code == 200 and explain["path"] != "device-fused", explain
        n_drains = 150
        triggers = [0, 33, 71, 108, 149]
        keys, vals = seeded_drains(seed, n_drains, triggers)
        topos = [api.rules.state(r).topo
                 for r in (rule_id, rule_id + "_host")]
        stamps = []
        for i in range(n_drains):
            stamps.append(mock_clock.now_ms())
            _publish(f"{rule_id}/in", keys[i], vals[i])
            for topo in topos:
                assert topo.wait_idle(20.0)
            mock_clock.advance(210)  # past a bucket edge every drain
        assert stamps[-1] > 3 * LENGTH_MS
        _wait_for(got, len(triggers), "device windows")
        _wait_for(got_host, len(triggers), "host windows")
        _fused(api, rule_id)._drain_async_emits()
        batch_of = list(range(n_drains))
        held_to_reference(got, keys, vals, stamps, batch_of, triggers)
        host = [w if isinstance(w, list) else [w] for w in got_host]
        held_to_reference(host, keys, vals, stamps, batch_of, triggers,
                          host=True)
        for dev_msgs, host_msgs in zip(got, host):
            assert {m["deviceId"]: m["c"] for m in dev_msgs} \
                == {m["deviceId"]: m["c"] for m in host_msgs}
    finally:
        api.rules.stop_all()


@pytest.mark.parametrize("fault", ["sketch_halved", "edge_bucket_dropped"])
def test_reference_refuses_a_weaker_answer(fault):
    """What the comparison above is tight enough for, shown on the
    reference's own rows (the engine untouched): the answer of a sketch
    of half the bins, and a window without its low edge bucket's rows,
    are refused."""
    n_drains, triggers = 568, [531]
    keys, vals = seeded_drains(11, n_drains, triggers)
    stamps = [60 * i for i in range(n_drains)]
    batch_of = [i // 4 for i in range(n_drains)]
    d = triggers[0]
    lo_cut = stamps[d] - LENGTH_MS
    edge_end = (lo_cut // 208 + 1) * 208  # the low edge bucket's end
    want = exact_window(keys, vals, stamps, batch_of, d)
    if fault == "edge_bucket_dropped":
        kept = [i for i in range(n_drains) if lo_cut < stamps[i] <= stamps[d]
                and not stamps[i] < edge_end]
        assert len(kept) < sum(lo_cut < s <= stamps[d] for s in stamps)
        rows = {}
        for i in kept:
            for k, v in zip(keys[i].tolist(), vals[i].tolist()):
                rows.setdefault(k, []).append(v)
        want_answer = {k: sorted(v) for k, v in rows.items()}
        gamma = _GAMMA
    else:
        want_answer = want
        gamma = _GAMMA ** 2  # half the bins: twice the bin width in log

    def sketch(values):
        r = max(math.ceil(FRAC * len(values) - 1e-4), 1)
        idx = math.floor(math.log(values[r - 1] / 1e-9) / math.log(gamma))
        return 1e-9 * gamma ** (idx + 0.5)
    msgs = [{"deviceId": f"dev_{k}", "c": len(v), "p99": sketch(v),
             "we": stamps[d]} for k, v in want_answer.items()]
    with pytest.raises(AssertionError):
        held_to_reference([msgs], keys, vals, stamps, batch_of, triggers)
    # ... and the sound answer of the stated sketch passes
    sound = [{"deviceId": f"dev_{k}", "c": len(v), "we": stamps[d],
              "p99": 1e-9 * _GAMMA ** (math.floor(
                  math.log(rank_interval(v)[0] / 1e-9)
                  / math.log(_GAMMA)) + 0.5)} for k, v in want.items()]
    held_to_reference([sound], keys, vals, stamps, batch_of, triggers)


# --------------------------------------------- what the trigger is seen by
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


NEW_STAGES = ("slide_edge", "slide_advance", "slide_query", "slide_merge")


def test_slide_stages_in_metrics_trace_and_profile(mock_clock, fresh_tracer,
                                                   tmp_path):
    import jax

    rule_id = "slide_seen"
    api, got, _ = _start(rule_id, 2 * DRAIN_ROWS)
    try:
        # every trigger in the last drain of its micro-batch: none finds
        # the head moved on, so each is served by the ring's own program
        n_drains, triggers = 120, [11, 51, 91, 119]
        keys, vals = seeded_drains(3, n_drains, triggers)
        steps = np.full(n_drains, 60)
        topic = f"{rule_id}/in"
        stamps = _drive(mock_clock, topic, keys[:40], vals[:40], steps)
        _wait_for(got, 1, "windows")  # everything has compiled
        fresh_tracer.enable(rule_id)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            stamps += _drive(mock_clock, topic, keys[40:80], vals[40:80],
                             steps)
            _wait_for(got, 2, "windows")
        finally:
            jax.profiler.stop_trace()
        stamps += _drive(mock_clock, topic, keys[80:], vals[80:], steps)
        _wait_for(got, len(triggers), "windows")
        fused = _fused(api, rule_id)
        fused._drain_async_emits()
        held_to_reference(got, keys, vals, stamps,
                          [i // 2 for i in range(n_drains)], triggers)

        # ---- counters: per trigger one slide_edge on the fused worker,
        # one slide_query and one slide_merge on the emit worker; one
        # slide_advance per closed bucket and per flip
        st = fused.stats.snapshot()["stage_timings"]
        n = len(triggers)
        assert st["slide_edge"]["calls"] == st["slide_query"]["calls"] \
            == st["slide_merge"]["calls"] == n
        assert st["slide_query"]["rows"] == st["slide_merge"]["rows"] \
            == n * N_KEYS
        # the low edge bucket's rows: at most one bucket's (4 drains) a
        # trigger; none while the window reaches back past the first row
        assert 0 <= st["slide_edge"]["rows"] <= n * 4 * DRAIN_ROWS
        assert st["slide_advance"]["calls"] >= stamps[-1] // 208 - 2
        assert st["slide_merge"]["total_us"] <= st["emit"]["total_us"]
        # dispatch -> landed: longer than the worker's wait for it alone
        assert st["slide_query"]["total_us"] >= st["slide_query"]["cpu_us"]
        assert 0 < st["slide_merge"]["cpu_us"] <= \
            st["slide_merge"]["total_us"]
        assert fused.stats.nested_stages == {
            "slide_query", "slide_merge", "key_encode", "fold_h2d"}
        assert set(fused.stats.health_sample()["stages"]) == {
            "upload", "fold", "emit", "slide_edge", "slide_advance",
            "slide_ring", "release"}
        # the ring's bookkeeping beside the fold (PR 40): twice a
        # micro-batch — the stamps' buckets, guard, recycle and expiry
        # before `upload`; the row ring's append and the trigger mask after
        # `fold`, with its rows
        assert st["slide_ring"]["calls"] == 2 * st["fold"]["calls"]
        assert st["slide_ring"]["rows"] == st["fold"]["rows"]
        assert 0 < st["slide_ring"]["cpu_us"] <= st["slide_ring"]["total_us"]
        # ... which leaves the worker under a quarter of its dispatch
        # time in no stage (10-12 % here, at 8-row micro-batches, where
        # what a dispatch costs whatever its rows weighs most)
        snap = fused.stats.snapshot()
        assert 0 <= snap["unstaged_us_total"] \
            <= 0.25 * snap["process_time_us_total"]
        code, text = api.dispatch("GET", "/metrics", None, {})
        for stage in NEW_STAGES:
            for fam in ("us", "cpu_us", "calls", "rows"):
                assert any(
                    ln.startswith(f"kuiper_op_stage_{fam}_total{{")
                    and f'stage="{stage}"' in ln
                    for ln in text.splitlines()), (stage, fam)
        by_path = {
            ln.split('path="')[1].split('"')[0]: float(ln.rsplit(" ", 1)[1])
            for ln in text.splitlines()
            if ln.startswith("kuiper_sliding_triggers_total{")
            and f'rule="{rule_id}"' in ln}
        assert by_path == {k: float(v)
                           for k, v in fused.sliding_triggers.items()}
        assert sum(by_path.values()) == n and by_path.get("dyn", 0) == 0
        assert by_path.get("flip", 0) >= 1 and by_path.get("fast", 0) >= 1
        # every one of them finished on the device: the three stages are
        # those of the device-tail path
        assert fused.sliding_tails == {"device": n}
        assert any(ln.startswith("kuiper_sliding_tail_total{")
                   and f'rule="{rule_id}"' in ln and 'tail="device"' in ln
                   and ln.endswith(f" {n}") for ln in text.splitlines())

        # ---- the rule's trace: the nested stages under `emit`, the
        # fused worker's two beside `fold` under the node's dispatch
        spans = [s for tid in fresh_tracer.rule_traces(rule_id)
                 for s in fresh_tracer.trace(tid)]
        by_id = {s["spanId"]: s for s in spans}
        for stage in ("slide_query", "slide_merge"):
            mine = [s for s in spans if s.get("stage") == stage]
            assert mine, stage
            for s in mine:
                assert by_id[s["parentSpanId"]].get("stage") == "emit"
                assert s["attributes"]["within"] == "emit"
        folds = {s["parentSpanId"] for s in spans if s.get("stage") == "fold"}
        edge = [s for s in spans if s.get("stage") == "slide_edge"]
        assert edge and all(s["parentSpanId"] in folds for s in edge)
        adv = [s for s in spans if s.get("stage") == "slide_advance"]
        assert adv and all(s["parentSpanId"] in folds for s in adv)
        assert {s["attributes"]["flip"] for s in adv} <= {True, False}

        # ---- the profiler's host plane
        names = _host_event_names(str(tmp_path))
        assert {"kuiper:slide_edge", "kuiper:slide_advance",
                "kuiper:slide_query", "kuiper:slide_merge",
                "kuiper:jit:query", "kuiper:jit:tail", "kuiper:jit:advance",
                "kuiper:emit", "kuiper:fold"} <= names, sorted(names)
    finally:
        api.rules.stop_all()


def test_ring_query_program_name_and_scopes():
    """`trace_call_roofline` finds the program by `query_impl` in its
    name; the scopes name the ops inside it, a component each."""
    import jax

    from ekuiper_tpu.ops.aggspec import extract_kernel_plan
    from ekuiper_tpu.ops.groupby import DeviceGroupBy
    from ekuiper_tpu.ops.slidingring import (QUERY_ADJ, SlidingRing,
                                             ring_layout_for)
    from ekuiper_tpu.sql.parser import parse_select

    stmt = parse_select(SQL.format(stream="s"))
    plan = extract_kernel_plan(stmt)
    layout = ring_layout_for(stmt.window, plan, 64, 256)
    assert (layout.bucket_ms, layout.n_ring_panes, layout.n_panes) \
        == (208, 52, 53)
    gb = DeviceGroupBy(plan, capacity=64, n_panes=layout.n_panes,
                       micro_batch=64)
    ring = SlidingRing(gb, layout)
    text = jax.jit(ring._query_impl).lower(
        ring.init_state(), gb.init_state(), np.bool_(True), np.bool_(False),
        np.int32(0), np.zeros(QUERY_ADJ, np.int32),
        np.zeros(QUERY_ADJ, np.float32), np.zeros(QUERY_ADJ, np.bool_),
    ).as_text(debug_info=True)
    head = next(ln for ln in text.splitlines() if ln.startswith("module @"))
    assert "module @jit__query_impl" in head, head
    for scope in ("kuiper/slide_query/hist", "kuiper/slide_query/n",
                  "kuiper/slide_query/act", "kuiper/slide_query/stack"):
        assert scope in text, scope
    assert ring._query.rec.trace_name == "kuiper:jit:query"
    assert ring._advance.rec.trace_name == "kuiper:jit:advance"
    # the tail is a program of its own: `query_impl` stays what it was
    # (its roofline's bytes are reckoned for it alone), and the tail is
    # found neither by `query_impl` nor by `fold` (the fold's roofline
    # sums the programs so named) though it runs the fold's scatter
    cols, valid, slots, n = ring.edge_buffers([])[0]
    text = jax.jit(ring._tail_impl).lower(
        jax.eval_shape(ring._query_impl, ring.init_state(), gb.init_state(),
                       np.bool_(True), np.bool_(False), np.int32(0),
                       np.zeros(QUERY_ADJ, np.int32),
                       np.zeros(QUERY_ADJ, np.float32),
                       np.zeros(QUERY_ADJ, np.bool_)),
        {**cols, **{"__valid_" + k: v for k, v in valid.items()}}, slots,
        np.int32(n)).as_text(debug_info=True)
    head = next(ln for ln in text.splitlines() if ln.startswith("module @"))
    name = head.split()[1]
    assert name == "@jit__tail_impl", head
    assert "query_impl" not in name and "fold" not in name
    for scope in ("kuiper/slide_tail/edge_scatter",
                  "kuiper/slide_tail/merge_hist", "kuiper/slide_tail/merge_n",
                  "kuiper/slide_tail/merge_act", "kuiper/slide_tail/values",
                  "kuiper/slide_tail/stack"):
        assert scope in text, scope
    assert ring._tail.rec.trace_name == "kuiper:jit:tail"


# ------------------------------------- the trigger's host tail, piece by piece
@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 9])
def test_two_level_search_names_the_bin_the_cumulative_sum_names(seed):
    """`hist_quantile_np` finds the first bin whose cumulative count
    reaches frac x n through block sums (the plain form is kept here as
    the reference): the same bin for every key, empty ones included, at
    any fraction — counts are whole numbers, so either order is exact."""
    from ekuiper_tpu.ops.prefinalize import (_hist_first_reaching,
                                             hist_quantile_np)
    from ekuiper_tpu.ops.sketches import HIST_BINS

    rng = np.random.default_rng(seed)
    hist = np.zeros((300, 2, HIST_BINS), dtype=np.float32)
    for i in range(1, 300):  # key 0 stays empty
        for k in range(2):
            at = rng.integers(0, HIST_BINS, int(rng.integers(1, 60)))
            np.add.at(hist[i, k], at,
                      rng.integers(1, 3000, len(at)).astype(np.float32))
    def plain(view, frac):
        total = np.sum(view, axis=-1)
        target = np.maximum(frac * total[..., None], 1e-9)
        return total, np.argmax(np.cumsum(view, axis=-1) >= target, axis=-1)

    for k in range(2):
        view = hist[:, k]  # a strided view, as the components arrive
        for frac in (0.0, 0.01, 0.5, 0.99, 1.0):
            for mine, want in zip(_hist_first_reaching(view, frac),
                                  plain(view, frac)):
                assert np.array_equal(mine, want), (k, frac)
        q = hist_quantile_np(view, 0.99)
        assert np.isnan(q[0]) and not np.isnan(q[1:]).any()


@pytest.mark.parametrize("seed", [4, 2 ** 31 + 3])
def test_shadow_hist_fold_equals_one_add_a_row(seed):
    """`HostShadow.fold` adds a histogram's rows as one add per distinct
    (key, bin); a plain loop, one add a row, gives the same sketch — and
    the merge keeps to the window's key slots."""
    from ekuiper_tpu.ops.aggspec import extract_kernel_plan
    from ekuiper_tpu.ops.groupby import DeviceGroupBy
    from ekuiper_tpu.ops.prefinalize import (HostShadow, hist_bin_np,
                                             merge_components)
    from ekuiper_tpu.sql.parser import parse_select

    plan = extract_kernel_plan(parse_select(SQL.format(stream="s")))
    gb = DeviceGroupBy(plan, capacity=64, n_panes=1, micro_batch=64)
    rng = np.random.default_rng(seed)
    shadow = HostShadow(plan, gb.comp_specs, 40)
    want = np.zeros((128, 1024), dtype=np.float32)
    rows = 0
    for _ in range(3):
        n = int(rng.integers(1, 3000))
        slots = rng.integers(0, 50, n)  # past the 40 it was made for
        vals = np.round(rng.normal(20, 15, n), 2).astype(np.float32)
        shadow.fold({"temperature": vals}, slots, {})
        for s, b in zip(slots.tolist(), hist_bin_np(vals).tolist()):
            want[s, b] += 1.0
        rows += n
    assert shadow.n_rows == rows and shadow.capacity >= 50
    assert np.array_equal(shadow.data["hist"][:, 0], want[:shadow.capacity])
    assert shadow.data["n"][:, 0].sum() == rows
    dev = {c: np.ones_like(a) for c, a in shadow.data.items()}
    merged = merge_components(dev, shadow, 45)
    assert {c: a.shape[0] for c, a in merged.items()} == {
        "hist": 45, "n": 45, "act": 45}
    assert np.array_equal(merged["hist"][:, 0], want[:45] + 1.0)
    outs, act = gb.prefinalize_merge(None, shadow, 45)
    assert len(outs[0]) == len(outs[1]) == len(act) == 45
    assert np.array_equal(outs[1], want[:45].sum(axis=1))
