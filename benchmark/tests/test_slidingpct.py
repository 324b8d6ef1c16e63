"""CPU self-checks of what PR 34 added to the benchmark (run by hand, with
the others): the triggered generator, the sliding-quantile reference and its
controls, and the three new layer files on hand-made marks.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""
from __future__ import annotations

import copy
import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.dirname(BENCH), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run  # noqa: E402
from generators import keyed_triggered  # noqa: E402
from readers import stage_busy, trace_call_roofline  # noqa: E402
from references import sliding_quantile as sq  # noqa: E402

CFG = run.load_json("configs", "slidingpct10k.json")
N_KEYS, DRAIN = 50, 128
DRAIN_MS = 125  # the stamp one drain further on, in the made-up runs here


def small_rows() -> dict:
    rows = copy.deepcopy(CFG["rows"])
    rows.update(n_keys=N_KEYS, pool_rows=40960, block_rows=20480,
                drain_rows=DRAIN)
    rows["value"].update(trigger_every_rows=4096, trigger_offset=300)
    return rows


def small_params() -> dict:
    return {**small_rows(), **CFG["reference_params"],
            "micro_batch_rows": 8 * DRAIN}


# -------------------------------------------------------------- generator
def test_generator_places_one_row_over_the_threshold_a_period():
    """At the cell's own size: exactly one row over 44.5 in every 262,144,
    at row 13,522 of the period, none elsewhere; the rest follows the seed
    and stays under the threshold."""
    rows = CFG["rows"]
    big = 2 ** 31 + 12345  # the driver's seeds pass 32 signed bits
    pool = keyed_triggered.make(big, rows)
    flat = pool.values.ravel()
    over = np.flatnonzero(flat > 44.5)
    assert over.tolist() == [13522 + 262144 * k for k in range(8)]
    assert (flat[over] == np.float32(99.0)).all()
    rest = np.delete(flat, over)
    assert rest.max() <= np.float32(44.49) < 44.5
    assert abs(float(rest.mean()) - 20.0) < 0.02
    assert abs(float(rest.std()) - 5.0) < 0.02
    assert pool.keys.shape == (512, 4096) and len(pool.drains[0]) == 4096
    for block in pool.keys.reshape(16, -1):  # every key in every block
        assert len(np.unique(block)) == 10000
    d, r = divmod(13522, 4096)
    assert json.loads(pool.drains[d][r]) == {
        "deviceId": "dev_%d" % pool.keys[d, r], "temperature": 99.0}
    row = json.loads(pool.drains[3][7])
    assert row["deviceId"] == "dev_%d" % pool.keys[3, 7]
    assert np.float32(row["temperature"]) == pool.values[3, 7]


def test_generator_follows_the_seed_and_keeps_the_cadence_in_a_small_pool():
    rows = small_rows()
    big = 2 ** 31 + 12345
    a, b, c = (keyed_triggered.make(s, rows) for s in (big, big, big + 1))
    assert a.drains == b.drains and np.array_equal(a.values, b.values)
    assert a.drains != c.drains
    over = np.flatnonzero(a.values.ravel() > 44.5)
    assert over.tolist() == [300 + 4096 * k for k in range(10)]
    # a pool that holds no whole period: the greatest common divisor of
    # the two is the period, for the generator and the reference alike
    odd = dict(rows, pool_rows=20480, value=dict(
        rows["value"], trigger_every_rows=12288, trigger_offset=13522))
    pool = keyed_triggered.make(big, odd)
    assert np.flatnonzero(pool.values.ravel() > 44.5).tolist() \
        == [13522 % 4096 + 4096 * k for k in range(5)]
    assert sq.period_of({**odd}) == 4096


# -------------------------------------------------------------- reference
def _made_up_run(pool, params, n_sent=700, window_drains=80):
    """A run as the rule owes it: drain i stamped i x 125 ms, every due
    trigger answered for the drains stamped in its last 10 s up to its
    own (80 drains), and nothing behind it in its micro-batch."""
    sent = [i % len(pool.drains) for i in range(n_sent)]
    stream = sq.Stream(pool, sent, params)
    fits, we = [], []
    for d in stream.trigger_drain.tolist():
        fits.append((max(0, d + 1 - window_drains), d + 1))
        we.append(d * DRAIN_MS)
    return sent, stream, fits, np.asarray(we)


def _payloads(got):
    out = []
    for j in range(len(got["c"])):
        out.append([
            {"deviceId": "dev_%d" % k, "c": int(got["c"][j, k]),
             "p99": (None if np.isnan(got["p"][j, k])
                     else float(got["p"][j, k])),
             "we": int(got["we"][j])}
            for k in range(got["c"].shape[1]) if got["c"][j, k] > 0])
    return out


def _windows_of(payloads):
    return [SimpleNamespace(index=i, t=0.0, n_groups=len(p), payload=p)
            for i, p in enumerate(payloads)]


def _exact_windows(pool, params, every_p=True, **kw):
    sent, stream, fits, we = _made_up_run(pool, params, **kw)
    got = sq.exact_answers(stream, fits, we, params)
    if every_p:  # a percentile in every window, not in the sample alone
        for j, (a, b) in enumerate(fits):
            keys, values = stream.rows(a, b)
            got["p"][j] = sq.sketch_quantile(
                keys, values, pool.n_keys, 0.99, 1024, params)
    return sent, stream, fits, _windows_of(_payloads(got))


def test_reference_agrees_with_a_loop():
    params = small_params()
    pool = keyed_triggered.make(5, small_rows())
    sent, stream, fits, _we = _made_up_run(pool, params)
    assert stream.cyclic and stream.due == 22
    assert sq.rows_due(700 * DRAIN, params) == 22 * 4096
    assert sq.n_triggers_due(8 * DRAIN, params) == 1
    assert sq.n_triggers_due(2 * DRAIN, params) == 0  # no whole micro-batch
    flat_k = pool.keys[sent].ravel()
    flat_v = pool.values[sent].ravel()
    assert stream.trigger_drain.tolist() == \
        (np.flatnonzero(flat_v > 44.5)[:22] // DRAIN).tolist()
    for a, b in fits[::5]:
        want = np.bincount(flat_k[a * DRAIN:b * DRAIN], minlength=N_KEYS)
        assert np.array_equal(stream.counts(a, b), want)
        keys, values = stream.rows(a, b)
        n, x_lo, x, x_hi = sq.order_statistics(keys, values, N_KEYS, 0.99)
        assert np.array_equal(n, want)
        for k in range(N_KEYS):
            rows = np.sort(values[keys == k].astype(np.float64))
            r = int(np.ceil(0.99 * len(rows)))
            assert x[k] == rows[r - 1]
            assert x_lo[k] == rows[max(r - 2, 0)]
            assert x_hi[k] == rows[min(r, len(rows) - 1)]
    # a stream not sent in cycles is counted the slow way, to the same
    other = sq.Stream(pool, sent[::-1], params)
    assert not other.cyclic
    assert np.array_equal(
        other.counts(3, 90),
        np.bincount(pool.keys[sent[::-1][3:90]].ravel(), minlength=N_KEYS))


def test_sketch_answer_lies_within_its_stated_error():
    params = small_params()
    rng = np.random.default_rng(3)
    keys = rng.integers(0, N_KEYS, 200000)
    values = np.round(rng.normal(20, 5, 200000), 2).astype(np.float32)
    n, _lo, x, _hi = sq.order_statistics(keys, values, N_KEYS, 0.99)
    for bins, stated in ((1024, 0.0486), (512, 0.0998)):
        root = np.sqrt(sq.gamma_of(bins, params))
        assert root - 1 == pytest.approx(stated, abs=2e-4)
        p = sq.sketch_quantile(keys, values, N_KEYS, 0.99, bins, params)
        assert (np.abs(p / x - 1) <= root - 1 + 1e-9).all()
    assert sq.window_rows([], params) == 4096.0


def test_check_passes_exact_answers_and_fails_each_control():
    params = small_params()
    pool = keyed_triggered.make(9, small_rows())
    sent, _stream, _fits, windows = _exact_windows(pool, params)
    ok = sq.check(pool, sent, windows, params)
    assert all(v <= lim for v, lim in ok["numbers"].values()), ok
    assert ok["failed"] == 0 and ok["attempted"] == 22 * N_KEYS
    assert ok["numbers"]["p99_outside_share"][0] == 0.0
    assert 0.0 < ok["numbers"]["p99_mean_rel_err"][0] < 0.035
    assert set(sq.CONTROLS) == {"drain_lost", "edge_lost", "stale_window",
                                "bins_halved"}
    tripped = {}
    for name, control in sq.CONTROLS.items():
        bad = control(pool, sent, windows, params)
        tripped[name] = {k for k, (v, lim) in bad["numbers"].items()
                         if v > lim}
    assert "window_counts_off" in tripped["drain_lost"]
    assert "window_counts_off" in tripped["stale_window"]
    # the counts are right with a coarser sketch: only the percentile tells
    assert tripped["bins_halved"] == {"p99_outside_share",
                                      "p99_mean_rel_err"}


def test_edge_lost_is_caught_by_the_brackets_at_the_cells_geometry():
    """A window short of its low edge bucket fits a later stretch exactly,
    so the counts say nothing; the trigger stamps do, where a bucket holds
    as many drains as lie between two triggers (the cell: 208 ms of rows
    against one trigger every 262,144 rows). Here: a trigger every 4
    drains, a drain every 50 ms."""
    rows = small_rows()
    rows["value"].update(trigger_every_rows=4 * DRAIN, trigger_offset=70)
    params = {**rows, **CFG["reference_params"],
              "micro_batch_rows": 4 * DRAIN}
    pool = keyed_triggered.make(21, rows)
    sent = [i % len(pool.drains) for i in range(1200)]
    stream = sq.Stream(pool, sent, params)
    fits, we = [], []
    for d in stream.trigger_drain.tolist():
        t = d * 50
        first = next(i for i in range(d + 1) if i * 50 > t - 10000)
        fits.append((first, d + 1))
        we.append(t)
    got = sq.exact_answers(stream, fits, np.asarray(we), params)
    windows = _windows_of(_payloads(got))
    ok = sq.check(pool, sent, windows, params)
    assert all(v <= lim for v, lim in ok["numbers"].values()), ok
    bad = sq.control_edge_lost(pool, sent, windows, params)
    assert bad["numbers"]["window_counts_off"][0] == 0
    assert bad["numbers"]["window_cut_outside_bracket"][0] > 50


FAULTS = ["half_of_each_drain_left_out", "every_second_answer_altered",
          "a_count_altered", "a_key_twice", "a_key_missing",
          "a_window_missing", "a_stamp_from_another_trigger",
          "a_percentile_of_the_whole_stream"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_wrong_answer_is_not_correct(fault):
    params = small_params()
    pool = keyed_triggered.make(13, small_rows())
    sent, stream, fits, windows = _exact_windows(pool, params)
    payloads = [w.payload for w in windows]
    sampled = sq.sample_of(len(payloads), pool)
    if fault == "half_of_each_drain_left_out":
        # the program saw the first half of every drain: exact answers for
        # a pool of half drains (the trigger rows lie in the kept half:
        # row 300 = row 44 of its drain), held to what was sent
        half = copy.copy(pool)
        half.keys, half.values = pool.keys[:, :64], pool.values[:, :64]
        half.drain_rows = 64
        hstream = sq.Stream(half, sent, {**params, "micro_batch_rows": 512,
                                         "pool_rows": 20480,
                                         "value": dict(
                                             params["value"],
                                             trigger_every_rows=2048,
                                             trigger_offset=44)})
        assert hstream.trigger_drain.tolist() == \
            stream.trigger_drain.tolist()
        payloads = _payloads(sq.exact_answers(
            hstream, fits, [d * DRAIN_MS for d in
                            stream.trigger_drain.tolist()], params))
    elif fault == "every_second_answer_altered":
        for msgs in payloads:
            for m in msgs[::2]:
                m["c"] += 40
    elif fault == "a_count_altered":
        payloads[4][5]["c"] += 1
    elif fault == "a_key_twice":
        payloads[4].append(dict(payloads[4][5]))
    elif fault == "a_key_missing":
        del payloads[4][5]
    elif fault == "a_window_missing":
        del payloads[4]
    elif fault == "a_stamp_from_another_trigger":
        for m in payloads[20]:
            m["we"] -= 3000  # its counts fit its own stretch; its cut not
    elif fault == "a_percentile_of_the_whole_stream":
        keys, values = stream.rows(0, len(sent))
        whole = sq.sketch_quantile(keys, values, N_KEYS, 0.5, 1024, params)
        for j in sampled:
            for m in payloads[j]:
                m["p99"] = float(whole[int(m["deviceId"][4:])])
    got = sq.check(pool, sent, _windows_of(payloads), params)
    over = {k: v for k, (v, lim) in got["numbers"].items() if v > lim}
    assert over, fault
    if fault == "a_count_altered":
        # one key of one window is off by one row: no stretch of whole
        # drains holds the window's total, so all its keys are counted
        assert over.keys() >= {"window_counts_off"}
    if fault == "a_key_twice":
        assert over.keys() >= {"window_groups_off"}
    if fault == "a_window_missing":
        assert got["numbers"]["windows_missing"][0] == 1
    if fault == "a_stamp_from_another_trigger":
        assert over.keys() == {"window_cut_outside_bracket"}
    if fault == "a_percentile_of_the_whole_stream":
        assert over.keys() == {"p99_outside_share", "p99_mean_rel_err"}
        assert got["failed"] == 0


# ---------------------------------------------------------------- readers
OP = 'rule="r",op="window_agg",type="op"'


def _marks(t: float, lines: list) -> dict:
    return {"t": t, "metrics": "\n".join(lines) + "\n", "status": {}}


def _calls(n_query: int, n_emit: int) -> list:
    return [f'kuiper_op_stage_calls_total{{{OP},stage="slide_query"}} '
            f'{n_query}',
            f'kuiper_op_stage_calls_total{{{OP},stage="emit"}} {n_emit}']


def test_query_roofline_is_bytes_times_calls_over_peak_over_device_time():
    shapes = CFG["query_shapes"]
    per_call = trace_call_roofline.needed_bytes_per_call(shapes)
    assert per_call == 3 * 16384 * (4096 + 4 + 4) == 201719808
    ctx = SimpleNamespace(
        cfg=CFG, device={"kind": "TPU v5 lite"},
        trace={"programs": {"jit__query_impl": 0.036,
                            "jit__components_dyn_impl": 0.2,
                            "jit__fold_impl": 1.0,
                            "jit__advance_impl": 0.5}},
        trace_marks0=_marks(100.0, _calls(30, 60)),
        trace_marks1=_marks(108.0, _calls(66, 132)))
    args = run.load_json("layers", "slide_query_roofline.json")["args"]
    # 36 calls of 1 ms each; at 819 GB/s one call needs 0.2463 ms
    want = 100.0 * (36 * per_call / 819e9) / 0.036
    assert trace_call_roofline.read(ctx, **args) == pytest.approx(want)
    assert 24.0 < want < 25.0
    # the parent commit has no such stage, a cell without the ring no such
    # program, another configuration no such shapes: nothing to read
    old = copy.copy(ctx)
    old.trace_marks0 = _marks(100.0, _calls(0, 60)[1:])
    old.trace_marks1 = _marks(108.0, _calls(0, 132)[1:])
    assert trace_call_roofline.read(old, **args) is None
    other = copy.copy(ctx)
    other.trace = {"programs": {"jit__fold_impl": 1.0}}
    assert trace_call_roofline.read(other, **args) is None
    plain = copy.copy(ctx)
    plain.cfg = run.load_json("configs", "hophh10k.json")
    assert trace_call_roofline.read(plain, **args) is None
    untraced = copy.copy(ctx)
    untraced.trace = None
    assert trace_call_roofline.read(untraced, **args) is None


@pytest.mark.parametrize("metric", ["slide_edge_share", "slide_merge_share"])
def test_slide_stage_shares_read_their_own_stage(metric):
    spec = run.load_json("layers", metric + ".json")
    stage = spec["args"]["stage"]

    def lines(us):
        return [f'kuiper_op_stage_us_total{{{OP},stage="{stage}"}} {us}',
                f'kuiper_op_stage_us_total{{{OP},stage="emit"}} 999999',
                f'kuiper_op_stage_us_total{{{OP},stage="fold"}} 999999']
    ctx = SimpleNamespace(marks0=_marks(100.0, lines(1_000_000)),
                          marks1=_marks(110.0, lines(3_500_000)))
    assert spec["reader"] == "stage_busy"
    assert stage_busy.read(ctx, **spec["args"]) == pytest.approx(25.0)
    parent = SimpleNamespace(marks0=_marks(100.0, lines(0)[1:]),
                             marks1=_marks(110.0, lines(0)[1:]))
    assert stage_busy.read(parent, **spec["args"]) is None


def test_the_new_cell_owes_the_new_metrics_and_the_old_ones():
    cell = run.load_cell("slidingpct10k.sat", True)
    owed = {name for _folder, name in cell.metrics}
    assert {"slide_edge_share", "slide_merge_share", "slide_query_roofline",
            "fold_roofline", "device_idle_share", "decode_busy_cores",
            "upload_busy_share", "fold_dispatch_share", "ingest_busy_share",
            "emit_busy_share", "sink_busy_share", "fold_starved_share",
            "host_offcpu_share"} == owed
    assert {name for _f, name in
            run.load_cell("slidingpct10k.sat", False).metrics} \
        == {"rows_per_s", "setup_s"}
    for old in ("tumbling10k.sat", "hll1m.sat", "tumbling10k.paced",
                "hophh10k.sat"):
        names = {name for _f, name in run.load_cell(old, True).metrics}
        assert not names & {"slide_edge_share", "slide_merge_share",
                            "slide_query_roofline"}
    # the deployment's size, reckoned from the shapes the file states
    assert CFG["device_state_bytes"] == \
        53 * 16384 * (1024 + 2) * 4 + 16384 * (1024 + 2) * 4
    assert CFG["reduced"] == [] and CFG["window_s"] == 10
    assert "slidingImpl" not in CFG["options"] \
        and "slidingDevRingMb" not in CFG["options"]
