"""CPU self-checks of what PR 29 added to the benchmark (run by hand, with
the others): the mixture generator, the hopping top-k reference and its
controls, and the per-call roofline reader on two hand-made marks.

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
from generators import keyed_mixture  # noqa: E402
from readers import stage_busy, trace_call_roofline  # noqa: E402
from references import hopping_topk  # noqa: E402

CFG = run.load_json("configs", "hophh10k.json")


def small_rows() -> dict:
    rows = dict(CFG["rows"])
    rows.update(n_keys=50, pool_rows=40000, block_rows=20000, drain_rows=100)
    return rows


def small_params() -> dict:
    return {**small_rows(), **CFG["reference_params"],
            "micro_batch_rows": 800}


# -------------------------------------------------------------- generator
def test_mixture_generator_follows_the_seed_and_the_shares():
    rows = small_rows()
    big = 2 ** 31 + 12345  # the driver's seeds pass 32 signed bits
    a, b, c = (keyed_mixture.make(s, rows) for s in (big, big, big + 1))
    assert a.drains == b.drains and np.array_equal(a.values, b.values)
    assert a.drains != c.drains
    assert a.keys.shape == (400, 100) and len(a.drains[0]) == 100
    for block in a.keys.reshape(2, -1):  # every key in every block
        assert len(np.unique(block)) == 50
    row = json.loads(a.drains[3][7])
    assert row == {"deviceId": "dev_%d" % a.keys[3, 7],
                   "code": int(a.values[3, 7])}
    share = {v: float((a.values == v).mean()) for v in (7, 13, 99)}
    assert share[7] == pytest.approx(0.35, abs=0.01)
    assert share[13] == pytest.approx(0.20, abs=0.01)
    assert share[99] == pytest.approx(0.15, abs=0.01)
    tail = a.values[~np.isin(a.values, (7, 13, 99))]
    assert tail.min() >= 100 and tail.max() < 2100
    assert len(np.unique(tail)) > 1900


# -------------------------------------------------------------- reference
def _exact_payloads(pool, sent, cuts, params, sketch=None):
    """Windows as the rule owes them for hops cut at `cuts`, as payloads."""
    got = hopping_topk.exact_answers(pool, sent, cuts, params, sketch)
    out = []
    for j in range(len(cuts) - 1):
        msgs = []
        for k in range(pool.n_keys):
            if got["c"][j, k] == 0:
                continue
            top = [{"value": int(v), "count": int(e)}
                   for v, e in zip(got["val"][j, k], got["est"][j, k])
                   if v >= 0]
            msgs.append({"deviceId": "dev_%d" % k, "top": top,
                         "c": int(got["c"][j, k])})
        out.append(msgs)
    return out


def _windows_of(payloads):
    return [SimpleNamespace(index=i, t=0.0, n_groups=len(p), payload=p)
            for i, p in enumerate(payloads)]


def _sent_and_cuts(pool):
    sent = list(range(400)) + list(range(83))  # the pool cycles; 483 drains
    # due: 48,000 rows (whole micro-batches of 800); the last window holds
    # the last hop alone, so the last two cuts coincide
    cuts = np.array([0, 8000, 20000, 20800, 36000, 48000, 48000])
    return sent, cuts


def test_reference_agrees_with_a_loop():
    params = small_params()
    pool = keyed_mixture.make(5, small_rows())
    sent, cuts = _sent_and_cuts(pool)
    stream = hopping_topk.Sent(pool, sent, params)
    assert stream.due == 48000
    flat_k = pool.keys[sent].ravel()
    flat_v = pool.values[sent].ravel()
    for j, win, new in hopping_topk.exact_windows(stream, cuts):
        lo, hi = int(cuts[max(j - 1, 0)]), int(cuts[j + 1])
        want = {}
        for k, v in zip(flat_k[lo:hi].tolist(), flat_v[lo:hi].tolist()):
            want[(k, v)] = want.get((k, v), 0) + 1
        assert int(win.n.sum()) == hi - lo
        for (k, v), n in want.items():
            assert win.of(np.array(k), stream.index_of(np.array(v))) == n
        assert int(new.n.sum()) == int(cuts[j + 1] - cuts[j])


def test_window_rows_add_up_to_the_rows_sent():
    params = small_params()
    pool = keyed_mixture.make(6, small_rows())
    sent, cuts = _sent_and_cuts(pool)
    payloads = _exact_payloads(pool, sent, cuts, params)
    assert sum(hopping_topk.window_rows(p, params) for p in payloads) \
        == hopping_topk.rows_due(len(sent) * 100, params) == 48000
    assert hopping_topk.rows_due(48799, params) == 48000


def test_check_passes_exact_answers_and_fails_each_control():
    params = small_params()
    pool = keyed_mixture.make(9, small_rows())
    sent, cuts = _sent_and_cuts(pool)
    windows = _windows_of(_exact_payloads(pool, sent, cuts, params))
    ok = hopping_topk.check(pool, sent, windows, params)
    assert all(v <= lim for v, lim in ok["numbers"].values()), ok
    assert ok["failed"] == 0 and ok["attempted"] == 48000
    assert ok["numbers"]["top_est_mean_excess"][0] == 0.0
    assert set(hopping_topk.CONTROLS) == {
        "drain_lost", "one_pane", "sketch_one_pane", "stale_top"}
    tripped = {}
    for name, control in hopping_topk.CONTROLS.items():
        bad = control(pool, sent, windows, params)
        tripped[name] = {k for k, (v, lim) in bad["numbers"].items()
                         if v > lim}
        assert tripped[name], name
    assert "keys_miscounted" in tripped["drain_lost"]
    assert "keys_miscounted" in tripped["one_pane"]
    # the counts are right in these two: only the estimates can tell
    assert tripped["sketch_one_pane"] == {"top_est_outside_share"} or \
        tripped["sketch_one_pane"] == {"top_est_outside_share",
                                       "top_est_mean_excess"}
    assert "keys_miscounted" not in tripped["stale_top"]
    assert "top_est_outside_share" in tripped["stale_top"]


FAULTS = ["half_of_each_drain_left_out", "every_second_top_altered",
          "a_window_one_hop_short", "a_count_altered",
          "a_value_never_sent", "a_key_twice", "a_top_list_too_long"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_wrong_answer_is_not_correct(fault):
    params = small_params()
    pool = keyed_mixture.make(13, small_rows())
    sent, cuts = _sent_and_cuts(pool)
    payloads = _exact_payloads(pool, sent, cuts, params)
    if fault == "half_of_each_drain_left_out":
        # the program saw the first half of every drain: exact answers for
        # a pool of half drains, held to what was sent
        half = copy.copy(pool)
        half.keys, half.values = pool.keys[:, :50], pool.values[:, :50]
        half.drain_rows = 50
        payloads = _exact_payloads(half, sent, cuts // 2,
                                   {**params, "micro_batch_rows": 400})
    elif fault == "every_second_top_altered":
        for msgs in payloads:
            for m in msgs[::2]:
                m["top"][0]["count"] += 40
    elif fault == "a_window_one_hop_short":
        payloads[3] = _exact_payloads(
            pool, sent, cuts, params,
            sketch=lambda j, win, new: new)[3]
        stream = hopping_topk.Sent(pool, sent, params)
        new = np.bincount(stream.key[int(cuts[3]):int(cuts[4])],
                          minlength=pool.n_keys)
        for m in payloads[3]:
            m["c"] = int(new[int(m["deviceId"][4:])])
        payloads[3] = [m for m in payloads[3] if m["c"]]
    elif fault == "a_count_altered":
        payloads[2][5]["c"] += 1
    elif fault == "a_value_never_sent":
        payloads[2][5]["top"][1]["value"] = 5
    elif fault == "a_key_twice":
        payloads[2].append(dict(payloads[2][5]))
    elif fault == "a_top_list_too_long":
        payloads[2][5]["top"].append({"value": 100, "count": 1})
    got = hopping_topk.check(pool, sent, _windows_of(payloads), params)
    over = {k: v for k, (v, lim) in got["numbers"].items() if v > lim}
    assert over, fault
    if fault == "a_count_altered":
        assert got["numbers"]["keys_miscounted"][0] == 1
        assert got["failed"] == 0 and over.keys() >= {"keys_miscounted",
                                                      "window_counts_off"}
    if fault in ("every_second_top_altered", "a_value_never_sent"):
        assert "keys_miscounted" not in over  # the counts are untouched


# ---------------------------------------------------------------- readers
OP = 'rule="r",op="window_agg",type="op"'


def _marks(t: float, lines: list) -> dict:
    return {"t": t, "metrics": "\n".join(lines) + "\n", "status": {}}


def _calls(n_hh: int, n_emit: int) -> list:
    return [f'kuiper_op_stage_calls_total{{{OP},stage="hh_finalize"}} {n_hh}',
            f'kuiper_op_stage_calls_total{{{OP},stage="emit"}} {n_emit}']


def test_call_roofline_is_bytes_times_calls_over_peak_over_device_time():
    shapes = CFG["finalize_shapes"]
    per_call = trace_call_roofline.needed_bytes_per_call(shapes)
    assert per_call == 2 * 16384 * 10752 + 16384 * (4 * 3 + 2) * 4
    ctx = SimpleNamespace(
        cfg=CFG, device={"kind": "TPU v5 lite"},
        trace={"programs": {"jit__hh_finalize_impl": 0.016,
                            "jit__fold_impl": 1.0,
                            "jit__reset_pane_impl": 0.5}},
        trace_marks0=_marks(100.0, _calls(30, 60)),
        trace_marks1=_marks(108.0, _calls(38, 76)))
    args = run.load_json("layers", "hh_finalize_roofline.json")["args"]
    # 8 calls of 2 ms each; at 819 GB/s one call needs 0.4314 ms
    want = 100.0 * (8 * per_call / 819e9) / 0.016
    assert trace_call_roofline.read(ctx, **args) == pytest.approx(want)
    assert 20.0 < want < 23.0
    # the parent commit has no such stage, a cell without the kernel no
    # such program, another configuration no such shapes: nothing to read
    old = copy.copy(ctx)
    old.trace_marks0 = _marks(100.0, _calls(0, 60)[1:])
    old.trace_marks1 = _marks(108.0, _calls(0, 76)[1:])
    assert trace_call_roofline.read(old, **args) is None
    other = copy.copy(ctx)
    other.trace = {"programs": {"jit__fold_impl": 1.0}}
    assert trace_call_roofline.read(other, **args) is None
    plain = copy.copy(ctx)
    plain.cfg = run.load_json("configs", "tumbling10k.json")
    assert trace_call_roofline.read(plain, **args) is None
    untraced = copy.copy(ctx)
    untraced.trace = None
    assert trace_call_roofline.read(untraced, **args) is None


@pytest.mark.parametrize("metric", ["hh_assemble_share", "hh_encode_share"])
def test_hh_stage_shares_read_their_own_stage(metric):
    spec = run.load_json("layers", metric + ".json")
    stage = spec["args"]["stage"]

    def lines(us):
        return [f'kuiper_op_stage_us_total{{{OP},stage="{stage}"}} {us}',
                f'kuiper_op_stage_us_total{{{OP},stage="emit"}} 999999',
                f'kuiper_op_stage_us_total{{{OP},stage="upload"}} 999999']
    ctx = SimpleNamespace(marks0=_marks(100.0, lines(1_000_000)),
                          marks1=_marks(110.0, lines(3_500_000)))
    assert spec["reader"] == "stage_busy"
    assert stage_busy.read(ctx, **spec["args"]) == pytest.approx(25.0)
    parent = SimpleNamespace(marks0=_marks(100.0, lines(0)[1:]),
                             marks1=_marks(110.0, lines(0)[1:]))
    assert stage_busy.read(parent, **spec["args"]) is None


def test_the_new_cell_owes_the_new_metrics_and_the_old_ones():
    cell = run.load_cell("hophh10k.sat", True)
    owed = {name for _folder, name in cell.metrics}
    assert {"hh_assemble_share", "hh_encode_share",
            "hh_finalize_roofline", "fold_roofline", "device_idle_share",
            "decode_busy_cores", "upload_busy_share", "fold_dispatch_share",
            "ingest_busy_share", "emit_busy_share", "sink_busy_share",
            "fold_starved_share", "host_offcpu_share"} == owed
    assert {name for _f, name in run.load_cell("hophh10k.sat", False).metrics
            } == {"rows_per_s", "setup_s"}
    for old in ("tumbling10k.sat", "hll1m.sat", "tumbling10k.paced"):
        names = {name for _f, name in run.load_cell(old, True).metrics}
        assert not names & {"hh_assemble_share", "hh_encode_share",
                            "hh_finalize_roofline"}
