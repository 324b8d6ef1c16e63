"""CPU self-checks of what PR 37 added to the benchmark (run by hand, with
the others): the NEXmark bid generator against the source's key model, the
tumbling-count reference and its controls, the counter-ratio reader on
hand-made marks, and the new cell with its timed path broken underneath.

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
from generators import nexmark_bids  # noqa: E402
from readers import counter_ratio, stage_busy  # noqa: E402
from references import tumbling_count as tc  # noqa: E402

CFG = run.load_json("configs", "nexmarkq12.json")
BIG = 2 ** 31 + 12345  # the driver's seeds pass 32 signed bits


def small_rows(pool_rows=40960, drain_rows=512) -> dict:
    rows = copy.deepcopy(CFG["rows"])
    rows.update(pool_rows=pool_rows, drain_rows=drain_rows)
    return rows


def params(rows) -> dict:
    return {**rows, **CFG["reference_params"]}


# -------------------------------------------------------------- generator
def test_generator_matches_the_sources_key_model_at_the_cells_size():
    """hotBiddersRatio 4: three bids in four on the hot bidder, which moves
    every 100 persons = 4,600 bids; one person per 46 bids; the rest over
    the last 1,000 persons and a lead of 10; ids from 1,000."""
    rows = dict(CFG["rows"], keys={"distribution": "uniform"})  # sat's
    pool = nexmark_bids.make(BIG, rows)
    n = rows["pool_rows"]
    bidder = pool.ids[pool.keys.ravel()]
    i = np.arange(n)
    last_person = i // 46
    hot_id = (last_person // 100) * 100 + 1 + 1000
    is_hot = bidder == hot_id
    assert abs(is_hot.mean() - 0.75) < 0.002
    moves = np.flatnonzero(np.diff(hot_id)) + 1
    assert moves[0] == 4600 and (np.diff(moves) == 4600).all()
    cold = bidder[~is_hot] - 1000
    people = last_person[~is_hot] + 1
    low = people - np.minimum(people, 1000)
    assert (cold >= low).all() and (cold < people + 10).all()
    late = people > 1000  # the uniform part, once 1,000 persons exist
    spread = (cold[late] - low[late]) / 1010.0
    assert abs(spread.mean() - 0.5) < 0.005
    persons = n // 46 + 1
    # (the pool's last persons are drawn from for a shorter while)
    assert persons - 150 <= pool.n_keys <= persons + 10  # ~45,500
    assert pool.ids[0] == 1000 and (np.diff(pool.ids) > 0).all()
    per_batch = [len(np.unique(bidder[j:j + 32768]))
                 for j in range(32768, n, 32768 * 8)]
    assert 1450 < np.mean(per_batch) < 1650
    sizes = [len(r) for d in pool.drains[::64] for r in d]
    assert 235 < np.mean(sizes) < 260  # ~250 B of JSON a bid
    row = json.loads(pool.drains[7][9])
    assert list(row) == ["auction", "bidder", "price", "channel", "url",
                         "dateTime", "extra"]  # the record's order
    assert row["bidder"] == bidder[7 * rows["drain_rows"] + 9]
    assert 54 <= len(row["extra"]) <= 81
    assert row["url"].startswith("https://www.nexmark.com/")


def test_generator_follows_the_seed():
    rows = small_rows()
    a, b, c = (nexmark_bids.make(s, rows) for s in (BIG, BIG, BIG + 1))
    assert a.drains == b.drains and np.array_equal(a.keys, b.keys)
    assert a.drains != c.drains
    assert a.keys.shape == (80, 512) and len(a.drains[0]) == 512
    assert json.loads(a.drains[3][7])["bidder"] == a.ids[a.keys[3, 7]]


# -------------------------------------------------------------- reference
def _windows_of(payloads):
    return [SimpleNamespace(index=i, t=0.0, n_groups=len(p), payload=p)
            for i, p in enumerate(payloads)]


def _exact_payloads(pool, sent, cuts, t0=1_700_000_000_000):
    """Exact answers for the sent rows cut at `cuts` into 2 s windows."""
    flat = pool.keys[sent].ravel()
    out = []
    for w, (lo, hi) in enumerate(zip([0] + cuts, cuts + [len(flat)])):
        ks, cs = np.unique(flat[lo:hi], return_counts=True)
        out.append([{"bidder": int(pool.ids[k]), "c": int(c),
                     "ws": t0 + 2000 * w, "we": t0 + 2000 * (w + 1)}
                    for k, c in zip(ks, cs)])
    return out


def _made_up_run():
    rows = small_rows()
    pool = nexmark_bids.make(9, rows)
    sent = list(range(80)) + list(range(25))  # the pool cycles
    windows = _windows_of(_exact_payloads(
        pool, sent, [10000, 23456, 40000]))
    return pool, sent, windows, params(rows)


def test_reference_agrees_with_a_loop():
    pool, sent, _, _ = _made_up_run()
    want = tc.sent_counts(pool, sent)
    cnt = {}
    for d in sent:  # brute force, row by row
        for k in pool.keys[d].tolist():
            cnt[k] = cnt.get(k, 0) + 1
    assert {k: int(c) for k, c in enumerate(want) if c} == cnt
    assert want.sum() == len(sent) * 512


def test_check_passes_exact_answers_and_fails_each_control():
    pool, sent, windows, prm = _made_up_run()
    ok = tc.check(pool, sent, windows, prm)
    assert all(v <= lim for v, lim in ok["numbers"].values()), ok
    assert ok["failed"] == 0 and ok["attempted"] == 105 * 512
    assert set(tc.CONTROLS) == {"drain_lost", "keys_aliased",
                                "window_merged"}
    before = json.dumps([w.payload for w in windows])
    for name, control in tc.CONTROLS.items():
        bad = control(pool, sent, windows, prm)
        assert any(v > lim for v, lim in bad["numbers"].values()), name
    assert json.dumps([w.payload for w in windows]) == before  # untouched
    lost = tc.CONTROLS["drain_lost"](pool, sent, windows, prm)
    assert lost["numbers"]["keys_miscounted"][0] == len(
        np.unique(pool.keys[sent[len(sent) // 2]]))
    aliased = tc.CONTROLS["keys_aliased"](pool, sent, windows, prm)
    assert aliased["numbers"]["keys_miscounted"][0] == 2
    merged = tc.CONTROLS["window_merged"](pool, sent, windows, prm)
    assert merged["numbers"]["window_length_off"][0] == 1
    assert merged["numbers"]["keys_miscounted"][0] == 0


@pytest.mark.parametrize("fault, number", [
    ("a_count_altered", "keys_miscounted"),
    ("a_key_twice", "key_twice_in_window"),
    ("a_key_as_text", "keys_not_sent_integers"),
    ("a_key_as_float", "keys_not_sent_integers"),
    ("a_key_never_sent", "keys_not_sent_integers"),
    ("a_window_too_long", "window_length_off"),
    ("two_edges_in_a_window", "window_length_off"),
    ("a_window_off_the_grid", "windows_overlapping_or_unaligned"),
    ("a_window_emitted_twice", "windows_overlapping_or_unaligned"),
])
def test_a_wrong_answer_is_not_correct(fault, number):
    pool, sent, windows, prm = _made_up_run()
    msgs = windows[1].payload
    if fault == "a_count_altered":
        msgs[0]["c"] += 1
    elif fault == "a_key_twice":
        half = msgs[3]["c"] // 2 or 1
        msgs.append(dict(msgs[3], c=half))
        msgs[3]["c"] -= half
    elif fault == "a_key_as_text":
        msgs[0]["bidder"] = str(msgs[0]["bidder"])
    elif fault == "a_key_as_float":
        msgs[0]["bidder"] = float(msgs[0]["bidder"])
    elif fault == "a_key_never_sent":
        msgs[0]["bidder"] = 999
    elif fault == "a_window_too_long":
        for m in msgs:
            m["we"] += 1
    elif fault == "two_edges_in_a_window":
        msgs[0]["ws"] -= 2000
        msgs[0]["we"] -= 2000
    elif fault == "a_window_off_the_grid":
        for w in windows:
            for m in w.payload:
                m["ws"] += 21
                m["we"] += 21
    elif fault == "a_window_emitted_twice":
        windows.append(SimpleNamespace(**{**vars(windows[1]), "index": 4}))
    got = tc.check(pool, sent, windows, prm)["numbers"]
    assert got[number][0] > got[number][1], got
    if fault in ("a_window_too_long", "a_window_off_the_grid"):
        assert got["keys_miscounted"][0] == 0  # the counts are untouched


# ----------------------------------------------------------------- readers
def _marks(t: float, lines: list) -> dict:
    return {"t": t, "metrics": "\n".join(lines), "status": {}}


def _fields(kept: int, skipped: int) -> list:
    fam = "kuiper_source_decode_fields_total"
    return [f"# TYPE {fam} counter",
            f'{fam}{{rule="__shared__",op="bench_in",fate="kept"}} {kept}',
            f'{fam}{{rule="__shared__",op="bench_in",fate="skipped"}} '
            f'{skipped}']


def test_decode_skipped_share_is_growth_over_growth():
    spec = run.load_json("layers", "decode_skipped_share.json")
    ctx = SimpleNamespace(marks0=_marks(10.0, _fields(1000, 6000)),
                          marks1=_marks(50.0, _fields(5000, 30000)))
    got = counter_ratio.read(ctx, **spec["args"])
    assert got == pytest.approx(100.0 * 24000 / 28000)
    # the parent has no such family, an idle window no growth: nothing
    ctx.marks1 = _marks(50.0, _fields(1000, 6000))
    assert counter_ratio.read(ctx, **spec["args"]) is None
    ctx.marks0 = ctx.marks1 = _marks(10.0, ["kuiper_rule_status 1"])
    assert counter_ratio.read(ctx, **spec["args"]) is None
    ctx.marks0 = None
    assert counter_ratio.read(ctx, **spec["args"]) is None


def test_key_encode_share_reads_its_own_stage():
    spec = run.load_json("layers", "key_encode_share.json")
    fam = "kuiper_op_stage_us_total"

    def lines(pool_us, fused_us, upload_us):
        return [f'{fam}{{rule="__shared__",op="bench_in",type="source",'
                f'stage="key_encode"}} {pool_us}',
                f'{fam}{{rule="bench_rule",op="window_agg",type="op",'
                f'stage="key_encode"}} {fused_us}',
                f'{fam}{{rule="__shared__",op="bench_in",type="source",'
                f'stage="upload"}} {upload_us}']

    ctx = SimpleNamespace(marks0=_marks(0.0, lines(0, 0, 0)),
                          marks1=_marks(10.0, lines(2_400_000, 100_000,
                                                    9_000_000)))
    assert stage_busy.read(ctx, **spec["args"]) == pytest.approx(25.0)
    ctx.marks1 = _marks(10.0, lines(0, 0, 9_000_000))  # the parent: none
    assert stage_busy.read(ctx, **spec["args"]) is None


def test_the_new_cell_owes_the_new_metrics_and_the_old_ones():
    traced = [n for _, n in run.load_cell("nexmarkq12.sat", True).metrics]
    assert {"key_encode_share", "decode_skipped_share", "fold_roofline",
            "decode_busy_cores", "upload_busy_share", "device_idle_share",
            "fold_dispatch_share", "host_offcpu_share"} <= set(traced)
    assert not {"hh_encode_share", "slide_edge_share", "hbm_peak_gb",
                "emit_landed_share"} & set(traced)
    plain = [n for _, n in run.load_cell("nexmarkq12.sat", False).metrics]
    assert plain == ["rows_per_s", "setup_s"]
    for cell in ("tumbling10k.sat", "hll1m.sat", "hophh10k.sat",
                 "slidingpct10k.sat", "tumbling10k.paced"):
        owed = [n for _, n in run.load_cell(cell, True).metrics]
        assert "key_encode_share" not in owed
        assert "decode_skipped_share" not in owed


# ------------------------- the rest of a run, with the timed path broken
def test_a_key_table_that_aliases_two_ids_is_not_correct(tmp_path,
                                                         monkeypatch):
    """The cell at a tiny size on the CPU, with what a wrong integer hash
    would give planted under the timed path. (test_benchmark.py drives every
    cell of BENCHMARK.json, this one too, sound and with its own faults:
    half of each drain left out, every second answer altered, every second
    fold a no-op.)"""
    import test_benchmark as tb

    from ekuiper_tpu.ops.keytable import KeyTable

    encode = KeyTable.encode_column

    def aliasing(self, col):
        if col.dtype == np.int64:
            col = np.where(col == 1003, 1002, col)
        return encode(self, col)

    monkeypatch.setattr(KeyTable, "encode_column", aliasing)
    result = tb._drive(tb._tiny_cell("nexmarkq12.sat"), tmp_path, seconds=4.5)
    assert result["correct"] is False, result["checks"]
    assert result["checks"]["keys_miscounted"][0] == 2
