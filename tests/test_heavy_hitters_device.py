"""Device-path heavy_hitters (BASELINE config #2): count-min totals +
group-testing bit recovery as a fused wide kernel component, with reversible
dictionary encoding so values of any type decode exactly at emit.

Reference scenario: HOPPINGWINDOW GROUP BY device_id with a count-min
heavy-hitters UDF (BASELINE.json configs[1]); host-path exact semantics in
functions/funcs_sketch.py f_heavy_hitters.
"""
from collections import Counter

import numpy as np
import pytest

from ekuiper_tpu.data.batch import ColumnBatch
from ekuiper_tpu.ops import aggspec
from ekuiper_tpu.ops.aggspec import ValueDict, extract_kernel_plan
from ekuiper_tpu.ops.emit import build_direct_emit
from ekuiper_tpu.planner.planner import device_path_eligible
from ekuiper_tpu.runtime.events import Trigger
from ekuiper_tpu.runtime.nodes_fused import FusedWindowAggNode
from ekuiper_tpu.ops.sketches import HH_MAX_CODES
from ekuiper_tpu.sql.parser import parse_select
from ekuiper_tpu.utils.config import RuleOptionConfig

SQL = ("SELECT deviceId, heavy_hitters(code, 3) AS top FROM s "
       "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)")

SQL_HOP = ("SELECT deviceId, heavy_hitters(code, 2) AS top, count(*) AS c "
           "FROM s GROUP BY deviceId, HOPPINGWINDOW(ss, 10, 5)")


def make_node(sql, **kw):
    stmt = parse_select(sql)
    plan = extract_kernel_plan(stmt)
    assert plan is not None
    node = FusedWindowAggNode(
        "hh", stmt.window, plan, dims=[d.expr for d in stmt.dimensions],
        capacity=64, micro_batch=256,
        direct_emit=build_direct_emit(stmt, plan, ["deviceId"]), **kw)
    node.state = node.gb.init_state()
    got = []
    node.broadcast = lambda item: got.append(item)
    return node, got


def skewed_batch(rng, n=20000, keys=5, values="int", ts=1000):
    """~40/25/15% mass on three heavy values, tail uniform over 1000."""
    key_col = np.array([f"d{i}" for i in rng.integers(0, keys, n)],
                       dtype=np.object_)
    p = rng.random(n)
    code = np.where(
        p < 0.4, 7, np.where(p < 0.65, 13, np.where(
            p < 0.8, 99, rng.integers(100, 1100, n)))).astype(np.int64)
    if values == "str":
        code_col = np.array([f"ev{c}" for c in code], dtype=np.object_)
    else:
        code_col = code
    return ColumnBatch(
        n=n, columns={"deviceId": key_col, "code": code_col},
        timestamps=np.full(n, ts, dtype=np.int64), emitter="s")


def exact_topk(batch, k):
    keys = batch.columns["deviceId"]
    code = batch.columns["code"]
    out = {}
    for key in set(keys.tolist()):
        out[key] = Counter(code[keys == key].tolist()).most_common(k)
    return out


def check_parity(node, got_groups, batch, k, count_tol=0.05):
    """Sketch top-k values == exact top-k values; counts within tol."""
    exact = exact_topk(batch, k)
    assert got_groups, "no emission"
    seen_keys = set()
    for msg in got_groups:
        key = msg["deviceId"]
        seen_keys.add(key)
        want = exact[key]
        got = msg["top"]
        assert [d["value"] for d in got] == [v for v, _ in want]
        for d, (_, cnt) in zip(got, want):
            assert d["count"] >= cnt  # count-min never underestimates
            assert d["count"] <= cnt * (1 + count_tol) + 5
    assert seen_keys == set(exact)


def collect_msgs(got):
    msgs = []
    for item in got:
        if isinstance(item, list):
            msgs.extend(item)
        elif isinstance(item, dict):
            msgs.append(item)
    return msgs


class TestHeavyHittersDevice:
    def test_tumbling_int_parity(self):
        rng = np.random.default_rng(1)
        node, got = make_node(SQL)
        batch = skewed_batch(rng)
        node.process(batch)
        node.on_trigger(Trigger(ts=10_000))
        node._drain_async_emits()
        check_parity(node, collect_msgs(got), batch, 3)

    def test_tumbling_string_values_decode(self):
        rng = np.random.default_rng(2)
        node, got = make_node(SQL)
        batch = skewed_batch(rng, values="str")
        node.process(batch)
        node.on_trigger(Trigger(ts=10_000))
        node._drain_async_emits()
        msgs = collect_msgs(got)
        assert msgs
        for m in msgs:
            vals = [d["value"] for d in m["top"]]
            assert vals[0] == "ev7"  # heaviest decodes to the original str
            assert all(isinstance(v, str) for v in vals)

    def test_hopping_pane_merge(self):
        """Two 5s panes fold separately; the 10s window merges them by +
        and recovers the combined heavy hitters."""
        rng = np.random.default_rng(3)
        node, got = make_node(SQL_HOP)
        b1 = skewed_batch(rng, n=8000, ts=1000)
        node.process(b1)
        node.on_trigger(Trigger(ts=5_000))
        node._drain_async_emits()
        node.cur_pane = 1
        b2 = skewed_batch(rng, n=8000, ts=6000)
        node.process(b2)
        got.clear()
        node.on_trigger(Trigger(ts=10_000))
        node._drain_async_emits()
        msgs = collect_msgs(got)
        assert msgs
        both = ColumnBatch(
            n=b1.n + b2.n,
            columns={k: np.concatenate([b1.columns[k], b2.columns[k]])
                     for k in b1.columns},
            timestamps=np.concatenate([b1.timestamps, b2.timestamps]),
            emitter="s")
        exact = exact_topk(both, 2)
        for m in msgs:
            assert [d["value"] for d in m["top"]] == [
                v for v, _ in exact[m["deviceId"]]]
            assert m["c"] == sum(
                1 for x in both.columns["deviceId"] if x == m["deviceId"])

    def test_checkpoint_restore_preserves_dict_and_sketch(self):
        rng = np.random.default_rng(4)
        node, got = make_node(SQL)
        batch = skewed_batch(rng, n=10000)
        node.process(batch)
        snap = node.snapshot_state()
        assert "hh_dicts" in snap

        node2, got2 = make_node(SQL)
        node2.restore_state(snap)
        batch2 = skewed_batch(rng, n=10000, ts=2000)
        node2.process(batch2)
        node2.on_trigger(Trigger(ts=10_000))
        node2._drain_async_emits()
        both = ColumnBatch(
            n=batch.n + batch2.n,
            columns={k: np.concatenate([batch.columns[k], batch2.columns[k]])
                     for k in batch.columns},
            timestamps=np.concatenate([batch.timestamps, batch2.timestamps]),
            emitter="s")
        check_parity(node2, collect_msgs(got2), both, 3)

    def test_null_values_masked(self):
        node, got = make_node(SQL)
        code = np.array([7, None, 7, None, 13], dtype=np.object_)
        keys = np.array(["d0"] * 5, dtype=np.object_)
        node.process(ColumnBatch(
            n=5, columns={"deviceId": keys, "code": code},
            timestamps=np.full(5, 1000, dtype=np.int64), emitter="s"))
        node.on_trigger(Trigger(ts=10_000))
        node._drain_async_emits()
        msgs = collect_msgs(got)
        assert len(msgs) == 1
        assert msgs[0]["top"] == [
            {"value": 7, "count": 2}, {"value": 13, "count": 1}]

    def test_empty_group_emits_empty_list(self):
        node, got = make_node(SQL)
        code = np.array([None, None], dtype=np.object_)
        keys = np.array(["d0", "d0"], dtype=np.object_)
        node.process(ColumnBatch(
            n=2, columns={"deviceId": keys, "code": code},
            timestamps=np.full(2, 1000, dtype=np.int64), emitter="s"))
        node.on_trigger(Trigger(ts=10_000))
        node._drain_async_emits()
        msgs = collect_msgs(got)
        assert len(msgs) == 1
        assert msgs[0]["top"] == []


class TestPlannerGates:
    def _opts(self, **kw):
        return RuleOptionConfig(**kw)

    def test_eligible_single_chip(self):
        stmt = parse_select(SQL)
        assert device_path_eligible(stmt, self._opts()) is not None

    def test_mesh_routes_to_host(self):
        stmt = parse_select(SQL)
        opts = self._opts(
            plan_optimize_strategy={"mesh": {"devices": 8}})
        assert device_path_eligible(stmt, opts) is None

    def test_hh_in_having_routes_to_host(self):
        stmt = parse_select(
            "SELECT deviceId, heavy_hitters(code, 3) AS top FROM s "
            "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10) "
            "HAVING count(*) > 1")
        # count(*) HAVING is fine — hh itself is a bare field
        assert device_path_eligible(stmt, self._opts()) is not None

    def test_hh_nested_expr_not_planned(self):
        stmt = parse_select(
            "SELECT deviceId, len(heavy_hitters(code, 3)) AS n FROM s "
            "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)")
        assert device_path_eligible(stmt, self._opts()) is None

    def test_bad_args_not_planned(self):
        stmt = parse_select(
            "SELECT deviceId, heavy_hitters(code * 2, 3) AS top FROM s "
            "GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)")
        assert extract_kernel_plan(stmt) is None


# ---- seeded streams for TestValueDict.test_encode_as_the_routine_stood:
# name -> () -> (steps, code budget or None); a step is a column or "restore"
# ---- the array assemble (PR 33) against the routine as it stood: the scalar
# `hh_dedupe_topk` per key, then one `ValueDict.decode` per kept candidate
N_VALUES = 40  # codes 0..39 decode; a block also holds codes that do not


def _block_dup_pairs(k2, rng):
    """One key per pair of positions (i < j) that hold the same code."""
    cols = []
    for j in range(1, k2):
        for i in range(j):
            codes = rng.permutation(N_VALUES)[:k2].astype(np.float32)
            codes[j] = codes[i]
            cols.append((codes, np.sort(rng.integers(1, 500, k2))[::-1]))
    return cols


def _block_stop_in_the_middle(k2, rng, at):
    """An estimate `at` (0 or below) at every position, live ones after
    it: nothing from there on may appear."""
    cols = []
    for pos in range(k2):
        est = np.sort(rng.integers(1, 500, k2))[::-1].astype(np.float32)
        est[pos] = at
        cols.append((rng.permutation(N_VALUES)[:k2], est))
    return cols


def _block_few_uniques(k2, rng):
    """Fewer than topk distinct codes, by duplicates and by a short list."""
    cols = [(np.full(k2, 5.0), np.arange(k2, 0, -1))]  # one code k2 times
    for n_live in range(1, max(k2 // 2, 2)):
        est = np.zeros(k2)
        est[:n_live] = np.arange(n_live, 0, -1) * 3
        cols.append((rng.permutation(N_VALUES)[:k2], est))
        cols.append((np.resize([3.0, 9.0], k2), est + 1))  # two codes
    return cols


def _block_all_zero(k2, rng):
    return [(np.zeros(k2), np.zeros(k2)),
            (rng.permutation(N_VALUES)[:k2], np.zeros(k2))]


def _block_half_estimates(k2, rng):
    """Estimates ending in .5: `round` and `rint` go to the even one."""
    est = np.sort(rng.integers(0, 40, (4, k2)), axis=1)[:, ::-1] + 0.5
    return [(rng.permutation(N_VALUES)[:k2], e) for e in est]


def _block_below_one(k2, rng):
    """Estimates between 0 and 1 are alive (only `<= 0` stops the list),
    whatever count they round to."""
    est = np.sort(rng.choice([0.125, 0.25, 0.5, 0.75, 1.0], (6, k2)),
                  axis=1)[:, ::-1]
    return [(rng.permutation(N_VALUES)[:k2], e) for e in est]


def _block_outside_dictionary(k2, rng):
    """Codes the dictionary lacks (-> None), a negative one among them."""
    cols = []
    for _ in range(6):
        codes = rng.permutation(N_VALUES + 30)[:k2].astype(np.float32)
        codes[rng.integers(0, k2)] = -1.0
        codes[rng.integers(0, k2)] = float(HH_MAX_CODES - 1)
        cols.append((codes, np.sort(rng.integers(1, 500, k2))[::-1]))
    return cols


def _block_random(k2, rng):
    """Seeded candidates from a few codes, so duplicates, ties, zeros and
    a stray negative all occur, in no particular place."""
    n = 300
    codes = rng.integers(0, 2 * k2, (n, k2))
    est = np.sort(rng.integers(-1, 30, (n, k2)), axis=1)[:, ::-1] \
        * rng.choice([1.0, 0.5, 0.25], (n, 1))
    return list(zip(codes, est))


_BLOCKS = {
    "dup_pairs": _block_dup_pairs,
    "zero_in_the_middle": lambda k2, rng: _block_stop_in_the_middle(
        k2, rng, 0.0),
    "negative_in_the_middle": lambda k2, rng: _block_stop_in_the_middle(
        k2, rng, -2.0),
    "few_uniques": _block_few_uniques,
    "all_zero": _block_all_zero,
    "half_estimates": _block_half_estimates,
    "below_one": _block_below_one,
    "outside_dictionary": _block_outside_dictionary,
    "random": _block_random,
    "no_keys": lambda k2, rng: [],
}


def _assemble_as_it_stood(stacked, n_keys, topk, vd):
    """The sync form and the served form of the column, key by key."""
    from ekuiper_tpu.ops.prefinalize import hh_dedupe_topk

    k2 = 2 * topk
    codes, est = stacked[:k2, :n_keys], stacked[k2:2 * k2, :n_keys]
    sync = [hh_dedupe_topk(codes[:, j], est[:, j], topk)
            for j in range(n_keys)]
    served = [[{"value": vd.decode(c) if vd else None, "count": n}
               for c, n in row] for row in sync]
    return sync, served


def _same(got, want):
    """Equal, value for value and type for type; a decoded value is the
    dictionary's own object."""
    assert type(got) is type(want), (got, want)
    if isinstance(want, (list, tuple)):
        assert len(got) == len(want), (got, want)
        for g, w in zip(got, want):
            _same(g, w)
    elif isinstance(want, dict):
        assert list(got) == list(want) == ["value", "count"]
        assert got["value"] is want["value"], (got, want)
        _same(got["count"], want["count"])
    else:
        assert got == want, (got, want)


@pytest.fixture(scope="module", params=[1, 3, 5])
def topk_node(request):
    topk = request.param
    node, _ = make_node(
        f"SELECT deviceId, heavy_hitters(code, {topk}) AS top, "
        "count(*) AS c FROM s GROUP BY deviceId, TUMBLINGWINDOW(ss, 10)")
    return topk, node


@pytest.mark.parametrize("with_dict", [True, False],
                         ids=["dict", "no_dict"])
@pytest.mark.parametrize("block", sorted(_BLOCKS))
def test_hh_assemble_as_the_routine_stood(topk_node, block, with_dict):
    topk, node = topk_node
    k2 = 2 * topk
    rng = np.random.default_rng([33, topk, sorted(_BLOCKS).index(block)])
    cols = _BLOCKS[block](k2, rng)
    n_keys, cap = len(cols), len(cols) + 7  # fewer keys than slots
    # the device's layout: k2 rows of codes, k2 of estimates, c, act; the
    # slots past n_keys hold candidates too, which must not be read
    stacked = rng.integers(1, 9, (2 * k2 + 2, cap)).astype(np.float32)
    for j, (codes, est) in enumerate(cols):
        stacked[:k2, j] = codes
        stacked[k2:2 * k2, j] = est
    vd = None
    node._hh_dicts.clear()
    if with_dict:
        vd = node._hh_dicts["code"] = ValueDict()
        vd.encode(np.array([f"ev{i}" for i in range(N_VALUES)],
                           dtype=np.object_))
    sync, served = _assemble_as_it_stood(stacked, n_keys, topk, vd)
    if cols and block != "all_zero":
        assert any(sync), "the block exercises nothing"

    outs, act = node.gb.hh_assemble(stacked, n_keys)
    assert outs[0].dtype == np.object_ and outs[0].shape == (n_keys,)
    _same(outs[0].tolist(), sync)
    assert outs[1].dtype == np.int64  # apply_int_semantics still runs
    assert (outs[1] == stacked[2 * k2, :n_keys]).all()
    assert (act == stacked[-1, :n_keys]).all()
    # the served form, built on the emit worker from the flat arrays ...
    hot, _ = node.gb.hh_assemble(stacked, n_keys, node._hh_items)
    assert hot[0].dtype == np.object_ and hot[0].shape == (n_keys,)
    _same(hot[0].tolist(), served)
    # ... and from the sync form, where a window was finalized in line
    _same(node._decode_hh(outs)[0].tolist(), served)


def test_decode_array_follows_the_dictionary():
    """`decode_array` is `decode` over an array; its table is built again
    when the dictionary has grown or was restored, not on every call."""
    vd = ValueDict()
    codes = np.array([-1, 0, 1, 2, 3, 10 ** 12], dtype=np.int64)

    def held():
        got = vd.decode_array(codes)
        assert got.dtype == np.object_
        for g, c in zip(got.tolist(), codes.tolist()):
            assert g is vd.decode(c)
        return got.tolist()

    assert held() == [None] * 6  # an empty dictionary
    vd.encode(np.array(["a", (1, 2), "c"], dtype=np.object_))
    assert held() == [None, "a", (1, 2), "c", None, None]
    table = vd._decode_table
    assert held() and vd._decode_table is table  # nothing new: kept
    vd.encode(np.array([2.5, 2.5]))
    assert held() == [None, "a", (1, 2), "c", 2.5, None]
    assert vd._decode_table is not table
    vd.restore(["x", "y", "z", "w"])  # as long as before, other values
    assert held() == [None, "x", "y", "z", "w", None]


def _mixture(rng, n, dtype=np.int64):
    """The heavy-hitters cell's codes: 7 / 13 / 99 heavy, the rest uniform
    over 100..2099."""
    codes = rng.integers(100, 2100, n)
    p = rng.random(n)
    for value, lo, hi in ((7, 0.0, 0.35), (13, 0.35, 0.55), (99, 0.55, 0.7)):
        codes[(p >= lo) & (p < hi)] = value
    return codes.astype(dtype)


def _stream_int64_mixture():
    rng = np.random.default_rng(2 ** 31 + 30)
    return [_mixture(rng, 32_768) for _ in range(4)], None


def _stream_float64_with_nan():
    rng = np.random.default_rng(31)
    cols = []
    for _ in range(4):
        col = rng.integers(-50, 50, 4_096) / 4.0
        col[rng.random(4_096) < 0.1] = np.nan
        cols.append(col)
    cols.append(np.full(16, np.nan))  # nothing but NaN
    cols.append(np.array([np.inf, -np.inf, 0.25, np.nan, 1e300]))
    return cols, None


def _stream_negative_and_beyond_2_31():
    rng = np.random.default_rng(32)
    pool = np.concatenate([
        rng.integers(-(1 << 62), 1 << 62, 500),
        rng.integers(-(1 << 33), -(1 << 31), 200),
        rng.integers(1 << 31, 1 << 33, 200),
        [-(1 << 63), (1 << 63) - 1, 0, -1, (1 << 53) + 1]])
    return [rng.choice(pool, 4_096) for _ in range(3)], None


def _stream_known_values_only():
    rng = np.random.default_rng(33)
    first = np.arange(100, 2100)
    return [first] + [rng.choice(first, 8_192) for _ in range(3)], None


def _stream_first_seen_mid_stream():
    rng = np.random.default_rng(34)
    steps = []
    for hi in (200, 200, 900, 900, 5_000, 200, 70_000, 70_000):
        steps.append(rng.integers(100, hi, 2_048))
    # below the table's lowest and above its highest, both ways round
    steps += [np.array([-5, 99, 100, 69_999, 70_000, 1 << 40]),
              np.array([1 << 40, -5, -6])]
    return steps, None


def _stream_int64_then_float64():
    rng = np.random.default_rng(35)
    steps = []
    for i in range(6):
        col = _mixture(rng, 4_096, np.float64 if i % 2 else np.int64)
        steps.append(col)
    steps.append(np.array([7.5, 7.0, 8.0, 7.25]))  # not all integral
    steps.append(np.array([7, 8, 9], dtype=np.int64))
    # beyond 2**53 an int and the float beside it are different values
    steps.append(np.array([(1 << 53) + 1, 1 << 53], dtype=np.int64))
    steps.append(np.array([float(1 << 53), float((1 << 53) + 2)]))
    return steps, None


def _stream_empty_columns():
    return [np.array([], dtype=np.int64), np.array([3, 1, 2]),
            np.array([], dtype=np.int64), np.array([], dtype=np.float64),
            np.array([], dtype=np.object_), np.array([2.0, 4.0]),
            np.array([], dtype=np.float64)], None


def _stream_narrow_range():
    rng = np.random.default_rng(36)
    return [rng.integers(-300, 300, 4_096) for _ in range(3)], None


def _stream_wide_range():
    rng = np.random.default_rng(37)
    pool = rng.integers(0, 1 << 40, 300)
    return [rng.choice(pool, 4_096) for _ in range(3)] + \
        [rng.integers(0, 1 << 40, 64)], None


def _stream_narrow_turns_wide_turns_narrow():
    rng = np.random.default_rng(38)
    return [rng.integers(0, 50, 512), np.array([1 << 30, 3, 1 << 20]),
            rng.integers(0, 50, 512),
            rng.integers(0, 1 << 21, 300_000),  # fills the span in
            rng.integers(0, 1 << 21, 4_096)], None


def _stream_overflow_small_budget():
    rng = np.random.default_rng(39)
    return [rng.integers(0, 40, 256), rng.integers(0, 120, 256),
            rng.integers(0, 120, 256), rng.integers(0, 40, 256),
            rng.integers(0, 120, 256) / 2.0,
            np.array(["a", "b", None], dtype=np.object_)], 64


def _stream_restore_then_more():
    rng = np.random.default_rng(40)
    return [_mixture(rng, 8_192), _mixture(rng, 8_192), "restore",
            _mixture(rng, 8_192), rng.integers(0, 4_000, 8_192), "restore",
            _mixture(rng, 8_192, np.float64), _mixture(rng, 8_192)], None


def _stream_other_dtypes():
    rng = np.random.default_rng(41)
    return [rng.integers(-100, 100, 512).astype(np.int32),
            rng.integers(-100, 100, 512).astype(np.int8),
            rng.integers(0, 200, 512).astype(np.uint16),
            rng.integers(0, 200, 512).astype(np.uint64),
            np.array([(1 << 64) - 1, 5, 1 << 63], dtype=np.uint64),
            (rng.integers(-100, 100, 512) / 8).astype(np.float32),
            np.array([0.1, 0.2, np.nan], dtype=np.float32),
            np.array([0.1, 0.2, 0.1 + 2 ** -30]),
            rng.random(64) < 0.5,
            rng.integers(-100, 100, 512)], None


def _stream_object_branch():
    return [np.array(["a", "b", "a", None, "c"], dtype=np.object_),
            np.array([7, "7", 7.0, None, [1, 2], {"k": 1}, [1, 2]],
                     dtype=np.object_),
            np.array([7, 8, 9]), np.array([7.0, 8.5]),
            np.array(["c", 8, 8.5, "d"], dtype=np.object_)], None


_STREAMS = {f.__name__[len("_stream_"):]: f for f in (
    _stream_int64_mixture, _stream_float64_with_nan,
    _stream_negative_and_beyond_2_31, _stream_known_values_only,
    _stream_first_seen_mid_stream, _stream_int64_then_float64,
    _stream_empty_columns, _stream_narrow_range, _stream_wide_range,
    _stream_narrow_turns_wide_turns_narrow, _stream_overflow_small_budget,
    _stream_restore_then_more, _stream_other_dtypes, _stream_object_branch)}


class TestValueDict:
    def test_roundtrip_mixed(self):
        vd = ValueDict()
        col = np.array(["a", "b", "a", None, "c"], dtype=np.object_)
        codes = vd.encode(col)
        assert np.isnan(codes[3])
        assert codes[0] == codes[2]
        assert vd.decode(int(codes[1])) == "b"

    def test_numeric_nan_passthrough(self):
        vd = ValueDict()
        col = np.array([1.5, np.nan, 1.5, 2.5], dtype=np.float64)
        codes = vd.encode(col)
        assert np.isnan(codes[1])
        assert codes[0] == codes[2] != codes[3]
        # a second batch reuses the same codes
        codes2 = vd.encode(np.array([2.5, 1.5]))
        assert codes2[0] == codes[3] and codes2[1] == codes[0]

    def test_snapshot_restore(self):
        vd = ValueDict()
        vd.encode(np.array(["x", "y"], dtype=np.object_))
        vd2 = ValueDict()
        vd2.restore(vd.snapshot())
        assert vd2.decode(0) == "x"
        c = vd2.encode(np.array(["y", "z"], dtype=np.object_))
        assert c[0] == 1.0 and c[1] == 2.0

    # ---- the table lookup (PR 30) against the routine as it stood: on the
    # same batches, the same codes, the same dictionary
    @pytest.mark.parametrize("name", sorted(_STREAMS))
    def test_encode_as_the_routine_stood(self, name, monkeypatch):
        steps, budget = _STREAMS[name]()
        if budget is not None:
            monkeypatch.setattr(aggspec, "HH_MAX_CODES", budget)
        vd, ref = ValueDict(), _ValueDictAsItStood(
            budget if budget is not None else HH_MAX_CODES)
        for step in steps:
            if isinstance(step, str):  # "restore": a checkpoint round trip
                snap = vd.snapshot()
                vd, ref2 = ValueDict(), _ValueDictAsItStood(ref.budget)
                vd.restore(snap)
                ref2.restore(ref.snapshot())
                ref2.overflowed, ref = False, ref2
                continue
            got, want = vd.encode(step), ref.encode(step)
            assert got.dtype == np.float32 and got.shape == want.shape
            np.testing.assert_array_equal(got, want)  # NaN == NaN here
            assert vd.snapshot() == ref.snapshot()
            assert [type(v) for v in vd.snapshot()] == \
                [type(v) for v in ref.snapshot()]
            assert vd.overflowed == ref.overflowed
        n = len(ref.snapshot())
        assert [vd.decode(c) for c in range(-1, n + 1)] == \
            [None] + ref.snapshot() + [None]

    @pytest.mark.parametrize("dtype,values,base", [
        # base: what the dense table subtracts; None: the searched table
        (np.int64, [7, 13, 99, 2099, 100], 0),  # low and positive: no base
        (np.int64, [-300, 299, 5], -301),  # under the floor of 4,096 slots
        (np.int64, [0, 4093], -1),
        (np.int64, [0, 4094], None),  # one slot too many
        (np.int64, [5000, 5001], 4999),  # positive, but not low
        (np.int64, [0, 1 << 40], None),
        (np.int64, [-(1 << 63), -(1 << 63) + 5], None),  # no slot below
        (np.int64, [(1 << 63) - 9, (1 << 63) - 1], (1 << 63) - 10),
        (np.int64, list(range(0, 80_000, 9)), None),  # 9 slots a value
        (np.int64, list(range(0, 80_000, 7)), -1),  # 7 slots a value
        (np.int32, [5, 6, 7], 0),
        (np.float64, [1.0, 2.0, 3.0], None),  # floats are searched
    ])
    def test_table_form_follows_the_known_values(self, dtype, values, base):
        vd = ValueDict()
        col = np.array(values, dtype=dtype)
        first = vd.encode(col)
        (table,) = vd._tables.values()
        if base is None:
            assert table.dense is None
        else:
            assert table.dense is not None and table.base == base
            assert len(table.dense) == max(values) - base + 2
        # the second time round no row is left for the dictionary
        codes, missed = vd.lookup(col[::-1])
        assert missed is None
        np.testing.assert_array_equal(codes, first[::-1])

    @pytest.mark.parametrize("dtype", [np.int64, np.float64])
    def test_known_values_never_reach_python(self, dtype, monkeypatch):
        vd = ValueDict()
        rng = np.random.default_rng(5)
        vd.encode(np.arange(7, 2100).astype(dtype))
        monkeypatch.setattr(vd, "_code", None)  # a call would raise
        monkeypatch.setattr(aggspec.np, "unique", None)
        col = rng.integers(7, 2100, 32_768).astype(dtype)
        codes = vd.encode(col)
        monkeypatch.undo()
        np.testing.assert_array_equal(codes, col - 7)

    def test_learn_is_given_the_unknown_rows_only(self):
        vd = ValueDict()
        vd.encode(np.array([10, 11, 12]))
        col = np.array([11, 500, 10, -3, 500, 12])
        codes, missed = vd.lookup(col)
        assert missed.tolist() == [1, 3, 4]
        assert np.isnan(codes[missed]).all()
        vd.learn(col, codes, missed)
        assert codes.tolist() == [1.0, 4.0, 0.0, 3.0, 4.0, 2.0]
        # a float column: NaN rows are nobody's to learn
        codes, missed = vd.lookup(np.array([np.nan, 11.0, np.nan]))
        assert missed.tolist() == [1]
        vd.encode(np.array([11.0]))
        codes, missed = vd.lookup(np.array([np.nan, 11.0, np.nan]))
        assert missed is None and np.isnan(codes[[0, 2]]).all()

    def test_overflow_at_the_real_budget(self):
        """HH_MAX_CODES distinct values take every code; the next ones
        encode NaN, stay out of the table and set `overflowed`."""
        vd = ValueDict()
        codes = vd.encode(np.arange(HH_MAX_CODES + 3, dtype=np.int64))
        assert vd.overflowed and len(vd.snapshot()) == HH_MAX_CODES
        assert codes[HH_MAX_CODES - 1] == HH_MAX_CODES - 1
        assert np.isnan(codes[HH_MAX_CODES:]).all()
        again, missed = vd.lookup(
            np.array([0, HH_MAX_CODES - 1, HH_MAX_CODES + 1]))
        assert missed.tolist() == [2] and again[1] == HH_MAX_CODES - 1


class _ValueDictAsItStood:
    """`ValueDict` before PR 30, kept as the reference: a sort of the whole
    batch and a Python call per distinct value, known or not."""

    def __init__(self, budget):
        self.budget = budget
        self._ids = {}
        self._values = []
        self.overflowed = False

    def _code(self, v):
        c = self._ids.get(v)
        if c is None:
            if len(self._values) >= self.budget:
                self.overflowed = True
                return np.nan
            c = len(self._values)
            self._ids[v] = c
            self._values.append(v)
        return float(c)

    def encode(self, col):
        n = len(col)
        out = np.empty(n, dtype=np.float32)
        if col.dtype == np.object_:
            for i, v in enumerate(col.tolist()):
                if v is None:
                    out[i] = np.nan
                    continue
                try:
                    out[i] = self._code(v)
                except TypeError:
                    out[i] = self._code(repr(v))
            return out
        arr = np.asarray(col)
        if np.issubdtype(arr.dtype, np.floating):
            nan = np.isnan(arr)
        else:
            nan = np.zeros(n, dtype=bool)
        out = np.full(n, np.nan, dtype=np.float32)
        clean = arr[~nan] if nan.any() else arr
        if len(clean):
            uniq, inverse = np.unique(clean, return_inverse=True)
            ucodes = np.array(
                [self._code(u.item()) for u in uniq], dtype=np.float32)
            out[~nan] = ucodes[inverse]
        return out

    def snapshot(self):
        return list(self._values)

    def restore(self, values):
        self._values = list(values)
        self._ids = {}
        for i, v in enumerate(self._values):
            try:
                self._ids[v] = i
            except TypeError:
                pass
