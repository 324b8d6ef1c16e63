"""Exact order statistics on the device (ops/orderstat.py): `median`,
`percentile_cont` and `percentile_disc` of the group-by kernel against a
plain numpy reference over the float32-rounded values and against the host
operator's own functions (`funcs_agg._percentile`, `statistics.median`), in
tumbling and hopping windows; and the multi-column integer key encode
(`int_multi`) against the dict path it replaces.

Tolerances: the host operator's functions run over the very float32 values
the device holds, in float64, as the device's host tail does — equal, bit
for bit. numpy's `percentile` interpolates as a + (b - a) w, not a (1 - w)
+ b w: within one float32 ulp of the answer (the same bound the benchmark's
reference holds the served rule to)."""
import statistics

import numpy as np
import pytest

from ekuiper_tpu.functions.funcs_agg import _percentile
from ekuiper_tpu.ops import orderstat
from ekuiper_tpu.ops.aggspec import extract_kernel_plan
from ekuiper_tpu.ops.groupby import DeviceGroupBy
from ekuiper_tpu.ops.keytable import KeyTable
from ekuiper_tpu.sql.parser import parse_select

CAP = 64
MB = 16

AGGS = {
    "median": ("median(v)", lambda vals: statistics.median(vals),
               lambda a: np.median(a)),
    "cont90": ("percentile_cont(v, 0.9)",
               lambda vals: _percentile(vals, 0.9, cont=True),
               lambda a: np.percentile(a, 90)),
    "cont10": ("percentile_cont(v, 0.1)",
               lambda vals: _percentile(vals, 0.1, cont=True),
               lambda a: np.percentile(a, 10)),
    "disc30": ("percentile_disc(v, 0.3)",
               lambda vals: _percentile(vals, 0.3, cont=False), None),
    "disc50": ("percentile_disc(v, 0.5)",
               lambda vals: _percentile(vals, 0.5, cont=False), None),
}


def kernel(aggs, n_panes: int):
    window = ("TUMBLINGWINDOW(ss, 1)" if n_panes == 1
              else f"HOPPINGWINDOW(ss, {n_panes}, 1)")
    fields = ", ".join(f"{AGGS[a][0]} AS {a}" for a in aggs)
    plan = extract_kernel_plan(parse_select(
        f"SELECT k, count(*) AS c, {fields} FROM s GROUP BY k, {window}"))
    assert plan is not None and plan.has_orderstat
    return DeviceGroupBy(plan, capacity=CAP, n_panes=n_panes,
                         micro_batch=MB)


class Driver:
    """What the window node does around the kernel: each pane's fill as
    the host knows it, the rung climbed before a fold would not fit."""

    def __init__(self, gb):
        self.gb = gb
        self.state = gb.init_state()
        self.fill = [0] * gb.n_panes
        self.grows = 0

    def fold(self, pane, keys, vals, valid=None):
        rung = self.state["osv"].shape[-1]
        need = orderstat.rung_for(self.fill[pane], len(keys), rung, MB)
        if need > rung:
            self.state = orderstat.grow_leaves(self.state, need)
            self.grows += 1
        self.state = self.gb.fold(
            self.state, {"v": vals}, keys.astype(np.int32),
            valid=None if valid is None else {"v": valid}, pane_idx=pane)
        self.fill[pane] += len(keys)

    def reset(self, pane):
        self.state = self.gb.reset_pane(self.state, pane)
        self.fill[pane] = 0


def seeded_rows(rng, n):
    """Keys over [0, CAP) with slot CAP - 1 and one-row / absent groups;
    loads with ties, zeros, negatives, NaN and a few NULLs."""
    keys = rng.integers(0, CAP - 8, n)
    keys[:3] = CAP - 1  # the last slot
    keys[3] = CAP - 2  # a one-row group
    keys[rng.random(n) < 0.1] = CAP - 3  # an all-zero group
    vals = np.round(rng.normal(50, 30, n), 1)
    vals[rng.random(n) < 0.15] = 0.0
    vals[keys == CAP - 3] = 0.0
    vals[rng.random(n) < 0.05] = np.nan  # non-numeric reads NaN
    valid = rng.random(n) >= 0.05  # NULL cells
    valid[3] = True
    vals[3] = 7.5
    return keys, vals.astype(np.float32), valid


def reference(keys, vals, valid, aggs):
    """Per key present: (count of rows, {agg: (host value, numpy value)})
    over the valid, numeric values as float32 holds them."""
    out = {}
    for k in np.unique(keys):
        rows = keys == k
        ok = rows & valid & ~np.isnan(vals)
        nums = [float(x) for x in vals[ok]]
        res = {}
        for a in aggs:
            host = AGGS[a][1](nums) if nums else None
            npf = AGGS[a][2]
            res[a] = (host, None if (npf is None or not nums)
                      else float(npf(np.asarray(nums, dtype=np.float64))))
        out[int(k)] = (int(rows.sum()), res)
    return out


def check(outs, act, want, aggs):
    present = set(np.flatnonzero(act > 0).tolist())
    assert present == set(want)
    for k, (n, res) in want.items():
        assert outs[0][k] == n
        for i, a in enumerate(aggs, start=1):
            host, npv = res[a]
            got = outs[i][k]
            if host is None:
                assert np.isnan(got), (a, k, got)
                continue
            assert got == host, (a, k, got, host)
            if npv is not None:
                assert abs(got - npv) <= abs(np.spacing(np.float32(npv))), \
                    (a, k, got, npv)


@pytest.mark.parametrize("aggs", [["median"], ["cont90", "disc30"],
                                  ["median", "cont10", "disc50"]])
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 9])
def test_tumbling_order_statistics_match_the_host_and_numpy(aggs, seed):
    rng = np.random.default_rng(seed)
    gb = kernel(aggs, 1)
    drv = Driver(gb)
    keys, vals, valid = seeded_rows(rng, 700)
    for lo in range(0, 700, 300):  # micro-batches of unequal size
        drv.fold(0, keys[lo:lo + 300], vals[lo:lo + 300],
                 valid[lo:lo + 300])
    outs, act = gb.finalize(drv.state, CAP)
    check(outs, act, reference(keys, vals, valid, aggs), aggs)
    # a recycled pane starts empty: the next window holds its own rows
    drv.reset(0)
    k2, v2, m2 = seeded_rows(rng, 90)
    drv.fold(0, k2, v2, m2)
    outs, act = gb.finalize(drv.state, CAP)
    check(outs, act, reference(k2, v2, m2, aggs), aggs)


@pytest.mark.parametrize("aggs", [["median"], ["median", "cont90",
                                               "disc30"]])
@pytest.mark.parametrize("seed", [5, 2 ** 31 + 77])
def test_hopping_windows_merge_their_panes(aggs, seed):
    """Two panes: a window is both; after the older one is reset, only
    the newer; a key only in the reset pane is absent from the window."""
    rng = np.random.default_rng(seed)
    gb = kernel(aggs, 2)
    drv = Driver(gb)
    k0, v0, m0 = seeded_rows(rng, 400)
    k0[-5:] = CAP - 4  # only in pane 0
    k1, v1, m1 = seeded_rows(rng, 500)
    drv.fold(0, k0, v0, m0)
    drv.fold(1, k1, v1, m1)
    outs, act = gb.finalize(drv.state, CAP)
    check(outs, act, reference(np.concatenate([k0, k1]),
                               np.concatenate([v0, v1]),
                               np.concatenate([m0, m1]), aggs), aggs)
    drv.reset(0)
    outs, act = gb.finalize(drv.state, CAP)
    assert act[CAP - 4] == 0
    check(outs, act, reference(k1, v1, m1, aggs), aggs)


@pytest.mark.parametrize("n_panes", [1, 2])
def test_a_pane_climbs_a_rung_between_two_micro_batches(n_panes):
    """More rows than the first rung holds: the buffers double on the
    device between micro-batches, no row is dropped, the answers stay
    exact, and the kernel re-specializes inside the rung ladder."""
    rng = np.random.default_rng(41)
    gb = kernel(["median", "cont90"], n_panes)
    drv = Driver(gb)
    first = drv.state["osv"].shape[-1]
    assert first == orderstat.first_rung(MB)
    keys, vals, valid = seeded_rows(rng, 3 * first)
    for lo in range(0, len(keys), MB):
        drv.fold(n_panes - 1, keys[lo:lo + MB], vals[lo:lo + MB],
                 valid[lo:lo + MB])
    assert drv.grows == 2 and drv.state["osv"].shape[-1] == 4 * first
    assert int(np.asarray(drv.state["osf"])[n_panes - 1]) == len(keys)
    outs, act = gb.finalize(drv.state, CAP)
    check(outs, act, reference(keys, vals, valid, ["median", "cont90"]),
          ["median", "cont90"])


@pytest.mark.parametrize("n_panes", [1, 2])
def test_the_selection_counts_the_rows_the_closing_pane_holds(n_panes):
    """The selection's last row is the device's own count of the rows its
    buffers took in the pane it closes: NULL and NaN rows are not taken, a
    reset pane holds none, and a finalize that closes no pane (-1) counts
    none — what kuiper_orderstat_rows_total adds up."""
    rng = np.random.default_rng(5)
    gb = kernel(["median"], n_panes)
    drv = Driver(gb)
    held = [0] * n_panes
    for pane in range(n_panes):
        keys, vals, valid = seeded_rows(rng, 3 * MB + 5)
        drv.fold(pane, keys, vals, valid)
        held[pane] = int(np.count_nonzero(valid & ~np.isnan(vals)))
    mask = np.ones(n_panes, dtype=np.bool_)

    def closed(pane):
        return gb.os_closed_rows(np.asarray(
            gb._os_sel(drv.state, mask, gb._pane_arg(pane))))

    assert [closed(p) for p in range(n_panes)] == held
    assert 0 < held[0] < 3 * MB + 5 and closed(-1) == 0
    drv.reset(n_panes - 1)
    assert closed(n_panes - 1) == 0


def test_rung_for_leaves_room_for_every_padded_chunk():
    assert orderstat.rung_for(0, 1, 512, 128) == 512
    assert orderstat.rung_for(384, 128, 512, 128) == 512
    assert orderstat.rung_for(385, 128, 512, 128) == 1024
    assert orderstat.rung_for(0, 1000, 512, 128) == 1024  # 8 chunks


def test_specs_of_one_argument_share_one_buffer():
    plan = extract_kernel_plan(parse_select(
        "SELECT k, median(v) AS m, percentile_cont(v, 0.9) AS p, "
        "percentile_disc(w, 0.5) AS d, median(v) FILTER (WHERE w > 1) AS f "
        "FROM s GROUP BY k, TUMBLINGWINDOW(ss, 1)"))
    assert len(plan.os_bufs) == 3
    assert [s.os_buf for s in plan.specs] == [0, 0, 1, 2]
    assert not plan.host_foldable  # no pre-issued host shadow


@pytest.mark.parametrize("sql", [
    "SELECT k, median(*) AS m FROM s GROUP BY k, TUMBLINGWINDOW(ss, 1)",
    "SELECT k, percentile_cont(v, 1.5) AS m FROM s "
    "GROUP BY k, TUMBLINGWINDOW(ss, 1)",
    "SELECT k, percentile_disc(v, w) AS m FROM s "
    "GROUP BY k, TUMBLINGWINDOW(ss, 1)",
])
def test_what_the_device_cannot_answer_stays_with_the_host(sql):
    assert extract_kernel_plan(parse_select(sql)) is None


# ------------------------------------------------ the composite int key
def _tables():
    from ekuiper_tpu.io import fastjson

    if not fastjson.ensure_native(background=False):
        pytest.skip("the native module did not build")
    fast, slow = KeyTable(16), KeyTable(16)
    slow._multi_ok = False  # the dict path, as before int_multi
    return fast, slow


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 3])
def test_int_multi_encode_matches_the_dict_path(seed):
    """Slots, growth and decoded tuples equal the dict path's over batches
    of int keys, a batch with a NULL (object column, the dict path) and a
    batch with a string dimension in between: the packed table catches up
    with what the dict path added."""
    rng = np.random.default_rng(seed)
    fast, slow = _tables()
    for step in range(6):
        n = 3000
        cols = [rng.integers(0, 40, n), rng.integers(0, 5, n),
                rng.integers(-2, 12, n).astype(np.int32)]
        if step == 2:
            obj = cols[2].astype(object)
            obj[7] = None
            cols[2] = obj
        if step == 3:
            cols[1] = np.array([f"h{x}" for x in cols[1]], dtype=object)
        a, ga = fast.encode_multi(cols)
        b, gb = slow.encode_multi(cols)
        assert np.array_equal(a, b) and ga == gb, step
        assert fast.decode_all() == slow.decode_all()
    assert fast.encode_rows["int_multi"] == 4 * 3000
    assert fast.encode_rows["hashed"] == 2 * 3000
    assert all(type(v) is int for v in fast.decode(0))


def test_int_multi_leaves_a_table_whose_keys_the_dict_may_alias():
    """A float tuple already in the table would equal an int tuple in the
    dict: the packed table must not give the int tuple a second slot."""
    fast, _ = _tables()
    fast.encode_multi([np.array([1.0, 2.0]), np.array([3, 4])])
    s, _ = fast.encode_multi([np.array([1, 2]), np.array([3, 4])])
    assert s.tolist() == [0, 1] and fast.n_keys == 2
    assert fast.encode_rows.get("int_multi", 0) == 0


def test_int_multi_decodes_a_hundred_thousand_new_keys_in_linear_time():
    """A micro-batch of 10^5 new composite keys over a column of 10^5
    distinct ids: the same slots and tuples as the dict path, and about
    its time — each new key decodes by one index a column, never a copy of
    the column's table (which made the decode quadratic)."""
    import time

    fast, slow = _tables()
    rng = np.random.default_rng(7)
    ids = rng.permutation(100_000).astype(np.int64) + 10 ** 12
    batches = [[ids, ids % 40],
               [np.concatenate([ids[:50_000], ids[50_000:] + 1]),
                np.concatenate([ids[:50_000] % 40, ids[50_000:] % 40])]]
    took = {"fast": 0.0, "slow": 0.0}
    for cols in batches:
        t0 = time.perf_counter()
        a, ga = fast.encode_multi(cols)
        took["fast"] += time.perf_counter() - t0
        t0 = time.perf_counter()
        b, gb = slow.encode_multi(cols)
        took["slow"] += time.perf_counter() - t0
        assert np.array_equal(a, b) and ga == gb
    assert fast.n_keys == 150_000 and fast.decode_all() == slow.decode_all()
    assert fast.encode_rows["int_multi"] == 200_000
    assert took["fast"] < 5 * took["slow"] + 0.5, took
