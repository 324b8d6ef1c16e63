"""KeyTable coverage (round 7): sorted-fallback vs hashed-path slot parity,
None/"" alias normalization (one slot per normalized key, regression for
the repr-fallback double-slot bug), native-vs-Python slot parity across
decode shard counts including the new-key appendix sync, checkpoint
restore round-trips, and the uint16/int32 slot-dtype switch at capacity
growth."""
import json

import numpy as np
import pytest

from ekuiper_tpu.io import fastjson
from ekuiper_tpu.ops.groupby import slot_dtype
from ekuiper_tpu.ops.keytable import KeyTable


def python_table() -> KeyTable:
    """A KeyTable pinned to the pure-Python paths (parity reference)."""
    kt = KeyTable()
    kt._native_ok = False
    return kt


@pytest.fixture(scope="module")
def native():
    fastjson.ensure_native(background=False)
    mod = fastjson._load()
    if mod is None or not fastjson.has_keytab():
        pytest.skip("native keytab unavailable (no toolchain)")
    return mod


def obj_col(vals):
    col = np.empty(len(vals), dtype=object)
    col[:] = vals
    return col


class TestAliasNormalization:
    def test_none_and_empty_share_one_slot_hashed(self):
        kt = python_table()
        s, _ = kt.encode_column(obj_col([None, "", "x", None]))
        assert s[0] == s[1] == s[3]
        assert kt.decode(int(s[0])) == ""

    def test_mixed_batch_repr_fallback_no_double_slot(self):
        """Regression: a batch with None, "" AND an unhashable element used
        to take the blanket-repr sort fallback, storing '' under its repr
        "''" — a later hashed batch then assigned '' a SECOND slot."""
        kt = python_table()
        s1, _ = kt.encode_column(obj_col([None, "", [1], "x"]))
        assert s1[0] == s1[1]
        s2, _ = kt.encode_column(obj_col(["", None, "x"]))
        assert s2[0] == s2[1] == s1[0]
        assert s2[2] == s1[3]
        # exactly one slot exists for the normalized empty key
        assert kt.decode_all().count("") == 1

    def test_tuple_variants_share_one_slot(self):
        kt = python_table()
        s1, _ = kt.encode_multi([obj_col(["a", "a"]),
                                 obj_col([None, ""])])
        assert s1[0] == s1[1]
        # unhashable element elsewhere routes through the _h stringify path
        s2, _ = kt.encode_multi([obj_col(["a", "a"]),
                                 obj_col(["", None])])
        assert set(s2.tolist()) == {s1[0]}
        assert kt.decode(int(s1[0])) == ("a", "")

    def test_mixed_strings_keep_identity_across_paths(self):
        """A plain string in a mixed (repr-fallback) batch must get the
        same slot the hashed path would assign it."""
        kt = python_table()
        s1, _ = kt.encode_column(obj_col(["dev1", {"u": 1}]))
        s2, _ = kt.encode_column(obj_col(["dev1"]))
        assert s2[0] == s1[0]


class TestSortedHashedParity:
    def test_unicode_vs_object_same_slots(self):
        """The same key sequence through the sorted (fixed-width unicode)
        and hashed (object) paths assigns consistent slots."""
        ka, kb = python_table(), python_table()
        vals = ["b", "a", "", "b", "c", "a"]
        sa, _ = ka.encode_column(np.array(vals, dtype="U"))
        sb, _ = kb.encode_column(obj_col(vals))
        # slot NUMBERING differs (sorted path assigns in sorted order) but
        # grouping must agree and cross-path reuse must resolve
        assert [ka.decode(int(x)) for x in sa] == vals
        assert [kb.decode(int(x)) for x in sb] == vals
        s2, _ = ka.encode_column(obj_col(vals))  # hashed batch, same table
        np.testing.assert_array_equal(s2, sa)

    def test_sorted_none_matches_hashed_alias(self):
        ka = python_table()
        sa, _ = ka.encode_column(np.array([None, "", "x"], dtype=object))
        kb = python_table()
        # numeric->unicode col with empty string via sorted path
        sb1, _ = kb.encode_column(np.array(["", "x"], dtype="U"))
        sb2, _ = kb.encode_column(obj_col([None]))
        assert sb2[0] == sb1[0]
        assert ka.decode(int(sa[0])) == kb.decode(int(sb2[0])) == ""


class TestNativeParity:
    def test_random_parity_and_appendix_sync(self, native):
        rng = np.random.default_rng(11)
        kn, kp = KeyTable(), python_table()
        for batch in range(8):
            vals = [f"dev_{int(rng.integers(0, 300))}" for _ in range(400)]
            for i in range(0, 400, 17):
                vals[i] = None
            for i in range(3, 400, 41):
                vals[i] = ""
            col = obj_col(vals)
            sn, gn = kn.encode_column(col)
            sp, gp = kp.encode_column(col)
            np.testing.assert_array_equal(sn, sp)
            assert gn == gp
        assert kn._ntab is not None and kn._native_ok
        assert kn.decode_all() == kp.decode_all()
        assert kn.capacity == kp.capacity

    def test_parity_across_decode_shards(self, native):
        """Key columns decoded with 1/2/4 native parse shards feed the
        native slot encode; slots + appendix must be identical to the
        Python table fed the same column."""
        from ekuiper_tpu.data.types import DataType, Field, Schema

        schema = Schema(fields=[Field("deviceId", DataType.STRING),
                                Field("v", DataType.FLOAT)])
        spec = fastjson.schema_field_spec(schema)
        rng = np.random.default_rng(5)
        payloads = []
        for i in range(3000):
            m = {"v": float(i)}
            if i % 9 != 0:  # ~1/9 rows miss the key (None -> "" slot)
                m["deviceId"] = f"d{int(rng.integers(0, 150))}"
            payloads.append(json.dumps(m).encode())
        ref_slots = None
        for shards in (1, 2, 4):
            cols, valid, bad = fastjson.decode_columns(
                payloads, spec, shards=shards)
            kn, kp = KeyTable(), python_table()
            sn, _ = kn.encode_column(cols["deviceId"])
            sp, _ = kp.encode_column(cols["deviceId"])
            np.testing.assert_array_equal(sn, sp)
            assert kn.decode_all() == kp.decode_all()
            if ref_slots is None:
                ref_slots = sn
            else:
                np.testing.assert_array_equal(sn, ref_slots)

    def test_native_catches_up_after_python_only_batches(self, native):
        kn, kp = KeyTable(), python_table()
        # sorted path first (unicode col): keys enter WITHOUT the native tab
        for kt in (kn, kp):
            kt.encode_column(np.array(["s1", "s2"], dtype="U"))
        sn, _ = kn.encode_column(obj_col(["s2", "new", None]))
        sp, _ = kp.encode_column(obj_col(["s2", "new", None]))
        np.testing.assert_array_equal(sn, sp)
        assert kn._native_n == kn.n_keys  # mirror caught up

    def test_tuple_keys_disable_mirror_without_divergence(self, native):
        kn, kp = KeyTable(), python_table()
        for kt in (kn, kp):
            kt.encode_multi([obj_col(["a", "b"]), obj_col([1, None])])
        sn, _ = kn.encode_column(obj_col(["z", "a"]))
        sp, _ = kp.encode_column(obj_col(["z", "a"]))
        np.testing.assert_array_equal(sn, sp)
        assert kn._native_ok is False  # tuples can't mirror natively
        assert kn.decode_all() == kp.decode_all()

    def test_restore_roundtrip(self, native):
        kn = KeyTable()
        kn.encode_column(obj_col(["a", None, "b"]))
        saved = kn.decode_all()
        kr = KeyTable()
        kr.restore(saved)
        s, _ = kr.encode_column(obj_col(["b", "", "c", "a"]))
        assert s.tolist() == [2, 1, 3, 0]
        assert kr.decode_all() == saved + ["c"]

    def test_surrogate_key_falls_back_cleanly(self, native):
        kn, kp = KeyTable(), python_table()
        col = obj_col(["ok", "\ud800bad", "ok"])
        sn, _ = kn.encode_column(col)
        sp, _ = kp.encode_column(col)
        np.testing.assert_array_equal(sn, sp)
        assert kn.decode_all() == kp.decode_all()
        # and the mirror still serves later clean batches
        sn2, _ = kn.encode_column(obj_col(["ok", "fresh"]))
        sp2, _ = kp.encode_column(obj_col(["ok", "fresh"]))
        np.testing.assert_array_equal(sn2, sp2)


class TestSlotDtypeSwitch:
    def test_boundary(self):
        assert slot_dtype(16384) is np.uint16
        assert slot_dtype(65535) is np.uint16
        assert slot_dtype(65536) is np.int32
        assert slot_dtype(131072) is np.int32

    def test_fold_switches_dtype_at_growth_and_stays_exact(self):
        """Capacity doubling past the uint16 boundary mid-stream: folds
        before the grow ship uint16, after ship int32; per-slot counts
        stay exact across the switch (the grow preserves partials)."""
        from ekuiper_tpu.ops.aggspec import extract_kernel_plan
        from ekuiper_tpu.ops.groupby import DeviceGroupBy
        from ekuiper_tpu.sql.parser import parse_select

        stmt = parse_select(
            "SELECT count(*) FROM s GROUP BY k, TUMBLINGWINDOW(ss, 10)")
        plan = extract_kernel_plan(stmt)
        gb = DeviceGroupBy(plan, capacity=65536 // 2, n_panes=1,
                           micro_batch=64)
        assert slot_dtype(gb.capacity) is np.uint16
        state = gb.init_state()
        # fold rows into slots near the top of the uint16 range
        lo_slots = np.array([0, 1, 32766, 32767] * 16, dtype=np.int32)
        state = gb.fold(state, {}, lo_slots, pane_idx=0)
        # grow past the boundary (as a 65k+1-th key would force)
        state = gb.grow(state, 65536 * 2)
        assert slot_dtype(gb.capacity) is np.int32
        hi_slots = np.array([0, 70000, 100000, 32767] * 16, dtype=np.int32)
        state = gb.fold(state, {}, hi_slots, pane_idx=0)
        outs, act = gb.finalize(state, 100001)
        counts = outs[0]
        assert counts[0] == 32 and counts[1] == 16
        assert counts[32766] == 16 and counts[32767] == 32
        assert counts[70000] == 16 and counts[100000] == 16

    def test_cached_uint16_batches_refold_after_growth(self):
        """Sliding _dev_ring scenario: pre-padded uint16 slot arrays cached
        BEFORE a grow must refold exactly against the grown state (their
        values predate the grow, so no invalidation is needed), alongside
        new int32 uploads."""
        import jax.numpy as jnp

        from ekuiper_tpu.ops.aggspec import extract_kernel_plan
        from ekuiper_tpu.ops.groupby import DeviceGroupBy
        from ekuiper_tpu.sql.parser import parse_select

        stmt = parse_select(
            "SELECT count(*), sum(v) FROM s "
            "GROUP BY k, TUMBLINGWINDOW(ss, 10)")
        plan = extract_kernel_plan(stmt)
        mb = 32
        gb = DeviceGroupBy(plan, capacity=65536 // 2, n_panes=2,
                           micro_batch=mb)
        state = gb.init_state()
        # cached entry built while capacity allowed uint16
        slots_a = np.arange(mb, dtype=np.int32) % 7
        dev_a = {
            "v": jnp.asarray(np.full(mb, 2.0, dtype=np.float32)),
            "__valid_v": None,
        }
        s_dev_a = jnp.asarray(slots_a.astype(slot_dtype(gb.capacity)))
        assert s_dev_a.dtype == jnp.uint16
        state = gb.grow(state, 65536 * 2)  # capacity doubles past 65,536
        # post-grow upload ships int32
        slots_b = np.full(mb, 90000, dtype=np.int32)
        s_dev_b = jnp.asarray(slots_b.astype(slot_dtype(gb.capacity)))
        assert s_dev_b.dtype == jnp.int32
        dev_b = {
            "v": jnp.asarray(np.full(mb, 3.0, dtype=np.float32)),
            "__valid_v": None,
        }
        mask = np.ones(mb, dtype=np.bool_)
        state = gb.fold_masked(state, dev_a, s_dev_a, mask, 0)
        state = gb.fold_masked(state, dev_b, s_dev_b, mask, 0)
        outs, act = gb.finalize(state, 90001)
        counts, sums = outs
        assert counts[0] == 5 and counts[6] == 4  # 32 rows over slots 0..6
        assert counts[90000] == mb and sums[90000] == 3.0 * mb
        assert sums[0] == 2.0 * counts[0]


# ------------------------------------------------------------ integer keys
I64 = np.iinfo(np.int64)


class SortedIntTable(KeyTable):
    """Integer columns held to the sort-based Python path the native int64
    table replaces (the reference), whatever `clear` / `restore` reset."""

    def _native_encode_int(self, col):
        return None


def int_table(path: str) -> KeyTable:
    return SortedIntTable() if path == "sorted" else KeyTable()


def first_seen_slots(col: np.ndarray) -> np.ndarray:
    """Dense first-seen ranks of a column's values: what a fresh table's
    int path must return."""
    _, first, inverse = np.unique(col, return_index=True, return_inverse=True)
    rank = np.empty(len(first), dtype=np.int32)
    rank[np.argsort(first)] = np.arange(len(first), dtype=np.int32)
    return rank[inverse]


def assert_decodes_to(kt: KeyTable, slots: np.ndarray, col) -> None:
    assert slots.dtype == np.int32
    keys = [kt.decode(int(s)) for s in slots]
    assert keys == [int(v) for v in col]
    assert all(type(k) is int for k in keys)


INT_COLUMNS = {
    "int64_edges": np.array([0, -1, I64.min, I64.max, 0, 7, I64.min],
                            dtype=np.int64),
    "int32": np.array([5, -5, 2 ** 31 - 1, -2 ** 31, 5], dtype=np.int32),
    "int16": np.array([300, -300, 32767, -32768, 300], dtype=np.int16),
    "uint32": np.array([0, 2 ** 32 - 1, 17, 0], dtype=np.uint32),
    "uint8": np.array([0, 255, 255, 1], dtype=np.uint8),
    "uint64_small": np.array([0, 2 ** 63 - 1, 9], dtype=np.uint64),
    "strided": np.arange(40, dtype=np.int64)[::-3],
    "big_endian": np.array([1, 2, 1, 3], dtype=">i8"),
}


class TestIntKeys:
    @pytest.mark.parametrize("path", ["native_int", "sorted"])
    @pytest.mark.parametrize("name", sorted(INT_COLUMNS))
    def test_integer_column_round_trips_as_python_ints(
            self, native, path, name):
        col = INT_COLUMNS[name]
        kt = int_table(path)
        slots, grew = kt.encode_column(col)
        assert_decodes_to(kt, slots, col)
        assert not grew
        assert kt.n_keys == len(set(col.tolist()))
        assert kt.encode_rows[path] == len(col)
        assert sum(kt.encode_rows.values()) == len(col)
        if path == "native_int":  # new keys take slots as they are met
            np.testing.assert_array_equal(slots, first_seen_slots(col))
        # the same keys again, and through the other dtypes: same slots
        again, _ = kt.encode_column(col.copy())
        np.testing.assert_array_equal(again, slots)
        as_obj, _ = kt.encode_column(obj_col(col.tolist()))
        np.testing.assert_array_equal(as_obj, slots)

    @pytest.mark.parametrize("name,col", [
        ("uint64_beyond_int64",
         np.array([1, 2 ** 63, 2 ** 64 - 1, 1], dtype=np.uint64)),
        ("bool", np.array([True, False, True])),
        ("float", np.array([1.5, 2.0, 1.5])),
        ("unicode", np.array(["a", "b", "a"], dtype="U")),
        ("empty_int", np.array([], dtype=np.int64)),
    ])
    def test_other_columns_stay_on_the_sorted_path(self, native, name, col):
        kt, kp = KeyTable(), int_table("sorted")
        sn, _ = kt.encode_column(col)
        sp, _ = kp.encode_column(col)
        np.testing.assert_array_equal(sn, sp)
        assert kt.decode_all() == kp.decode_all()
        assert kt.encode_rows["sorted"] == len(col)
        assert kt.encode_rows["native_int"] == 0

    def test_one_slot_a_key_across_paths_and_batches(self, native):
        """One table, the same keys by way of the int path, the hashed
        path (object column) and the sorted path (a uint64 column with a
        value beyond int64 falls back whole): one slot each."""
        kt = KeyTable()
        s1, _ = kt.encode_column(np.array([40, 10, 40, 30], dtype=np.int64))
        assert s1.tolist() == [0, 1, 0, 2]
        s2, _ = kt.encode_column(obj_col([30, 20, 10]))  # hashed: 20 is new
        assert s2.tolist() == [2, 3, 1]
        s3, _ = kt.encode_column(
            np.array([10, 2 ** 63 + 5, 20, 50], dtype=np.uint64))  # sorted
        assert s3[0] == 1 and s3[2] == 3
        s4, _ = kt.encode_column(np.array([50, 20, 60, 40], dtype=np.int32))
        assert s4.tolist() == [int(s3[3]), 3, 6, 0]
        assert kt.decode_all()[:4] == [40, 10, 30, 20]
        assert kt.encode_rows == {"native_int": 8, "native_str": 0,
                                  "hashed": 3, "sorted": 4}
        # 2**63 + 5 is a Python int beyond int64: no int64 column can hold
        # its like, so the int table skips it and stays on
        assert kt._int_ok and kt.decode(int(s3[1])) == 2 ** 63 + 5

    def test_str_and_null_keys_beside_int_keys(self, native):
        """A table that first saw "a" / None (a null BIGINT key arrives as
        None in an object column), then an int64 batch, then None again:
        the int path stays on, its slots follow the table's."""
        kn, kp = KeyTable(), int_table("sorted")
        for kt in (kn, kp):
            s, _ = kt.encode_column(obj_col(["a", None, 7]))
            assert s.tolist() == [0, 1, 2]
            s, _ = kt.encode_column(np.array([9, 7, 9], dtype=np.int64))
            assert s.tolist() == [3, 2, 3]
            s, _ = kt.encode_column(obj_col([None, 9, "", "a", 8]))
            assert s.tolist() == [1, 3, 1, 0, 4]
            s, _ = kt.encode_column(np.array([8, 11], dtype=np.int64))
            assert s.tolist() == [4, 5]
            assert kt.decode_all() == ["a", "", 7, 9, 8, 11]
        assert kn._int_ok and kn.encode_rows["native_int"] == 5
        assert kp.encode_rows["native_int"] == 0

    @pytest.mark.parametrize("alias", [1.0, True, np.int64(1)])
    def test_a_key_the_dict_aliases_to_an_int_pins_the_python_path(
            self, native, alias):
        """`1.0 == 1` and hashes alike: Python's dict gives both one slot.
        With such a key in the history the int table cannot know, so the
        table stays on the Python path — and aliases as the dict does."""
        kt = KeyTable()
        kt.encode_column(obj_col([alias, "x"]))
        s, _ = kt.encode_column(np.array([1, 2, 1], dtype=np.int64))
        assert s.tolist() == [0, 2, 0]  # 1 is the alias's slot
        assert kt._int_ok is False and kt.encode_rows["native_int"] == 0
        assert kt.encode_rows["sorted"] == 3
        # the other way round needs no pin: the int key is in the dict,
        # the alias finds it there and adds nothing to the history
        kt2 = KeyTable()
        kt2.encode_column(np.array([2, 1], dtype=np.int64))
        s, _ = kt2.encode_column(obj_col([alias]))
        assert s.tolist() == [1] and kt2.n_keys == 2
        s, _ = kt2.encode_column(np.array([1, 3], dtype=np.int64))
        assert s.tolist() == [1, 2] and kt2._int_ok

    @pytest.mark.parametrize("path", ["native_int", "sorted"])
    def test_restore_clear_retire_round_trips(self, native, path):
        kt = int_table(path)
        kt.track_new = True
        kt.encode_column(np.array([70, 50, 70, 60], dtype=np.int64))
        saved = kt.decode_all()
        assert saved == ([70, 50, 60] if path == "native_int"
                         else [50, 60, 70])
        assert kt.drain_new_keys() == [(k, i) for i, k in enumerate(saved)]
        kr = int_table(path)
        kr.restore(saved)
        col = np.array([60, 80, 50, 70], dtype=np.int64)
        s, _ = kr.encode_column(col)
        assert s.tolist() == [saved.index(60), 3, saved.index(50),
                              saved.index(70)]
        assert kr.decode_all() == saved + [80]
        assert kr.encode_rows[path] == 4
        # clear: both sides restart in lockstep
        kr.clear()
        s, _ = kr.encode_column(np.array([50, 80], dtype=np.int64))
        assert s.tolist() == [0, 1] and kr.decode_all() == [50, 80]
        # retire leaves a hole the native table cannot hold: Python path,
        # the freed slot recycled to the next new key
        kr.retire([1], [80])
        s, _ = kr.encode_column(np.array([50, 90, 80], dtype=np.int64))
        assert s[0] == 0 and sorted(s[1:].tolist()) == [1, 2]
        assert kr._int_ok is False
        # a checkpoint with a hole restores onto the Python path too
        kh = int_table(path)
        kh.restore([5, None, 6])
        s, _ = kh.encode_column(np.array([6, 7, 5], dtype=np.int64))
        assert s.tolist() == [2, 1, 0] and kh._int_ok is False

    @pytest.mark.parametrize("own_path", [
        "native_int", "sorted", "no_native_module"])
    @pytest.mark.parametrize("every", [1, 2])
    @pytest.mark.parametrize("keys", [
        "ints", "ints_and_null", "strs", "beyond_int64"])
    def test_a_consumer_mirrored_through_keys_slice_holds_the_same_ids(
            self, native, monkeypatch, keys, every, own_path):
        """nodes_fused / nodes_sharedfold feed their own table the neutral
        table's new keys (`mirror(keys_slice(...))`): the same ids, in the
        neutral table's order whatever path serves either side — its new
        keys are first-seen (native, hashed) or sorted (no native module),
        the consumer's int path may be missing or pinned off, and a slice
        may span batches (`every` 2: a late or lagging consumer)."""
        import ekuiper_tpu.ops.keytable as ktmod

        if own_path == "no_native_module":
            monkeypatch.setattr(ktmod, "_native_keytab_module",
                                lambda api="keytab_encode": None)
        rng = np.random.default_rng(3)
        neutral, own = KeyTable(), int_table(own_path)
        for b in range(6):
            col = rng.integers(-50, 400, 500)
            if keys == "strs":
                col = obj_col([f"k{v}" for v in col])
            elif keys == "ints_and_null" and b % 2:
                col = obj_col([None if v % 7 == 0 else int(v) for v in col])
            elif keys == "beyond_int64" and b == 2:
                col = obj_col([int(v) + 2 ** 70 for v in col])
            slots, _ = neutral.encode_column(col)
            if (b + 1) % every:
                continue
            new = neutral.keys_slice(own.n_keys, neutral.n_keys)
            own.mirror(new)
            assert own.decode_all() == neutral.decode_all()
            assert [own.decode(int(s)) for s in slots[:50]] == [
                neutral.decode(int(s)) for s in slots[:50]]
        assert own.n_keys == neutral.n_keys > 0
        assert own.encode_rows["sorted"] == 0  # never renumbered
        if keys == "ints" and own_path == "native_int":
            assert own.encode_rows["native_int"] == own.n_keys
            assert neutral.encode_rows["native_int"] == 3000

    def test_mirror_picks_the_int_path_for_all_int_slices_only(self, native):
        for new, path in (([1, 2], "native_int"), ([3, ""], "hashed"),
                          ([True, 4.5], "hashed"), ([2 ** 70], "hashed"),
                          (["a", "b"], "native_str")):
            kt = KeyTable()
            assert kt.mirror(new) is False and kt.decode_all() == new
            assert kt.encode_rows[path] == len(new), (new, kt.encode_rows)
        kt = KeyTable(initial_capacity=2)
        assert kt.mirror([7, 8, 9]) is True and kt.capacity == 4

    @pytest.mark.parametrize("path", ["native_int", "sorted"])
    def test_grew_at_each_capacity_crossing(self, native, path):
        kt = int_table(path)
        kt.capacity = 16
        seen = []
        for lo in range(0, 80, 8):
            _, grew = kt.encode_column(np.arange(lo, lo + 8, dtype=np.int64))
            seen.append((kt.n_keys, kt.capacity, grew))
        assert seen == [
            (8, 16, False), (16, 16, False), (24, 32, True), (32, 32, False),
            (40, 64, True), (48, 64, False), (56, 64, False), (64, 64, False),
            (72, 128, True), (80, 128, False)]
        # one batch across two doublings still reports one `grew`
        _, grew = kt.encode_column(np.arange(80, 600, dtype=np.int64))
        assert grew and kt.capacity == 1024

    @pytest.mark.parametrize("path", ["native_int", "sorted"])
    def test_fold_switches_slot_dtype_at_65535_with_int_keys(
            self, native, path):
        """`test_fold_switches_dtype_at_growth_and_stays_exact`'s setting
        with the slots coming from integer keys: the table's `grew` drives
        the device state's growth past the uint16 boundary, counts exact."""
        from ekuiper_tpu.ops.aggspec import extract_kernel_plan
        from ekuiper_tpu.ops.groupby import DeviceGroupBy
        from ekuiper_tpu.sql.parser import parse_select

        stmt = parse_select(
            "SELECT count(*) FROM s GROUP BY k, TUMBLINGWINDOW(ss, 10)")
        gb = DeviceGroupBy(extract_kernel_plan(stmt), capacity=65536 // 2,
                           n_panes=1, micro_batch=16384)
        kt = int_table(path)
        kt.capacity = gb.capacity
        state = gb.init_state()
        rng = np.random.default_rng(1)
        sent = []
        dtypes = []
        for lo, hi in ((0, 30000), (20000, 50000), (-70000, 10 ** 12)):
            col = np.concatenate([
                np.arange(lo, lo + 16000, dtype=np.int64) * 3,
                rng.integers(lo, hi, 16768)])
            slots, grew = kt.encode_column(col)
            if grew:
                state = gb.grow(state, kt.capacity)
            dtypes.append((slot_dtype(gb.capacity), grew))
            state = gb.fold(state, {}, slots, pane_idx=0)
            sent.append(col)
        assert dtypes == [(np.uint16, False), (np.int32, True),
                          (np.int32, True)]
        outs, _ = gb.finalize(state, kt.n_keys)
        uniq, exact = np.unique(np.concatenate(sent), return_counts=True)
        got = {kt.decode(s): int(c) for s, c in enumerate(outs[0][:kt.n_keys])}
        assert got == dict(zip(uniq.tolist(), exact.tolist()))

    def test_a_failure_mid_batch_leaves_the_table_unchanged(
            self, native, monkeypatch):
        """Slot ids run out at int32: the pass fails after it has taken new
        keys in, and gives every one of them back."""
        tab = native.keytab_i64_new()
        first = np.array([5, 6, 7], dtype=np.int64)
        slots, appendix = native.keytab_encode_i64(tab, first, 0)
        assert slots.tolist() == [0, 1, 2] and appendix.tolist() == [5, 6, 7]
        many = np.arange(100, 5000, dtype=np.int64)
        many[::9] = 6  # hits between the misses
        with pytest.raises(OverflowError):
            native.keytab_encode_i64(tab, many, 2 ** 31 - 1000)
        # none of the failed pass's keys stayed: they are all new again
        slots, appendix = native.keytab_encode_i64(
            tab, np.array([7, 100, 5, 4999, 101], dtype=np.int64), 3)
        assert slots.tolist() == [2, 3, 0, 4, 5]
        assert appendix.tolist() == [100, 4999, 101]
        # pairs that contradict the table are refused whole
        with pytest.raises(ValueError):
            native.keytab_load_i64(
                tab, np.array([200, 5], dtype=np.int64),
                np.array([9, 1], dtype=np.int32))
        with pytest.raises(ValueError):
            native.keytab_load_i64(
                tab, np.array([300, 300], dtype=np.int64),
                np.array([9, 10], dtype=np.int32))
        native.keytab_load_i64(tab, np.array([200, 5], dtype=np.int64),
                               np.array([9, 0], dtype=np.int32))
        slots, appendix = native.keytab_encode_i64(
            tab, np.array([200, 300, 4999], dtype=np.int64), 50)
        assert slots.tolist() == [9, 50, 4] and appendix.tolist() == [300]
        # wrong dtypes never reach the pass (a cast could alias keys)
        for bad in (np.array([1.0]), np.array([1], dtype=np.int32),
                    [1, 2], np.zeros((2, 2), dtype=np.int64)):
            with pytest.raises(TypeError):
                native.keytab_encode_i64(tab, bad, 10)

    @pytest.mark.parametrize("faulty", ["keytab_encode_i64",
                                        "keytab_load_i64"])
    def test_a_native_fault_pins_the_python_path_once(
            self, native, monkeypatch, caplog, faulty):
        """A fault in the pass or in the catch-up's load leaves the native
        table as it was; the table takes the Python path from then on (one
        decision and one log line, not a retry — and a catch-up over the
        unmirrored history — every micro-batch)."""
        import ekuiper_tpu.ops.keytable as ktmod

        calls = []

        def fault(*_a):
            calls.append(1)
            raise MemoryError

        class Faulty:
            keytab_i64_new = staticmethod(native.keytab_i64_new)
            keytab_load_i64 = staticmethod(native.keytab_load_i64)
            keytab_encode_i64 = staticmethod(native.keytab_encode_i64)

        setattr(Faulty, faulty, staticmethod(fault))
        kt = KeyTable()
        kt.encode_column(obj_col([5, None, 6]))  # history for the catch-up
        kt.encode_column(np.array([7], dtype=np.int64))
        assert kt.encode_rows["native_int"] == 1 and kt.decode(3) == 7
        kt.encode_column(obj_col([None, 9]))  # more history to catch up
        monkeypatch.setattr(ktmod, "_native_keytab_module",
                            lambda api="keytab_encode": Faulty)
        with caplog.at_level("WARNING", logger="ekuiper_tpu"):
            s, _ = kt.encode_column(np.array([7, 8, 5], dtype=np.int64))
            assert s.tolist() == [3, 5, 0] and kt._int_ok is False
            s, _ = kt.encode_column(np.array([8, 9, 10], dtype=np.int64))
            assert s.tolist() == [5, 4, 6]
        assert len(calls) == 1 and kt.encode_rows["sorted"] == 6
        assert sum("native int encode failed" in r.getMessage()
                   for r in caplog.records) == 1
        assert kt.decode_all() == [5, "", 6, 7, 9, 8, 10]

    def test_without_the_native_module_the_sorted_path_runs(
            self, monkeypatch):
        import ekuiper_tpu.ops.keytable as ktmod

        monkeypatch.setattr(ktmod, "_native_keytab_module",
                            lambda api="keytab_encode": None)
        kt = KeyTable()
        col = np.array([3, 1, 3, 2], dtype=np.int64)
        s, _ = kt.encode_column(col)
        assert_decodes_to(kt, s, col)
        assert kt.encode_rows == {"native_int": 0, "native_str": 0,
                                  "hashed": 0, "sorted": 4}

    def test_a_million_random_keys_parity(self, native):
        rng = np.random.default_rng(2 ** 31 + 7)
        pool = rng.integers(I64.min, I64.max, 300_000, dtype=np.int64)
        kn, kp = KeyTable(), int_table("sorted")
        batches = [pool[rng.integers(0, len(pool), 250_000)]
                   for _ in range(4)]
        every = np.concatenate(batches)
        expect = first_seen_slots(every)
        got = np.concatenate([kn.encode_column(b)[0] for b in batches])
        np.testing.assert_array_equal(got, expect)
        assert kn.n_keys == int(expect.max()) + 1
        assert kn.encode_rows["native_int"] == 1_000_000
        # the Python path numbers a batch's new keys in sorted order: other
        # slot ids, the same keys under them
        ref = np.concatenate([kp.encode_column(b)[0] for b in batches])
        assert kp.n_keys == kn.n_keys
        keys_n = np.array(kn.decode_all(), dtype=np.int64)
        keys_p = np.array(kp.decode_all(), dtype=np.int64)
        np.testing.assert_array_equal(keys_n[got], every)
        np.testing.assert_array_equal(keys_p[ref], every)
