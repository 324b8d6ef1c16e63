"""Native columnar JSON decoder (native/jsoncol.cpp via io/fastjson.py):
parity with the Python decode→from_messages chain, fallback behavior, and
the SourceNode raw fast path end-to-end.
"""
import json

import numpy as np
import pytest

from ekuiper_tpu.data.batch import from_messages
from ekuiper_tpu.data.types import DataType, Field, Schema
from ekuiper_tpu.io import fastjson
from ekuiper_tpu.io.converters import JsonConverter
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


def decode_both(payloads, schema=SCHEMA):
    spec = fastjson.schema_field_spec(schema)
    assert spec is not None
    out = fastjson.decode_columns(payloads, spec)
    msgs = []
    for p in payloads:
        try:
            msgs.append(json.loads(p))
        except Exception:
            msgs.append(None)
    good = [m for m in msgs if isinstance(m, dict)]
    ref, _ = from_messages(good, [0] * len(good), schema=schema)
    return out, ref


class TestNativeParity:
    def test_basic_types(self, native):
        payloads = [
            json.dumps({"deviceId": "d1", "temperature": 21.5,
                        "count": 7, "ok": True}).encode(),
            json.dumps({"deviceId": "d2", "temperature": -3.25,
                        "count": -12, "ok": False}).encode(),
        ]
        (cols, valid, bad), ref = decode_both(payloads)
        assert not bad.any()
        np.testing.assert_array_equal(cols["deviceId"], ref.columns["deviceId"])
        np.testing.assert_allclose(cols["temperature"],
                                   ref.columns["temperature"])
        np.testing.assert_array_equal(cols["count"], ref.columns["count"])
        np.testing.assert_array_equal(cols["ok"], ref.columns["ok"])

    def test_nulls_and_missing(self, native):
        payloads = [
            b'{"deviceId": null, "temperature": 1.0}',
            b'{"count": 3}',
        ]
        (cols, valid, bad), ref = decode_both(payloads)
        assert not bad.any()
        assert not valid["deviceId"].any()
        assert valid["temperature"].tolist() == [True, False]
        assert np.isnan(cols["temperature"][1])
        assert valid["count"].tolist() == [False, True]

    def test_numeric_strings_coerce(self, native):
        payloads = [b'{"temperature": "21.5", "count": "42", "ok": "true"}']
        (cols, valid, bad), ref = decode_both(payloads)
        assert not bad.any()
        assert cols["temperature"][0] == pytest.approx(21.5)
        assert cols["count"][0] == 42
        assert cols["ok"][0]

    def test_number_to_string_matches_python(self, native):
        payloads = [b'{"deviceId": 5.0}', b'{"deviceId": 2.5}',
                    b'{"deviceId": 17}', b'{"deviceId": true}']
        (cols, valid, bad), ref = decode_both(payloads)
        assert cols["deviceId"].tolist() == ["5", "2.5", "17", "true"]
        assert cols["deviceId"].tolist() == ref.columns["deviceId"].tolist()

    def test_bad_rows_marked(self, native):
        payloads = [b'{"count": 1}', b'not json', b'{"count": {"a": 1}}',
                    b'{"count": "xyz"}']
        (cols, valid, bad), _ = decode_both(payloads)
        assert bad.tolist() == [False, True, True, True]

    def test_escapes_and_unicode(self, native):
        s = 'a"b\\c\ndé☃\U0001F600'
        payloads = [json.dumps({"deviceId": s}).encode()]
        (cols, valid, bad), _ = decode_both(payloads)
        assert cols["deviceId"][0] == s

    def test_invalid_utf8_is_bad_row_like_python(self, native):
        # json.loads raises on these bytes -> python path drops the row;
        # the native path must classify them the same (not U+FFFD-replace)
        payloads = [b'{"deviceId": "ok"}',
                    b'{"deviceId": "\xff\xfe"}',      # not UTF-8
                    b'{"deviceId": "\xed\xa0\x80"}']  # raw surrogate bytes OK
        spec = fastjson.schema_field_spec(SCHEMA)
        cols, valid, bad = fastjson.decode_columns(payloads, spec)
        assert not bad[0] and bad[1]
        assert not bad[2]  # surrogatepass keeps raw-surrogate bytes decodable
        assert cols["deviceId"][0] == "ok"
        assert cols["deviceId"][2] == "\ud800"

    def test_lone_surrogate_escape_matches_python(self, native):
        # valid JSON: json.loads keeps the lone surrogate in the string
        payloads = [b'{"deviceId": "x\\ud800y"}']
        (cols, valid, bad), ref = decode_both(payloads)
        assert not bad.any()
        assert cols["deviceId"][0] == json.loads(payloads[0])["deviceId"]
        assert cols["deviceId"][0] == ref.columns["deviceId"][0]

    def test_plus_prefixed_number_is_bad_like_python(self, native):
        payloads = [b'{"count": +5}', b'{"other": +5}', b'{"count": 5}']
        (cols, valid, bad), _ = decode_both(payloads)
        assert bad.tolist() == [True, True, False]
        assert cols["count"][2] == 5

    def test_bytearray_payloads_are_copied_safely(self, native):
        # bytearrays can be resized by another thread while the GIL-free
        # parse runs; the decoder must copy them at prefetch time
        payloads = [bytearray(b'{"deviceId": "ba", "temperature": 1.5}'),
                    b'{"deviceId": "b2", "temperature": 2.5}']
        spec = fastjson.schema_field_spec(SCHEMA)
        cols, valid, bad = fastjson.decode_columns(payloads, spec)
        assert not bad.any()
        assert cols["deviceId"].tolist() == ["ba", "b2"]
        assert cols["temperature"][0] == pytest.approx(1.5)

    def test_interning_reuses_objects(self, native):
        payloads = [b'{"deviceId": "dev_1"}'] * 100
        (cols, _, _), _ = decode_both(payloads)
        assert all(v is cols["deviceId"][0] for v in cols["deviceId"])

    def test_int64_overflow_falls_back(self, native):
        spec = fastjson.schema_field_spec(SCHEMA)
        out = fastjson.decode_columns(
            [b'{"count": 99999999999999999999999}'], spec)
        assert out is None  # Fallback -> python path handles bigints

    def test_undeclared_nested_fields_skipped(self, native):
        payloads = [
            b'{"extra": {"deep": [1, {"x": "y"}]}, "count": 5, '
            b'"more": [true, null, "s"]}'
        ]
        (cols, valid, bad), _ = decode_both(payloads)
        assert not bad.any()
        assert cols["count"][0] == 5

    def test_schema_spec_gates(self):
        assert fastjson.schema_field_spec(None) is None
        assert fastjson.schema_field_spec(
            Schema(fields=[Field("a", DataType.ARRAY)])) is None
        assert fastjson.schema_field_spec(
            Schema(fields=[Field("a", DataType.BIGINT)])) is not None


class TestSourceFastPath:
    def make_source(self, timestamp_field=""):
        src = SourceNode(
            "s", connector=type("C", (), {
                "open": lambda self, cb: None,
                "close": lambda self: None})(),
            schema=SCHEMA, converter=JsonConverter(),
            micro_batch_rows=1000, timestamp_field=timestamp_field)
        got = []
        src.broadcast = lambda item: got.append(item)
        return src, got

    def test_raw_bytes_batch_to_columns(self, native):
        src, got = self.make_source()
        assert src._fast_spec is not None
        drain = [json.dumps({"deviceId": f"d{i % 3}", "temperature": 1.0 * i,
                             "count": i, "ok": i % 2 == 0}).encode()
                 for i in range(10)]
        src.ingest(drain)
        src._flush()
        assert len(got) == 1
        cb = got[0]
        assert cb.n == 10
        assert cb.columns["deviceId"][3] == "d0"
        assert cb.columns["count"].dtype == np.int64

    def test_aligned_flush_keeps_remainder_until_linger(self, native,
                                                        mock_clock):
        """An over-threshold raw drain flushes micro_batch-aligned slices
        (the fused kernel pads every chunk to a static micro-batch shape,
        so misaligned tails would upload ~2x the bytes) and the linger
        timer drains the remainder without losing rows."""
        src = SourceNode(
            "s", connector=type("C", (), {
                "open": lambda self, cb: None,
                "close": lambda self: None})(),
            schema=SCHEMA, converter=JsonConverter(),
            micro_batch_rows=8, linger_ms=20)
        got = []
        src.broadcast = lambda item: got.append(item)
        drain = [json.dumps({"deviceId": f"d{i}", "count": i}).encode()
                 for i in range(23)]
        src.ingest(drain)
        assert [b.n for b in got] == [16]  # aligned cut, remainder pending
        mock_clock.advance(20)
        assert [b.n for b in got] == [16, 7]
        ids = [d for b in got for d in b.columns["deviceId"].tolist()]
        assert ids == [f"d{i}" for i in range(23)]  # order, no loss

    def test_bad_rows_dropped_and_counted(self, native):
        src, got = self.make_source()
        src.ingest([b'{"count": 1}', b'garbage', b'{"count": 2}'])
        src._flush()
        assert got[0].n == 2
        assert src.stats.exceptions >= 1

    def test_event_time_int64_column(self, native):
        schema = Schema(fields=[Field("deviceId", DataType.STRING),
                                Field("ts", DataType.BIGINT)])
        src = SourceNode(
            "s", connector=type("C", (), {
                "open": lambda self, cb: None,
                "close": lambda self: None})(),
            schema=schema, converter=JsonConverter(),
            micro_batch_rows=1000, timestamp_field="ts")
        got = []
        src.broadcast = lambda item: got.append(item)
        assert src._fast_spec is not None
        src.ingest([b'{"deviceId": "a", "ts": 1234}',
                    b'{"deviceId": "b"}'])  # missing ts -> dropped
        src._flush()
        assert got[0].n == 1
        assert got[0].timestamps[0] == 1234

    def test_mixed_dict_and_raw_pendings(self, native):
        src, got = self.make_source()
        src.ingest({"deviceId": "x", "count": 1})
        src.ingest([b'{"deviceId": "y", "count": 2}'])
        src._flush()
        names = [cb.columns["deviceId"][0] for cb in got]
        assert set(names) == {"x", "y"}


class TestFromMessages:
    """Columnar preprocessor parity (data/batch.py from_messages)."""

    def test_typed_bulk_and_fallback(self):
        sch = Schema(fields=[Field("a", DataType.BIGINT),
                             Field("b", DataType.FLOAT)])
        msgs = [{"a": 1, "b": 2.5}, {"a": "3", "b": "4.5"}, {"a": None}]
        cb, drop = from_messages(msgs, [0, 1, 2], schema=sch)
        assert drop == 0
        assert cb.columns["a"].tolist() == [1, 3, 0]
        assert cb.valid["a"].tolist() == [True, True, False]
        assert cb.columns["b"][1] == pytest.approx(4.5)
        assert np.isnan(cb.columns["b"][2])

    def test_uncastable_row_drops(self):
        sch = Schema(fields=[Field("a", DataType.BIGINT)])
        errs = []
        cb, drop = from_messages(
            [{"a": 1}, {"a": "zebra"}, {"a": 2}], [0, 1, 2], schema=sch,
            on_error=lambda m, n=1: errs.append(m))
        assert drop == 1
        assert cb.n == 2 and cb.columns["a"].tolist() == [1, 2]
        assert errs

    def test_big_int_fallback_to_object(self):
        sch = Schema(fields=[Field("a", DataType.BIGINT)])
        big = 99999999999999999999999
        cb, drop = from_messages([{"a": big}, {"a": 1}], [0, 1], schema=sch)
        assert drop == 0
        assert cb.columns["a"][0] == big

    def test_timestamp_extraction_paths(self):
        sch = Schema(fields=[Field("ts", DataType.BIGINT)])
        cb, drop = from_messages(
            [{"ts": 5000}, {"ts": 6000}], [1, 2], schema=sch,
            timestamp_field="ts")
        assert cb.timestamps.tolist() == [5000, 6000]
        # missing -> drop
        cb, drop = from_messages(
            [{"ts": 5000}, {}], [1, 2], schema=sch, timestamp_field="ts")
        assert drop == 1 and cb.n == 1
        # iso string timestamps take the per-value path
        sch2 = Schema(fields=[Field("ts", DataType.STRING)])
        cb, drop = from_messages(
            [{"ts": "1970-01-01T00:00:10"}], [0], schema=sch2,
            timestamp_field="ts")
        assert cb.timestamps[0] == 10_000

    def test_schemaless_inference_with_project(self):
        cb, drop = from_messages(
            [{"a": 1, "b": "x", "c": 2.0}, {"a": 2}], [0, 1],
            schema=None, project={"a", "b"})
        assert set(cb.columns) == {"a", "b"}
        assert cb.columns["a"].dtype == np.int64


class TestReviewRegressions:
    def test_strict_streams_skip_fast_path(self):
        src = SourceNode(
            "s", connector=type("C", (), {
                "open": lambda self, cb: None,
                "close": lambda self: None})(),
            schema=SCHEMA, converter=JsonConverter(),
            micro_batch_rows=1000, strict_validation=True)
        assert src._fast_spec is None

    def test_array_payload_expands_rows(self, native):
        src, got = TestSourceFastPath().make_source()
        src.ingest([b'[{"count": 1}, {"count": 2}]', b'{"count": 3}'])
        src._flush()
        total = sum(cb.n for cb in got)
        assert total == 3  # array payloads expand via the python fallback

    def test_heterogeneous_list_does_not_crash(self, native):
        src, got = TestSourceFastPath().make_source()
        src.ingest([b'{"count": 1}', {"count": 2}])  # mixed bytes + dict
        src._flush()
        assert sum(cb.n for cb in got) == 2

    def test_tuple_timestamp_preserved_in_batch_mode(self):
        from ekuiper_tpu.data.rows import Tuple as Row

        src, got = TestSourceFastPath().make_source()
        src.ingest(Row(emitter="s", message={"count": 5}, timestamp=777))
        src._flush()
        assert got[0].timestamps[0] == 777

    def test_empty_object_with_trailing_garbage_is_bad(self, native):
        src, got = TestSourceFastPath().make_source()
        src.ingest([b'{} trailing', b'{}', b'{"count": 1}'])
        src._flush()
        # '{} trailing' drops; bare '{}' is a legal all-null row
        assert sum(cb.n for cb in got) == 2

    def test_interner_many_unique_strings_stable(self, native):
        # regression: storage growth must not dangle intern keys
        payloads = [json.dumps({"deviceId": f"dev_{i}"}).encode()
                    for i in range(5000)] * 2
        spec = fastjson.schema_field_spec(SCHEMA)
        cols, valid, bad = fastjson.decode_columns(payloads, spec)
        assert not bad.any()
        got = cols["deviceId"].tolist()
        assert got[:5000] == [f"dev_{i}" for i in range(5000)]
        assert got[5000:] == got[:5000]


class TestSkippedFields:
    """A member whose key the field spec does not bear is stepped over
    (PR 37: a shared source's spec is what its rules read)."""

    WIDE = [json.dumps({"id": i, "url": "http://x/%06d/" % i + "u" * 500,
                        "extra": "e" * 300, "v": i / 2}).encode()
            for i in range(4000)]

    @staticmethod
    def _python_bytes_at_peak(fn) -> int:
        import tracemalloc

        tracemalloc.start()
        try:
            fn()
            return tracemalloc.get_traced_memory()[1]
        finally:
            tracemalloc.stop()

    def test_a_skipped_string_builds_no_object_and_is_counted(self, native):
        tally = {}
        out = []
        narrow = self._python_bytes_at_peak(lambda: out.append(
            fastjson.decode_columns(self.WIDE, (("id", 1),), 2, tally)))
        cols, valid, bad = out[0]
        assert list(cols) == ["id"] and list(valid) == ["id"]
        assert cols["id"].tolist() == list(range(4000)) and not bad.any()
        assert tally == {"kept": 4000, "skipped": 3 * 4000,
                         "bytes": sum(len(p) for p in self.WIDE)}
        # 4,000 int64 and two masks; the 800 bytes of strings a row are in
        # no Python object. With `url` in the spec they are.
        assert narrow < 300_000
        wide = self._python_bytes_at_peak(lambda: fastjson.decode_columns(
            self.WIDE, (("id", 1), ("url", 3))))
        assert wide > 2_000_000

    @pytest.mark.parametrize("shards", [1, 3, 8])
    def test_the_tally_is_the_same_for_any_shard_count(self, native, shards):
        tally = {}
        fastjson.decode_columns(self.WIDE, (("v", 0), ("extra", 3)), shards,
                                tally)
        assert tally == {"kept": 2 * 4000, "skipped": 2 * 4000,
                         "bytes": sum(len(p) for p in self.WIDE)}

    def test_every_kind_of_value_is_stepped_over(self, native):
        payloads = [
            b'{"a": "q\\"uote\\\\ \\u00e9", "id": 1, "b": {"x": [1, {"y": "}"}]},'
            b' "c": [true, null, "]"], "d": null, "e": -1.5e3, "f": false}',
            b'{"id": 2}',
            b'{"a": "unterminated, "id": 3}',
        ]
        tally = {}
        cols, valid, bad = fastjson.decode_columns(
            payloads, (("id", 1),), 1, tally)
        assert bad.tolist() == [False, False, True]
        assert cols["id"][:2].tolist() == [1, 2]
        assert tally["kept"] == 2  # row 3 broke inside its skipped member
        assert tally["skipped"] == 6 + 0 + 1

    def test_an_empty_spec_still_counts_rows_and_fields(self, native):
        tally = {}
        cols, valid, bad = fastjson.decode_columns(
            self.WIDE[:10] + [b"not json"], (), 1, tally)
        assert cols == {} and valid == {}
        assert bad.tolist() == [False] * 10 + [True]
        assert tally["kept"] == 0 and tally["skipped"] == 40

    def test_without_a_tally_the_result_is_the_three_as_before(self, native):
        out = fastjson.decode_columns(self.WIDE[:5], (("id", 1),))
        assert len(out) == 3
        assert len(native.decode(self.WIDE[:5], (("id", 1),))) == 4
