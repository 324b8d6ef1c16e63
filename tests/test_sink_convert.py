"""ColumnBatch → sink messages: the columnar conversion
(`ColumnBatch.to_messages`) against a literal copy of the per-cell loop it
replaced, message for message (values, value types, key order, omitted
keys), and the sink's edge fed one ColumnBatch against payloads recorded
from the code before the change."""
import datetime

import numpy as np
import pytest

from ekuiper_tpu.data.batch import ColumnBatch
from ekuiper_tpu.data.rows import Tuple
from ekuiper_tpu.io.converters import get_converter
from ekuiper_tpu.runtime.nodes_chain import EncodeNode, TransformNode
from ekuiper_tpu.runtime.nodes_sink import SinkNode, to_messages


def per_cell_reference(cb):
    """The loop `ColumnBatch.to_tuples` ran before the columnar routine,
    kept here as the reference: (message, timestamp) per row."""
    out = []
    names = cb.names()
    cols = [cb.columns[k] for k in names]
    valids = [cb.valid.get(k) for k in names]
    ts = cb.timestamps
    for i in range(cb.n):
        msg = {}
        for name, col, v in zip(names, cols, valids):
            if v is not None and not v[i]:
                continue
            val = col[i]
            if isinstance(val, np.generic):
                val = val.item()
            msg[name] = val
        out.append((msg, int(ts[i]) if ts is not None else 0))
    return out


def obj(*vals):
    col = np.empty(len(vals), dtype=np.object_)
    col[:] = list(vals)
    return col


def _keys(n):
    return obj(*[f"dev{i}" for i in range(n)])


def _mask(n, false_at):
    m = np.ones(n, dtype=np.bool_)
    m[list(false_at)] = False
    return m


N = 7
BATCHES = {
    "key_and_numeric": lambda: ColumnBatch(
        n=N, emitter="s", timestamps=np.arange(N, dtype=np.int64) * 1000,
        columns={"deviceId": _keys(N),
                 "c": np.arange(N, dtype=np.int64) * 3 - 4,
                 "a": np.linspace(0.1, 9.7, N, dtype=np.float32),
                 "d": np.linspace(-1e12, 1e-12, N, dtype=np.float64),
                 "ok": np.arange(N) % 2 == 0}),
    "emit_shape_tumbling": lambda: ColumnBatch(
        n=N, timestamps=np.full(N, 5000, dtype=np.int64),
        columns={"deviceId": _keys(N),
                 "a": np.linspace(20, 30, N, dtype=np.float32),
                 "c": np.full(N, 100, dtype=np.int64),
                 "mn": np.linspace(1, 2, N, dtype=np.float32),
                 "mx": np.linspace(3, 4, N, dtype=np.float32)}),
    "emit_shape_hll": lambda: ColumnBatch(
        n=N, columns={"deviceId": _keys(N),
                      "uniq": np.arange(N, dtype=np.int64)}),
    "object_none_holes": lambda: ColumnBatch(
        n=4, timestamps=np.arange(4, dtype=np.int64),
        columns={"deviceId": _keys(4), "avg": obj(1.5, None, 2.5, None)}),
    "object_np_generic": lambda: ColumnBatch(
        n=4, columns={"k": obj(np.str_("a"), "b", np.int64(3), None),
                      "v": obj(np.float32(1.5), 2.5, np.bool_(True), 7)}),
    "object_nested": lambda: ColumnBatch(
        n=3, columns={"arr": obj([1, 2], {"x": 1}, ("t",)),
                      "n": np.arange(3, dtype=np.int64)}),
    "unicode_and_datetime_dtypes": lambda: ColumnBatch(
        n=2, columns={"u": np.array(["ab", "c"]),
                      "t": np.array(["2020-01-01", "2021-06-01T01:02:03.004"],
                                    dtype="datetime64[ms]"),
                      "i32": np.array([1, -2], dtype=np.int32),
                      "u8": np.array([0, 255], dtype=np.uint8)}),
    "float_nan_inf": lambda: ColumnBatch(
        n=3, columns={"f": np.array([np.nan, np.inf, -0.0], dtype=np.float32)}),
    "valid_partly_false": lambda: ColumnBatch(
        n=N, timestamps=np.arange(N, dtype=np.int64),
        columns={"deviceId": _keys(N),
                 "a": np.linspace(0, 1, N, dtype=np.float32),
                 "c": np.arange(N, dtype=np.int64)},
        valid={"a": _mask(N, [1, 4]), "c": _mask(N, [4, 6])}),
    "valid_first_column_false": lambda: ColumnBatch(
        n=3, columns={"k": _keys(3), "v": np.arange(3, dtype=np.int64)},
        valid={"k": _mask(3, [0])}),
    "valid_all_true": lambda: ColumnBatch(
        n=N, columns={"deviceId": _keys(N), "c": np.arange(N, dtype=np.int64)},
        valid={"c": _mask(N, [])}),
    "valid_all_false": lambda: ColumnBatch(
        n=N, columns={"deviceId": _keys(N), "c": np.arange(N, dtype=np.int64)},
        valid={"c": np.zeros(N, dtype=np.bool_)}),
    "valid_every_column_all_false": lambda: ColumnBatch(
        n=2, columns={"c": np.arange(2, dtype=np.int64)},
        valid={"c": np.zeros(2, dtype=np.bool_)}),
    "valid_for_absent_column": lambda: ColumnBatch(
        n=2, columns={"c": np.arange(2, dtype=np.int64)},
        valid={"gone": np.zeros(2, dtype=np.bool_)}),
    "n_zero": lambda: ColumnBatch(n=0, emitter="s"),
    "n_zero_with_columns": lambda: ColumnBatch(
        n=0, columns={"k": obj(), "c": np.zeros(0, dtype=np.int64)},
        timestamps=np.zeros(0, dtype=np.int64)),
    "n_one": lambda: ColumnBatch(
        n=1, timestamps=np.array([42], dtype=np.int64),
        columns={"deviceId": obj("only"), "c": np.array([9], dtype=np.int64)}),
    "no_columns": lambda: ColumnBatch(
        n=3, timestamps=np.arange(3, dtype=np.int64)),
    "timestamps_none": lambda: ColumnBatch(
        n=3, emitter="e",
        columns={"k": _keys(3), "v": np.arange(3, dtype=np.float32)}),
}


def _same_nan(a, b):
    return isinstance(a, float) and isinstance(b, float) and a != a and b != b


def assert_same_messages(got, want):
    assert type(got) is list and len(got) == len(want)
    for g, w in zip(got, want):
        assert type(g) is dict
        assert list(g) == list(w)  # the same keys, omitted ones too, in order
        for k in w:
            assert type(g[k]) is type(w[k]), (k, g[k], w[k])
            assert g[k] == w[k] or _same_nan(g[k], w[k]), (k, g[k], w[k])


@pytest.mark.parametrize("case", sorted(BATCHES))
def test_to_messages_matches_the_per_cell_loop(case):
    cb = BATCHES[case]()
    want = [m for m, _ in per_cell_reference(cb)]
    assert_same_messages(cb.to_messages(), want)
    # the sink's edge goes through the same routine
    assert_same_messages(to_messages(cb), want)


@pytest.mark.parametrize("case", sorted(BATCHES))
def test_to_tuples_is_built_from_the_same_routine(case):
    cb = BATCHES[case]()
    ref = per_cell_reference(cb)
    rows = cb.to_tuples()
    assert all(type(r) is Tuple for r in rows)
    assert_same_messages([r.message for r in rows], [m for m, _ in ref])
    assert [r.timestamp for r in rows] == [t for _, t in ref]
    assert all(type(r.timestamp) is int for r in rows)
    assert all(r.emitter == cb.emitter for r in rows)
    assert all(r.cal_cols == {} and r.metadata == {} for r in rows)


def test_rows_are_distinct_dicts():
    cb = BATCHES["no_columns"]()
    msgs = cb.to_messages()
    msgs[0]["x"] = 1
    assert msgs[1] == {}
    msgs = BATCHES["n_one"]().to_messages()
    assert msgs == [{"deviceId": "only", "c": 9}]


def test_value_types_are_plain_python():
    msgs = BATCHES["unicode_and_datetime_dtypes"]().to_messages()
    assert msgs[1] == {
        "u": "c", "t": datetime.datetime(2021, 6, 1, 1, 2, 3, 4000),
        "i32": -2, "u8": 255}
    assert [type(v) for v in msgs[1].values()] == [
        str, datetime.datetime, int, int]


def test_to_messages_reads_no_timestamps_and_builds_no_tuples(monkeypatch):
    import ekuiper_tpu.data.batch as batch_mod

    class Unreadable:
        def __getattribute__(self, name):
            raise AssertionError("timestamps touched")

    def no_tuples(*a, **kw):
        raise AssertionError("Tuple built")

    cb = BATCHES["key_and_numeric"]()
    want = [m for m, _ in per_cell_reference(cb)]
    cb.timestamps = Unreadable()
    monkeypatch.setattr(batch_mod, "Tuple", no_tuples)
    assert_same_messages(to_messages(cb), want)


# ------------------------------------------------------------ the sink's edge
class ListSink:
    accepts_batches = False

    def __init__(self):
        self.got = []

    def connect(self):
        pass

    def collect(self, item):
        self.got.append(item)

    def close(self):
        pass


class BatchSink(ListSink):
    accepts_batches = True


class Collect:
    def __init__(self):
        self.items = []

    def put(self, item, from_name=None):
        self.items.append(item)


def window():
    """One window's emission: a NULL aggregate as a None hole, one column
    with a partly false valid mask."""
    return ColumnBatch(
        n=3, timestamps=np.full(3, 1000, dtype=np.int64),
        columns={"deviceId": obj("a", "b", "c"),
                 "c": np.array([1, 2, 3], dtype=np.int64),
                 "avg": obj(1.5, None, 2.5),
                 "mx": np.array([0.5, 1.5, 2.5], dtype=np.float32)},
        valid={"mx": np.array([True, False, True])})


def one_row():
    return ColumnBatch(n=1, columns={"deviceId": obj("a"),
                                     "c": np.array([1], dtype=np.int64)})


def empty():
    return ColumnBatch(n=0, columns={})


ROWS = [{"deviceId": "a", "c": 1, "avg": 1.5, "mx": 0.5},
        {"deviceId": "b", "c": 2, "avg": None},
        {"deviceId": "c", "c": 3, "avg": 2.5, "mx": 2.5}]
PICKED = [{"deviceId": "a", "mx": 0.5}, {"deviceId": "b", "mx": None},
          {"deviceId": "c", "mx": 2.5}]
WITHOUT = [{"deviceId": "a", "avg": 1.5, "mx": 0.5},
           {"deviceId": "b", "avg": None},
           {"deviceId": "c", "avg": 2.5, "mx": 2.5}]
RENDERED = ["a=1/0.5", "b=2/", "c=3/2.5"]
TEMPLATE = "{{.deviceId}}={{.c}}/{{.mx}}"

# (transform options, batch) -> what the sink collected / the transform node
# emitted at the parent commit, call by call
EDGE_CASES = {
    "plain": ({}, window, [ROWS]),
    "fields": ({"fields": ["deviceId", "mx"]}, window, [PICKED]),
    "exclude_fields": ({"exclude_fields": ["c"]}, window, [WITHOUT]),
    "fields_and_exclude": (
        {"fields": ["deviceId", "mx"], "exclude_fields": ["mx"]}, window,
        [[{"deviceId": "a"}, {"deviceId": "b"}, {"deviceId": "c"}]]),
    "data_template": ({"data_template": TEMPLATE}, window, [RENDERED]),
    "send_single": ({"send_single": True}, window, ROWS),
    "send_single_fields": (
        {"send_single": True, "fields": ["deviceId", "mx"]}, window, PICKED),
    "send_single_template": (
        {"send_single": True, "data_template": TEMPLATE}, window, RENDERED),
    "one_row_is_a_dict": ({}, one_row, [{"deviceId": "a", "c": 1}]),
    "one_row_send_single": (
        {"send_single": True}, one_row, [{"deviceId": "a", "c": 1}]),
    "one_row_template": (
        {"data_template": "{{.deviceId}}"}, one_row, ["a"]),
    "empty_is_an_empty_list": ({}, empty, [[]]),
    "empty_omitted": ({"omit_if_empty": True}, empty, []),
    "empty_send_single": ({"send_single": True}, empty, []),
    "omit_if_empty_with_rows": ({"omit_if_empty": True}, window, [ROWS]),
}


@pytest.mark.parametrize("case", sorted(EDGE_CASES))
def test_sink_node_delivers_what_it_delivered_before(case):
    opts, make, want = EDGE_CASES[case]
    sink = ListSink()
    node = SinkNode("snk", sink, **opts)
    node.process(make())
    assert sink.got == want
    assert node.results == want
    for payload in sink.got:
        for m in payload if isinstance(payload, list) else [payload]:
            assert type(m) in (dict, str)
            if type(m) is dict:
                assert all(type(v) in (str, int, float, type(None))
                           for v in m.values())


@pytest.mark.parametrize("case", sorted(EDGE_CASES))
def test_transform_node_emits_what_it_emitted_before(case):
    opts, make, want = EDGE_CASES[case]
    node = TransformNode("tr", **opts)
    out = Collect()
    node.outputs.append(out)
    node.process(make())
    assert out.items == want


def test_key_order_survives_the_sink():
    sink = ListSink()
    SinkNode("snk", sink).process(window())
    assert [list(m) for m in sink.got[0]] == [
        ["deviceId", "c", "avg", "mx"], ["deviceId", "c", "avg"],
        ["deviceId", "c", "avg", "mx"]]


def test_batch_sink_receives_the_batch_itself():
    sink = BatchSink()
    cb = window()
    SinkNode("snk", sink).process(cb)
    assert len(sink.got) == 1 and sink.got[0] is cb


@pytest.mark.parametrize("opts,want", [
    ({"fields": ["deviceId", "mx"]}, [PICKED]),
    ({"exclude_fields": ["c"]}, [WITHOUT]),
    ({"data_template": TEMPLATE}, [RENDERED]),
    ({"send_single": True}, ROWS),
], ids=["fields", "exclude_fields", "data_template", "send_single"])
def test_batch_sink_with_a_transform_gets_messages(opts, want):
    sink = BatchSink()
    SinkNode("snk", sink, **opts).process(window())
    assert sink.got == want


def test_sink_passes_a_dict_through_untouched():
    sink = ListSink()
    msg = {"a": 1}
    SinkNode("snk", sink).process(msg)
    assert sink.got == [msg] and sink.got[0] is msg


@pytest.mark.parametrize("make,want", [
    (window, b'[{"deviceId":"a","c":1,"avg":1.5,"mx":0.5},'
             b'{"deviceId":"b","c":2,"avg":null},'
             b'{"deviceId":"c","c":3,"avg":2.5,"mx":2.5}]'),
    (one_row, b'{"deviceId":"a","c":1}'),
    (empty, b"[]"),
], ids=["window", "one_row", "empty"])
def test_encode_node_encodes_what_it_encoded_before(make, want):
    node = EncodeNode("enc", get_converter("json"))
    out = Collect()
    node.outputs.append(out)
    node.process(make())
    assert [b.replace(b" ", b"") for b in out.items] == [want]


@pytest.mark.parametrize("module,cls", [
    ("ekuiper_tpu.io.influx_io", "InfluxSink"),
    ("ekuiper_tpu.io.edgex_io", "EdgexSink"),
])
def test_connectors_flatten_a_batch_through_the_same_routine(module, cls,
                                                             monkeypatch):
    """The two connectors that take a ColumnBatch themselves call
    `to_messages`, not a conversion of their own."""
    import importlib

    calls = []
    real = ColumnBatch.to_messages

    def spy(self):
        calls.append(self)
        return real(self)

    monkeypatch.setattr(ColumnBatch, "to_messages", spy)
    monkeypatch.setattr(
        ColumnBatch, "to_tuples",
        lambda self: pytest.fail("connector built Tuples"))
    mod = importlib.import_module(module)
    sink = getattr(mod, cls)()
    cb = window()
    if cls == "InfluxSink":
        sent = []
        monkeypatch.setattr(mod, "to_lines",
                            lambda rows, *a, **kw: sent.append(rows) or b"")
        sink.collect(cb)
        assert sent == [ROWS]
    else:
        assert sink._rows(cb) == ROWS
    assert calls == [cb]
