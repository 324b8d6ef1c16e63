"""Compile the served path's device programs for a described TPU v5e.

No chip is attached in the sandbox; the TPU compiler is. Each test takes the
arguments a kernel's own host wrapper would dispatch (captured at the jit
site, state shapes from `jax.eval_shape`), re-places them on a described
`v5e:2x2` device and lowers the `_impl` with plain `jax.jit` — what the
chip's compiler refuses (alignment, memory, partitioning) fails here at no
chip time. Nothing runs, so a pass says nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process may load libtpu, and every xdist worker imports this file.
"""
import os

import numpy as np
import pytest

import jax

from ekuiper_tpu.ops.aggspec import extract_kernel_plan
from ekuiper_tpu.ops.groupby import DeviceGroupBy
from ekuiper_tpu.sql.parser import parse_select

P1_SQL = ("SELECT deviceId, avg(temperature) AS a, count(*) AS c, "
          "min(temperature) AS mn, max(temperature) AS mx FROM pipe "
          "GROUP BY deviceId, TUMBLINGWINDOW(ss, 2)")
P2_SQL = ("SELECT deviceId, hll(uid) AS uniq FROM pipe "
          "GROUP BY deviceId, COUNTWINDOW(2097152)")
# the sliding percentile shape bench.py's bench_sliding_percentile drives
SLIDING_SQL = ("SELECT deviceId, percentile_approx(temperature, 0.99) AS "
               "p99, count(*) AS c FROM demo GROUP BY deviceId, "
               "SLIDINGWINDOW(ss, 10) OVER (WHEN temperature > 44.5)")
HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to JAX's persistent
    cache but cannot be read back without the chip — keep it off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


class _Captured(Exception):
    pass


def _capture(owner, attr, call):
    """Run `call()` with the jit site `owner.<attr>` replaced by a
    recorder; returns (site, the positional args the wrapper dispatched).
    The wrapper is abandoned at the dispatch — nothing executes."""
    site = getattr(owner, attr)
    box = []

    def rec(*args):
        box.append(args)
        raise _Captured

    setattr(owner, attr, rec)
    try:
        call()
    except _Captured:
        pass
    finally:
        setattr(owner, attr, site)
    assert box, f"{attr} was never dispatched"
    return site, box[0]


def _compile(site, args, sharding):
    """Lower + compile `site`'s `_impl` for the described device(s). Array
    leaves become ShapeDtypeStructs placed by `sharding` (leaves that
    already carry a sharding of the described mesh keep it); static
    arguments pass through by value."""
    def place(x):
        sh = getattr(x, "sharding", None)
        if isinstance(x, jax.ShapeDtypeStruct) and sh is not None:
            return x
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding)

    args = tuple(a if i in site._static else jax.tree.map(place, a)
                 for i, a in enumerate(args))
    return jax.jit(site._fn, **site._jit_kwargs).lower(*args).compile()


def _gb(sql, capacity, micro_batch, n_panes=1):
    plan = extract_kernel_plan(parse_select(sql))
    assert plan is not None
    return DeviceGroupBy(plan, capacity=capacity, n_panes=n_panes,
                         micro_batch=micro_batch)


def _rows(gb, n=8):
    """A few host rows for every column of the kernel's plan."""
    cols = {name: np.arange(n, dtype=np.float64) for name in gb.plan.columns}
    return cols, np.arange(n, dtype=np.int32)


# ------------------------------------------------------------- P1: tumbling
@pytest.fixture(scope="module")
def p1():
    gb = _gb(P1_SQL, capacity=16384, micro_batch=32768)
    return gb, jax.eval_shape(gb.init_state)


def test_p1_fold(p1, one_chip):
    gb, state = p1
    cols, slots = _rows(gb)
    site, args = _capture(gb, "_fold", lambda: gb.fold(state, cols, slots))
    assert args[2].shape == (32768,)  # padded to the micro-batch
    mem = _compile(site, args, one_chip).memory_analysis()
    assert mem.alias_size_in_bytes > 0  # donated state updates in place


def test_p1_finalize(p1, one_chip):
    gb, state = p1
    site, args = _capture(gb, "_finalize", lambda: gb.finalize(state, 1))
    mem = _compile(site, args, one_chip).memory_analysis()
    # ONE stacked array back: a row per aggregate plus the activity row
    # (the chip's (8, 128) tiling pads the five rows to eight)
    rows = len(gb.plan.specs) + 1
    assert rows * 16384 * 4 <= mem.output_size_in_bytes <= 8 * 16384 * 4


def test_p1_components(p1, one_chip):
    gb, state = p1
    site, args = _capture(gb, "_components",
                          lambda: gb.prefinalize_begin(state))
    _compile(site, args, one_chip)
    site, args = _capture(gb, "_reset_pane", lambda: gb.reset_pane(state, 0))
    _compile(site, args, one_chip)


# ----------------------------------------------------- P2: HLL at 1M keys
def test_p2_hll_fold_fits_and_aliases(one_chip):
    gb = _gb(P2_SQL, capacity=1 << 20, micro_batch=65536)
    state = jax.eval_shape(gb.init_state)
    cols, slots = _rows(gb)
    site, args = _capture(gb, "_fold", lambda: gb.fold(state, cols, slots))
    mem = _compile(site, args, one_chip).memory_analysis()
    state_bytes = sum(int(np.prod(s.shape)) * 4 for s in state.values())
    assert state_bytes > 1.0e9
    assert mem.argument_size_in_bytes < HBM_BYTES
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
    site, args = _capture(gb, "_finalize", lambda: gb.finalize(state, 1))
    mem = _compile(site, args, one_chip).memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BYTES


# ------------------------------------------------------------ sliding ring
def test_slidingring_advance_flip_query(one_chip):
    from ekuiper_tpu.ops.slidingring import (QUERY_ADJ, SlidingRing,
                                             ring_layout_for)

    stmt = parse_select(SLIDING_SQL)
    plan = extract_kernel_plan(stmt)
    layout = ring_layout_for(stmt.window, plan, capacity=16384,
                             budget_mb=256)
    gb = DeviceGroupBy(plan, capacity=16384, n_panes=layout.n_panes,
                       micro_batch=65536)
    ring = SlidingRing(gb, layout)
    panes = jax.eval_shape(gb.init_state)
    rs = jax.eval_shape(ring.init_state)
    calls = {
        "_advance": lambda: ring.advance(rs, panes, 0, True, 1, True),
        "_flip": lambda: ring.flip(
            rs, panes, 0, np.ones(layout.n_ring_panes, dtype=np.bool_)),
        "_query": lambda: ring.query(
            rs, panes, body_on=True, f_on=True, f_slot=0,
            adj_slots=np.zeros(QUERY_ADJ, dtype=np.int32),
            adj_weights=np.zeros(QUERY_ADJ, dtype=np.float32),
            adj_mm=np.zeros(QUERY_ADJ, dtype=np.bool_)),
    }
    for attr, call in calls.items():
        site, args = _capture(ring, attr, call)
        mem = _compile(site, args, one_chip).memory_analysis()
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < HBM_BYTES, attr


def test_slidingring_tail(one_chip):
    """The trigger's device tail at `slidingpct10k`'s size: the query's
    (16,384 x 1,026) components, one edge buffer of 16 micro-batches of
    32,768 rows. Beside the panes (3.56 GB) it may take a few copies of
    the 67 MB sketch, not more."""
    from ekuiper_tpu.ops.slidingring import (QUERY_ADJ, SlidingRing,
                                             ring_layout_for)

    stmt = parse_select(SLIDING_SQL)
    plan = extract_kernel_plan(stmt)
    layout = ring_layout_for(stmt.window, plan, capacity=16384,
                             budget_mb=256)
    gb = DeviceGroupBy(plan, capacity=16384, n_panes=layout.n_panes,
                       micro_batch=32768)
    ring = SlidingRing(gb, layout)
    assert ring.edge_rows == 524288
    body = jax.eval_shape(
        ring._query_impl, jax.eval_shape(ring.init_state),
        jax.eval_shape(gb.init_state), np.bool_(True), np.bool_(True),
        np.int32(0), np.zeros(QUERY_ADJ, np.int32),
        np.zeros(QUERY_ADJ, np.float32), np.zeros(QUERY_ADJ, np.bool_))
    assert body.shape == (16384, 1026)
    site, args = _capture(
        ring, "_tail",
        lambda: ring.tail_begin(body, ring.edge_buffers([])))
    assert args[2].shape == (524288,) and args[2].dtype == np.uint16
    compiled = _compile(site, args, one_chip)
    mem = compiled.memory_analysis()
    sketch = 16384 * 1026 * 4
    assert mem.output_size_in_bytes < sketch + (1 << 20)
    assert mem.temp_size_in_bytes < 4 * sketch, mem.temp_size_in_bytes


# ---------------------------------------------------------------- joinring
@pytest.mark.parametrize("n", [100, 3000])  # the 256 pad floor; a 4096 block
def test_joinring_match(one_chip, n):
    from ekuiper_tpu.ops.joinring import JoinRing, SideBatch

    jr = JoinRing(n_key_cols=1, band=True, lo=-5, hi=5)
    keys = [f"k{i % 17}" for i in range(n)]
    side = SideBatch(n=n, key_cols=[keys], band=list(range(n)))
    site, args = _capture(jr, "_match", lambda: jr.match(side, side))
    want = 256 if n <= 256 else 4096
    assert args[0].shape == (want,)
    _compile(site, args, one_chip)


# ----------------------------------------------------------------- segscan
def test_segscan_shift_and_sort(one_chip):
    from ekuiper_tpu.ops.segscan import SegScan

    mb = 4096
    ss = SegScan(capacity=16384)
    slots = (np.arange(mb) % 1000).astype(np.int32)
    vals = np.arange(mb, dtype=np.float32)
    site, args = _capture(ss, "_shift", lambda: ss.shift(slots, vals, mb))
    text = _compile(site, args, one_chip).as_text()
    assert "sort" in text
    site, args = _capture(ss, "_sort", lambda: ss.ranks(slots, vals, mb))
    _compile(site, args, one_chip)


# --------------------------------------------------------------- tierstore
def test_tierstore_demote_promote(p1, one_chip):
    from ekuiper_tpu.ops.tierstore import TierLayout, TierStore

    gb, state = p1
    ts = TierStore(gb, TierLayout(hot_slots=8192, demote_batch=256,
                                  scan_interval_ms=1000, min_idle_scans=2))
    slots = np.arange(4, dtype=np.int32)
    site, args = _capture(ts, "_demote", lambda: ts.demote(state, slots))
    _compile(site, args, one_chip)
    rows = np.tile(ts.init_row(), (4, 1))
    site, args = _capture(ts, "_promote",
                          lambda: ts.promote(state, rows, slots))
    mem = _compile(site, args, one_chip).memory_analysis()
    assert mem.alias_size_in_bytes > 0


# ------------------------------------------------------ four-chip sharding
@pytest.mark.parametrize("rows,keys", [(1, 4), (2, 2)])
def test_sharded_fold_step_on_four_chips(topo, rows, keys):
    """The sharded fold over the four described devices: state key-range-
    partitioned over "keys", batch split over "rows". 1x4 is the mesh
    `chip_smoke.py --chips 4` plans; 2x2 makes the row-shard merge a real
    collective."""
    from ekuiper_tpu.parallel.mesh import make_mesh
    from ekuiper_tpu.parallel.sharded import ShardedGroupBy

    mesh = make_mesh(rows=rows, keys=keys, devices=topo.devices)
    plan = extract_kernel_plan(parse_select(P1_SQL))
    sgb = ShardedGroupBy(plan, mesh, capacity=16384, micro_batch=32768)
    # nothing can be placed on a described device: the kernel's own
    # placement hook hands back the shape with the sharding it asked for
    sgb._put = lambda arr, sharding: jax.ShapeDtypeStruct(
        np.shape(arr), np.asarray(arr).dtype, sharding=sharding)
    state = sgb.init_state()
    assert state["act"].sharding.spec[1] == "keys"
    cols, slots = _rows(sgb)
    site, args = _capture(sgb, "_fold",
                          lambda: sgb.fold(state, cols, slots))
    compiled = _compile(site, args, None)
    mem = compiled.memory_analysis()
    full = sum(int(np.prod(s.shape)) * 4 for s in state.values())
    batch = 32768 * 16  # columns + masks + slots, upper bound
    # per-device argument bytes: this device's key range plus the batch
    assert mem.argument_size_in_bytes <= full // keys + batch
    assert mem.alias_size_in_bytes >= full // keys  # donated in place
    if rows > 1:
        assert "all-reduce" in compiled.as_text()
    site, args = _capture(sgb, "_finalize", lambda: sgb.finalize(state, 1))
    _compile(site, args, None)
