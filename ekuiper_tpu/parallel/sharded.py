"""Sharded GROUP BY aggregation — the multi-chip form of ops/groupby.py.

SPMD layout over a Mesh(("rows", "keys")):

- event batch columns + slot ids + validity masks: sharded over "rows"
  (data parallel);
- per-key partial state (n_panes, capacity, k): capacity axis sharded over
  "keys" — each device owns capacity/K contiguous slots;
- fold (shard_map): every device folds ITS row shard into a local partial
  for ITS key range (rows whose slot falls outside the local range mask
  out), then one `psum`/`pmin`/`pmax` per state component merges the
  row-shards. No gather of raw events ever happens — only the
  (capacity/K, k) partials move, and only across the rows axis;
- finalize: inherited from DeviceGroupBy (pane-mask merge + final values);
  XLA all_gathers the sharded capacity axis only at window triggers.

ShardedGroupBy subclasses DeviceGroupBy so pane semantics (hopping
windows), per-column validity masks, grow(), checkpointing, and the
finalize math are all the *same code* as the single-chip path — parity by
construction. Only state placement and the fold step differ.

This mirrors the scaling-book recipe: pick the mesh, shard the state/batch,
let XLA insert the collectives, keep them on ICI.

Reference analogue: the process-level scale-out of
internal/topo/subtopo_pool.go:34 (N rules sharing source fan-out) becomes a
device mesh here; the cross-worker merge the reference never needs (each Go
rule is single-process) is the psum over "rows".
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..ops.aggspec import KernelPlan, WIDE_COMPONENTS
from ..ops.groupby import DeviceGroupBy, _INIT, _NO_STAGE


class ShardedGroupBy(DeviceGroupBy):
    """Multi-chip group-by aggregation over a ("rows", "keys") mesh.

    State layout matches DeviceGroupBy: {comp: (n_panes, capacity, k[, R])},
    act (n_panes, capacity), with capacity sharded over "keys". Batch
    layout: cols/valid/slots (N,) sharded over "rows".
    """

    watch_prefix = "sharded"

    # finalize runs collective gathers across the mesh; the pre-issued
    # emit pipeline (ops/prefinalize.py) is single-chip only for now
    supports_prefinalize = False

    def __init__(
        self, plan: KernelPlan, mesh, capacity: int = 16384,
        n_panes: int = 1, micro_batch: int = 4096,
        track_touch: bool = False,
    ) -> None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.mesh = mesh
        self.n_keys_shards = int(mesh.shape["keys"])
        self.n_row_shards = int(mesh.shape["rows"])
        # round capacity / micro_batch up to even divisibility across shards
        K, R = self.n_keys_shards, self.n_row_shards
        capacity = -(-int(capacity) // K) * K
        micro_batch = -(-int(micro_batch) // R) * R
        super().__init__(plan, capacity=capacity, n_panes=n_panes,
                         micro_batch=micro_batch, track_touch=track_touch)
        self.mesh_tag = f"{R}x{K}"
        self.state_sharding = {
            comp: NamedSharding(
                mesh,
                P(None, "keys", None, None) if comp in WIDE_COMPONENTS
                else P(None, "keys", None),
            )
            for comp in self.comp_specs
        }
        self.state_sharding["act"] = NamedSharding(mesh, P(None, "keys"))
        if track_touch:
            # tiered-state recency column (ops/tierstore.py): (capacity,)
            # uint32, key axis 0 — same key-range partitioning as the
            # pane state, so a later sharded tier reads local slices
            self.state_sharding["touch"] = NamedSharding(mesh, P("keys"))
        self.batch_sharding = NamedSharding(mesh, P("rows"))
        self.scalar_sharding = NamedSharding(mesh, P())
        # meshes spanning processes can't device_put host data onto
        # non-addressable devices; global arrays assemble from each
        # process's local slice instead (docs/DISTRIBUTED.md)
        import jax

        self.multiprocess = any(
            d.process_index != jax.process_index()
            for d in np.asarray(mesh.devices).flat)
        # the zero-copy ingest-prep upload stage (runtime/ingest.py) can
        # pre-place batch columns/slots with this kernel's row sharding —
        # single-process meshes only (multi-host data arrives as local
        # slices through _put)
        self.accepts_device_inputs = not self.multiprocess
        self._fold = self._build_fold()  # replaces the single-chip jit
        # per-row pane-vector variant (event-time multi-bucket batches);
        # built lazily — most rules never need it
        self._fold_vec = None
        self._all_true = None  # cached device ones-mask (common no-null case)
        # per-shard observability (kuiper_shard_* families): rows folded
        # into each shard's key range, counted host-side off the slot
        # vector (one bincount per batch), plus a key-occupancy hint the
        # driving node refreshes from its KeyTable
        self.shard_rows = np.zeros(K, dtype=np.int64)
        self.n_keys_hint = 0
        from ..utils.rulelog import current_rule

        _registry.register(self, current_rule())
        # retired-kernel rollup (the devwatch retire_dead discipline):
        # when this kernel is collected — rule dropped, or replaced by a
        # restore onto a different mesh — its accrued per-shard rows fold
        # into the module counters so kuiper_shard_rows_total stays
        # monotonic across 8->1->8 restore cycles. The finalize captures
        # shard_rows itself (note_rows mutates it in place), so the
        # callback always sees the final counts.
        import weakref as _weakref

        _weakref.finalize(
            self, _note_retired, _gen[0], current_rule(), self.shard_rows)

    def _put(self, arr, sharding):
        """Host→mesh placement that also works when the mesh spans
        processes: each process contributes its local slice of `arr`
        (callers pass process-local data in multi-host mode)."""
        import jax

        if self.multiprocess:
            return jax.make_array_from_process_local_data(sharding, arr)
        return jax.device_put(arr, sharding)

    # ------------------------------------------------------------------ state
    def init_state(self) -> Dict[str, Any]:
        import jax

        return {
            comp: self._put(arr, self.state_sharding[comp])
            for comp, arr in super().init_state().items()
        }

    def grow(self, state: Dict[str, Any], new_capacity: int) -> Dict[str, Any]:
        """Double the key capacity, preserving partials. The host roundtrip
        re-distributes slots to their new owner shard (global slot s lives on
        shard s // (capacity/K), so ranges shift when capacity grows)."""
        import jax

        new_capacity = -(-int(new_capacity) // self.n_keys_shards) * self.n_keys_shards
        out: Dict[str, Any] = {}
        for comp, arr in state.items():
            np_arr = np.asarray(arr)
            # the touch column is (capacity,), not pane-scoped — key axis
            # 0 there, axis 1 everywhere else; its uint32 dtype rides
            # np_arr.dtype (ops/groupby.py grew the same special case)
            key_axis = 0 if comp == "touch" else 1
            pad_shape = list(np_arr.shape)
            pad_shape[key_axis] = new_capacity - np_arr.shape[key_axis]
            pad = np.full(pad_shape, _INIT[comp], dtype=np_arr.dtype)
            out[comp] = self._put(
                np.concatenate([np_arr, pad], axis=key_axis),
                self.state_sharding[comp]
            )
        self.capacity = new_capacity
        return out

    def state_from_host(self, host: Dict[str, np.ndarray]) -> Dict[str, Any]:
        """Host partials -> mesh-sharded device state. Mesh-size-change
        tolerant: a checkpoint taken on a different shard count (incl.
        the single-chip kernel, K=1) may carry a capacity that does not
        divide this mesh's K — pad the key axis up to divisibility with
        each component's identity (the extra slots are unassigned; the
        KeyTable's dense slot ids are placement-independent, so every
        restored slot keeps its key). The uint32 touch column keeps its
        dtype (np.asarray preserves it; host_from_partials already
        typed it)."""
        import jax

        K = self.n_keys_shards
        out: Dict[str, Any] = {}
        cap = None
        for k, v in host.items():
            np_arr = np.asarray(v)
            key_axis = 0 if k == "touch" else 1
            c = np_arr.shape[key_axis]
            rounded = -(-int(c) // K) * K
            if rounded != c:
                pad_shape = list(np_arr.shape)
                pad_shape[key_axis] = rounded - c
                pad = np.full(pad_shape, _INIT.get(k, 0.0),
                              dtype=np_arr.dtype)
                np_arr = np.concatenate([np_arr, pad], axis=key_axis)
            cap = rounded if cap is None else max(cap, rounded)
            sharding = self.state_sharding.get(k)
            if sharding is None:
                # a checkpoint component this kernel form doesn't track
                # (host_from_partials should have dropped it) — replicate
                # rather than crash the restore
                sharding = self.scalar_sharding
            out[k] = self._put(np_arr, sharding)
        if cap is not None:
            self.capacity = int(cap)
        return out

    # ------------------------------------------------------------------- fold
    def _build_fold(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        comp_specs = self.comp_specs
        plan = self.plan

        def local_fold(state, cols, slots, row_valid, pane_idx):
            """Runs per device: fold my row shard into my key range, then
            merge partials across the rows axis with one collective per
            state component."""
            cap_per_shard = state["act"].shape[1]
            kidx = jax.lax.axis_index("keys")
            offset = (kidx * cap_per_shard).astype(slots.dtype)
            local = slots - offset
            in_range = jnp.logical_and(local >= 0, local < cap_per_shard)
            base = jnp.logical_and(row_valid, in_range)
            if plan.filter is not None:
                base = jnp.logical_and(base, plan.filter(cols))
            local = jnp.clip(local, 0, cap_per_shard - 1)

            # same per-spec value/mask derivation as the single-chip fold:
            # per-column validity masks compose into per-spec masks
            per_spec: List[Tuple[Any, Any]] = []
            for spec in plan.specs:
                if spec.arg is None:
                    v = jnp.ones_like(base, dtype=jnp.float32)
                    m = base
                else:
                    v = spec.arg(cols).astype(jnp.float32)
                    m = base
                    for col in spec.arg.columns:
                        vm = cols.get("__valid_" + col)
                        if vm is not None:
                            m = jnp.logical_and(m, vm)
                    m = jnp.logical_and(m, jnp.logical_not(jnp.isnan(v)))
                if spec.filter is not None:
                    m = jnp.logical_and(m, spec.filter(cols))
                per_spec.append((v, m))

            out = {}
            act_add = jnp.zeros((cap_per_shard,), jnp.float32).at[local].add(
                base.astype(jnp.float32)
            )
            out["act"] = state["act"].at[pane_idx].add(
                jax.lax.psum(act_add, "rows")
            )
            if "touch" in state:
                # tier recency signal (ops/tierstore.py): per-slot touched-
                # row count, key axis sharded like the pane state — each
                # device's row shard contributes, one psum merges
                t_add = jnp.zeros((cap_per_shard,), jnp.uint32).at[local].add(
                    base.astype(jnp.uint32))
                out["touch"] = state["touch"] + jax.lax.psum(t_add, "rows")
            for comp, spec_idxs in comp_specs.items():
                arr = state[comp]
                parts = []
                for si in spec_idxs:
                    v, m = per_spec[si]
                    mf = m.astype(jnp.float32)
                    if comp == "n":
                        parts.append(
                            jnp.zeros((cap_per_shard,), jnp.float32)
                            .at[local].add(mf)
                        )
                    elif comp == "s1":
                        parts.append(
                            jnp.zeros((cap_per_shard,), jnp.float32)
                            .at[local].add(jnp.where(m, v, 0.0))
                        )
                    elif comp == "s2":
                        parts.append(
                            jnp.zeros((cap_per_shard,), jnp.float32)
                            .at[local].add(jnp.where(m, v * v, 0.0))
                        )
                    elif comp == "mn":
                        parts.append(
                            jnp.full((cap_per_shard,), jnp.inf, jnp.float32)
                            .at[local].min(jnp.where(m, v, jnp.inf))
                        )
                    elif comp == "mx":
                        parts.append(
                            jnp.full((cap_per_shard,), -jnp.inf, jnp.float32)
                            .at[local].max(jnp.where(m, v, -jnp.inf))
                        )
                    elif comp == "hll":
                        from ..ops.sketches import hll_parts

                        reg, rho = hll_parts(v)
                        parts.append(
                            jnp.zeros((cap_per_shard, arr.shape[-1]), jnp.float32)
                            .at[local, reg].max(jnp.where(m, rho, 0.0))
                        )
                    elif comp == "hist":
                        from ..ops.sketches import hist_bin

                        b = hist_bin(v)
                        parts.append(
                            jnp.zeros((cap_per_shard, arr.shape[-1]), jnp.float32)
                            .at[local, b].add(mf)
                        )
                stacked = jnp.stack(parts, axis=1)  # (cap, k[, R])
                if comp in ("n", "s1", "s2", "hist"):
                    merged = jax.lax.psum(stacked, "rows")
                    out[comp] = arr.at[pane_idx].add(merged)
                elif comp == "mn":
                    merged = jax.lax.pmin(stacked, "rows")
                    out[comp] = arr.at[pane_idx].min(merged)
                else:  # mx, hll merge by max
                    merged = jax.lax.pmax(stacked, "rows")
                    out[comp] = arr.at[pane_idx].max(merged)
            return out

        state_specs = {
            comp: P(None, "keys", None, None) if comp in WIDE_COMPONENTS
            else P(None, "keys", None)
            for comp in comp_specs
        }
        state_specs["act"] = P(None, "keys")
        if self.track_touch:
            state_specs["touch"] = P("keys")
        cols_specs: Dict[str, Any] = {}
        for name in plan.columns:
            cols_specs[name] = P("rows")
            cols_specs["__valid_" + name] = P("rows")

        def step(state, cols, slots, row_valid, pane_idx):
            return shard_map(
                local_fold,
                mesh=self.mesh,
                in_specs=(state_specs, cols_specs, P("rows"), P("rows"), P()),
                out_specs=state_specs,
            )(state, cols, slots, row_valid, pane_idx)

        from ..runtime.aotcache import aot_jit

        return aot_jit(step, op=self._watch_op("fold_step"),
                           donate_argnums=(0,))

    def _build_fold_vec(self):
        """Per-row pane-vector fold (event-time multi-bucket batches under
        the mesh): each device scatters its row shard into (n_panes,
        local_capacity) partials, one collective per component merges the
        rows axis, and the full-shape merge folds into the state."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        comp_specs = self.comp_specs
        plan = self.plan
        n_panes = self.n_panes

        def local_fold(state, cols, slots, row_valid, pane_vec):
            cap_per_shard = state["act"].shape[1]
            kidx = jax.lax.axis_index("keys")
            offset = (kidx * cap_per_shard).astype(slots.dtype)
            local = slots - offset
            in_range = jnp.logical_and(local >= 0, local < cap_per_shard)
            base = jnp.logical_and(row_valid, in_range)
            if plan.filter is not None:
                base = jnp.logical_and(base, plan.filter(cols))
            local = jnp.clip(local, 0, cap_per_shard - 1)
            pv = pane_vec.astype(jnp.int32)

            per_spec: List[Tuple[Any, Any]] = []
            for spec in plan.specs:
                if spec.arg is None:
                    v = jnp.ones_like(base, dtype=jnp.float32)
                    m = base
                else:
                    v = spec.arg(cols).astype(jnp.float32)
                    m = base
                    for col in spec.arg.columns:
                        vm = cols.get("__valid_" + col)
                        if vm is not None:
                            m = jnp.logical_and(m, vm)
                    m = jnp.logical_and(m, jnp.logical_not(jnp.isnan(v)))
                if spec.filter is not None:
                    m = jnp.logical_and(m, spec.filter(cols))
                per_spec.append((v, m))

            out = {}
            act_add = (jnp.zeros((n_panes, cap_per_shard), jnp.float32)
                       .at[pv, local].add(base.astype(jnp.float32)))
            out["act"] = state["act"] + jax.lax.psum(act_add, "rows")
            if "touch" in state:
                t_add = jnp.zeros((cap_per_shard,), jnp.uint32).at[local].add(
                    base.astype(jnp.uint32))
                out["touch"] = state["touch"] + jax.lax.psum(t_add, "rows")
            for comp, spec_idxs in comp_specs.items():
                arr = state[comp]
                parts = []
                for si in spec_idxs:
                    v, m = per_spec[si]
                    mf = m.astype(jnp.float32)
                    if comp == "n":
                        parts.append(
                            jnp.zeros((n_panes, cap_per_shard), jnp.float32)
                            .at[pv, local].add(mf))
                    elif comp == "s1":
                        parts.append(
                            jnp.zeros((n_panes, cap_per_shard), jnp.float32)
                            .at[pv, local].add(jnp.where(m, v, 0.0)))
                    elif comp == "s2":
                        parts.append(
                            jnp.zeros((n_panes, cap_per_shard), jnp.float32)
                            .at[pv, local].add(jnp.where(m, v * v, 0.0)))
                    elif comp == "mn":
                        parts.append(
                            jnp.full((n_panes, cap_per_shard), jnp.inf,
                                     jnp.float32)
                            .at[pv, local].min(jnp.where(m, v, jnp.inf)))
                    elif comp == "mx":
                        parts.append(
                            jnp.full((n_panes, cap_per_shard), -jnp.inf,
                                     jnp.float32)
                            .at[pv, local].max(jnp.where(m, v, -jnp.inf)))
                    elif comp == "hll":
                        from ..ops.sketches import hll_parts

                        reg, rho = hll_parts(v)
                        parts.append(
                            jnp.full((n_panes, cap_per_shard, arr.shape[-1]),
                                     -jnp.inf, jnp.float32)
                            .at[pv, local, reg].max(jnp.where(m, rho, 0.0)))
                    elif comp == "hist":
                        from ..ops.sketches import hist_bin

                        b = hist_bin(v)
                        parts.append(
                            jnp.zeros((n_panes, cap_per_shard, arr.shape[-1]),
                                      jnp.float32)
                            .at[pv, local, b].add(mf))
                stacked = jnp.stack(parts, axis=2)  # (P, cap, k[, R])
                if comp in ("n", "s1", "s2", "hist"):
                    out[comp] = arr + jax.lax.psum(stacked, "rows")
                elif comp == "mn":
                    out[comp] = jnp.minimum(
                        arr, jax.lax.pmin(stacked, "rows"))
                else:  # mx, hll merge by max (-inf fill is identity)
                    out[comp] = jnp.maximum(
                        arr, jax.lax.pmax(stacked, "rows"))
            return out

        state_specs = {
            comp: P(None, "keys", None, None) if comp in WIDE_COMPONENTS
            else P(None, "keys", None)
            for comp in comp_specs
        }
        state_specs["act"] = P(None, "keys")
        if self.track_touch:
            state_specs["touch"] = P("keys")
        cols_specs: Dict[str, Any] = {}
        for name in plan.columns:
            cols_specs[name] = P("rows")
            cols_specs["__valid_" + name] = P("rows")

        def step(state, cols, slots, row_valid, pane_vec):
            return shard_map(
                local_fold,
                mesh=self.mesh,
                in_specs=(state_specs, cols_specs, P("rows"), P("rows"),
                          P("rows")),
                out_specs=state_specs,
            )(state, cols, slots, row_valid, pane_vec)

        from ..runtime.aotcache import aot_jit

        return aot_jit(step, op=self._watch_op("fold_step_vec"),
                           donate_argnums=(0,))

    def fold(
        self,
        state: Dict[str, Any],
        cols: Dict[str, np.ndarray],
        slots: np.ndarray,
        valid: Optional[Dict[str, np.ndarray]] = None,
        pane_idx: int = 0,
        n_rows: Optional[int] = None,
        h2d: Optional[Callable[[int], Any]] = None,
    ) -> Dict[str, Any]:
        """Host entry: chunk/pad to the static micro_batch, upload with
        row shardings, run the SPMD step. Signature matches DeviceGroupBy
        so FusedWindowAggNode drives either interchangeably (n_rows is the
        pre-padded-inputs convention — the mesh-aware ingest prep hands
        columns/slots already padded AND placed with this kernel's row
        sharding, single-chunk by contract; host arrays re-pad here; h2d
        is the caller's stage opener around each chunk's placement)."""
        import jax

        from ..ops.aggspec import materialize_hll_columns

        n = n_rows if n_rows is not None else len(slots)
        mb = self.micro_batch
        valid = valid or {}
        cols = materialize_hll_columns(self.plan.columns, cols, n)
        if isinstance(slots, np.ndarray):
            # per-shard row accounting (kuiper_shard_rows_total) off the
            # host slot vector; the prep path's device slots are counted
            # by the driving node (it still holds the host vector)
            self.note_rows(slots, n)
        vec = isinstance(pane_idx, np.ndarray)
        if vec and self._fold_vec is None:
            self._fold_vec = self._build_fold_vec()
        # pre-padded device inputs (runtime/ingest.py pad_*_for_device
        # with this kernel's shardings): single-chunk by contract — used
        # as they are
        has_dev = isinstance(slots, jax.Array) or any(
            isinstance(cols.get(name), jax.Array)
            for name in self.plan.columns)
        if has_dev:
            assert n <= mb, "pre-uploaded device inputs must be one chunk"
        for start in range(0, max(n, 1), mb):
            end = min(start + mb, n)
            if end <= start:
                break
            with (h2d(end - start) if h2d is not None else _NO_STAGE):
                staged = self._stage_chunk(cols, slots, valid, pane_idx,
                                           start, end)
            state = (self._fold_vec if vec else self._fold)(state, *staged)
        return state

    def _stage_chunk(self, cols, slots, valid, pane_idx, start: int,
                     end: int):
        """Placement of rows [start:end) across the mesh: pad to the
        static micro-batch, one `_put` an array that is not placed yet.
        Returns the SPMD step's arguments after the state."""
        import jax
        import jax.numpy as jnp

        mb = self.micro_batch
        cnt = end - start
        pad = mb - cnt
        calls = 0

        def put(arr, dtype):
            nonlocal calls
            calls += 1
            arr = np.asarray(arr[start:end], dtype=dtype)
            if pad:
                arr = np.pad(arr, (0, pad))  # padded rows masked by rv
            return self._put(arr, self.batch_sharding)

        dev_cols = {}
        for name in self.plan.columns:
            c = cols[name]
            dev_cols[name] = (c if isinstance(c, jax.Array)
                              else put(c, np.float32))
            # masks are always materialized (all-true when absent) so the
            # shard_map pytree structure is static across batches; the
            # all-true mask is one cached device buffer, not a per-batch
            # host allocation + upload
            vm = valid.get(name)
            if isinstance(vm, jax.Array):
                dev_cols["__valid_" + name] = vm
            elif vm is not None:
                dev_cols["__valid_" + name] = put(vm, np.bool_)
            else:
                if self._all_true is None:
                    self._all_true = self._put(
                        np.ones(mb, dtype=np.bool_), self.batch_sharding)
                dev_cols["__valid_" + name] = self._all_true
        s_dev = (slots if isinstance(slots, jax.Array)
                 else put(slots, np.int32))
        rv = np.zeros(mb, dtype=np.bool_)
        rv[:cnt] = True
        rv_dev = self._put(rv, self.batch_sharding)
        if isinstance(pane_idx, np.ndarray):
            pane = put(pane_idx, np.int32)
        else:
            pane = self._put(jnp.asarray(pane_idx, dtype=jnp.int32),
                             self.scalar_sharding)
            calls += 1
        self.transfers_total += calls + 1  # + the row mask
        return dev_cols, s_dev, rv_dev, pane

    # finalize / reset_pane / state_to_host / observe_dtypes inherited from
    # DeviceGroupBy: they are plain jit over the (sharded) state arrays, so
    # the whole finalize (pane merge + final values) runs LOCAL per shard —
    # XLA keeps the capacity axis sharded end-to-end and the only cross-
    # shard movement is the host-side assembly of the per-shard result
    # slices at the final np.asarray device->host transfer (the "host-side
    # merge at window boundaries" of docs/DISTRIBUTED.md).

    # ------------------------------------------------------- observability
    def note_rows(self, slots: np.ndarray, n: Optional[int] = None,
                  n_keys: Optional[int] = None) -> None:
        """Accrue per-shard fold rows off a HOST slot vector (the shard of
        slot s is s // (capacity/K)). One bincount per batch — the
        kuiper_shard_rows_total source. `n_keys` refreshes the occupancy
        hint (the driving node's KeyTable count)."""
        if n is not None:
            slots = slots[:n]
        if n_keys is not None:
            self.n_keys_hint = int(n_keys)
        if len(slots) == 0:
            return
        K = self.n_keys_shards
        cap_per_shard = max(self.capacity // K, 1)
        shard = np.minimum(
            np.asarray(slots, dtype=np.int64) // cap_per_shard, K - 1)
        self.shard_rows += np.bincount(shard, minlength=K)[:K]

    def shard_stats(self, state: Optional[Dict[str, Any]] = None
                    ) -> List[Dict[str, Any]]:
        """Per-shard view for metrics/diagnostics/bench: rows folded into
        each shard's key range, key slots it owns (from the occupancy
        hint), and its share of the state bytes. Pure host math — never
        syncs the device."""
        K = self.n_keys_shards
        cap_per_shard = max(self.capacity // K, 1)
        state_bytes = 0
        if state is not None:
            state_bytes = sum(int(getattr(a, "nbytes", 0) or 0)
                              for a in state.values())
        out = []
        for i in range(K):
            keys = min(max(self.n_keys_hint - i * cap_per_shard, 0),
                       cap_per_shard)
            out.append({
                "shard": i,
                "rows": int(self.shard_rows[i]),
                "keys": int(keys),
                "slots": cap_per_shard,
                "state_bytes": state_bytes // K,
            })
        return out

    def collective_bytes_per_fold(self) -> int:
        """Estimated cross-chip bytes ONE fold step moves per chip: the
        psum/pmin/pmax merge over the "rows" axis reduces each chip's
        (n_panes, capacity/K, k) component partials, which a ring
        all-reduce ships as ~2*(R-1)/R of the slice bytes. R == 1 meshes
        fold with no collective at all (key-sharded state is chip-local),
        so the estimate is exactly 0 there. Wide sketch components carry
        their trailing dim. Host math only — meshwatch's
        collective-vs-compute split divides this by the ICI bandwidth
        class to price kernwatch's sampled device time."""
        R = self.n_row_shards
        if R <= 1:
            return 0
        from ..ops.groupby import _wide_size

        K = max(self.n_keys_shards, 1)
        cap_per_shard = max(self.capacity // K, 1)
        elems = self.n_panes * cap_per_shard  # the "act" activity mask
        for comp, spec_idxs in self.comp_specs.items():
            w = _wide_size(comp) if comp in WIDE_COMPONENTS else 1
            elems += self.n_panes * cap_per_shard * len(spec_idxs) * w
        return int(2 * (R - 1) / R * elems * 4)  # float32 partials


# ----------------------------------------------------------- shard registry
# weakref index of live sharded kernels for the kuiper_shard_* families
# (utils/weakreg.py — THE shared ownership model, also tierstore's)
import threading as _threading

from ..utils.weakreg import WeakRegistry as _Registry

_registry = _Registry()

# rows rolled up from collected kernels, keyed (rule, shard). The
# generation counter guards against finalizers from a previous test
# epoch landing after reset() — a late GC must not resurrect counts.
_retired_lock = _threading.Lock()
_retired_rows: Dict[Tuple[str, int], int] = {}
_gen = [0]


def _note_retired(gen: int, rule: Optional[str], shard_rows) -> None:
    """weakref.finalize callback — fold a dead kernel's shard rows into
    the module rollup (GC thread; keep it lock-tight and exception-free)."""
    with _retired_lock:
        if gen != _gen[0]:
            return
        label = rule or "__engine__"
        for i, n in enumerate(shard_rows):
            if n:
                key = (label, i)
                _retired_rows[key] = _retired_rows.get(key, 0) + int(n)


def retired_rows() -> Dict[Tuple[str, int], int]:
    """Snapshot of the retired-kernel rollup ((rule, shard) -> rows)."""
    with _retired_lock:
        return dict(_retired_rows)


def registry() -> _Registry:
    return _registry


def reset() -> None:
    """Test hook."""
    _registry.clear()
    with _retired_lock:
        _gen[0] += 1
        _retired_rows.clear()


def render_prometheus(out: List[str], esc) -> None:
    """Append the per-shard serving families to a /metrics scrape."""
    fams = (
        ("kuiper_shard_rows_total", "counter",
         "rows folded into each mesh shard's key range",
         lambda st: st["rows"]),
        ("kuiper_shard_keys", "gauge",
         "key slots occupied in each mesh shard's range",
         lambda st: st["keys"]),
    )
    kernels = _registry.items()
    for name, mtype, help_txt, fn in fams:
        out.append(f"# TYPE {name} {mtype}")
        out.append(f"# HELP {name} {help_txt}")
        # aggregate per (rule, shard) label pair: duplicate sample lines
        # would fail the whole Prometheus scrape. The rows counter seeds
        # from the retired-kernel rollup so it never regresses when a
        # restore replaces the kernel.
        agg: Dict[Tuple[str, int], int] = {}
        if name == "kuiper_shard_rows_total":
            agg.update(retired_rows())
        for kernel, rule in kernels:
            label = rule or "__engine__"
            try:
                for st in kernel.shard_stats():
                    key = (label, st["shard"])
                    agg[key] = agg.get(key, 0) + int(fn(st))
            except Exception:
                continue
        for (label, shard), v in sorted(agg.items()):
            out.append(f'{name}{{rule="{esc(label)}",shard="{shard}"}} {v}')


def diagnostics() -> List[Dict[str, Any]]:
    """Per-kernel shard state for GET /diagnostics + kuiperdiag."""
    rows = []
    for kernel, rule in _registry.items():
        rows.append({
            "rule": rule or "__engine__",
            "mesh": kernel.mesh_tag,
            "capacity": int(kernel.capacity),
            "shards": kernel.shard_stats(),
        })
    return rows
