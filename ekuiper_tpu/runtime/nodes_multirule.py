"""Multi-rule fused window node — N homogeneous rules, one device program.

Extends FusedWindowAggNode with a BatchedGroupBy kernel (leading rule axis,
parallel/multirule.py) and per-rule output routing: each attached rule gets
its own downstream entry (its own sink chain, stats, backpressure), while
ingest, key encode, upload, fold, and finalize happen ONCE for the group.
This is the TPU-native answer to the reference's 300-rules-on-one-stream
fan-out deployment (reference: test/benchmark/multiple_rules, shared source
instances internal/topo/subtopo.go).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from ..data.rows import WindowRange
from ..parallel.multirule import BatchedGroupBy, RuleBatchSpec
from ..sql import ast
from .node import Node
from .nodes_fused import FusedWindowAggNode


class MultiRuleFusedNode(FusedWindowAggNode):
    def __init__(
        self,
        name: str,
        window: ast.Window,
        spec: RuleBatchSpec,
        dims: List[ast.FieldRef],
        capacity: int = 16384,
        micro_batch: int = 4096,
        **kw,
    ) -> None:
        self.spec = spec  # before super().__init__: _make_gb reads it
        super().__init__(name, window, spec.plan, dims, capacity=capacity,
                         micro_batch=micro_batch, **kw)
        # boundary emits go through the async worker: one stacked (R,S+1,K)
        # transfer per family is MBs and must not stall the fold stream
        self._async_mr = (self.wt == ast.WindowType.TUMBLING_WINDOW
                          and not self.is_event_time)
        #: rule_id -> downstream entry node (per-rule sink chain); also
        #: connect()-ed so control events (EOF, errors) broadcast to all
        self.rule_outputs: Dict[str, Node] = {}

    def _make_gb(self, plan, capacity: int, micro_batch: int, mesh):
        return BatchedGroupBy(self.spec, capacity=capacity,
                              n_panes=int(self.n_panes),
                              micro_batch=micro_batch)

    def add_rule_output(self, rule_id: str, entry: Node) -> None:
        self.rule_outputs[rule_id] = entry
        self.connect(entry)  # control events (EOF) reach every rule chain

    # ------------------------------------------------------------------- emit
    def _emit(self, wr: WindowRange) -> None:
        """Synchronous family emit (EOF flush / non-boundary paths)."""
        n_keys = self.kt.n_keys
        if n_keys == 0:
            return
        outs, act = self.gb.finalize(self.state, n_keys)  # (R, S, K), (R, K)
        self._emit_rules(outs, act, n_keys, wr)

    def _emit_mr_async(self, wr: WindowRange) -> None:
        """Window-boundary family emit: dispatch the ONE-launch stacked
        finalize on the immutable state snapshot and hand the (R, S+1, K)
        transfer — MBs per family — to the emit worker. The boundary then
        resets the pane and folding continues; a sync fetch here would
        stall every rider of the shared source for the transfer duration."""
        n_keys = self.kt.n_keys
        if n_keys == 0:
            self.last_emit_info = None
            return
        self._emit_async(
            "mr", lambda: self.gb.finalize_begin(self.state, n_keys), wr)

    def _deliver_mr(self, arr: np.ndarray, n_keys: int,
                    wr: WindowRange) -> None:
        """Emit-worker delivery: slice the landed stacked array per rule.
        n_keys was captured at dispatch; keys are append-only so the first
        n_keys table entries still match the snapshot's slot ids."""
        from ..ops.groupby import apply_int_semantics

        outs = [arr[:, i, :n_keys] for i in range(len(self.plan.specs))]
        act = arr[:, -1, :n_keys]
        outs = apply_int_semantics(self.plan.specs, outs)
        self._emit_rules(outs, act, n_keys, wr)

    def _emit_rules(self, outs, act, n_keys: int, wr: WindowRange) -> None:
        dim_names = [d.name for d in self.dims]
        keys = self.kt.keys_slice(0, n_keys)
        keys_arr = np.empty(len(keys), dtype=np.object_)
        keys_arr[:] = keys
        for r, rid in enumerate(self.gb.rule_ids):
            out_node = self.rule_outputs.get(rid)
            if out_node is None:
                continue
            active = np.nonzero(act[r] > 0)[0]
            if len(active) == 0:
                continue
            dim_cols: Dict[str, np.ndarray] = {}
            if dim_names:
                sel = keys_arr[active]
                if len(dim_names) == 1:
                    dim_cols[dim_names[0]] = sel
                else:
                    for i, dn in enumerate(dim_names):
                        col = np.empty(len(active), dtype=np.object_)
                        col[:] = [k[i] for k in sel.tolist()]
                        dim_cols[dn] = col
            agg_cols = [o[r][active] for o in outs]
            if self.emit_columnar:
                cb = self.direct_emit.run_columnar(
                    dim_cols, agg_cols, wr.window_start, wr.window_end)
                if cb is not None and cb.n:
                    self.stats.inc_out(cb.n)
                    self.send_to(out_node, cb)
            else:
                msgs = self.direct_emit.run(
                    dim_cols, agg_cols, wr.window_start, wr.window_end)
                if msgs:
                    self.stats.inc_out(len(msgs))
                    # Always a list (same emission-type contract as
                    # FusedWindowAggNode._build_direct).
                    self.send_to(out_node, msgs)

    # ------------------------------------------------------------------ state
    def restore_state(self, state: dict) -> None:
        keys = state.get("keys", [])
        self.kt.restore([tuple(k) if isinstance(k, list) else k for k in keys])
        partials = state.get("partials")
        if partials:
            host = {k: np.asarray(v, dtype=np.float32)
                    for k, v in partials.items()}
            cap = next(iter(host.values())).shape[2]  # (R, panes, cap, k)
            self.gb.capacity = cap
            self.kt.capacity = max(self.kt.capacity, cap)
            self.state = self.gb.state_from_host(host)
        self.cur_pane = state.get("cur_pane", 0)
        self._rows_in_window = state.get("rows_in_window", 0)
