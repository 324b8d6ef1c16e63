"""Batched homogeneous rules — one compiled device program serving N rules.

The reference's fan-out benchmark runs 300 rules over one shared MQTT stream,
each rule a goroutine pipeline applying its own filter (BASELINE.md row 5;
reference test/benchmark/multiple_rules/). The TPU-native equivalent batches
homogeneous rules on a LEADING RULE AXIS: rules that differ only in literal
constants (thresholds etc.) canonicalize to one kernel plan whose literals
become per-rule parameters, the group-by state becomes
{comp: (R, n_panes, capacity, k)}, and `jax.vmap` over the rule axis turns
the single-rule fold into one XLA program folding every rule at once.

What this buys vs N independent pipelines:
- ONE ingest + decode + key-encode per batch (shared, host)
- ONE H2D upload per batch (the batch is broadcast across the rule axis)
- ONE device program launch per batch, one finalize/transfer per window
- per-rule cost on device is a scatter-add slice — MXU/VPU-friendly and
  compiled once, not R interpreter loops

Homogeneity contract (`build_rule_batch` validates): identical SELECT
fields, window, GROUP BY dims, source, and HAVING; WHERE clauses must be
structurally identical with numeric literals free to differ per rule.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..ops.aggspec import KernelPlan, extract_kernel_plan
from ..ops.groupby import DeviceGroupBy, _INIT, apply_int_semantics
from ..sql import ast

PARAM_PREFIX = "__param_"


# ------------------------------------------------------- canonicalization
def _canonicalize_expr(expr: Optional[ast.Expr],
                       params: List[float]) -> Optional[ast.Expr]:
    """Replace numeric literals with per-rule parameter refs, appending each
    literal's value to `params` in placeholder order."""
    if expr is None:
        return None
    sub = lambda e: _canonicalize_expr(e, params)  # noqa: E731
    if isinstance(expr, (ast.IntegerLiteral, ast.NumberLiteral)):
        idx = len(params)
        params.append(float(expr.val))
        return ast.FieldRef(name=f"{PARAM_PREFIX}{idx}")
    if isinstance(expr, ast.BinaryExpr):
        return ast.BinaryExpr(expr.op, sub(expr.lhs), sub(expr.rhs))
    if isinstance(expr, ast.UnaryExpr):
        return ast.UnaryExpr(expr.op, sub(expr.expr))
    if isinstance(expr, ast.BetweenExpr):
        return ast.BetweenExpr(sub(expr.value), sub(expr.lo), sub(expr.hi),
                               expr.negate)
    if isinstance(expr, ast.CaseExpr):
        return ast.CaseExpr(
            sub(expr.value) if expr.value is not None else None,
            [ast.WhenClause(sub(w.cond), sub(w.result)) for w in expr.whens],
            sub(expr.else_expr) if expr.else_expr is not None else None,
        )
    # anything else (field refs, string/bool literals, calls, IN lists) must
    # match exactly across rules — returned as-is
    return expr


@dataclass
class RuleBatchSpec:
    """Canonical template + per-rule parameters for a homogeneous group."""

    stmt: ast.SelectStatement  # canonical statement (params substituted)
    plan: KernelPlan  # kernel plan compiled from the canonical statement
    param_names: List[str]
    params: np.ndarray  # (R, P) float32
    rule_ids: List[str]


def build_rule_batch(
    rule_ids: List[str], stmts: List[ast.SelectStatement],
) -> RuleBatchSpec:
    """Validate homogeneity and build the canonical parameterized plan.
    Raises ValueError when the statements cannot batch."""
    if not stmts:
        raise ValueError("empty rule group")
    canon_keys = []
    param_rows: List[List[float]] = []
    canon_stmt = None
    for stmt in stmts:
        params: List[float] = []
        cond = _canonicalize_expr(stmt.condition, params)
        key = (
            repr(stmt.fields), repr(stmt.window), repr(stmt.dimensions),
            repr(cond), repr(stmt.having), repr(stmt.sources),
            repr(stmt.sorts),
        )
        canon_keys.append(key)
        param_rows.append(params)
        if canon_stmt is None:
            canon_stmt = ast.SelectStatement(
                fields=stmt.fields, sources=stmt.sources, joins=stmt.joins,
                condition=cond, dimensions=stmt.dimensions,
                window=stmt.window, having=stmt.having, sorts=stmt.sorts,
                limit=stmt.limit,
            )
    if len(set(canon_keys)) != 1:
        raise ValueError(
            "rules are not homogeneous: statements must be identical up to "
            "numeric literals in WHERE")
    if len({len(p) for p in param_rows}) != 1:
        raise ValueError("rules have differing parameter counts")
    plan = extract_kernel_plan(canon_stmt)
    if plan is None:
        raise ValueError("rule group is not device-eligible")
    if any(s.kind == "heavy_hitters" for s in plan.specs):
        # hh finalize is a host-side top-k recovery, not part of the vmapped
        # device finalize program — such rules run as individual fused nodes
        raise ValueError("heavy_hitters rules do not batch")
    if plan.has_orderstat:
        # row buffers recycle with one node's panes (ops/orderstat.py)
        raise ValueError("order-statistic rules do not batch")
    n_params = len(param_rows[0])
    param_names = [f"{PARAM_PREFIX}{i}" for i in range(n_params)]
    # params are injected at fold time, not uploaded as batch columns
    plan.columns -= set(param_names)
    return RuleBatchSpec(
        stmt=canon_stmt, plan=plan, param_names=param_names,
        params=np.asarray(param_rows, dtype=np.float32).reshape(
            len(stmts), n_params),
        rule_ids=list(rule_ids),
    )


# ------------------------------------------------------------ batched kernel
class BatchedGroupBy(DeviceGroupBy):
    """DeviceGroupBy with a leading rule axis: state
    {comp: (R, n_panes, capacity, k)}, one vmapped fold/finalize program for
    all R rules. The key table, batch upload, and launch are shared; only
    the per-rule filter parameters differ along the axis."""

    supports_prefinalize = False  # group emits are fetched in one transfer
    watch_prefix = "multirule"

    def __init__(self, spec: RuleBatchSpec, capacity: int = 16384,
                 n_panes: int = 1, micro_batch: int = 4096) -> None:
        import jax

        self.n_rules = len(spec.rule_ids)
        self.param_names = spec.param_names
        self.rule_ids = spec.rule_ids
        super().__init__(spec.plan, capacity=capacity, n_panes=n_panes,
                         micro_batch=micro_batch)
        import jax.numpy as jnp

        self._params = jnp.asarray(spec.params)  # (R, P)
        from ..runtime.aotcache import aot_jit

        self._fold = aot_jit(self._batched_fold_impl,
                                 op="multirule.fold", donate_argnums=(0,))
        self._finalize = aot_jit(self._batched_finalize_impl,
                                     op="multirule.finalize",
                                     kind="boundary",
                                     static_argnums=(1,))
        self._reset_pane = aot_jit(self._batched_reset_impl,
                                       op="multirule.reset_pane",
                                       kind="boundary",
                                       donate_argnums=(0,))

    # state ------------------------------------------------------------
    def init_state(self) -> Dict[str, Any]:
        import jax.numpy as jnp

        from ..ops.aggspec import WIDE_COMPONENTS
        from ..ops.groupby import _wide_size

        state: Dict[str, Any] = {}
        for comp, spec_idxs in self.comp_specs.items():
            shape = (self.n_rules, self.n_panes, self.capacity, len(spec_idxs))
            if comp in WIDE_COMPONENTS:
                shape = shape + (_wide_size(comp),)
            state[comp] = jnp.full(shape, _INIT[comp], dtype=jnp.float32)
        state["act"] = jnp.zeros(
            (self.n_rules, self.n_panes, self.capacity), dtype=jnp.float32)
        return state

    def grow(self, state: Dict[str, Any], new_capacity: int) -> Dict[str, Any]:
        import jax.numpy as jnp

        out: Dict[str, Any] = {}
        for comp, arr in state.items():
            np_arr = np.asarray(arr)
            pad_shape = list(np_arr.shape)
            pad_shape[2] = new_capacity - np_arr.shape[2]  # capacity axis
            pad = np.full(pad_shape, _INIT[comp], dtype=np_arr.dtype)
            out[comp] = jnp.asarray(np.concatenate([np_arr, pad], axis=2))
        self.capacity = new_capacity
        return out

    # fold -------------------------------------------------------------
    def _batched_fold_impl(self, state, cols, slots, n_valid, pane_idx):
        import jax

        def one_rule(st, par):
            c = dict(cols)
            for i, name in enumerate(self.param_names):
                c[name] = par[i]  # scalar broadcasts against row columns
                c["__valid_" + name] = None
            return DeviceGroupBy._fold_impl(self, st, c, slots, n_valid,
                                            pane_idx)

        return jax.vmap(one_rule, in_axes=(0, 0))(state, self._params)

    # finalize ----------------------------------------------------------
    def _batched_finalize_impl(self, state, pane_mask_tuple):
        import jax

        return jax.vmap(
            lambda st: DeviceGroupBy._finalize_impl(self, st, pane_mask_tuple)
        )(state)

    def _slice_keys(self, n_keys: int) -> int:
        """Device-side transfer cut: round the live-key count up to a power
        of two (floor 1024) so the (R, S+1, K) result ships K≈n_keys floats
        instead of full capacity — at R=63 rules the full-capacity transfer
        is 4x the bytes for a quarter-full table — while the rounded shape
        set stays bounded (one slice executable per power of two)."""
        if n_keys >= self.capacity:
            return self.capacity
        k = 1024
        while k < n_keys:
            k <<= 1
        return min(k, self.capacity)

    def finalize_begin(self, state: Dict[str, Any], n_keys: int,
                       panes: Optional[List[int]] = None):
        """Dispatch the stacked finalize and return the (R, S+1, K) DEVICE
        array (K = rounded n_keys) — the async boundary path hands this to
        the emit worker, which fetches and slices host-side."""
        pane_mask = np.zeros(self.n_panes, dtype=np.bool_)
        if panes is None:
            pane_mask[:] = True
        else:
            pane_mask[panes] = True
        dev = self._finalize(state, tuple(pane_mask.tolist()))
        return dev[:, :, : self._slice_keys(n_keys)]

    def finalize(
        self, state: Dict[str, Any], n_keys: int,
        panes: Optional[List[int]] = None,
    ) -> Tuple[List[np.ndarray], np.ndarray]:
        """Per-spec value arrays of shape (R, n_keys) + act (R, n_keys) —
        ONE device launch, ONE transfer for the whole rule group."""
        stacked = np.asarray(self.finalize_begin(state, n_keys, panes))
        outs = [stacked[:, i, :n_keys] for i in range(len(self.plan.specs))]
        act = stacked[:, -1, :n_keys]
        outs = apply_int_semantics(self.plan.specs, outs)
        return outs, act

    # reset -------------------------------------------------------------
    def _batched_reset_impl(self, state, pane_idx):
        import jax

        return jax.vmap(
            lambda st: DeviceGroupBy._reset_pane_impl(self, st, pane_idx)
        )(state)
