"""Relational operator nodes — analogues of internal/topo/operator/*:
FilterOp, AnalyticFuncsOp, AggregateOp, HavingOp, OrderOp, ProjectOp,
ProjectSetOp, plus join. Host path: these run on row collections after
windowing; the fused device path (nodes_fused.py) replaces
window+aggregate+having-on-aggs with one kernel.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional

import numpy as np

from ..data import cast
from ..data.batch import ColumnBatch, from_tuples
from ..data.rows import (
    GroupedTuples, GroupedTuplesSet, JoinTuple, Row, Tuple, WindowTuples,
)
from ..functions import registry
from ..sql import ast
from ..sql.compiler import CompiledExpr, try_compile
from ..sql.eval import EvalError, Evaluator
from .node import Node


class FilterNode(Node):
    """WHERE — vectorized over ColumnBatch when compilable, row fallback
    otherwise (reference: internal/topo/operator/filter_operator.go)."""

    def __init__(self, name: str, condition: ast.Expr, **kw) -> None:
        super().__init__(name, op_type="op", **kw)
        self.condition = condition
        self.compiled = try_compile(condition, mode="host")
        self.ev = Evaluator()

    def process(self, item: Any) -> None:
        # stage accounting: WHERE evaluation (vectorized or per-row) is
        # "host_expr" — the health plane's bottleneck attribution names
        # host expression eval instead of binning it as "other"
        if isinstance(item, ColumnBatch):
            with self.stats.stage("host_expr", item.n):
                out = self._filter_batch(item)
            if out is not None and out.n > 0:
                self.emit(out, count=out.n)
            return
        if isinstance(item, WindowTuples):
            with self.stats.stage("host_expr", len(item.rows())):
                kept = [r for r in item.rows()
                        if self.ev.eval_condition(self.condition, r)]
            if kept:
                self.emit(WindowTuples(content=kept, window_range=item.window_range))
            return
        if isinstance(item, Row):
            with self.stats.stage("host_expr", 1):
                keep = self.ev.eval_condition(self.condition, item)
            if keep:
                self.emit(item)
            return
        self.emit(item)

    def _filter_batch(self, batch: ColumnBatch) -> Optional[ColumnBatch]:
        if self.compiled is not None and all(
            c in batch.columns for c in self.compiled.columns
        ):
            try:
                mask = np.asarray(self.compiled(batch.columns), dtype=bool)
                for c in self.compiled.columns:
                    mask &= batch.is_valid(c)
                return batch.select(mask)
            except Exception:
                pass  # fall back to rows
        rows = batch.to_tuples()
        kept = [r for r in rows if self.ev.eval_condition(self.condition, r)]
        if not kept:
            return None
        return from_tuples(kept, emitter=batch.emitter)


class AnalyticNode(Node):
    """Pre-computes analytic function values per row before filtering
    (reference: analyticfuncs_operator.go). Results cache on the row as
    __analytic_{func_id} cal-cols which the evaluator reads back."""

    def __init__(self, name: str, calls: List[ast.Call], rule_id: str = "", **kw) -> None:
        super().__init__(name, op_type="op", **kw)
        self.calls = calls
        self.ev = Evaluator(rule_id=rule_id)

    def process(self, item: Any) -> None:
        if isinstance(item, ColumnBatch):
            rows = item.to_tuples()
        elif isinstance(item, Row):
            rows = [item]
        else:
            self.emit(item)
            return
        for r in rows:
            for call in self.calls:
                val = self.ev.eval(call, r)
                r.set_cal_col(f"__analytic_{call.func_id}", val)
        if isinstance(item, ColumnBatch):
            for r in rows:
                self.emit(r)
        else:
            self.emit(item)

    def snapshot_state(self) -> Optional[dict]:
        # analytic state is json-serializable (lists/scalars)
        try:
            import json

            # round-trip: the snapshot must be a frozen copy — handing out
            # the live dict lets post-barrier rows mutate the checkpoint
            return {"func_states": json.loads(json.dumps(self.ev.func_states))}
        except (TypeError, ValueError):
            return None

    def restore_state(self, state: dict) -> None:
        fs = state.get("func_states", {})
        self.ev.func_states = {int(k): v for k, v in fs.items()}


class AggregateNode(Node):
    """GROUP BY on window output: evaluates dimension exprs per row, builds
    GroupedTuplesSet (reference: aggregate_operator.go:34-74)."""

    def __init__(self, name: str, dimensions: List[ast.Expr], **kw) -> None:
        super().__init__(name, op_type="op", **kw)
        self.dimensions = dimensions
        self.ev = Evaluator()

    def process(self, item: Any) -> None:
        if isinstance(item, ColumnBatch):
            rows: List[Row] = item.to_tuples()
            wr = None
        elif isinstance(item, WindowTuples):
            rows = item.rows()
            wr = item.window_range
        elif isinstance(item, Row):
            rows = [item]
            wr = None
        else:
            self.emit(item)
            return
        groups: Dict[str, GroupedTuples] = {}
        order: List[str] = []
        for r in rows:
            key_parts = []
            for d in self.dimensions:
                v = self.ev.eval(d, r)
                key_parts.append(cast.to_string(v) if v is not None else "")
            key = "#".join(key_parts)
            g = groups.get(key)
            if g is None:
                g = GroupedTuples(content=[], group_key=key, window_range=wr)
                groups[key] = g
                order.append(key)
            g.content.append(r)
        self.emit(GroupedTuplesSet(groups=[groups[k] for k in order], window_range=wr))


class HavingNode(Node):
    """Post-agg filter (reference: having_operator.go)."""

    def __init__(self, name: str, condition: ast.Expr, rule_id: str = "", **kw) -> None:
        super().__init__(name, op_type="op", **kw)
        self.condition = condition
        self.ev = Evaluator(rule_id=rule_id)

    def process(self, item: Any) -> None:
        if isinstance(item, GroupedTuplesSet):
            self.ev.window_range = item.window_range
            kept = [
                g for g in item.groups
                if self.ev.eval_condition(self.condition, g)
            ]
            if kept:
                self.emit(GroupedTuplesSet(groups=kept, window_range=item.window_range))
            return
        if isinstance(item, WindowTuples):
            # non-grouped agg condition applies to the whole window
            self.ev.window_range = item.window_range
            if self.ev.eval_condition(self.condition, item):
                self.emit(item)
            return
        if isinstance(item, Row):
            if self.ev.eval_condition(self.condition, item):
                self.emit(item)
            return
        self.emit(item)


class OrderNode(Node):
    """ORDER BY (reference: order_operator.go + internal/xsql/sorter.go)."""

    def __init__(self, name: str, sorts: List[ast.SortField], **kw) -> None:
        super().__init__(name, op_type="op", **kw)
        self.sorts = sorts
        self.ev = Evaluator()

    def process(self, item: Any) -> None:
        if isinstance(item, GroupedTuplesSet):
            item.groups = self._sort(item.groups)
        elif isinstance(item, WindowTuples):
            item.content = self._sort(item.content)
        elif isinstance(item, ColumnBatch):
            rows = self._sort(item.to_tuples())
            item = from_tuples(rows, emitter=item.emitter)
        self.emit(item)

    def _sort(self, rows: List[Any]) -> List[Any]:
        def cmp(a, b) -> int:
            for sf in self.sorts:
                expr = sf.expr if sf.expr is not None else ast.FieldRef(sf.name, sf.stream)
                va = self.ev.eval(expr, a)
                vb = self.ev.eval(expr, b)
                c = cast.compare(va, vb)
                if c is None:
                    c = 0
                if c != 0:
                    return c if sf.ascending else -c
            return 0

        return sorted(rows, key=functools.cmp_to_key(cmp))


class ProjectNode(Node):
    """SELECT projection (reference: project_operator.go:54-136). Emits
    result Tuples with the output message per row/group."""

    def __init__(
        self, name: str, fields: List[ast.Field], rule_id: str = "",
        limit: Optional[int] = None, send_nil: bool = False,
        is_agg: bool = False, **kw,
    ) -> None:
        super().__init__(name, op_type="op", **kw)
        self.fields = fields
        self.limit = limit
        self.is_agg = is_agg
        self.ev = Evaluator(rule_id=rule_id)

    def process(self, item: Any) -> None:
        rows: List[Row]
        wr = None
        if isinstance(item, GroupedTuplesSet):
            rows = list(item.groups)
            wr = item.window_range
        elif isinstance(item, WindowTuples):
            # aggregate query without GROUP BY: whole window = one group
            rows = [item] if self.is_agg else item.rows()
            wr = item.window_range
        elif isinstance(item, ColumnBatch):
            rows = item.to_tuples()
        elif isinstance(item, Row):
            rows = [item]
        else:
            self.emit(item)
            return
        self.ev.window_range = wr
        if self.limit is not None:
            rows = rows[: self.limit]
        out: List[Tuple] = []
        for r in rows:
            msg: Dict[str, Any] = {}
            for idx, f in enumerate(self.fields):
                if f.invisible:
                    continue
                if isinstance(f.expr, ast.Wildcard):
                    val = self.ev.eval(f.expr, r)
                    if isinstance(val, dict):
                        msg.update(val)
                    continue
                val = self.ev.eval(f.expr, r)
                msg[f.output_name or f"kuiper_field_{idx}"] = val
            ts = getattr(r, "timestamp", 0)
            meta = getattr(r, "metadata", None)
            out.append(Tuple(emitter="", message=msg, timestamp=ts,
                             metadata=dict(meta) if meta else {}))
        if out:
            self.emit(out if len(out) > 1 else out[0], count=len(out))


class ProjectSetNode(Node):
    """SRF expansion post-projection (reference: projectset_operator.go).
    The projected message holds the SRF result list under `srf_name`; each
    element becomes one output row — dict elements merge into the row,
    scalar elements replace the column."""

    def __init__(self, name: str, srf_name: str, **kw) -> None:
        super().__init__(name, op_type="op", **kw)
        self.srf_name = srf_name

    def process(self, item: Any) -> None:
        rows: List[Tuple]
        if isinstance(item, list):
            rows = [r for r in item if isinstance(r, Tuple)]
        elif isinstance(item, Tuple):
            rows = [item]
        else:
            self.emit(item)
            return
        for r in rows:
            expanded = r.message.get(self.srf_name)
            if not isinstance(expanded, list):
                self.emit(r)
                continue
            for v in expanded:
                new_msg = dict(r.message)
                if isinstance(v, dict):
                    del new_msg[self.srf_name]
                    new_msg.update(v)
                else:
                    new_msg[self.srf_name] = v
                self.emit(Tuple(emitter=r.emitter, message=new_msg,
                                timestamp=r.timestamp))


class WindowFuncNode(Node):
    """SQL window functions (row_number) applied post-agg
    (reference: windowfunc_operator.go)."""

    def __init__(self, name: str, calls: List[ast.Call], **kw) -> None:
        super().__init__(name, op_type="op", **kw)
        self.calls = calls
        self.ev = Evaluator()

    def process(self, item: Any) -> None:
        rows: List[Row]
        if isinstance(item, GroupedTuplesSet):
            rows = list(item.groups)
        elif isinstance(item, WindowTuples):
            rows = item.rows()
        elif isinstance(item, Row):
            rows = [item]
        elif isinstance(item, ColumnBatch):
            rows = item.to_tuples()
        else:
            self.emit(item)
            return
        # row_number restarts per collection
        self.ev.func_states = {}
        for r in rows:
            for call in self.calls:
                val = self.ev.eval(call, r)
                r.set_cal_col(f"__analytic_{call.func_id}", val)
        if isinstance(item, ColumnBatch):
            # emit the mutated rows, not the unmodified batch
            for r in rows:
                self.emit(r)
        else:
            self.emit(item)
