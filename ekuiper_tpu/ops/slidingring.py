"""Constant-time sliding aggregation rings — the DABA replacement for the
refold-on-trigger sliding path (ROADMAP item 2, per "In-Order
Sliding-Window Aggregation in Worst-Case Constant Time" / the two-stacks
discipline, PAPERS.md).

The refold path answers a trigger by merging EVERY pane inside the window
(`finalize_dyn` over a ~window-span pane mask) plus device refolds of the
two partial edge buckets from the cached `_dev_ring` batch history — work
proportional to the window length, per trigger, and exactly the owner of
the 400-900ms sliding emit stalls (BENCH_r04, kernwatch attribution).

This module keeps the same pane ring the fold path already maintains
(`ops/groupby.py` state, one pane per time bucket) and adds per-key
running partials over the CLOSED panes so a trigger is a single combine
of two running partials instead of a window-length fold:

- **subtract-on-evict totals** for components whose combine is addition
  (`n`, `s1`, `s2`, `hist`, `hh`, `act` — sum/count/avg, stddev via
  sum-of-squares, log-histogram percentiles, heavy-hitter counters):
  one `tot_<comp>` array of shape ``[keys, agg_width]``; closing a
  bucket adds its pane slice, evicting the expired bucket subtracts it.
  O(1) per bucket advance, O(1) per query.
- **two-stack front/back partials** for non-invertible combines
  (`mn`, `mx`, `hll` — min/max-merge cannot subtract): `back_<comp>`
  ``[keys, agg_width]`` accumulates panes closed since the last flip;
  `front_<comp>` ``[keys, ring_slots, agg_width]`` (stored slot-major as
  ``[ring_slots, keys, ...]``) holds SUFFIX combines over the older
  panes, rebuilt by one reverse cumulative scan per ring rotation
  (amortized O(1) per pane — the DABA flip). A query is
  ``combine(front[j], back)``.

All three operations — ``advance`` (insert+evict), ``flip`` (rebuild),
``query`` — are single jitted device programs over dense
``[keys, ...]``/``[ring_slots, keys, ...]`` arrays, vectorized across
every GROUP BY key, with statically bounded shapes (capacity ladder ×
plan-time ring geometry) so jitcert can certify the closed signature
set (`observability/jitcert.py _derive_ring`).

A fourth program, ``tail``, finishes a trigger the running partials
served: it takes the query's stacked components where they lie (on the
device), scatters the rows of the window's partial edge buckets with the
fold's own code, combines body and edges per component class and applies
the final values — the trigger fetches ``(n_specs + 1, capacity)``
floats, not the sketch.

The ring caches are pure functions of the pane state: a checkpoint
restore or any host-side confusion (late rows into closed buckets, time
gaps) simply marks the cache dirty and the next trigger rebuilds it with
one flip. Exactness never depends on the cache being fresh.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

from .groupby import _INIT, DeviceGroupBy

#: components whose pane combine is elementwise addition — these take the
#: subtract-on-evict fast path (one running total, no suffix stack)
#: "touch" never materializes ring partials (it rides the pane state
#: pytree, not the ring — comp_specs never contains it); it is listed so
#: the combine classification stays TOTAL over groupby._INIT, which the
#: guardrail test (test_sliding_ring.py combine-classes-are-total)
#: enforces for every state component
ADD_COMBINE = frozenset({"n", "s1", "s2", "hist", "hh", "act", "touch"})
#: min-merge components (two-stack discipline; subtraction undefined)
MIN_COMBINE = frozenset({"mn"})
#: max-merge components (two-stack discipline; hll registers merge by max)
MAX_COMBINE = frozenset({"mx", "hll"})

#: micro-batches of edge rows one call of the tail program takes: its one
#: static edge shape is TAIL_EDGE_CHUNKS x micro_batch rows (the edge
#: buckets of a trigger hold about one bucket of rows: 270,000 at 1.3M
#: rows/s into 208 ms buckets, against 524,288 at a 32,768-row
#: micro-batch); a trigger with more loops over it. The program scatters
#: only the micro-batches that hold rows, so the spare room costs an
#: upload, not device time.
TAIL_EDGE_CHUNKS = 16

#: pane-slice adjustment slots a query carries: up to two low-edge
#: subtractions (the running total trails the window start by at most the
#: eviction hysteresis) plus the live head pane, with one slot spare
QUERY_ADJ = 4


@dataclass(frozen=True)
class RingLayout:
    """Plan-time sliding ring geometry — chosen by the planner from the
    window/hop/pane declarations (planner/planner.py) and shared with the
    fused node so both agree on bucket routing and certificate shapes."""

    bucket_ms: int      # fine time-pane width rows route into
    n_ring_panes: int   # pane ring slots (window span + slack)
    n_panes: int        # n_ring_panes + 1 (scratch pane, refold impl only)
    span_buckets: int   # buckets a full window spans (ceil((L+delay)/B))
    scratch_pane: int   # scratch slot index (refold edge folds)


def plan_ring_layout(length_ms: int, delay_ms: int, wide: bool,
                     budget_bytes: Optional[int] = None,
                     mm_slot_bytes: int = 0,
                     fixed_bytes: int = 0) -> RingLayout:
    """Ring geometry for a sliding window: finer buckets shrink the edge
    corrections (≤1 bucket of rows host-folded per trigger edge); bounded
    by the uint8 pane budget AND by HBM. Wide sketch components
    (hist=512, hll=64 registers) pay panes×capacity×width×4B of
    front-stack state, so they start coarser — and when `budget_bytes`
    is given (the slidingDevRingMb budget), the bucket target walks DOWN
    a ladder until the ring's static footprint fits: a wide-hll sliding
    rule coarsens its ring instead of silently refolding (ROADMAP item-2
    remnant). `mm_slot_bytes` is the per-ring-slot front-stack cost at
    the plan's key capacity; `fixed_bytes` the slot-count-independent
    part (running totals + back stacks)."""
    targets = (48,) if wide else (128,)
    if budget_bytes is not None:
        targets = (48, 32, 24, 16, 12, 8) if wide \
            else (128, 64, 48, 32, 24, 16, 12, 8)
    layout = None
    for target in targets:
        bucket_ms = max(length_ms // target, 25,
                        -(-(length_ms + delay_ms) // 250))
        span = -(-(length_ms + delay_ms) // bucket_ms)
        n_ring = span + 3
        n_panes = n_ring + 1  # +1 scratch pane (refold impl edge folds)
        if n_panes > 255:
            raise ValueError(
                f"sliding window needs {n_panes} panes (max 255)")
        layout = RingLayout(
            bucket_ms=int(bucket_ms), n_ring_panes=int(n_ring),
            n_panes=int(n_panes), span_buckets=int(span),
            scratch_pane=int(n_ring))
        if budget_bytes is None:
            return layout
        est = fixed_bytes + (1 + n_ring) * mm_slot_bytes
        if est <= budget_bytes:
            return layout
    return layout  # coarsest rung; the node's own budget check decides


def _plan_ring_bytes(plan, capacity: int):
    """(mm_slot_bytes, fixed_bytes) of a plan's ring state at `capacity`
    — the same component arithmetic SlidingRing.estimate_bytes uses,
    computed WITHOUT constructing the kernel (plan-time layout choice)."""
    from .aggspec import WIDE_COMPONENTS
    from .groupby import _wide_size

    comp_specs: dict = {}
    for i, spec in enumerate(plan.specs):
        for comp in spec.components:
            comp_specs.setdefault(comp, []).append(i)
    mm_slot = 0
    fixed = 0
    for comp in sorted(list(comp_specs) + ["act"]):
        k = len(comp_specs.get(comp, ()))
        dims = 1 if comp == "act" else (
            k * (_wide_size(comp) if comp in WIDE_COMPONENTS else 1))
        per = capacity * dims * 4
        if comp in ADD_COMBINE:
            fixed += per              # tot_<comp>
        else:
            # back_<comp> + front_<comp>: one per-slot unit covers the
            # back stack too, matching SlidingRing.estimate_bytes's
            # per×(1+n_ring) exactly (the regression test pins parity)
            mm_slot += per
    return mm_slot, fixed


def ring_layout_for(window, plan, capacity: Optional[int] = None,
                    budget_mb: Optional[int] = None) -> RingLayout:
    """Layout from the parsed window + kernel plan (the planner's entry).
    With `capacity` + `budget_mb` the layout is budget-aware: the ring
    coarsens until its static HBM estimate fits slidingDevRingMb."""
    from .aggspec import WIDE_COMPONENTS

    wide = any(set(s.components) & WIDE_COMPONENTS for s in plan.specs)
    if capacity is None or budget_mb is None:
        return plan_ring_layout(window.length_ms(), window.delay_ms(),
                                wide)
    mm_slot, fixed = _plan_ring_bytes(plan, int(capacity))
    return plan_ring_layout(window.length_ms(), window.delay_ms(), wide,
                            budget_bytes=int(budget_mb) << 20,
                            mm_slot_bytes=mm_slot, fixed_bytes=fixed)


class SlidingRing:
    """Device-resident DABA ring over a DeviceGroupBy's pane state.

    Owns four jit sites (`slidingring.advance/flip/query/tail`), each
    certified by jitcert (`_derive_ring`); the host-side bucket
    bookkeeping (which bucket is closed/evicted/queried) lives in the
    fused node — this class is the pure device kernel."""

    watch_prefix = "slidingring"

    def __init__(self, gb: DeviceGroupBy, layout: RingLayout) -> None:
        self.gb = gb
        self.layout = layout
        self.capacity = int(gb.capacity)
        self.n_ring_panes = int(layout.n_ring_panes)
        comps = sorted(list(gb.comp_specs) + ["act"])
        self.add_comps = [c for c in comps if c in ADD_COMBINE]
        self.mm_comps = [c for c in comps
                         if c in MIN_COMBINE or c in MAX_COMBINE]
        unknown = [c for c in comps
                   if c not in ADD_COMBINE
                   and c not in MIN_COMBINE and c not in MAX_COMBINE]
        if unknown:
            raise ValueError(
                f"no sliding-ring combine class for components {unknown}")
        from ..runtime.aotcache import aot_jit

        self._advance = aot_jit(self._advance_impl,
                                    op=self._watch_op("advance"),
                                    kind="boundary", donate_argnums=(0,))
        self._flip = aot_jit(self._flip_impl,
                                 op=self._watch_op("flip"),
                                 kind="boundary", donate_argnums=(0,))
        self._query = aot_jit(self._query_impl,
                                  op=self._watch_op("query"),
                                  kind="boundary")
        # the query's components are the tail's to overwrite: nothing
        # else reads them on this path. Watched at the fold's cadence
        # ("hot": one call in 64 sampled), not a boundary's one in 4: a
        # sampled call waits for the program on the dispatching thread,
        # the fused worker, and this one scatters a bucket of rows
        self._tail = aot_jit(self._tail_impl,
                                 op=self._watch_op("tail"),
                                 donate_argnums=(0,))
        #: rows of the tail's one static edge shape
        self.edge_rows = TAIL_EDGE_CHUNKS * int(gb.micro_batch)
        from ..observability import jitcert

        jitcert.register_kernel(self)

    def _watch_op(self, site: str) -> str:
        return f"{self.watch_prefix}.{site}"

    # ------------------------------------------------------------ layout
    def _comp_dims(self, comp: str):
        """Per-key trailing dims of one component (matches the pane state
        minus its (n_panes, capacity) lead)."""
        if comp == "act":
            return ()
        from .aggspec import WIDE_COMPONENTS
        from .groupby import _wide_size

        k = len(self.gb.comp_specs[comp])
        if comp in WIDE_COMPONENTS:
            return (k, _wide_size(comp))
        return (k,)

    def init_state(self) -> Dict[str, Any]:
        import jax.numpy as jnp

        out: Dict[str, Any] = {}
        for c in self.add_comps:
            out[f"tot_{c}"] = jnp.zeros(
                (self.capacity,) + self._comp_dims(c), dtype=jnp.float32)
        for c in self.mm_comps:
            shape = (self.capacity,) + self._comp_dims(c)
            out[f"back_{c}"] = jnp.full(shape, _INIT[c], dtype=jnp.float32)
            out[f"front_{c}"] = jnp.full(
                (self.n_ring_panes,) + shape, _INIT[c], dtype=jnp.float32)
        return out

    def grow(self, ring: Dict[str, Any], new_capacity: int) -> Dict[str, Any]:
        """Pad the key axis to a grown capacity, preserving partials (the
        add identity is 0, mn/mx/hll pad with their combine identities)."""
        import jax.numpy as jnp

        out: Dict[str, Any] = {}
        for key, arr in ring.items():
            comp = key.split("_", 1)[1]
            axis = 1 if key.startswith("front_") else 0
            pad = [(0, 0)] * arr.ndim
            pad[axis] = (0, int(new_capacity) - arr.shape[axis])
            out[key] = jnp.pad(arr, pad, constant_values=_INIT[comp])
        self.capacity = int(new_capacity)
        return out

    @staticmethod
    def state_nbytes(ring: Dict[str, Any]) -> int:
        return sum(int(getattr(a, "nbytes", 0) or 0) for a in ring.values())

    def estimate_bytes(self, capacity: int) -> int:
        """Static HBM footprint at a given key capacity — checked against
        the sliding_dev_ring_mb budget before the ring is allocated."""
        total = 0
        for c in self.add_comps:
            total += int(np.prod((capacity,) + self._comp_dims(c),
                                 dtype=np.int64)) * 4
        for c in self.mm_comps:
            per = int(np.prod((capacity,) + self._comp_dims(c),
                              dtype=np.int64)) * 4
            total += per * (1 + self.n_ring_panes)
        return total

    # ----------------------------------------------------------- kernels
    @staticmethod
    def _combine(comp: str, a, b):
        import jax.numpy as jnp

        if comp in MIN_COMBINE:
            return jnp.minimum(a, b)
        return jnp.maximum(a, b)

    def _advance_impl(self, ring, pane_state, closed_slot, closed_on,
                      evict_slot, evict_on):
        """O(1) ring step: absorb the just-closed pane into the running
        partials, subtract the evicted pane from the additive totals."""
        import jax.numpy as jnp

        out = dict(ring)
        for c in self.add_comps:
            p_new = pane_state[c][closed_slot]
            p_old = pane_state[c][evict_slot]
            zero = jnp.zeros_like(p_new)
            out[f"tot_{c}"] = (ring[f"tot_{c}"]
                               + jnp.where(closed_on, p_new, zero)
                               - jnp.where(evict_on, p_old, zero))
        for c in self.mm_comps:
            p_new = jnp.where(closed_on, pane_state[c][closed_slot],
                              jnp.float32(_INIT[c]))
            out[f"back_{c}"] = self._combine(c, ring[f"back_{c}"], p_new)
        return out

    def _flip_impl(self, ring, pane_state, order, valid):
        """The DABA flip: rebuild every running partial from the live
        panes in one pass. `order` is an age-ordered rotation of the ring
        slots (a permutation — the scatter back to slot-major rows is
        collision-free); `valid` masks slots to their combine identity.
        The front stack becomes the reverse cumulative combine (suffix
        aggregates); the back stack resets to identity; additive totals
        become the masked sum."""
        import jax
        import jax.numpy as jnp

        out = dict(ring)
        # a masked sum does not depend on the panes' order: carry the mask
        # to the slots and read the panes where they lie (a gather into
        # age order would be a second copy of every pane: 3.5 GB of
        # 1,024-bin histograms at 16,384 keys)
        slot_on = jnp.zeros(self.gb.n_panes, dtype=jnp.bool_) \
            .at[order].set(valid)
        for c in self.add_comps:
            p = pane_state[c]
            vm = slot_on.reshape((-1,) + (1,) * (p.ndim - 1))
            out[f"tot_{c}"] = jnp.sum(jnp.where(vm, p, 0.0), axis=0)
        for c in self.mm_comps:
            ident = jnp.float32(_INIT[c])
            g = pane_state[c][order]
            vm = valid.reshape((-1,) + (1,) * (g.ndim - 1))
            g = jnp.where(vm, g, ident)
            if c in MIN_COMBINE:
                suffix = jax.lax.cummin(g, axis=0, reverse=True)
            else:
                suffix = jax.lax.cummax(g, axis=0, reverse=True)
            out[f"front_{c}"] = ring[f"front_{c}"].at[order].set(suffix)
            out[f"back_{c}"] = jnp.full_like(ring[f"back_{c}"], _INIT[c])
        return out

    def _query_impl(self, ring, pane_state, body_on, f_on, f_idx,
                    adj_slots, adj_w, adj_mm):
        """Trigger-time window body: one combine of the two running
        partials plus at most QUERY_ADJ pane-slice adjustments, stacked
        into the SAME (capacity, W) components array _components_body
        produces — the host merge/final-value tail is shared with the
        prefinalize emit path."""
        import jax
        import jax.numpy as jnp

        cap = self.capacity
        parts = []
        for c in sorted(self.gb.comp_specs) + ["act"]:
            # scoped so a device trace's op names say which component's
            # combine they belong to (kuiper/slide_query/<comp>)
            with jax.named_scope(f"kuiper/slide_query/{c}"):
                if c in ADD_COMBINE:
                    v = jnp.where(body_on, ring[f"tot_{c}"], 0.0)
                    for i in range(QUERY_ADJ):
                        v = v + adj_w[i] * pane_state[c][adj_slots[i]]
                else:
                    ident = jnp.float32(_INIT[c])
                    v = jnp.where(jnp.logical_and(body_on, f_on),
                                  ring[f"front_{c}"][f_idx], ident)
                    v = self._combine(
                        c, v, jnp.where(body_on, ring[f"back_{c}"], ident))
                    for i in range(QUERY_ADJ):
                        v = self._combine(
                            c, v, jnp.where(adj_mm[i],
                                            pane_state[c][adj_slots[i]],
                                            ident))
                parts.append(v.reshape(cap, -1))
        with jax.named_scope("kuiper/slide_query/stack"):
            return jnp.concatenate(parts, axis=1)

    def _tail_impl(self, body, cols, slots, n_valid):
        """Finish a trigger on the device: `body` is the query's stacked
        (capacity, W) components, `cols`/`slots` one edge buffer of
        `edge_rows` rows of which the first `n_valid` count. The edge
        rows scatter into a one-pane identity state with the fold's own
        code, a micro-batch at a time and only as many as hold rows; body
        and edges combine per component class as `merge_components` does
        (add / min / max); `_final_value` per spec gives the
        `(n_specs + 1, capacity)` values + `act` that `_finalize_dyn`
        returns. The merged components come back too, in the body's
        layout: a trigger with more edge rows than one buffer holds feeds
        them to the next call."""
        import jax
        import jax.numpy as jnp

        gb = self.gb
        cap = body.shape[0]
        mb = gb.micro_batch
        layout = gb._components_layout()
        with jax.named_scope("kuiper/slide_tail/edge_scatter"):
            edge = {comp: jnp.full((1, cap) + shape, _INIT[comp],
                                   dtype=jnp.float32)
                    for comp, _col, _w, shape in layout}
            pane = jnp.zeros((), dtype=jnp.int32)

            def scatter(i, state):
                at = i * mb
                rows = {k: jax.lax.dynamic_slice_in_dim(v, at, mb)
                        for k, v in cols.items()}
                base = at + jnp.arange(mb, dtype=jnp.int32) < n_valid
                return gb._fold_core(
                    dict(state), rows,
                    jax.lax.dynamic_slice_in_dim(slots, at, mb), base, pane)

            edge = jax.lax.fori_loop(0, (n_valid + mb - 1) // mb, scatter,
                                     edge)
        merged = {}
        for comp, col, w, shape in layout:
            with jax.named_scope(f"kuiper/slide_tail/merge_{comp}"):
                b = body[:, col:col + w].reshape((cap,) + shape)
                e = edge[comp][0]
                merged[comp] = (b + e if comp in ADD_COMBINE
                                else self._combine(comp, b, e))
        with jax.named_scope("kuiper/slide_tail/values"):
            outs = []
            for i, spec in enumerate(gb.plan.specs):
                outs.append(gb._final_value(spec, {
                    comp: merged[comp][:, gb.comp_specs[comp].index(i)]
                    for comp in spec.components}))
            final = jnp.stack(outs + [merged["act"]], axis=0)
        with jax.named_scope("kuiper/slide_tail/stack"):
            return jnp.concatenate(
                [merged[comp].reshape(cap, -1)
                 for comp, _col, _w, _shape in layout], axis=1), final

    # ---------------------------------------------------------- wrappers
    def advance(self, ring, pane_state, closed_slot: int, closed_on: bool,
                evict_slot: int, evict_on: bool):
        import jax.numpy as jnp

        return self._advance(
            ring, pane_state,
            jnp.asarray(int(closed_slot), dtype=jnp.int32),
            jnp.asarray(bool(closed_on)),
            jnp.asarray(int(evict_slot), dtype=jnp.int32),
            jnp.asarray(bool(evict_on)))

    def flip(self, ring, pane_state, base_slot: int, valid: np.ndarray):
        """Rebuild partials over the age-ordered rotation starting at
        `base_slot`; `valid[i]` says whether slot (base+i) % R holds live
        data for the flip span."""
        import jax.numpy as jnp

        order = ((int(base_slot)
                  + np.arange(self.n_ring_panes, dtype=np.int64))
                 % self.n_ring_panes).astype(np.int32)
        return self._flip(ring, pane_state, jnp.asarray(order),
                          jnp.asarray(np.asarray(valid, dtype=np.bool_)))

    def query(self, ring, pane_state, *, body_on: bool, f_on: bool,
              f_slot: int, adj_slots: np.ndarray,
              adj_weights: np.ndarray, adj_mm: np.ndarray):
        """Dispatch the O(1) window-body combine; returns the stacked
        (capacity, W) components on the device — the tail's input."""
        import jax.numpy as jnp

        return self._query(
            ring, pane_state,
            jnp.asarray(bool(body_on)), jnp.asarray(bool(f_on)),
            jnp.asarray(int(f_slot), dtype=jnp.int32),
            jnp.asarray(np.asarray(adj_slots, dtype=np.int32)),
            jnp.asarray(np.asarray(adj_weights, dtype=np.float32)),
            jnp.asarray(np.asarray(adj_mm, dtype=np.bool_)))

    def edge_buffers(self, segs):
        """A trigger's edge rows gathered into buffers at the tail's one
        static shape. `segs` is a list of (cols, valid, slots, sel): a
        retained segment's kernel inputs and the rows of it inside the
        window's cut (an index array; None: all of them). Returns a list
        of (cols, valid, slots, n), one entry but where the rows pass
        `edge_rows` (none still gives one, with n = 0): the plan's columns
        at their upload dtypes, a validity mask for every one of them
        (always there, so that one program serves batches with and
        without nulls), rows past n zeroed."""
        from .groupby import col_np_dtype, slot_dtype

        counts = [len(slots) if sel is None else len(sel)
                  for _c, _v, slots, sel in segs]
        n = sum(counts)
        rows = self.edge_rows
        n_buf = max(-(-n // rows), 1)

        def gather(parts, dtype):
            flat = np.empty(n_buf * rows, dtype=dtype)
            flat[n:] = 0
            if parts:
                np.concatenate(parts, out=flat[:n], casting="unsafe")
            return flat

        def pick(a, sel):
            return a if sel is None else a[sel]

        plan = self.gb.plan
        cols = {name: gather([pick(c[name], sel) for c, _v, _s, sel in segs],
                             col_np_dtype(plan, name))
                for name in plan.columns}
        valid = {name: gather(
            [np.ones(k, dtype=np.bool_) if v.get(name) is None
             else pick(v[name], sel)
             for (_c, v, _s, sel), k in zip(segs, counts)], np.bool_)
            for name in plan.columns}
        slots = gather([pick(s, sel) for _c, _v, s, sel in segs],
                       slot_dtype(self.capacity))
        return [({k: v[at:at + rows] for k, v in cols.items()},
                 {k: v[at:at + rows] for k, v in valid.items()},
                 slots[at:at + rows], min(n - at, rows))
                for at in range(0, n_buf * rows, rows)]

    def tail_begin(self, body, buffers):
        """Dispatch the device tail over the query's `body` and the
        trigger's edge rows (`buffers`: what `edge_buffers` returns) and
        start the async copy of the compact result; returns the
        `(n_specs + 1, capacity)` device array."""
        import jax.numpy as jnp

        final = None
        for cols, valid, slots, n in buffers:
            dev = {name: jnp.asarray(c) for name, c in cols.items()}
            for name, v in valid.items():
                dev["__valid_" + name] = jnp.asarray(v)
            body, final = self._tail(body, dev, jnp.asarray(slots),
                                     jnp.asarray(int(n), dtype=jnp.int32))
        final.copy_to_host_async()
        return final
