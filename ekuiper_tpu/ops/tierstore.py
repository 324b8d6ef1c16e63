"""Tiered key state — HBM-resident hot set with host spill and async prefetch.

Every GROUP BY key's window state has so far had to fit HBM (memwatch
budgets and dev-ring FIFO eviction were the only relief), capping
cardinality near the ~1M-slot bench shape. Following "Support Aggregate
Analytic Window Function over Large Data by Spilling" (arxiv 2007.10385),
this module splits key state into two tiers:

- **hot**: keys keep their dense device slots — today's `DeviceGroupBy`
  state, layout unchanged. A per-slot `uint32` touch column rides the
  state pytree and is bumped inside the existing certified fold (one
  scatter-add — no new host sync), giving the placement policy
  recency/frequency at zero extra round trips.
- **cold**: keys whose touch counter goes idle are demoted at pane
  boundaries: one certified gather (`tierstore.demote`) packs their
  per-pane partial aggregates into a `(D, W)` row block, resets the
  slots to the fold identity, and the freed slots recycle through
  `KeyTable`'s free list — capacity-grow becomes a last resort instead
  of the only move. The packed rows land (async copy, harvested off the
  fold thread by the prefinalize/emit worker) in a pinned host arena
  (`HostTierStore`).

When a demoted key reappears in an ingest batch, the slot-encode path is
the admission point: the batch's new-key log tells us exactly which keys
are returning before the fold runs, and one certified scatter
(`tierstore.promote`) merges their spilled per-pane partials back into a
fresh device slot — add/min/max per component, the fold's own combine
algebra, so the emission is bit-equal to never having demoted. The
ingest prep's upload stage can start the H2D copy of the packed rows a
batch early (`TierManager.prefetch`, runtime/ingest.py).

Exactness across demotion windows: spilled rows remember the per-pane
**reset epoch** they were packed under; a pane reset (window expiry)
bumps the live epoch, so stale pane slices are masked to the fold
identity at promote/emit time instead of leaking a closed window's rows
into a newer one. Spilled keys with live pane data still contribute to
window emissions: `TierManager.window_groups` computes their final
values host-side (the prefinalize numpy tail) and the fused node emits
them alongside the device groups. Sliding/DABA rules demote only
quiescent keys (idle past the whole ring retention), and every
demote/promote marks the ring dirty so the next trigger rebuilds from
the panes (the exact `components_dyn` fallback path).

docs/TIERED_STATE.md documents the policy, the demote/promote protocol,
the exactness argument, and the knobs.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..utils import timex
from .aggspec import WIDE_COMPONENTS
from .groupby import _INIT, _wide_size, apply_int_semantics


# ---------------------------------------------------------------- geometry
@dataclass(frozen=True)
class TierLayout:
    """Plan-time tier geometry — chosen once (planner/planner.py
    plan_tier_layout) and shared with the jitcert derivations, like the
    sliding ring's plan_ring_layout."""

    #: resident-slot target: the demote policy starts evicting cold keys
    #: once live (non-free) slots exceed this
    hot_slots: int
    #: D — slots per demote/promote dispatch; fixed at plan time so each
    #: site compiles ONE executable per capacity-ladder step
    demote_batch: int
    #: placement-policy cadence (engine clock, ms)
    scan_interval_ms: int
    #: consecutive zero-touch-delta scans before a key is demotable
    min_idle_scans: int

    def hot_capacity(self) -> int:
        """The pow2-rounded construction capacity the hot target implies
        — THE one formula shared by node construction (nodes_fused.py)
        and admission pricing (runtime/control.py), so pricing can never
        desynchronize from what gets built."""
        return max(1 << max(self.hot_slots - 1, 1).bit_length(), 1024)


def env_hbm_budget_mb() -> float:
    """The engine-wide KUIPER_HBM_BUDGET_MB (the QoS admission ledger's
    budget), 0 when unset/unparseable — the ONE parse shared by the
    planner's resolve_tier_budget_mb, the shared pane store, and bench."""
    import os

    try:
        return max(float(os.environ.get("KUIPER_HBM_BUDGET_MB", "0")
                         or 0), 0.0)
    except ValueError:
        return 0.0


def state_bytes_per_key(plan, n_panes: int) -> int:
    """Static device bytes per key slot of a plan's group-by state
    (float32 components + act + the uint32 touch column)."""
    comp_specs: Dict[str, int] = {}
    for spec in plan.specs:
        for comp in spec.components:
            comp_specs[comp] = comp_specs.get(comp, 0) + 1
    total = n_panes  # act
    for comp, k in comp_specs.items():
        total += n_panes * k * (_wide_size(comp) if comp in WIDE_COMPONENTS
                                else 1)
    return total * 4 + 4  # + uint32 touch


#: fraction of the HBM budget the hot group-by state may claim (the rest
#: covers micro-batch staging, sliding rings, emit transfers)
HOT_BUDGET_FRACTION = 0.5

DEFAULT_DEMOTE_BATCH = 2048
DEFAULT_MIN_IDLE_SCANS = 2
#: demote dispatches per boundary — bounds the fold-thread work a single
#: boundary can spend evicting (D x this = max slots freed per boundary)
MAX_DEMOTE_BATCHES = 8


def plan_tier_layout(plan, n_panes: int, capacity: int,
                     budget_mb: float, scan_interval_ms: int = 0,
                     window_ms: int = 0) -> Optional[TierLayout]:
    """Tier geometry for a rule: hot-slot target from the HBM budget and
    the plan's per-key state width. None when the budget already covers
    the requested capacity ladder headroom (tiering would be a no-op) —
    unless the budget is tighter than the base capacity, in which case
    the hot target clamps below it."""
    if budget_mb <= 0:
        return None
    per_key = max(state_bytes_per_key(plan, n_panes), 1)
    budget_keys = int(budget_mb * HOT_BUDGET_FRACTION * (1 << 20) / per_key)
    if budget_keys >= capacity * 4:
        # the budget fits two doublings of the requested capacity — the
        # grow ladder has room and eviction pressure would be noise
        return None
    hot = max(min(budget_keys, capacity * 4), 1024)
    scan = int(scan_interval_ms) or max(min(int(window_ms) or 1000, 5000),
                                        250)
    return TierLayout(hot_slots=hot, demote_batch=DEFAULT_DEMOTE_BATCH,
                      scan_interval_ms=scan,
                      min_idle_scans=DEFAULT_MIN_IDLE_SCANS)


# ----------------------------------------------------------- device kernel
class TierStore:
    """The certified demote/promote gather/scatter sites over one
    group-by kernel's state. Packed row layout (per key, float32[W]):
    each component's per-pane block `(n_panes, k[, wide])` flattened
    C-order in sorted component order, then the `(n_panes,)` act block —
    the same sort the state pytree flattens with, so the derivation in
    observability/jitcert.py mirrors the layout exactly."""

    watch_prefix = "tierstore"

    def __init__(self, gb, layout: TierLayout) -> None:
        self.gb = gb
        self.layout = layout
        self.capacity = int(gb.capacity)
        self.demote_batch = int(layout.demote_batch)
        self.n_panes = int(gb.n_panes)
        self.blocks: List[Tuple[str, int, Tuple[int, ...]]] = []
        col = 0
        for comp in sorted(gb.comp_specs):
            tail: Tuple[int, ...] = (len(gb.comp_specs[comp]),)
            if comp in WIDE_COMPONENTS:
                tail = tail + (_wide_size(comp),)
            w = self.n_panes * int(np.prod(tail))
            self.blocks.append((comp, col, tail))
            col += w
        self.blocks.append(("act", col, ()))
        col += self.n_panes
        self.packed_w = col
        from ..runtime.aotcache import aot_jit

        self._demote = aot_jit(self._demote_impl,
                                   op=self._watch_op("demote"),
                                   kind="boundary", donate_argnums=(0,))
        self._promote = aot_jit(self._promote_impl,
                                    op=self._watch_op("promote"),
                                    kind="boundary", donate_argnums=(0,))
        from ..observability import jitcert

        jitcert.register_kernel(self)

    def _watch_op(self, site: str) -> str:
        return f"{self.watch_prefix}.{site}"

    # ------------------------------------------------------------- rows
    def init_row(self) -> np.ndarray:
        """The fold-identity packed row (promote's no-op; also the
        demote result for a slot holding no live data)."""
        row = np.empty(self.packed_w, dtype=np.float32)
        for comp, off, tail in self.blocks:
            w = self.n_panes * int(np.prod(tail)) if tail else self.n_panes
            row[off:off + w] = _INIT[comp]
        return row

    def row_is_idle(self, row: np.ndarray) -> bool:
        """True when a packed row holds no live data — its act block is
        all-zero (act counts post-WHERE rows per pane; every other
        component is init-valued exactly when act is)."""
        comp, off, _ = self.blocks[-1]
        assert comp == "act"
        return not row[off:off + self.n_panes].any()

    def mask_stale_panes(self, row: np.ndarray,
                         stale: np.ndarray) -> np.ndarray:
        """Reset the pane slices of `row` flagged in `stale` (bool[P]) to
        the fold identity — a closed window's rows must never leak into
        the pane's next tenant bucket."""
        if not stale.any():
            return row
        for comp, off, tail in self.blocks:
            w = int(np.prod(tail)) if tail else 1
            seg = row[off:off + self.n_panes * w].reshape(self.n_panes, w)
            seg[stale] = _INIT[comp]
        return row

    # ----------------------------------------------------------- device
    def demote(self, state, slots: np.ndarray):
        """Gather `slots`' per-pane partials into a packed (D, W) device
        block and reset the slots (touch included) to the fold identity.
        `slots` pads to D with duplicates of a real entry — the gather
        rows are ignored by the harvester and the identity set is
        idempotent. Returns (state, packed_dev)."""
        import jax.numpy as jnp

        s = np.asarray(slots, dtype=np.int32)
        if len(s) < self.demote_batch:
            s = np.concatenate([
                s, np.full(self.demote_batch - len(s), s[0], np.int32)])
        return self._demote(state, jnp.asarray(s))

    def promote(self, state, packed: Any, slots: np.ndarray):
        """Scatter-merge packed rows back into device slots: add for the
        additive components (n/s1/s2/hist/hh/act), min/max for mn and
        mx/hll — the fold's combine algebra, so a promoted key's state
        is bit-equal to never having left. Padding rows must be
        `init_row()` (the combine identity) so duplicate pad slots are
        no-ops. `packed` may be a pre-uploaded device block (prefetch)."""
        import jax
        import jax.numpy as jnp

        s = np.asarray(slots, dtype=np.int32)
        n = len(s)
        if n < self.demote_batch:
            s = np.concatenate([
                s, np.full(self.demote_batch - n, s[0], np.int32)])
        if not isinstance(packed, jax.Array):
            # pad rows past the real entries with the combine IDENTITY —
            # the pad slots are duplicates of a real slot, so anything
            # else would double-merge it
            arr = np.asarray(packed, dtype=np.float32)
            block = np.tile(self.init_row(), (self.demote_batch, 1))
            block[:n] = arr[:n]
            packed = jnp.asarray(block)
        return self._promote(state, packed, jnp.asarray(s))

    def _demote_impl(self, state, slots):
        import jax.numpy as jnp

        parts = []
        for comp, _off, _tail in self.blocks:
            arr = state[comp]  # (P, cap[, k[, wide]])
            g = jnp.moveaxis(jnp.take(arr, slots, axis=1), 1, 0)
            parts.append(g.reshape(g.shape[0], -1))
            state[comp] = arr.at[:, slots].set(
                jnp.asarray(_INIT[comp], dtype=arr.dtype))
        if "touch" in state:
            t = state["touch"]
            state["touch"] = t.at[slots].set(jnp.asarray(0, dtype=t.dtype))
        return state, jnp.concatenate(parts, axis=1)

    def _promote_impl(self, state, packed, slots):
        import jax.numpy as jnp

        col = 0
        for comp, _off, tail in self.blocks:
            arr = state[comp]
            w = int(np.prod(tail)) if tail else 1
            seg = packed[:, col:col + self.n_panes * w]
            col += self.n_panes * w
            seg = seg.reshape(seg.shape[0], self.n_panes, *tail)
            seg = jnp.moveaxis(seg, 0, 1)  # (P, D, ...)
            if comp == "mn":
                state[comp] = arr.at[:, slots].min(seg)
            elif comp in ("mx", "hll"):
                state[comp] = arr.at[:, slots].max(seg)
            else:
                state[comp] = arr.at[:, slots].add(seg)
        return state


# ------------------------------------------------------------- host store
class HostTierStore:
    """Pinned host arena for spilled per-pane partial rows: one growable
    float32 `(rows, W)` block plus an int64 `(rows, P)` epoch sidecar —
    contiguous allocations, not a dict of a million small arrays, so the
    memwatch probe's estimate IS the allocation (tested)."""

    def __init__(self, packed_w: int, n_panes: int,
                 initial_rows: int = 1024) -> None:
        self.packed_w = int(packed_w)
        self.n_panes = int(n_panes)
        n = max(int(initial_rows), 16)
        self._rows = np.zeros((n, self.packed_w), dtype=np.float32)
        self._epochs = np.zeros((n, self.n_panes), dtype=np.int64)
        self._key_row: Dict[Any, int] = {}
        self._row_key: List[Any] = [None] * n
        self._free: List[int] = list(range(n - 1, -1, -1))

    def __len__(self) -> int:
        return len(self._key_row)

    def __contains__(self, key) -> bool:
        return key in self._key_row

    def nbytes(self) -> int:
        """Arena bytes — the tier_host_store memwatch probe."""
        return int(self._rows.nbytes + self._epochs.nbytes)

    def _grow(self) -> None:
        n = len(self._row_key)
        self._rows = np.concatenate(
            [self._rows, np.zeros_like(self._rows)], axis=0)
        self._epochs = np.concatenate(
            [self._epochs, np.zeros_like(self._epochs)], axis=0)
        self._row_key.extend([None] * n)
        self._free.extend(range(2 * n - 1, n - 1, -1))

    def put(self, key, row: np.ndarray, epochs: np.ndarray) -> None:
        at = self._key_row.get(key)
        if at is None:
            if not self._free:
                self._grow()
            at = self._free.pop()
            self._key_row[key] = at
            self._row_key[at] = key
        self._rows[at] = row
        self._epochs[at] = epochs

    def take(self, key) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Remove and return (row copy, epoch copy) for a promoted key."""
        at = self._key_row.pop(key, None)
        if at is None:
            return None
        self._row_key[at] = None
        self._free.append(at)
        return self._rows[at].copy(), self._epochs[at].copy()

    def peek(self, key) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        at = self._key_row.get(key)
        if at is None:
            return None
        return self._rows[at], self._epochs[at]

    def drop(self, key) -> bool:
        at = self._key_row.pop(key, None)
        if at is None:
            return False
        self._row_key[at] = None
        self._free.append(at)
        return True

    def items_arrays(self):
        """(keys list, rows view, epochs view) over the resident set —
        the vectorized base of window_groups. Views are read-only by
        contract (callers copy before mutating)."""
        if not self._key_row:
            return [], None, None
        idx = np.fromiter(self._key_row.values(), dtype=np.int64,
                          count=len(self._key_row))
        keys = [self._row_key[i] for i in idx]
        return keys, self._rows[idx], self._epochs[idx]


# -------------------------------------------------------------- telemetry
# weakref registry of live TierManagers — the kuiper_spill_* /
# kuiper_tier_host_bytes render source (utils/weakreg.py, THE shared
# ownership model)
from ..utils.weakreg import WeakRegistry as _TierRegistry

_registry = _TierRegistry()


def registry() -> _TierRegistry:
    return _registry


def reset() -> None:
    """Test hook."""
    _registry.clear()


def render_prometheus(out: List[str], esc) -> None:
    """Append the spill metric families to a /metrics scrape."""
    fams = (
        ("kuiper_spill_demoted_total", "counter",
         "key slots demoted to the host cold tier",
         lambda m: m.demoted_total),
        ("kuiper_spill_promoted_total", "counter",
         "demoted keys promoted back to device slots on reappearance",
         lambda m: m.promoted_total),
        ("kuiper_spill_resident_total", "gauge",
         "keys currently resident in the host cold tier",
         lambda m: len(m.store)),
        ("kuiper_tier_host_bytes", "gauge",
         "host arena bytes held by the cold-tier spill store",
         lambda m: m.store.nbytes()),
    )
    mgrs = _registry.managers()
    for name, mtype, help_txt, fn in fams:
        out.append(f"# TYPE {name} {mtype}")
        out.append(f"# HELP {name} {help_txt}")
        # aggregate per rule label: several managers can share one label
        # (every tiered shared pane store reports as "__shared__") and
        # duplicate sample lines would fail the whole Prometheus scrape
        agg: Dict[str, int] = {}
        for m, rule in mgrs:
            try:
                v = int(fn(m))
            except Exception:
                continue
            label = rule or "__engine__"
            agg[label] = agg.get(label, 0) + v
        for label, v in sorted(agg.items()):
            out.append(f'{name}{{rule="{esc(label)}"}} {v}')


def diagnostics() -> List[Dict[str, Any]]:
    """Per-manager tier state for GET /diagnostics + kuiperdiag."""
    rows = []
    for m, rule in _registry.managers():
        with m._mu:
            rows.append({
                "rule": rule, "hot_slots": m.layout.hot_slots,
                "demote_batch": m.layout.demote_batch,
                "demoted_total": m.demoted_total,
                "promoted_total": m.promoted_total,
                "recycled_total": m.recycled_total,
                "prefetch_hits": m.prefetch_hits,
                "resident": len(m.store),
                "host_bytes": m.store.nbytes(),
            })
    return rows


# ---------------------------------------------------------------- manager
class TierManager:
    """The placement policy + the host tier, bound to one fused node's
    kernel and key table. Thread contract:

    - fold thread: `admit` (promotions at the slot-encode admission
      point), `on_boundary` (apply the pending demote plan + dispatch the
      touch-column scan), `note_pane_reset` (epoch bumps),
      `window_groups` (spilled emissions).
    - prefinalize/emit worker: `worker_task` — harvest landed demote
      blocks into the arena, run the scan policy, prune stale rows.
    - ingest prep pool: `prefetch` — early H2D of packed rows for
      returning keys spotted in a decoding batch.

    `_mu` guards the store/mirror/plan; `KeyTable` is only ever touched
    from the fold thread."""

    def __init__(self, gb, kt, layout: TierLayout, *, rule_id: str = "",
                 key_name: Optional[str] = None,
                 submit: Optional[Callable[[tuple], None]] = None,
                 quiescent_only: bool = False,
                 min_idle_ms: int = 0,
                 on_tier_event: Optional[Callable[..., None]] = None
                 ) -> None:
        self.gb = gb
        self.kt = kt
        self.layout = layout
        self.ts = TierStore(gb, layout)
        self.store = HostTierStore(self.ts.packed_w, self.ts.n_panes)
        self.key_name = key_name
        self.rule_id = rule_id
        self._submit = submit
        self.quiescent_only = bool(quiescent_only)
        self.min_idle_ms = int(min_idle_ms)
        self._on_tier_event = on_tier_event
        self._mu = threading.Lock()
        self._pane_epoch = np.zeros(self.ts.n_panes, dtype=np.int64)
        self._mirror = np.zeros(0, dtype=np.int64)
        self._idle = np.zeros(0, dtype=np.int32)
        self._plan: List[int] = []  # slots pending demotion (worker-chosen)
        # demote blocks dispatched but not yet harvested: key ->
        # (packed_dev, row index, epochs). A key reappearing inside this
        # window must still promote exactly — admit() fetches its row
        # straight off the pending device block
        self._inflight: Dict[Any, Tuple[Any, int, np.ndarray]] = {}
        self._requeue: List[Tuple[Any, np.ndarray, np.ndarray]] = []
        self._prefetch_q: List[Tuple[tuple, Any]] = []  # (keys, dev block)
        self._last_scan_ms = 0
        self.demoted_total = 0
        self.promoted_total = 0
        self.recycled_total = 0
        self.prefetch_hits = 0
        kt.track_new = True
        from ..observability import memwatch

        memwatch.register("tier_host_store", self,
                          lambda m: m.store.nbytes(), rule=rule_id)
        _registry.register(self, rule_id)

    # ------------------------------------------------------------ epochs
    def note_pane_reset(self, pane: int) -> None:
        with self._mu:
            self._pane_epoch[int(pane)] += 1

    def pane_epochs(self) -> np.ndarray:
        with self._mu:
            return self._pane_epoch.copy()

    # ------------------------------------------------------- fold thread
    def admit(self, state):
        """Promotion at the slot-encode admission point: drain the key
        table's new-key log; any returning key (resident in the cold
        tier) gets its spilled partials merged back into its fresh slot
        before the batch folds. Dispatch-only — the scatter is async on
        the device stream, the fold queues behind it."""
        new = self.kt.drain_new_keys()
        requeued: List[Tuple[Any, np.ndarray, np.ndarray]] = []
        if self._requeue:
            with self._mu:
                requeued, self._requeue = self._requeue, []
        if not new and not requeued:
            return state
        batch_keys: List[Any] = []
        batch_slots: List[int] = []
        batch_rows: List[np.ndarray] = []
        pending: List[Tuple[Any, int, Any, int, np.ndarray]] = []
        with self._mu:
            epoch = self._pane_epoch.copy()
            hits = [(k, s) for (k, s) in new if k in self.store]
            rows = {k: self.store.take(k) for (k, _s) in hits}
            for k, s in new:
                entry = self._inflight.pop(k, None)
                if entry is not None:
                    # returned before its demote block was harvested:
                    # read the row straight off the pending device copy
                    pending.append((k, s, entry[0], entry[1], entry[2]))
        for k, s, packed_dev, idx, row_epochs in pending:
            # kuiperlint: ignore[host-sync]: rare promote-before-harvest path — the demote copy was already in flight, this only waits for it
            row = np.asarray(packed_dev)[idx].copy()
            hits.append((k, s))
            rows[k] = (row, row_epochs.copy())
        for key, row, row_epochs in requeued:
            # a non-quiescent demote raced the policy (quiescent mode):
            # the key re-enters the table and its partials go straight
            # back to the device — no data ever drops
            slots, _ = self.kt.encode_column(
                np.array([key], dtype=np.object_))
            hits.append((key, int(slots[0])))
            rows[key] = (row, row_epochs)
        if requeued and self.gb.capacity < self.kt.capacity:
            # the re-encode above ran AFTER the caller's grow check: a
            # slot past the state extent would be silently dropped by
            # the promote scatter — grow first
            state = self.gb.grow(state, self.kt.capacity)
        if not hits:
            return state
        for key, slot in hits:
            row, row_epochs = rows[key]
            stale = row_epochs != epoch
            self.ts.mask_stale_panes(row, stale)
            if self.ts.row_is_idle(row):
                # nothing live survived the stale mask: the key re-seats
                # with a fresh identity slot, no injection needed
                self.recycled_total += 1
                continue
            batch_keys.append(key)
            batch_slots.append(slot)
            batch_rows.append(row)
        if not batch_keys:
            return state
        D = self.ts.demote_batch
        for start in range(0, len(batch_keys), D):
            keys = batch_keys[start:start + D]
            slots = np.asarray(batch_slots[start:start + D],
                               dtype=np.int32)
            packed = self._prefetched_block(tuple(keys))
            if packed is None:
                block = np.tile(self.ts.init_row(), (D, 1))
                block[:len(keys)] = np.stack(batch_rows[start:start + D])
                packed = block
            state = self.ts.promote(state, packed, slots)
            self.promoted_total += len(keys)
        if self._on_tier_event is not None:
            self._on_tier_event("promote", n=len(batch_keys))
        return state

    def _prefetched_block(self, keys: tuple):
        """A device block the ingest prep staged for exactly this key
        run, if any (H2D already done off the fold thread). A block
        whose epoch snapshot no longer matches the live pane epochs is
        DISCARDED — a pane reset since the prefetch means its stale
        masking is out of date, and merging it would leak a closed
        window's partials into the pane's next tenant."""
        with self._mu:
            for i, (pk, dev, ep) in enumerate(self._prefetch_q):
                if pk == keys:
                    del self._prefetch_q[i]
                    if not np.array_equal(ep, self._pane_epoch):
                        return None  # stale prefetch: admit rebuilds
                    self.prefetch_hits += len(keys)
                    return dev
        return None

    def on_boundary(self, state):
        """Pane-boundary hook (fold thread): apply the worker's pending
        demote plan (one certified gather + async device→host copy, the
        harvest runs on the worker) and, on cadence, dispatch the touch
        scan the next plan is computed from."""
        with self._mu:
            plan, self._plan = self._plan, []
        if plan:
            keys: List[Any] = []
            slots: List[int] = []
            cap = self.ts.demote_batch * MAX_DEMOTE_BATCHES
            for slot in plan:
                if len(keys) >= cap:
                    break
                try:
                    key = self.kt.decode(slot)
                except Exception:
                    continue
                if key is None or not self._retirable(key):
                    continue
                keys.append(key)
                slots.append(int(slot))
            D = self.ts.demote_batch
            for start in range(0, len(keys), D):
                ck = keys[start:start + D]
                cs = slots[start:start + D]
                s = np.asarray(cs, dtype=np.int32)
                state, packed_dev = self.ts.demote(state, s)
                packed_dev.copy_to_host_async()
                self.kt.retire(cs, ck)
                self.demoted_total += len(ck)
                with self._mu:
                    epochs = self._pane_epoch.copy()
                    for i, key in enumerate(ck):
                        self._inflight[key] = (packed_dev, i, epochs)
                self._dispatch(("harvest", packed_dev, ck, epochs))
            if keys and self._on_tier_event is not None:
                self._on_tier_event("demote", n=len(keys))
        now = timex.now_ms()
        if now - self._last_scan_ms >= self.layout.scan_interval_ms \
                and "touch" in (state or {}):
            self._last_scan_ms = now
            import jax.numpy as jnp

            # a FRESH buffer, not the live state leaf: the next fold
            # donates the state pytree (donate_argnums), which would
            # delete the leaf out from under the worker's fetch — the
            # same class as bench.py's _block_marker slice
            touch_dev = state["touch"] + jnp.uint32(0)
            touch_dev.copy_to_host_async()
            self._dispatch(("scan", touch_dev, self.kt.n_keys,
                            list(self.kt.free_slots()), now))
        return state

    @staticmethod
    def _retirable(key) -> bool:
        """Keys whose normalized form aliases a raw form ("" from a nil
        key, tuples holding "") stay resident: retiring them would leave
        a dangling alias entry in the table. They are rare and bounded."""
        if key == "":
            return False
        if isinstance(key, tuple) and any(v == "" for v in key):
            return False
        return True

    def _dispatch(self, payload: tuple) -> None:
        if self._submit is not None:
            self._submit(payload)
        else:
            self.worker_task(payload)

    # ------------------------------------------------------ worker thread
    def worker_task(self, payload: tuple) -> None:
        """Prefinalize/emit-worker half: harvest landed demote blocks and
        run the placement policy. Never touches the KeyTable."""
        kind = payload[0]
        if kind == "harvest":
            self._harvest(payload[1], payload[2], payload[3])
        elif kind == "scan":
            self._scan(payload[1], payload[2], payload[3], payload[4])

    def _harvest(self, packed_dev, keys: List[Any],
                 epochs: np.ndarray) -> None:
        # kuiperlint: ignore[host-sync]: worker thread — the demote fetch IS the intended sync point, the fold thread dispatched and moved on
        arr = np.asarray(packed_dev)
        with self._mu:
            for i, key in enumerate(keys):
                entry = self._inflight.get(key)
                if entry is None or entry[0] is not packed_dev:
                    # admit() already consumed this key off the pending
                    # block (promote-before-harvest), or a NEWER demote
                    # of the same key superseded this one
                    continue
                del self._inflight[key]
                row = arr[i]
                if self.ts.row_is_idle(row):
                    self.recycled_total += 1  # pure slot recycle
                    continue
                if self.quiescent_only:
                    # the policy only demotes quiescent keys here; a racy
                    # touch between scan and apply can still spill live
                    # data — requeue it for immediate re-promotion
                    self._requeue.append((key, row.copy(), epochs.copy()))
                    continue
                self.store.put(key, row, epochs)

    def _scan(self, touch_dev, n_slots: int, free: List[int],
              now_ms: int) -> None:
        # kuiperlint: ignore[host-sync]: worker thread — scheduled touch-column fetch off the fold path
        counts = np.asarray(touch_dev)[:n_slots].astype(np.int64)
        with self._mu:
            if len(self._mirror) < len(counts):
                pad = len(counts) - len(self._mirror)
                self._mirror = np.concatenate(
                    [self._mirror, np.zeros(pad, np.int64)])
                self._idle = np.concatenate(
                    [self._idle, np.zeros(pad, np.int32)])
            mirror = self._mirror[:len(counts)]
            delta = counts - mirror
            idle = self._idle[:len(counts)]
            idle[delta != 0] = 0
            idle[delta == 0] += 1
            self._mirror[:len(counts)] = counts
            live = n_slots - len(free)
            overflow = live - self.layout.hot_slots
            plan: List[int] = []
            if overflow > 0:
                min_idle = self.layout.min_idle_scans
                if self.min_idle_ms:
                    min_idle = max(min_idle, -(-self.min_idle_ms
                                               // max(self.layout.
                                                      scan_interval_ms, 1)))
                free_set = set(free)
                cand = np.nonzero(idle >= min_idle)[0]
                if len(cand):
                    order = np.argsort(-idle[cand], kind="stable")
                    want = min(overflow,
                               self.layout.demote_batch
                               * MAX_DEMOTE_BATCHES)
                    for slot in cand[order].tolist():
                        if slot in free_set:
                            continue
                        plan.append(int(slot))
                        if len(plan) >= want:
                            break
            self._plan = plan
            # prune: resident rows whose every pane went stale carry no
            # information — a reappearance is just a fresh key
            self._prune_locked()

    def _prune_locked(self) -> None:
        keys, rows, epochs = self.store.items_arrays()
        if rows is None:
            return
        comp, off, _ = self.ts.blocks[-1]  # act block
        act = rows[:, off:off + self.ts.n_panes]
        valid = epochs == self._pane_epoch[None, :]
        dead = ~np.any((act > 0) & valid, axis=1)
        for i in np.nonzero(dead)[0].tolist():
            self.store.drop(keys[i])

    # ------------------------------------------------------ ingest prep
    def prefetch(self, batch) -> None:
        """Ingest-prep hook (decode-pool drainer): spot returning keys in
        a decoding batch and start their packed rows' H2D copy early, so
        `admit` finds the block already resident. Best-effort — a miss
        just means admit builds and uploads the block itself."""
        if self.key_name is None:
            return
        col = getattr(batch, "columns", {}).get(self.key_name)
        if col is None or not len(self.store):
            return
        try:
            distinct = list(dict.fromkeys(col.tolist()))
        except Exception:
            return
        # membership probes OUTSIDE the lock (GIL-atomic dict reads; a
        # stale hit just re-verifies below): a 64k-distinct batch must
        # not hold _mu — the fold thread's admit()/on_boundary() take it
        # every batch — for the whole scan. Bounded at D hits.
        key_map = self.store._key_row
        cand = []
        for k in distinct:
            if k in key_map:
                cand.append(k)
                if len(cand) >= self.ts.demote_batch:
                    break
        if not cand:
            return
        with self._mu:
            epoch = self._pane_epoch.copy()
            hits = []
            rows = []
            for k in cand:
                peeked = self.store.peek(k)  # re-verify under the lock
                if peeked is None:
                    continue
                row = peeked[0].copy()
                self.ts.mask_stale_panes(row, peeked[1] != epoch)
                hits.append(k)
                rows.append(row)
            if not hits:
                return
        D = self.ts.demote_batch
        block = np.tile(self.ts.init_row(), (D, 1))
        block[:len(rows)] = np.stack(rows)
        import jax.numpy as jnp

        dev = jnp.asarray(block)
        with self._mu:
            # the epoch snapshot rides along: a pane reset between this
            # prefetch and admit() invalidates the staged block (its
            # stale-masking was done against THESE epochs)
            self._prefetch_q.append((tuple(hits), dev, epoch))
            if len(self._prefetch_q) > 4:
                self._prefetch_q.pop(0)

    def _settle_inflight_locked(self) -> None:
        """Land any un-harvested demote blocks into the store NOW —
        boundary emission and checkpoints need the complete cold tier.
        Caller holds _mu. Rare: the worker normally harvests well inside
        one window period."""
        if not self._inflight:
            return
        items = list(self._inflight.items())
        self._inflight.clear()
        for key, (packed_dev, idx, epochs) in items:
            # kuiperlint: ignore[host-sync]: boundary/checkpoint settlement of an already-in-flight copy
            row = np.asarray(packed_dev)[idx]
            if self.ts.row_is_idle(row):
                self.recycled_total += 1
                continue
            if self.quiescent_only:
                # same contract as _harvest: a racy live spill in
                # quiescent mode re-promotes instead of parking in a
                # store the sliding emission path never reads
                self._requeue.append((key, row.copy(), epochs.copy()))
                continue
            self.store.put(key, row, epochs)

    # -------------------------------------------------------- emissions
    def window_groups(self, plan, panes: Optional[List[int]] = None):
        """Spilled keys' contribution to a closing window: merge each
        resident row's still-valid panes (subset `panes`, default all)
        and compute final values with the prefinalize numpy tail.
        Returns (keys, outs, act) like DeviceGroupBy.finalize, or None
        when no spilled key has live data for the window."""
        from .prefinalize import final_value_np

        with self._mu:
            self._settle_inflight_locked()
            keys, rows, epochs = self.store.items_arrays()
            if rows is None:
                return None
            rows = rows.copy()
            valid = epochs == self._pane_epoch[None, :]
        if panes is not None:
            pane_mask = np.zeros(self.ts.n_panes, dtype=np.bool_)
            pane_mask[list(panes)] = True
            valid = valid & pane_mask[None, :]
        comb: Dict[str, np.ndarray] = {}
        for comp, off, tail in self.ts.blocks:
            w = int(np.prod(tail)) if tail else 1
            seg = rows[:, off:off + self.ts.n_panes * w].reshape(
                len(keys), self.ts.n_panes, *(tail or ()))
            vm = valid.reshape(len(keys), self.ts.n_panes,
                               *([1] * len(tail)))
            if comp == "mn":
                m = np.min(np.where(vm, seg, np.inf), axis=1)
            elif comp in ("mx", "hll"):
                m = np.max(np.where(vm, seg, -np.inf), axis=1)
            else:
                m = np.sum(np.where(vm, seg, 0.0), axis=1)
            comb[comp] = m
        act = comb.pop("act")
        alive = np.nonzero(act > 0)[0]
        if not len(alive):
            return None
        comp_specs = self.gb.comp_specs
        outs: List[np.ndarray] = []
        for i, spec in enumerate(plan.specs):
            c = {comp: comb[comp][alive][:, comp_specs[comp].index(i)]
                 for comp in spec.components}
            outs.append(np.asarray(final_value_np(spec, c)))
        outs = apply_int_semantics(plan.specs, outs)
        return [keys[j] for j in alive.tolist()], outs, act[alive]

    # ------------------------------------------------------- checkpoint
    def snapshot(self) -> Dict[str, Any]:
        import base64

        with self._mu:
            self._settle_inflight_locked()
            keys, rows, epochs = self.store.items_arrays()
            return {
                "keys": [list(k) if isinstance(k, tuple) else k
                         for k in keys],
                "rows": base64.b64encode(
                    np.ascontiguousarray(
                        rows if rows is not None
                        else np.zeros((0, self.ts.packed_w), np.float32)
                    ).tobytes()).decode("ascii"),
                "epochs": base64.b64encode(
                    np.ascontiguousarray(
                        epochs if epochs is not None
                        else np.zeros((0, self.ts.n_panes), np.int64)
                    ).tobytes()).decode("ascii"),
                "pane_epoch": self._pane_epoch.tolist(),
                # racy live spills awaiting re-promotion (quiescent
                # mode): the first post-restore admit re-promotes them,
                # matching the uninterrupted behavior
                "requeue": [
                    [list(k) if isinstance(k, tuple) else k,
                     base64.b64encode(r.tobytes()).decode("ascii"),
                     base64.b64encode(e.tobytes()).decode("ascii")]
                    for (k, r, e) in self._requeue
                ],
                "counters": {
                    "demoted": self.demoted_total,
                    "promoted": self.promoted_total,
                    "recycled": self.recycled_total,
                },
            }

    def restore(self, snap: Dict[str, Any]) -> None:
        import base64

        keys = [tuple(k) if isinstance(k, list) else k
                for k in snap.get("keys", [])]
        rows = np.frombuffer(
            base64.b64decode(snap.get("rows", "")),
            dtype=np.float32).reshape(-1, self.ts.packed_w).copy()
        epochs = np.frombuffer(
            base64.b64decode(snap.get("epochs", "")),
            dtype=np.int64).reshape(-1, self.ts.n_panes).copy()
        with self._mu:
            self._pane_epoch = np.asarray(
                snap.get("pane_epoch", [0] * self.ts.n_panes),
                dtype=np.int64)
            counters = snap.get("counters", {})
            self.demoted_total = int(counters.get("demoted", 0))
            self.promoted_total = int(counters.get("promoted", 0))
            self.recycled_total = int(counters.get("recycled", 0))
            for i, key in enumerate(keys):
                self.store.put(key, rows[i], epochs[i])
            self._requeue = [
                (tuple(k) if isinstance(k, list) else k,
                 np.frombuffer(base64.b64decode(r),
                               dtype=np.float32).copy(),
                 np.frombuffer(base64.b64decode(e),
                               dtype=np.int64).copy())
                for (k, r, e) in snap.get("requeue", [])
            ]
