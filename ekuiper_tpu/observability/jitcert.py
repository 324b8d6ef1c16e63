"""jitcert — static compile-contract certification for every jitted kernel.

devwatch (this package) measures recompile storms AFTER they burn compile
time; jitcert proves the storm class away BEFORE a kernel ever traces.
Every `watched_jit` site in ops/ and parallel/ is covered by a **signature
certificate**: the closed set of (shape, dtype) argument signatures the
site may legally be traced with, derived by an abstract shape/dtype
interpreter over the engine's plan-time declarations —

  * the key-capacity growth ladder and the uint16/int32 `slot_dtype`
    boundary (ops/groupby.py `slot_dtype`, ops/keytable.py capacity
    doubling),
  * the micro-batch pad buckets every kernel input is padded to
    (runtime/ingest.py `pad_col_for_device` / `pad_slots_for_device`),
  * pane counts and spans from the shared-fold planner
    (planner/sharing.py MAX_SPAN_PANES, ops/panestore.py pane rings),
  * aggregate component layouts (ops/aggspec.py DEVICE_AGGS /
    WIDE_COMPONENTS), and
  * the power-of-two value pad buckets of the count-min sketch
    (ops/sketches.py).

Certificates are rendered in exactly devwatch's `_arg_signature` string
format, so the runtime twin (`diff_live`) can hold the engine to them:
any signature devwatch OBSERVES that the certificate does not contain is
a report — surfaced in `GET /diagnostics/xla`, the kuiperdiag bundle,
and per bench round. The TiLT argument (arxiv 2301.12030) applied to
tracing: compile-time reasoning about the kernel surface is what lets
operator breadth grow without paying tracing tax per shape.

Three consumers make the certificate load-bearing:

  1. kuiperlint passes (tools/kuiperlint/passes/jitcert.py):
     `cert-coverage` fails any watched_jit site in ops//parallel/ whose
     op does not resolve to a derivation registered here;
     `sig-stability` fails signature-unstable idioms inside jit bodies.
  2. the runtime diff (`diff_live`) — bench rounds and /diagnostics/xla
     gate on observed ⊆ certified.
  3. QoS admission (runtime/control.py) prices a candidate rule's
     *certified* new-signature count (`estimate_plan_signatures`)
     instead of waiting for the live storm-edge signal.

docs/STATIC_ANALYSIS.md § jitcert describes the certificate format and
how to certify a new jit site (required reading for ROADMAP items 2/4).
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, FrozenSet, List, Optional, Tuple

#: capacity doublings certified above the construction capacity — the
#: growth ladder is closed (10 doublings of the 16384 default reaches
#: 16M key slots, far past any single-chip HBM budget)
MAX_GROWS = int(os.environ.get("KUIPER_JITCERT_MAX_GROWS", "10") or 10)

#: enumeration bound per site: a derivation whose legal set would exceed
#: this is truncated and marked open (diff then reports the site as
#: uncertifiable instead of silently passing everything)
ENUM_CAP = 4096

#: validity-mask presence subsets enumerated per column set; past this
#: the derivation keeps only the none/all corners and marks truncation
MASK_SUBSET_CAP = 64

#: top of the certified count-min value pad ladder (the floor is
#: ops/sketches.py SKETCH_PAD_FLOOR — the padding site owns it); the
#: count-min hosts bounded candidate sets, so batches past 128k values
#: would be a bug worth surfacing as an uncertified signature
SKETCH_PAD_CAP = 1 << 17


def _sig(parts: List[str]) -> str:
    return "|".join(parts)


def _arr(dtype: str, *dims: int) -> str:
    return f"{dtype}[{','.join(str(d) for d in dims)}]"


@dataclass
class SiteCert:
    """One jit site's compile contract: the closed legal signature set
    plus the machine-checkable derivation that produced it (re-deriving
    from `params` with the named builder must reproduce `signatures`
    bit-for-bit — tools/jitcert certify verifies exactly that)."""

    op: str
    rule: Optional[str]
    builder: str                       # derivation function name
    params: Dict[str, Any]             # derivation inputs (plan-time)
    signatures: FrozenSet[str] = field(default_factory=frozenset)
    derivation: List[str] = field(default_factory=list)
    truncated: bool = False            # enumeration cap hit -> open set
    #: the TRUE cardinality of the legal set, computed from the
    #: derivation's product formula without enumerating — equals
    #: len(signatures) for closed certs, and stays honest past the
    #: enumeration caps (admission prices THIS, so a wide-column rule
    #: cannot under-price its compile surface by overflowing the cap)
    full_count: int = 0

    def to_json(self) -> Dict[str, Any]:
        return {
            "op": self.op,
            "rule": self.rule,
            "builder": self.builder,
            "params": {k: (sorted(v) if isinstance(v, (set, frozenset))
                           else v) for k, v in self.params.items()},
            "n_signatures": len(self.signatures),
            "full_count": self.full_count,
            "truncated": self.truncated,
            "derivation": self.derivation,
        }


# ------------------------------------------------------------ shape atoms
def _ladder(base_capacity: int, grows: int = MAX_GROWS) -> List[int]:
    return [int(base_capacity) << i for i in range(grows + 1)]


def _slot_dtypes() -> Tuple[str, ...]:
    # slots ship uint16 while the encoder's capacity allows and int32 past
    # 65,535 (ops/groupby.py slot_dtype). Cached pre-padded uint16 arrays
    # stay VALID after a grow (their values predate it), and the neutral
    # ingest table may run ahead of the kernel's own capacity — so both
    # wire dtypes are legal at every ladder step; only the shapes bind.
    return ("uint16", "int32")


def _state_leaves(comps: Dict[str, Tuple[int, int]], n_panes: int,
                  capacity: int, lead: Optional[int] = None,
                  touch: bool = False, os_bufs: int = 0,
                  rung: int = 0) -> List[str]:
    """Signature leaves of a group-by state pytree: dict keys sort, `act`
    rides along; `comps` maps component -> (n_specs, wide_size-or-0);
    `lead` prepends the multirule rule axis; `touch` appends the tiered
    state's per-slot uint32 counter (ops/tierstore.py — key axis only,
    no pane axis, sorts last among the state keys); `os_bufs` order-
    statistic row buffers add `osf` (panes,), `oss` / `osv` (buffers,
    panes, `rung`) (ops/orderstat.py — no key axis). Each leaf carries its
    component's device dtype (ops/groupby.py comp_dtype)."""
    from ..ops.groupby import comp_dtype, state_shape
    from ..ops.orderstat import OS_LEAVES

    parts: List[str] = []
    names = list(comps) + ["act"] + (["touch"] if touch else []) \
        + (list(OS_LEAVES) if os_bufs else [])
    for comp in sorted(names):
        if comp == "touch":
            parts.append(_arr("uint32", capacity))
            continue
        if comp == "osf":
            parts.append(_arr("int32", n_panes))
            continue
        if comp in ("oss", "osv"):
            parts.append(_arr(str(comp_dtype(comp)), os_bufs, n_panes,
                              rung))
            continue
        if comp == "act":
            dims: Tuple[int, ...] = (n_panes, capacity)
        else:
            dims = state_shape(comp, n_panes, capacity, comps[comp][0])
        if lead is not None:
            dims = (lead,) + dims
        parts.append(_arr(str(comp_dtype(comp)), *dims))
    return parts


def _col_leaves(columns: List[str], mb: int,
                mask_subset: FrozenSet[str],
                masks_always: bool = False,
                col_dtypes: Optional[Dict[str, str]] = None) -> List[str]:
    """Leaves of the kernel-columns dict: one [mb] array per column
    (float32 unless the plan's expression IR declared another dtype —
    int32 string-dict codes / rebased ts32, KernelPlan.col_dtypes) plus
    a bool[mb] validity mask per column in `mask_subset` (absent masks
    are None and vanish from the pytree — the sharded path materializes
    all of them, `masks_always`)."""
    dts = col_dtypes or {}
    present = set(columns) if masks_always else set(mask_subset)
    keys = sorted(list(columns) + [f"__valid_{c}" for c in present])
    return [_arr("bool", mb) if k.startswith("__valid_")
            # an exact BIGINT aggregate's column: its two uint32 words a row
            else _arr("uint32", mb, 2) if dts.get(k) == "int64"
            else _arr(dts.get(k, "float32"), mb) for k in keys]


def _mask_subsets(columns: List[str]) -> Tuple[List[FrozenSet[str]], bool]:
    """All validity-mask presence combinations (a column carries a mask
    only when its batch had nulls — per batch, per column)."""
    n = len(columns)
    if (1 << n) > MASK_SUBSET_CAP:
        return [frozenset(), frozenset(columns)], True
    out: List[FrozenSet[str]] = []
    for bits in range(1 << n):
        out.append(frozenset(c for i, c in enumerate(columns)
                             if bits & (1 << i)))
    return out, False


# ------------------------------------------------------- kernel spec view
@dataclass
class KernelShape:
    """The plan-time facts a derivation consumes, extracted once from a
    live kernel (or synthesized for admission pricing)."""

    comps: Dict[str, Tuple[int, int]]   # comp -> (n_specs, wide)
    columns: List[str]
    n_panes: int
    micro_batch: int
    base_capacity: int
    lead_rules: Optional[int] = None    # multirule rule axis
    host_finalize_only: bool = False    # heavy_hitters plans
    #: expression-IR column dtype overrides (KernelPlan.col_dtypes):
    #: int32 string-dict / ts32 columns change the fold leaves
    col_dtypes: Dict[str, str] = field(default_factory=dict)
    #: tiered key state (ops/tierstore.py): the per-slot uint32 touch
    #: column rides the state pytree of every site
    touch: bool = False
    #: order-statistic row buffers (ops/orderstat.py): how many, and the
    #: first rung of their power-of-two ladder (rows a pane)
    os_bufs: int = 0
    os_rung: int = 0


def _rungs(ks: "KernelShape", grows: int) -> List[int]:
    """The order-statistic buffers' rung ladder — one entry (0, no leaf)
    for a plan without them."""
    return _ladder(ks.os_rung, grows) if ks.os_bufs else [0]


def _kernel_shape(kernel) -> KernelShape:
    from ..ops.aggspec import WIDE_COMPONENTS
    from ..ops.groupby import _wide_size

    comps = {
        comp: (len(idxs),
               _wide_size(comp) if comp in WIDE_COMPONENTS else 0)
        for comp, idxs in kernel.comp_specs.items()
    }
    return KernelShape(
        comps=comps,
        columns=sorted(kernel.plan.columns),
        n_panes=int(kernel.n_panes),
        micro_batch=int(kernel.micro_batch),
        base_capacity=int(getattr(kernel, "_jitcert_base_capacity",
                                  kernel.capacity)),
        lead_rules=getattr(kernel, "n_rules", None),
        host_finalize_only=bool(getattr(kernel, "_host_finalize_only",
                                        False)),
        col_dtypes={k: v for k, v in sorted(
            getattr(kernel.plan, "col_dtypes", {}).items())
            if v != "float32"},
        touch=bool(getattr(kernel, "track_touch", False)),
        os_bufs=int(getattr(kernel, "n_os_bufs", 0)),
        os_rung=_first_rung(kernel.micro_batch),
    )


def _first_rung(micro_batch: int) -> int:
    from ..ops.orderstat import first_rung

    return first_rung(int(micro_batch))


def shape_from_plan(plan, n_panes: int, micro_batch: int,
                    capacity: int, touch: bool = False) -> KernelShape:
    """KernelShape for a candidate rule's plan — no kernel construction,
    no jax import (QoS admission pricing path)."""
    from ..ops.aggspec import WIDE_COMPONENTS
    from ..ops.groupby import _wide_size

    comp_specs: Dict[str, List[int]] = {}
    for i, spec in enumerate(plan.specs):
        for comp in spec.components:
            comp_specs.setdefault(comp, []).append(i)
    comps = {
        comp: (len(idxs),
               _wide_size(comp) if comp in WIDE_COMPONENTS else 0)
        for comp, idxs in comp_specs.items()
    }
    return KernelShape(
        comps=comps, columns=sorted(plan.columns), n_panes=int(n_panes),
        micro_batch=int(micro_batch), base_capacity=int(capacity),
        host_finalize_only=any(s.kind == "heavy_hitters"
                               for s in plan.specs),
        col_dtypes={k: v for k, v in sorted(
            getattr(plan, "col_dtypes", {}).items()) if v != "float32"},
        touch=bool(touch),
        os_bufs=len(getattr(plan, "os_bufs", ())),
        os_rung=_first_rung(micro_batch),
    )


# ------------------------------------------------------------ derivations
def _derive_fold(ks: KernelShape, op: str, rule: Optional[str],
                 masked: bool = False, sharded: bool = False,
                 pane_vec_dtype: str = "uint8",
                 grows: int = MAX_GROWS) -> SiteCert:
    """fold / fold_masked / sharded fold_step[_vec] / multirule.fold:
    state(capacity ladder) x columns(mask subsets) x slots(dtype
    boundary) x row-gate x pane form."""
    sigs: List[str] = []
    deriv = [
        f"capacity ladder: {ks.base_capacity} x2^0..{grows} "
        "(ops/keytable.py doubling)",
        f"columns pad to micro_batch={ks.micro_batch} "
        "(runtime/ingest.py pad_col_for_device)",
    ]
    subsets, trunc = _mask_subsets(ks.columns)
    if sharded:
        subsets, trunc = [frozenset(ks.columns)], False
        deriv.append("sharded: validity masks always materialized "
                     "(static shard_map pytree)")
    else:
        deriv.append(f"validity-mask presence subsets: {len(subsets)}")
    if masked:
        row_gates = [_arr("bool", ks.micro_batch)]
        deriv.append("row gate: bool[mb] edge-refold mask")
    elif sharded:
        row_gates = [_arr("bool", ks.micro_batch)]
        deriv.append("row gate: bool[mb] row_valid (sharded)")
    else:
        row_gates = [_arr("int32")]
        deriv.append("row gate: scalar n_valid vs on-device iota")
    if masked:
        panes = [_arr("int32")]
    elif sharded and pane_vec_dtype == "int32_vec":
        panes = [_arr("int32", ks.micro_batch)]
    elif sharded:
        panes = [_arr("int32")]
    else:
        panes = [_arr("int32"), _arr(pane_vec_dtype, ks.micro_batch)]
        deriv.append("pane: scalar (processing time) or per-row uint8 "
                     "vector (event time; n_panes <= 255)")
    slot_dts = ("int32",) if sharded else _slot_dtypes()
    if not sharded:
        deriv.append("slots: uint16 under the 65,535 slot_dtype boundary "
                     "(legal at every step: cached pre-grow arrays stay "
                     "valid), int32 above it")
    if ks.col_dtypes:
        deriv.append(
            "expression-IR column dtypes: "
            + ", ".join(f"{k}={v}" for k, v in sorted(
                ks.col_dtypes.items()))
            + " (KernelPlan.col_dtypes — __sd_* dict codes / __ts32_* "
            "rebased event time)")
    if ks.touch:
        deriv.append("tiered state: uint32[capacity] touch column in the "
                     "state pytree (ops/tierstore.py)")
    rungs = _rungs(ks, grows)
    if ks.os_bufs:
        deriv.append(f"order-statistic buffers: {ks.os_bufs} x panes x "
                     f"rung, rung ladder {ks.os_rung} x2^0..{grows} "
                     "(ops/orderstat.py rung_for)")
    for cap in _ladder(ks.base_capacity, grows):
        for rung in rungs:
            state = _state_leaves(ks.comps, ks.n_panes, cap, ks.lead_rules,
                                  touch=ks.touch, os_bufs=ks.os_bufs,
                                  rung=rung)
            for subset in subsets:
                cols = _col_leaves(ks.columns, ks.micro_batch, subset,
                                   masks_always=sharded,
                                   col_dtypes=ks.col_dtypes)
                for sd in slot_dts:
                    for gate in row_gates:
                        for pane in panes:
                            sigs.append(_sig(
                                state + cols
                                + [_arr(sd, ks.micro_batch), gate, pane]))
    truncated = trunc or len(sigs) > ENUM_CAP
    # true cardinality by the product formula, independent of the
    # enumeration caps (2^n mask-presence subsets for n columns)
    n_subsets_true = 1 if sharded else (1 << len(ks.columns))
    full = ((grows + 1) * len(rungs) * n_subsets_true * len(slot_dts)
            * len(row_gates) * len(panes))
    return SiteCert(op, rule, "_derive_fold",
                    {"base_capacity": ks.base_capacity, "grows": grows,
                     "micro_batch": ks.micro_batch, "n_panes": ks.n_panes,
                     "columns": ks.columns, "masked": masked,
                     "sharded": sharded, "lead_rules": ks.lead_rules,
                     "col_dtypes": dict(ks.col_dtypes),
                     "touch": ks.touch,
                     "os_bufs": ks.os_bufs, "os_rung": ks.os_rung,
                     "comps": {c: list(v) for c, v in ks.comps.items()}},
                    frozenset(sigs[:ENUM_CAP]), deriv, truncated,
                    full_count=full)


def _derive_boundary(ks: KernelShape, op: str, rule: Optional[str],
                     tail: str, grows: int = MAX_GROWS) -> SiteCert:
    """State-plus-tail sites over the capacity ladder. `tail` is one of:
    static_all  — all-True static pane tuple (finalize/components:
                  every caller passes panes=None on the static route;
                  subsets go through the traced-mask twin),
    pane_mask   — traced bool[n_panes] (finalize_dyn / hh_finalize),
    pane_mask_scalar — both, the mask then a pane (orderstat_select:
                  the pane whose held rows it counts),
    pane_scalar — scalar pane index (reset_pane)."""
    sigs: List[str] = []
    deriv = [f"capacity ladder: {ks.base_capacity} x2^0..{grows}"]
    if ks.touch:
        deriv.append("tiered state: uint32[capacity] touch column in the "
                     "state pytree (ops/tierstore.py)")
    rungs = _rungs(ks, grows)
    if ks.os_bufs:
        deriv.append(f"order-statistic buffers: rung ladder {ks.os_rung} "
                     f"x2^0..{grows}")
    for cap in _ladder(ks.base_capacity, grows):
        for rung in rungs:
            state = _state_leaves(ks.comps, ks.n_panes, cap, ks.lead_rules,
                                  touch=ks.touch, os_bufs=ks.os_bufs,
                                  rung=rung)
            if tail == "static_all":
                sigs.append(_sig(state + ["True"] * ks.n_panes))
            elif tail == "pane_mask":
                sigs.append(_sig(state + [_arr("bool", ks.n_panes)]))
            elif tail == "pane_mask_scalar":
                sigs.append(_sig(state + [_arr("bool", ks.n_panes),
                                          _arr("int32")]))
            elif tail == "pane_scalar":
                sigs.append(_sig(state + [_arr("int32")]))
            else:  # pragma: no cover - derivation bug
                raise ValueError(f"unknown boundary tail {tail!r}")
    if tail == "static_all":
        deriv.append("pane mask: static all-True tuple (subset emits ride "
                     "the traced-mask twin; nodes_fused/panestore pass "
                     "panes=None here)")
    elif tail in ("pane_mask", "pane_mask_scalar"):
        deriv.append(f"pane mask: traced bool[{ks.n_panes}] — one "
                     "executable per capacity, any pane subset")
    return SiteCert(op, rule, "_derive_boundary",
                    {"base_capacity": ks.base_capacity, "grows": grows,
                     "n_panes": ks.n_panes, "tail": tail,
                     "lead_rules": ks.lead_rules, "touch": ks.touch,
                     "os_bufs": ks.os_bufs, "os_rung": ks.os_rung,
                     "comps": {c: list(v) for c, v in ks.comps.items()}},
                    frozenset(sigs), deriv, len(sigs) > ENUM_CAP,
                    full_count=(grows + 1) * len(rungs))


def _ring_leaves(comps: Dict[str, Tuple[int, int]], capacity: int,
                 ring_slots: int) -> List[str]:
    """Signature leaves of a sliding-ring state pytree
    (ops/slidingring.py): running window totals (`tot_*`, [capacity,...])
    for add-combine components, two-stack back/front partials
    (`back_*` [capacity,...] + `front_*` [ring_slots, capacity,...]) for
    min/max-combine ones; dict keys sort."""
    from ..ops.slidingring import ADD_COMBINE

    entries: Dict[str, Tuple[int, ...]] = {}
    for comp in sorted(list(comps) + ["act"]):
        if comp == "act":
            dims: Tuple[int, ...] = ()
        else:
            k, wide = comps[comp]
            dims = (k,) + ((wide,) if wide else ())
        if comp in ADD_COMBINE:
            entries[f"tot_{comp}"] = (capacity,) + dims
        else:
            entries[f"back_{comp}"] = (capacity,) + dims
            entries[f"front_{comp}"] = (ring_slots, capacity) + dims
    return [_arr("float32", *entries[k]) for k in sorted(entries)]


def _derive_ring(ks: KernelShape, op: str, rule: Optional[str],
                 ring_slots: int, tail: str,
                 grows: int = MAX_GROWS) -> SiteCert:
    """slidingring advance/flip/query: ring state + pane state over the
    capacity ladder, with plan-time-fixed ring geometry. `tail` is one of:
    advance — scalar closed/evict slot indices + on flags,
    flip    — int32[R] age-ordered slot rotation + bool[R] validity,
    query   — body/front flags + front row index + QUERY_ADJ adjustment
              slot/weight/include vectors,
    tail    — no ring or pane state: the query's stacked [capacity, W]
              components, one edge buffer (every column with its
              validity mask, slots) at the one static edge shape, and
              the scalar row count."""
    from ..ops.slidingring import QUERY_ADJ, TAIL_EDGE_CHUNKS

    sigs: List[str] = []
    deriv = [
        f"capacity ladder: {ks.base_capacity} x2^0..{grows} "
        "(ops/keytable.py doubling; ring grows in lockstep)",
        f"ring slots fixed at plan time: {ring_slots} "
        "(ops/slidingring.py plan_ring_layout)",
        "components split by combine class: subtract-on-evict totals "
        "(n/s1/s2/hist/hh/act) vs two-stack front/back partials "
        "(mn/mx/hll)",
    ]
    edge_rows = TAIL_EDGE_CHUNKS * ks.micro_batch
    width = 1 + sum(k * (wide or 1) for k, wide in ks.comps.values())
    for cap in _ladder(ks.base_capacity, grows):
        if tail == "tail":
            sigs.append(_sig(
                [_arr("float32", cap, width)]
                + _col_leaves(ks.columns, edge_rows, frozenset(),
                              masks_always=True, col_dtypes=ks.col_dtypes)
                + [_arr("uint16" if cap <= 65535 else "int32", edge_rows),
                   _arr("int32")]))
            continue
        ring = _ring_leaves(ks.comps, cap, ring_slots)
        pane = _state_leaves(ks.comps, ks.n_panes, cap, touch=ks.touch)
        if tail == "advance":
            t = [_arr("int32"), _arr("bool"), _arr("int32"), _arr("bool")]
        elif tail == "flip":
            t = [_arr("int32", ring_slots), _arr("bool", ring_slots)]
        elif tail == "query":
            t = [_arr("bool"), _arr("bool"), _arr("int32"),
                 _arr("int32", QUERY_ADJ), _arr("float32", QUERY_ADJ),
                 _arr("bool", QUERY_ADJ)]
        else:  # pragma: no cover - derivation bug
            raise ValueError(f"unknown ring tail {tail!r}")
        sigs.append(_sig(ring + pane + t))
    if tail == "advance":
        deriv.append("tail: scalar closed/evict pane slots + on flags "
                     "(one executable per capacity)")
    elif tail == "flip":
        deriv.append(f"tail: int32[{ring_slots}] slot rotation + "
                     f"bool[{ring_slots}] validity (the amortized rebuild)")
    elif tail == "tail":
        deriv.append(
            f"tail: float32[capacity,{width}] query components, one edge "
            f"buffer of {TAIL_EDGE_CHUNKS} x micro_batch = {edge_rows} rows "
            "(validity masks always materialized; slots uint16 under the "
            "65,535 boundary of the buffer's own capacity, int32 above), "
            "scalar row count")
    else:
        deriv.append(f"tail: body/front flags, front row, and "
                     f"{QUERY_ADJ} pane-slice adjustment slots "
                     "(constant-time trigger)")
    return SiteCert(op, rule, "_derive_ring",
                    {"base_capacity": ks.base_capacity, "grows": grows,
                     "ring_slots": ring_slots, "n_panes": ks.n_panes,
                     "tail": tail, "query_adj": QUERY_ADJ,
                     "edge_rows": edge_rows, "touch": ks.touch,
                     "comps": {c: list(v) for c, v in ks.comps.items()}},
                    frozenset(sigs), deriv, len(sigs) > ENUM_CAP,
                    full_count=grows + 1)


def _tier_packed_w(comps: Dict[str, Tuple[int, int]], n_panes: int) -> int:
    """Packed-row width of the tier demote/promote block — mirrors
    ops/tierstore.py TierStore.blocks exactly (sorted components'
    per-pane blocks + the act block)."""
    w = n_panes  # act
    for _comp, (k, wide) in comps.items():
        w += n_panes * k * (wide or 1)
    return w


def _derive_tier(ks: KernelShape, op: str, rule: Optional[str],
                 demote_batch: int, tail: str,
                 grows: int = MAX_GROWS) -> SiteCert:
    """tierstore demote/promote (ops/tierstore.py): state pytree (touch
    column included) over the capacity ladder, plus the plan-time-fixed
    demote batch. `tail` is one of:
    demote  — int32[D] slot vector (gather + identity reset),
    promote — float32[D, W] packed rows + int32[D] slot vector
              (scatter-merge: add, or min / max, per component)."""
    packed_w = _tier_packed_w(ks.comps, ks.n_panes)
    sigs: List[str] = []
    deriv = [
        f"capacity ladder: {ks.base_capacity} x2^0..{grows}",
        f"demote batch fixed at plan time: D={demote_batch} "
        "(ops/tierstore.py TierLayout; slot vectors pad with duplicate "
        "real entries — identity under set/combine)",
        f"packed row width W={packed_w}: sorted components' per-pane "
        "blocks + the act block, C-order",
    ]
    for cap in _ladder(ks.base_capacity, grows):
        state = _state_leaves(ks.comps, ks.n_panes, cap,
                              touch=ks.touch)
        if tail == "demote":
            sigs.append(_sig(state + [_arr("int32", demote_batch)]))
        elif tail == "promote":
            sigs.append(_sig(
                state + [_arr("float32", demote_batch, packed_w),
                         _arr("int32", demote_batch)]))
        else:  # pragma: no cover - derivation bug
            raise ValueError(f"unknown tier tail {tail!r}")
    return SiteCert(op, rule, "_derive_tier",
                    {"base_capacity": ks.base_capacity, "grows": grows,
                     "n_panes": ks.n_panes, "tail": tail,
                     "demote_batch": demote_batch, "packed_w": packed_w,
                     "touch": ks.touch,
                     "comps": {c: list(v) for c, v in ks.comps.items()}},
                    frozenset(sigs), deriv, len(sigs) > ENUM_CAP,
                    full_count=grows + 1)


def _derive_sketch(op: str, rule: Optional[str], depth: int, width: int,
                   query_only: bool = False) -> SiteCert:
    """count-min update/query: the value batch pads to the next power of
    two (ops/sketches.py SKETCH_PAD_FLOOR), so the legal set is the pad
    ladder."""
    from ..ops.sketches import SKETCH_PAD_FLOOR

    sigs: List[str] = []
    b = SKETCH_PAD_FLOOR
    while b <= SKETCH_PAD_CAP:
        counts = _arr("float32", depth, width)
        if query_only:
            sigs.append(_sig([counts, _arr("float32", b)]))
        else:
            sigs.append(_sig([counts, _arr("float32", b),
                              _arr("float32", b)]))
        b <<= 1
    deriv = [
        f"value batches pad to powers of two "
        f"[{SKETCH_PAD_FLOOR}..{SKETCH_PAD_CAP}] "
        "(ops/sketches.py _pad_pow2; padded rows carry weight 0)",
        f"counts: float32[{depth},{width}] fixed at construction",
    ]
    return SiteCert(op, rule, "_derive_sketch",
                    {"depth": depth, "width": width,
                     "query_only": query_only},
                    frozenset(sigs), deriv, False,
                    full_count=len(sigs))


def _derive_join(op: str, rule: Optional[str],
                 resid_l: Dict[str, str], resid_r: Dict[str, str]
                 ) -> SiteCert:
    """joinring.match (ops/joinring.py): each side pads to the next
    power of two independently, so the legal set is the (PL, PR)
    pad-pair ladder. Leaf order is the call order: left slots/ts/valid,
    right slots/ts/valid, the two int32 band scalars, then each side's
    residual column dict (jax flattens dicts sorted by key). Residual
    columns are construction-frozen (the ON clause is plan text), so
    the set is closed — no mask subsets, no value dependence."""
    from ..ops.joinring import JOIN_PAD_CAP, JOIN_PAD_FLOOR

    pads: List[int] = []
    b = JOIN_PAD_FLOOR
    while b <= JOIN_PAD_CAP:
        pads.append(b)
        b <<= 1
    sigs: List[str] = []
    for pl in pads:
        for pr in pads:
            parts = [_arr("int32", pl), _arr("int32", pl),
                     _arr("bool", pl),
                     _arr("int32", pr), _arr("int32", pr),
                     _arr("bool", pr),
                     _arr("int32"), _arr("int32")]
            parts += [_arr(resid_l[c], pl) for c in sorted(resid_l)]
            parts += [_arr(resid_r[c], pr) for c in sorted(resid_r)]
            sigs.append(_sig(parts))
    deriv = [
        f"per-side pads: powers of two [{JOIN_PAD_FLOOR}..{JOIN_PAD_CAP}]"
        " (ops/joinring.py _pad_pow2; padded rows carry valid=False)",
        f"signature set = (PL, PR) pad pairs: {len(pads)}^2 = {len(sigs)}",
        "band bounds ride as int32 scalars (0-d), rebased per call",
        f"residual columns frozen at plan time: "
        f"L={sorted(resid_l)} R={sorted(resid_r)}",
    ]
    return SiteCert(op, rule, "_derive_join",
                    {"resid_l": dict(sorted(resid_l.items())),
                     "resid_r": dict(sorted(resid_r.items())),
                     "pad_floor": JOIN_PAD_FLOOR,
                     "pad_cap": JOIN_PAD_CAP},
                    frozenset(sigs), deriv, False, full_count=len(sigs))


def _derive_segscan(op: str, rule: Optional[str], tail: str,
                    base_capacity: int = 0,
                    grows: int = MAX_GROWS) -> SiteCert:
    """segscan.shift / segscan.sort (ops/segscan.py): micro-batches pad
    to the SEG_PAD_FLOOR..SEG_PAD_CAP power-of-two ladder. `shift`
    additionally carries the donated per-key partials (count, last
    value, has-last, running sum) on the key-capacity doubling ladder;
    `sort` is stateless (one complete collection per call)."""
    from ..ops.segscan import SEG_PAD_CAP, SEG_PAD_FLOOR

    mbs: List[int] = []
    b = SEG_PAD_FLOOR
    while b <= SEG_PAD_CAP:
        mbs.append(b)
        b <<= 1
    sigs: List[str] = []
    if tail == "sort":
        for mb in mbs:
            sigs.append(_sig([_arr("int32", mb), _arr("float32", mb),
                              _arr("bool", mb)]))
        params: Dict[str, Any] = {"tail": tail}
    elif tail == "shift":
        for cap in _ladder(base_capacity, grows):
            for mb in mbs:
                sigs.append(_sig([
                    _arr("int32", cap), _arr("float32", cap),
                    _arr("bool", cap), _arr("float32", cap),
                    _arr("int32", mb), _arr("float32", mb),
                    _arr("bool", mb)]))
        params = {"tail": tail, "base_capacity": base_capacity,
                  "grows": grows}
    else:  # pragma: no cover - derivation bug
        raise ValueError(f"unknown segscan tail {tail!r}")
    deriv = [
        f"micro-batches pad to powers of two "
        f"[{SEG_PAD_FLOOR}..{SEG_PAD_CAP}] (ops/segscan.py _pad_pow2; "
        "padded rows carry valid=False and segment to a ghost id)",
    ]
    if tail == "shift":
        deriv.append(
            f"carry partials (count/last/has/sum) on the key capacity "
            f"ladder: {base_capacity} x2^0..{grows}")
    return SiteCert(op, rule, "_derive_segscan", params,
                    frozenset(sigs), deriv, False, full_count=len(sigs))


# --------------------------------------------------- per-kernel dispatch
def _groupby_certs(kernel, prefix: str, rule: Optional[str]
                   ) -> List[SiteCert]:
    ks = _kernel_shape(kernel)
    certs = [
        _derive_fold(ks, f"{prefix}.fold", rule),
        _derive_fold(ks, f"{prefix}.fold_masked", rule, masked=True),
        _derive_boundary(ks, f"{prefix}.finalize", rule, "static_all"),
        _derive_boundary(ks, f"{prefix}.finalize_dyn", rule, "pane_mask"),
        _derive_boundary(ks, f"{prefix}.components", rule, "static_all"),
        _derive_boundary(ks, f"{prefix}.components_dyn", rule,
                         "pane_mask"),
        _derive_boundary(ks, f"{prefix}.reset_pane", rule, "pane_scalar"),
    ]
    if ks.host_finalize_only:
        certs.append(_derive_boundary(ks, f"{prefix}.hh_finalize", rule,
                                      "pane_mask"))
    if ks.os_bufs:
        certs.append(_derive_boundary(ks, f"{prefix}.orderstat_select",
                                      rule, "pane_mask_scalar"))
    return certs


def _multirule_certs(kernel, rule: Optional[str]) -> List[SiteCert]:
    ks = _kernel_shape(kernel)
    return [
        _derive_fold(ks, "multirule.fold", rule),
        _derive_boundary(ks, "multirule.finalize", rule, "static_all"),
        _derive_boundary(ks, "multirule.reset_pane", rule, "pane_scalar"),
    ]


def _sharded_certs(kernel, rule: Optional[str]) -> List[SiteCert]:
    ks = _kernel_shape(kernel)
    ks2 = KernelShape(**{**ks.__dict__})
    return [
        _derive_fold(ks, "sharded.fold_step", rule, sharded=True),
        _derive_fold(ks2, "sharded.fold_step_vec", rule, sharded=True,
                     pane_vec_dtype="int32_vec"),
        _derive_boundary(ks, "sharded.finalize", rule, "static_all"),
        _derive_boundary(ks, "sharded.finalize_dyn", rule, "pane_mask"),
        _derive_boundary(ks, "sharded.components", rule, "static_all"),
        _derive_boundary(ks, "sharded.reset_pane", rule, "pane_scalar"),
    ]


def _sliding_ring_certs(kernel, rule: Optional[str]) -> List[SiteCert]:
    ks = _kernel_shape(kernel.gb)
    # the ring pins its OWN base capacity at registration (it is created
    # alongside the group-by kernel, but battery/admission constructions
    # may differ)
    ks.base_capacity = int(getattr(kernel, "_jitcert_base_capacity",
                                   kernel.capacity))
    slots = int(kernel.n_ring_panes)
    return [
        _derive_ring(ks, "slidingring.advance", rule, slots, "advance"),
        _derive_ring(ks, "slidingring.flip", rule, slots, "flip"),
        _derive_ring(ks, "slidingring.query", rule, slots, "query"),
        _derive_ring(ks, "slidingring.tail", rule, slots, "tail"),
    ]


def _tier_certs(kernel, rule: Optional[str]) -> List[SiteCert]:
    ks = _kernel_shape(kernel.gb)
    # the tier store pins its OWN base capacity at registration (it is
    # created alongside the group-by kernel, but battery/admission
    # constructions may differ)
    ks.base_capacity = int(getattr(kernel, "_jitcert_base_capacity",
                                   kernel.capacity))
    D = int(kernel.demote_batch)
    return [
        _derive_tier(ks, "tierstore.demote", rule, D, "demote"),
        _derive_tier(ks, "tierstore.promote", rule, D, "promote"),
    ]


def certificates_for(kernel, rule: Optional[str] = None) -> List[SiteCert]:
    """Derive every certificate a kernel object's jit sites are bound by.
    Dispatches on the same `watch_prefix` devwatch attribution uses."""
    prefix = getattr(kernel, "watch_prefix", None)
    if prefix == "slidingring":
        return _sliding_ring_certs(kernel, rule)
    if prefix == "tierstore":
        return _tier_certs(kernel, rule)
    if prefix == "multirule":
        return _multirule_certs(kernel, rule)
    if prefix == "sharded":
        return _sharded_certs(kernel, rule)
    if prefix == "sketch":
        return [
            _derive_sketch("sketch.update", rule, kernel.depth,
                           kernel.width),
            _derive_sketch("sketch.query", rule, kernel.depth,
                           kernel.width, query_only=True),
        ]
    if prefix == "joinring":
        dt = getattr(kernel, "col_dtypes", {}) or {}
        return [_derive_join(
            "joinring.match", rule,
            {c: dt.get(c, "float32") for c in kernel.resid_l},
            {c: dt.get(c, "float32") for c in kernel.resid_r})]
    if prefix == "segscan":
        base = int(getattr(kernel, "_jitcert_base_capacity",
                           getattr(kernel, "capacity", 0)))
        return [
            _derive_segscan("segscan.shift", rule, "shift", base),
            _derive_segscan("segscan.sort", rule, "sort"),
        ]
    if prefix == "groupby":
        return _groupby_certs(kernel, prefix, rule)
    raise ValueError(
        f"no jitcert derivation for kernel {type(kernel).__name__} "
        f"(watch_prefix={prefix!r}) — register one in "
        "ekuiper_tpu/observability/jitcert.py (docs/STATIC_ANALYSIS.md "
        "§ certifying a new jit site)")


#: the static coverage table the kuiperlint `cert-coverage` pass checks
#: watched_jit op names against: every op here has a derivation above.
SITE_DERIVATIONS: Dict[str, str] = {
    "groupby.fold": "_derive_fold",
    "groupby.fold_masked": "_derive_fold(masked)",
    "groupby.finalize": "_derive_boundary(static_all)",
    "groupby.finalize_dyn": "_derive_boundary(pane_mask)",
    "groupby.components": "_derive_boundary(static_all)",
    "groupby.components_dyn": "_derive_boundary(pane_mask)",
    "groupby.reset_pane": "_derive_boundary(pane_scalar)",
    "groupby.hh_finalize": "_derive_boundary(pane_mask)",
    "groupby.orderstat_select": "_derive_boundary(pane_mask_scalar, "
                                "os_bufs)",
    "multirule.fold": "_derive_fold(lead_rules)",
    "multirule.finalize": "_derive_boundary(static_all)",
    "multirule.reset_pane": "_derive_boundary(pane_scalar)",
    "sharded.fold_step": "_derive_fold(sharded)",
    "sharded.fold_step_vec": "_derive_fold(sharded, pane_vec)",
    "sharded.finalize": "_derive_boundary(static_all)",
    "sharded.finalize_dyn": "_derive_boundary(pane_mask)",
    "sharded.components": "_derive_boundary(static_all)",
    "sharded.reset_pane": "_derive_boundary(pane_scalar)",
    "sketch.update": "_derive_sketch",
    "sketch.query": "_derive_sketch(query_only)",
    "slidingring.advance": "_derive_ring(advance)",
    "slidingring.flip": "_derive_ring(flip)",
    "slidingring.query": "_derive_ring(query)",
    "slidingring.tail": "_derive_ring(tail)",
    "tierstore.demote": "_derive_tier(demote)",
    "tierstore.promote": "_derive_tier(promote)",
    "joinring.match": "_derive_join",
    "segscan.shift": "_derive_segscan(shift)",
    "segscan.sort": "_derive_segscan(sort)",
}


# --------------------------------------------------------------- registry
class _Registry:
    """Weakref index of live certified kernels, mirroring devwatch's
    ownership model: strong ownership stays with the kernel object; a
    collected kernel's certificates simply stop applying (its watches
    are gone from devwatch too)."""

    def __init__(self) -> None:
        import weakref

        self._weakref = weakref
        self._lock = threading.Lock()
        self._entries: List[Tuple[Any, Optional[str]]] = []  # (ref, rule)

    def register(self, kernel, rule: Optional[str]) -> None:
        with self._lock:
            ref = self._weakref.ref(kernel)
            # re-registration (subclass __init__ chains) replaces
            self._entries = [(r, ru) for (r, ru) in self._entries
                             if r() is not None and r() is not kernel]
            self._entries.append((ref, rule))

    def kernels(self) -> List[Tuple[Any, Optional[str]]]:
        with self._lock:
            refs = list(self._entries)
        return [(k, rule) for (r, rule) in refs
                if (k := r()) is not None]

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


_registry = _Registry()


def registry() -> _Registry:
    return _registry


def register_kernel(kernel) -> None:
    """Called from kernel constructors (DeviceGroupBy and subclasses,
    CountMinSketch): binds the instance to its compile contract. Rule
    attribution rides the rule thread context, like devwatch."""
    from ..utils.rulelog import current_rule

    kernel._jitcert_base_capacity = int(getattr(kernel, "capacity", 0))
    _registry.register(kernel, current_rule())


def reset() -> None:
    """Test hook."""
    _registry.clear()


# ------------------------------------------------------------------- diff
def live_certificates() -> Dict[Tuple[str, str], Dict[str, Any]]:
    """(op, rule) -> {"signatures": set, "truncated": bool, "certs": n}
    across every live registered kernel. Derivation is a pure function
    of construction-frozen params (register_kernel pins the base
    capacity), so each kernel's certificates are derived ONCE and
    memoized on the instance — a diagnostics poller must not pay the
    full ladder×subset enumeration per /diagnostics/xla scrape."""
    out: Dict[Tuple[str, str], Dict[str, Any]] = {}
    for kernel, rule in _registry.kernels():
        certs = getattr(kernel, "_jitcert_cert_cache", None)
        if certs is None:
            try:
                certs = certificates_for(kernel, rule)
            except Exception:
                continue
            try:
                kernel._jitcert_cert_cache = certs
            except Exception:
                pass  # slotted/frozen owner: derive per call
        for c in certs:
            acc = out.setdefault((c.op, rule or ""), {
                "signatures": set(), "truncated": False, "certs": 0})
            acc["signatures"] |= c.signatures
            acc["truncated"] = acc["truncated"] or c.truncated
            acc["certs"] += 1
    return out


def diff_live(max_findings: int = 64) -> Dict[str, Any]:
    """The runtime twin: devwatch's observed signature tables vs the
    registered certificates. An observed-but-uncertified signature is
    the report, not a counter — each finding carries the op, rule, and
    offending signature so the derivation (or the kernel) can be fixed."""
    from . import devwatch

    certs = live_certificates()
    findings: List[Dict[str, Any]] = []
    open_sites: List[Dict[str, Any]] = []
    observed_total = 0
    sites_observed = 0
    sites_uncovered = 0
    for w in devwatch.registry().watches():
        observed = w.signature_dump()
        if not observed:
            continue
        sites_observed += 1
        observed_total += len(observed)
        key = (w.op, w.rule or "")
        entry = certs.get(key)
        if entry is None:
            # rule-attribution drift (restart, engine-owned site): any
            # certificate for the same op still binds the shapes
            pooled = [v for (op, _r), v in certs.items() if op == w.op]
            if pooled:
                entry = {
                    "signatures": set().union(
                        *(p["signatures"] for p in pooled)),
                    "truncated": any(p["truncated"] for p in pooled),
                }
        if entry is None:
            sites_uncovered += 1
            for sig, compiles in observed.items():
                findings.append({
                    "op": w.op, "rule": w.rule or "",
                    "signature": sig, "compiles": compiles,
                    "reason": "no certificate registered for this site",
                })
            continue
        if entry["truncated"]:
            # open set: the site cannot be HELD to its certificate —
            # that is a visible coverage hole, never a silent pass
            # (clean only claims observed ⊆ certified for the sites the
            # diff actually enforced)
            open_sites.append({
                "op": w.op, "rule": w.rule or "",
                "observed": len(observed),
                "reason": "certificate truncated (enumeration cap) — "
                          "site not enforced",
            })
            continue
        for sig, compiles in sorted(observed.items()):
            if sig not in entry["signatures"]:
                findings.append({
                    "op": w.op, "rule": w.rule or "",
                    "signature": sig, "compiles": compiles,
                    "reason": "observed signature outside the certified "
                              "set",
                })
    findings.sort(key=lambda f: (f["op"], f["rule"], f["signature"]))
    overflow = max(len(findings) - max_findings, 0)
    return {
        "clean": not findings,
        "sites_observed": sites_observed,
        "sites_certified": len(certs),
        "sites_uncovered": sites_uncovered,
        "sites_open": len(open_sites),
        "open_sites": open_sites[:max_findings],
        "observed_signatures": observed_total,
        "certified_signatures": sum(
            len(v["signatures"]) for v in certs.values()),
        "uncertified": findings[:max_findings],
        "uncertified_overflow": overflow,
    }


# --------------------------------------------------- admission estimation
def estimate_plan_signatures(plan, n_panes: int, micro_batch: int,
                             capacity: int,
                             sliding_ring_slots: int = 0,
                             tier_demote_batch: int = 0) -> int:
    """Certified signature count a candidate device rule adds at its
    CONSTRUCTION capacity (growth steps respecialize later, paced by key
    cardinality, not admission) — the compile load admission prices
    instead of waiting for devwatch's live storm edge. Sums each cert's
    `full_count` (the product-formula cardinality), NOT the enumerated
    set: a wide-column rule whose subset enumeration truncates must
    price its TRUE 2^n surface, or the signature budget inverts —
    admitting the compile-heaviest rules while rejecting narrower
    ones. `sliding_ring_slots` > 0 prices a DABA sliding rule's extra
    surface (slidingring.advance/flip/query/tail + the components_dyn
    fallback) so the budget cannot under-price sliding candidates;
    `tier_demote_batch` > 0 prices a tiered rule's demote/promote sites
    (the touch column changes every state signature, so the whole shape
    is derived with it)."""
    return sum(c.full_count for c in estimate_plan_certs(
        plan, n_panes, micro_batch, capacity,
        sliding_ring_slots=sliding_ring_slots,
        tier_demote_batch=tier_demote_batch))


def estimate_plan_certs(plan, n_panes: int, micro_batch: int,
                        capacity: int,
                        sliding_ring_slots: int = 0,
                        tier_demote_batch: int = 0) -> List[SiteCert]:
    """The cert OBJECTS behind estimate_plan_signatures. The AOT cache
    (runtime/aotcache.py) prices a candidate against their enumerated
    signature strings — certificate strings ARE cache-key material, so
    admission can tell certified-but-uncached signatures (real compile
    debt) from ones a fleet bake already persisted."""
    ks = shape_from_plan(plan, n_panes, micro_batch, capacity,
                         touch=tier_demote_batch > 0)
    certs = [
        _derive_fold(ks, "groupby.fold", None, grows=0),
        _derive_boundary(ks, "groupby.finalize", None, "static_all",
                         grows=0),
        _derive_boundary(ks, "groupby.finalize_dyn", None, "pane_mask",
                         grows=0),
        _derive_boundary(ks, "groupby.components", None, "static_all",
                         grows=0),
        _derive_boundary(ks, "groupby.reset_pane", None, "pane_scalar",
                         grows=0),
    ]
    if ks.host_finalize_only:
        certs.append(_derive_boundary(ks, "groupby.hh_finalize", None,
                                      "pane_mask", grows=0))
    elif ks.os_bufs:
        certs.append(_derive_boundary(ks, "groupby.orderstat_select", None,
                                      "pane_mask_scalar", grows=0))
    elif sliding_ring_slots > 0:
        certs.append(_derive_boundary(ks, "groupby.components_dyn", None,
                                      "pane_mask", grows=0))
        for op, tail in (("slidingring.advance", "advance"),
                         ("slidingring.flip", "flip"),
                         ("slidingring.query", "query"),
                         ("slidingring.tail", "tail")):
            certs.append(_derive_ring(ks, op, None, sliding_ring_slots,
                                      tail, grows=0))
    if tier_demote_batch > 0 and not ks.host_finalize_only:
        certs.append(_derive_tier(ks, "tierstore.demote", None,
                                  tier_demote_batch, "demote", grows=0))
        certs.append(_derive_tier(ks, "tierstore.promote", None,
                                  tier_demote_batch, "promote", grows=0))
    return certs


def estimate_relational_certs(join_resid_l: Optional[Dict[str, str]] = None,
                              join_resid_r: Optional[Dict[str, str]] = None,
                              join: bool = False,
                              analytic_shift: bool = False,
                              analytic_sort: bool = False,
                              capacity: int = 4096) -> List[SiteCert]:
    """Admission-pricing twin for the relational tier (joinring/segscan).
    A lifted join prices the full (PL, PR) pad-pair surface — the pads
    track window data, not capacity, so the construction-time truth IS
    the whole ladder. Analytic sites price the micro-batch ladder
    (shift at construction capacity, grows=0 — growth respecializes
    later, paced by key cardinality, exactly like the group-by sites)."""
    certs: List[SiteCert] = []
    if join:
        certs.append(_derive_join("joinring.match", None,
                                  dict(join_resid_l or {}),
                                  dict(join_resid_r or {})))
    if analytic_shift:
        certs.append(_derive_segscan("segscan.shift", None, "shift",
                                     capacity, grows=0))
    if analytic_sort:
        certs.append(_derive_segscan("segscan.sort", None, "sort"))
    return certs


def estimate_relational_signatures(**kw) -> int:
    """Sum of `full_count` over estimate_relational_certs — the number
    a candidate relational rule adds to the QoS signature budget."""
    return sum(c.full_count for c in estimate_relational_certs(**kw))
