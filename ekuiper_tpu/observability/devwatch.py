"""XLA compile watcher — trace/cache-hit accounting for every `jax.jit`
site in the engine.

A JAX streaming engine's worst silent failure mode is the recompile
storm: a shape- or dtype-unstable input (growing key capacity, a mixed
micro-batch tail, an unpinned static argument) makes every fold re-trace,
and throughput collapses by 100-1000x with NOTHING in the metrics to say
why — the fold "works", it is just compiling every call. TiLT (arxiv
2301.12030) treats compile cost as a first-class stream-query concern;
this module makes it measurable: `watched_jit` wraps `jax.jit` so each
site counts traces vs cache hits, records a compile-time histogram, tags
every compile with the argument shape/dtype signature that caused it,
and flags a storm (same site, many distinct signatures) as a structured
warning + flight-recorder event.

Detection rides jit semantics, no private JAX API: the wrapped function
body only EXECUTES while jax is tracing it, so a per-call flag set inside
the body distinguishes a trace (compile) from a cache hit. The cache-hit
path adds two attribute writes, one perf_counter read and two integer
increments per call (~1µs against 60µs+ folds — bench full_pipe records
the measured ratio as `devwatch_overhead`). Signature extraction — the
only allocation-heavy step — runs ONLY when a trace actually happened.

Counters are telemetry-grade: hit/call increments are unlocked (a lost
increment under a racing dispatch is acceptable; compile-side bookkeeping
takes the record lock).
"""
from __future__ import annotations

import collections
import threading
import time as _time
from typing import Any, Callable, Dict, List, Optional, Tuple

from .histogram import LatencyHistogram

#: distinct compile signatures at one site before it is flagged as a
#: recompile storm (legitimate respecialization — capacity doublings,
#: pane-mask combos — stays in single digits; shape churn does not)
STORM_SIGNATURES = 8

#: per-site signature table cap: a real storm can produce one signature per
#: batch forever; past the cap new signatures only bump `sig_overflow`
SIG_CAP = 128

#: retired-accumulator table cap: keyed by (op, rule), so it only grows
#: with distinct rule ids ever seen; past the cap the oldest keys drop
#: (their counters reset — an explicit bound, not a leak)
RETIRED_CAP = 4096


def _arg_signature(args: tuple, kwargs: dict) -> str:
    """Shape/dtype signature of one call's arguments — the jit cache key's
    observable part. Arrays render as dtype[d0,d1,...]; everything else
    (static argnums: ints, tuples) renders by repr, truncated."""
    import jax

    parts: List[str] = []
    for leaf in jax.tree_util.tree_leaves((args, kwargs)):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(f"{dtype}[{','.join(str(d) for d in shape)}]")
        else:
            parts.append(repr(leaf)[:48])
    return "|".join(parts)


class OpWatch:
    """Per-jit-site record: one per watched_jit() call (a DeviceGroupBy
    owns ~6 of these; instances do not share jit caches, so they do not
    share watch records either)."""

    def __init__(self, op: str, rule: Optional[str],
                 kind: str = "hot") -> None:
        from . import kernwatch

        self.op = op
        # name of the host span a profiler capture shows around each
        # dispatch of this site: the site's own name without the kernel
        # family prefix (`groupby.fold` -> `kuiper:jit:fold`)
        self.trace_name = "kuiper:jit:" + op.rsplit(".", 1)[-1]
        self.rule = rule  # attributed lazily from the rule thread context
        self.calls = 0
        self.traces = 0
        self.compile_hist = LatencyHistogram()  # µs per compile
        self.signatures: Dict[str, int] = {}  # sig -> compiles it caused
        self.sig_overflow = 0
        self.storms = 0  # threshold crossings flagged (0 or 1 per site)
        # device-side twin (observability/kernwatch.py): cost capture on
        # compiles + sampled device timing, cadence per site kind
        self.kern = kernwatch.KernelRecord(op, kind)
        self._trace_pending = False
        self._lock = threading.Lock()

    def __del__(self):
        # the registry tracks watches by WEAKREF (a live rule's counters
        # must never be evicted out from under it); monotonicity across
        # rule restarts comes from folding a dying watch's counts into
        # the retired rollup here, at the moment its owner is collected
        try:
            _registry.retire_dead(self)
        except Exception:
            pass  # interpreter teardown: registry may already be gone

    # ------------------------------------------------------------- recording
    def annotate(self):
        """Context manager for ONE dispatch of this site: a
        `kuiper:jit:<site>` event on the profiler's host plane (nothing
        without a profiler session), so every device program in a capture
        has the host span that enqueued it."""
        from jax.profiler import TraceAnnotation

        return TraceAnnotation(self.trace_name)

    def on_compile(self, us: float, args: tuple, kwargs: dict) -> None:
        if self.rule is None:
            # attribution rides the compile path only (compiles are rare;
            # a per-call context lookup tripled the cache-hit overhead):
            # construction and every compile run on rule-context threads
            # (the rule FSM worker at plan time, node workers at runtime)
            from ..utils.rulelog import current_rule

            self.rule = current_rule()
        self.compile_hist.record(us)
        try:
            sig = _arg_signature(args, kwargs)
        except Exception:
            sig = "<unavailable>"
        with self._lock:
            self.traces += 1
            if sig in self.signatures:
                self.signatures[sig] += 1
            elif len(self.signatures) < SIG_CAP:
                self.signatures[sig] = 1
            else:
                self.sig_overflow += 1
            n_sigs = len(self.signatures) + self.sig_overflow
            storm = n_sigs > STORM_SIGNATURES and self.storms == 0
            if storm:
                self.storms = 1
        if storm:
            from ..runtime.events import recorder
            from ..utils.infra import logger

            logger.warning(
                "recompile storm: op %s has compiled %d distinct "
                "shape/dtype signatures (%d traces over %d calls) — "
                "input shapes are unstable, every fold pays compile "
                "latency; latest signature: %s",
                self.op, n_sigs, self.traces, self.calls, sig)
            recorder().record(
                "compile_storm", rule=self.rule or "", severity="warn",
                op=self.op, signatures=n_sigs, traces=self.traces,
                last_signature=sig[:256])

    def signature_dump(self) -> Dict[str, int]:
        """Full signature table copy (sig -> compiles it caused) — the
        deep-capture bundle's HLO-signature dump (health.capture_profile);
        too wide for the per-scrape snapshot."""
        with self._lock:
            return dict(self.signatures)

    # -------------------------------------------------------------- queries
    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            sigs = len(self.signatures) + self.sig_overflow
            out = {
                "op": self.op,
                "rule": self.rule,
                "calls": self.calls,
                "compiles": self.traces,
                "cache_hits": max(self.calls - self.traces, 0),
                "distinct_signatures": sigs,
                "storms": self.storms,
            }
        out["compile_us"] = self.compile_hist.snapshot()
        return out


class _WatchedJit:
    """The callable watched_jit returns — jit cache behavior is identical
    to a bare jax.jit(fn, **jit_kwargs) (one cache per instance)."""

    __slots__ = ("rec", "_jitted")

    def __init__(self, fn: Callable, rec: OpWatch, jit_kwargs: dict) -> None:
        import jax

        self.rec = rec

        def traced(*args, **kwargs):
            # executes ONLY under tracing: jit replays the compiled
            # executable on cache hits without entering the Python body
            rec._trace_pending = True
            return fn(*args, **kwargs)

        self._jitted = jax.jit(traced, **jit_kwargs)

    def __call__(self, *args, **kwargs):
        rec = self.rec
        rec._trace_pending = False
        kern = rec.kern
        sampled = kern.tick()
        t0 = _time.perf_counter()
        with rec.annotate():
            out = self._jitted(*args, **kwargs)
        t1 = _time.perf_counter()
        rec.calls += 1
        compiled = rec._trace_pending
        if compiled:
            # the call's wall time IS trace+compile (+ one dispatch, noise
            # against multi-ms XLA compiles)
            rec.on_compile((t1 - t0) * 1e6, args, kwargs)
            # cost_analysis off the lowered HLO — compiles only (lower()
            # re-traces; never worth it on the call path)
            kern.on_compile(self._jitted, args, kwargs)
        if sampled and not compiled:
            # sampled device-timing path: block on the outputs and split
            # the call into host-dispatch vs device time (kernwatch). A
            # call that COMPILED is never a timing sample — its wall time
            # is the compile, which would poison the dispatch floor and
            # device/roofline math and double-count against the compile
            # histogram in any dispatch/compile/device decomposition
            kern.sample(out, t0, t1, args, kwargs)
        return out


class _Registry:
    """Weakref index of live OpWatch records + retired accumulators.

    Strong ownership lives with the _WatchedJit (and through it, the
    kernel object holding the jit site) — the registry must never pin a
    dead rule's watches NOR evict a live rule's (freezing its counters
    mid-flight). When an owner is collected, OpWatch.__del__ folds its
    final counts into the per-(op, rule) retired rollup, so exported
    counters stay monotonic across rule restarts. Watches that die
    having never traced or been called (e.g. a subclass re-wrapping a
    site its base registered) retire to nothing and simply vanish."""

    def __init__(self) -> None:
        import weakref

        self._weakref = weakref
        self._lock = threading.Lock()
        self._watches: List = []  # weakref.ref[OpWatch]
        self._retired: Dict[Tuple[str, str], Dict[str, int]] = {}
        # counts of watches collected since the rollup was last read:
        # (key, calls, compiles, storms), queued by retire_dead
        self._dying: collections.deque = collections.deque()

    def register(self, op: str, rule: Optional[str],
                 kind: str = "hot") -> OpWatch:
        w = OpWatch(op, rule, kind)
        with self._lock:
            self._watches.append(self._weakref.ref(w))
            if len(self._watches) % 64 == 0:  # amortized dead-ref prune
                self._watches = [r for r in self._watches
                                 if r() is not None]
        return w

    def retire_dead(self, w: OpWatch) -> None:
        """Queue a dying watch's counts for the retired rollup (called
        from OpWatch.__del__; w is mid-collection — touch plain counters
        only, never its histogram/lock machinery). The collector may run
        a __del__ inside ANY allocation, also one this thread makes while
        it holds `_lock` (the prune in register, a rollup's copy): so no
        lock is taken here — deque.append is atomic — and `_fold_dying`
        adds the counts under the lock before the rollup is read."""
        if w.calls == 0 and w.traces == 0:
            return  # never used: leave no zero-valued metric rows behind
        kern = getattr(w, "kern", None)
        if kern is not None:
            from . import kernwatch

            kernwatch.retire(w.op, w.rule or "", kern)
        self._dying.append(((w.op, w.rule or ""), w.calls, w.traces,
                            w.storms))

    def _fold_dying(self) -> None:
        """Queued counts into the rollup; the caller holds `_lock`."""
        while self._dying:
            key, calls, compiles, storms = self._dying.popleft()
            acc = self._retired.setdefault(
                key, {"calls": 0, "compiles": 0, "storms": 0})
            acc["calls"] += calls
            acc["compiles"] += compiles
            acc["storms"] += storms
        while len(self._retired) > RETIRED_CAP:
            del self._retired[next(iter(self._retired))]

    # -------------------------------------------------------------- queries
    def watches(self) -> List[OpWatch]:
        with self._lock:
            refs = list(self._watches)
        return [w for w in (r() for r in refs) if w is not None]

    def aggregate(self) -> Dict[Tuple[str, str], Dict[str, Any]]:
        """Rollup by (op, rule) for the Prometheus exposition: counters
        include retired instances; the compile histogram merges live ones."""
        watches = self.watches()
        with self._lock:
            self._fold_dying()
            out: Dict[Tuple[str, str], Dict[str, Any]] = {
                k: {**v, "hist": None, "signatures": 0}
                for k, v in self._retired.items()}
        for w in watches:
            snap = w.snapshot()
            key = (w.op, w.rule or "")
            acc = out.setdefault(
                key, {"calls": 0, "compiles": 0, "storms": 0,
                      "hist": None, "signatures": 0})
            acc["calls"] += snap["calls"]
            acc["compiles"] += snap["compiles"]
            acc["storms"] += snap["storms"]
            acc["signatures"] += snap["distinct_signatures"]
            if acc["hist"] is None:
                acc["hist"] = LatencyHistogram()
            acc["hist"].merge(w.compile_hist)
        return out

    def rule_status(self, rule_id: str) -> Dict[str, Any]:
        """Per-op compile summary for one rule's /status JSON."""
        out: Dict[str, Any] = {}
        for w in self.watches():
            if (w.rule or "") != rule_id:
                continue
            snap = w.snapshot()
            acc = out.get(w.op)
            if acc is None:
                out[w.op] = {k: snap[k] for k in (
                    "calls", "compiles", "cache_hits",
                    "distinct_signatures", "storms", "compile_us")}
            else:
                for k in ("calls", "compiles", "cache_hits",
                          "distinct_signatures", "storms"):
                    acc[k] += snap[k]
        return out

    def totals(self) -> Dict[str, int]:
        """Engine-wide compile/call totals (bench warm-vs-cold segments)."""
        calls = compiles = storms = 0
        watches = self.watches()
        with self._lock:
            self._fold_dying()
            for v in self._retired.values():
                calls += v["calls"]
                compiles += v["compiles"]
                storms += v["storms"]
        for w in watches:
            snap = w.snapshot()
            calls += snap["calls"]
            compiles += snap["compiles"]
            storms += snap["storms"]
        return {"calls": calls, "compiles": compiles, "storms": storms}

    def clear(self) -> None:
        """Test hook."""
        with self._lock:
            self._watches.clear()
            self._dying.clear()
            self._retired.clear()


_registry = _Registry()


def registry() -> _Registry:
    return _registry


def watched_jit(fn: Callable, op: str, kind: str = "hot",
                **jit_kwargs) -> Callable:
    """Drop-in instrumented `jax.jit(fn, **jit_kwargs)`. `op` names the
    site in metrics (`kuiper_xla_*{op=...}`); the owning rule is read from
    the rule thread context at first call (plan/worker threads carry it).
    `kind` is the kernwatch site class — "hot" (per-batch path, sparse
    device-timing samples) or "boundary" (window/trigger cadence, dense
    samples are affordable)."""
    from ..utils.rulelog import current_rule

    return _WatchedJit(fn, _registry.register(op, current_rule(), kind),
                       jit_kwargs)


#: `le` ladder for kuiper_xla_compile_seconds, in µs (rendered as seconds:
#: 1ms .. 2min — XLA fold compiles span ~10ms to tens of seconds)
COMPILE_BOUNDS_US = (1_000, 5_000, 25_000, 100_000, 500_000,
                     1_000_000, 5_000_000, 30_000_000, 120_000_000)


def render_prometheus(out: List[str], esc) -> None:
    """Append the kuiper_xla_* families to a /metrics scrape. `esc` is the
    exposition label escaper (observability/prometheus.py _esc)."""
    agg = _registry.aggregate()
    rows = sorted(agg.items())

    def label(op: str, rule: str) -> str:
        return f'op="{esc(op)}",rule="{esc(rule or "__engine__")}"'

    fams = (
        ("kuiper_xla_compile_total", "counter",
         "XLA traces (compiles) per jit site", lambda v: v["compiles"]),
        ("kuiper_xla_cache_hit_total", "counter",
         "jit executable cache hits per site",
         lambda v: max(v["calls"] - v["compiles"], 0)),
        ("kuiper_xla_compile_signatures", "gauge",
         "distinct arg shape/dtype signatures compiled per site",
         lambda v: v["signatures"]),
        ("kuiper_xla_compile_storms_total", "counter",
         "recompile storms flagged (unstable input shapes)",
         lambda v: v["storms"]),
    )
    for name, mtype, help_txt, value in fams:
        out.append(f"# TYPE {name} {mtype}")
        out.append(f"# HELP {name} {help_txt}")
        for (op, rule), v in rows:
            out.append(f"{name}{{{label(op, rule)}}} {value(v)}")
    name = "kuiper_xla_compile_seconds"
    out.append(f"# TYPE {name} histogram")
    out.append(f"# HELP {name} XLA compile wall time per jit site (s)")
    for (op, rule), v in rows:
        hist = v.get("hist")
        if hist is None:
            continue
        cum, count, total_us = hist.export(COMPILE_BOUNDS_US)
        lbl = label(op, rule)
        for b_us, c in zip(COMPILE_BOUNDS_US, cum):
            out.append(f'{name}_bucket{{{lbl},le="{b_us / 1e6:g}"}} {c}')
        out.append(f'{name}_bucket{{{lbl},le="+Inf"}} {count}')
        out.append(f"{name}_sum{{{lbl}}} {total_us / 1e6:g}")
        out.append(f"{name}_count{{{lbl}}} {count}")
