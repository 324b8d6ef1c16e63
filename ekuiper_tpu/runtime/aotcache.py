"""AOT executable cache — zero-compile serving off the jitcert manifest.

The engine's compile lifecycle used to be lazy: every rule create,
recover() and capacity-ladder grow paid seconds of trace+compile before
first emit — the exact stall class TiLT (arxiv 2301.12030) argues a
compilation-based stream engine must move out of the serve path. jitcert
(observability/jitcert.py) already proves compilation is fully determined
at plan time: each kernel carries a CLOSED certificate of every
(shape, dtype) signature it may legally trace with, and certificate
signature strings are byte-identical to devwatch's observed
`_arg_signature` strings. That identity is the cache key.

`aot_jit(fn, op=...)` replaces `watched_jit` at every kernel jit site.
Dispatch goes through a per-site table of pre-compiled XLA executables
keyed by the call's shape/dtype signature:

- table hit: run the executable — no jax.jit dispatch, no trace risk;
- table miss, disk hit: `deserialize_and_load` the persisted executable
  (~tens of ms, amortized once per site×signature per process) — this is
  what makes restart a non-event;
- disk miss: `jax.jit(fn).lower(...).compile()` the signature now,
  persist it, and leave a paper trail — a serve-time compile after a warm
  boot is a bug, so outside a `building()` scope it records a flight
  event on top of the devwatch trace accounting.

The disk layer lives under `KUIPER_AOT_CACHE_DIR` (opt-in: unset means
in-memory pinning only, which preserves test determinism). Entries are
keyed by `sha256(op × signature × jax/jaxlib version × platform × device
count × mesh shape)` so a toolchain or topology change yields a clean
miss, never a stale-executable load. jitcert's certify output doubles as
the build manifest: `python -m tools.aot build` drives the certification
battery with the disk layer on, and `verify` checks every certified
signature resolves to a cache entry (docs/AOT_CACHE.md).

devwatch accounting is unchanged: every aot_jit site owns the same
OpWatch record watched_jit would have registered, compiles count as
traces (kuiper_xla_compile_total), and jitcert diff_live still holds the
observed-signatures ⊆ certificate invariant — a serve-time trace outside
the manifest remains a hard failure, now with a cache-miss event
attached.
"""
from __future__ import annotations

import hashlib
import os
import pickle
import threading
import time as _time
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional, Tuple

#: per-site executable-table cap — certificates bound the legal signature
#: set well below this; a site past the cap has shape churn (devwatch
#: flags the storm) and stops pinning new executables rather than leak
TABLE_CAP = 128


def enabled() -> bool:
    """AOT dispatch kill switch (KUIPER_AOT=0 restores plain watched_jit
    semantics at every site)."""
    return os.environ.get("KUIPER_AOT", "1") != "0"


def cache_dir() -> Optional[str]:
    """On-disk layer root, or None when the disk layer is off."""
    d = os.environ.get("KUIPER_AOT_CACHE_DIR", "").strip()
    return d or None


# ------------------------------------------------------------ cache keys
def _fingerprint_parts() -> Tuple[str, ...]:
    """Everything outside (op, signature) that can invalidate a compiled
    executable: toolchain versions, backend, device topology. Split out
    so tests can monkeypatch one part and assert a clean miss."""
    import jax
    import jaxlib

    return (
        f"jax={jax.__version__}",
        f"jaxlib={jaxlib.__version__}",
        f"platform={jax.default_backend()}",
        f"devices={jax.device_count()}",
        f"mesh={os.environ.get('KUIPER_MESH', 'auto')}",
    )


def fingerprint() -> str:
    return "×".join(_fingerprint_parts())


def cache_key(op: str, signature: str, fp: Optional[str] = None) -> str:
    """Content address of one executable: hash(cert signature ×
    jaxlib/XLA version × mesh shape × platform). `signature` is the
    jitcert certificate string (== devwatch `_arg_signature`)."""
    fp = fingerprint() if fp is None else fp
    h = hashlib.sha256(f"{op}\n{signature}\n{fp}".encode())
    return h.hexdigest()


def _entry_path(root: str, key: str) -> str:
    return os.path.join(root, f"{key}.aotx")


def is_cached(op: str, signature: str, fp: Optional[str] = None) -> bool:
    """Disk-layer probe by certificate string alone — no kernel, no
    lowering. This is what admission pricing (runtime/control.py
    price.compile) and explain's "aot" section use: certified-but-
    uncached signatures are the compile debt a candidate rule carries."""
    root = cache_dir()
    if root is None:
        return False
    return os.path.exists(_entry_path(root, cache_key(op, signature, fp)))


# ----------------------------------------------------------------- stats
class _Stats:
    """Engine-wide counters behind kuiper_aot_* (all monotonic except
    `executables`, recomputed from live sites at scrape time)."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.hits = 0          # calls served by a pre-built executable
        self.misses = 0        # lower+compile events (build or serve)
        self.serve_misses = 0  # misses outside a building() scope
        self.disk_loads = 0    # executables deserialized from disk
        self.builds = 0        # executables compiled + persisted
        self.build_seconds = 0.0
        self.warmup_failures = 0

    def snapshot(self) -> Dict[str, Any]:
        with self.lock:
            return {
                "enabled": enabled(), "dir": cache_dir(),
                "hits": self.hits, "misses": self.misses,
                "serve_misses": self.serve_misses,
                "disk_loads": self.disk_loads, "builds": self.builds,
                "build_seconds": round(self.build_seconds, 3),
                "executables": executables_live(),
                "warmup_failures": self.warmup_failures,
            }


_stats = _Stats()
_tls = threading.local()


def stats() -> _Stats:
    return _stats


@contextmanager
def building():
    """Marks the current thread as running a deliberate cache build
    (boot prebuild, worker warmup, `tools/aot build`): misses inside the
    scope are the build doing its job and skip the serve-time flight
    event. Nests."""
    depth = getattr(_tls, "building", 0)
    _tls.building = depth + 1
    try:
        yield
    finally:
        _tls.building = depth


def in_build() -> bool:
    return getattr(_tls, "building", 0) > 0


def note_warmup_failure(rule: str, stage: str, exc: BaseException) -> None:
    """A failed warmup is a guaranteed serve-time compile stall later —
    count it (kuiper_warmup_failures_total) and leave a flight event so
    it bisects to a stage, never a silent logger.debug."""
    from .events import recorder

    with _stats.lock:
        _stats.warmup_failures += 1
    recorder().record(
        "warmup_failure", rule=rule or "", severity="warn", stage=stage,
        error=f"{type(exc).__name__}: {exc}"[:256])


# ---------------------------------------------------------- site registry
class _SiteRegistry:
    """Weakref index of live _AotJit sites (explain "aot" section,
    kuiper_aot_executables, /diagnostics rollups). Ownership stays with
    the kernel object, exactly like devwatch's watch registry."""

    def __init__(self) -> None:
        import weakref

        self._weakref = weakref
        self._lock = threading.Lock()
        self._sites: List = []  # weakref.ref[_AotJit]

    def register(self, site: "_AotJit") -> None:
        with self._lock:
            self._sites.append(self._weakref.ref(site))
            if len(self._sites) % 64 == 0:
                self._sites = [r for r in self._sites if r() is not None]

    def sites(self) -> List["_AotJit"]:
        with self._lock:
            refs = list(self._sites)
        return [s for s in (r() for r in refs) if s is not None]

    def clear(self) -> None:
        with self._lock:
            self._sites.clear()


_sites = _SiteRegistry()


def executables_live() -> int:
    return sum(len(s._table) for s in _sites.sites())


def site_report(rule: Optional[str] = None) -> List[Dict[str, Any]]:
    """Per-site hit/miss rollup (explain "aot" section, /status)."""
    out = []
    for s in _sites.sites():
        if rule is not None and (s.rec.rule or "") != rule:
            continue
        out.append({
            "op": s.rec.op, "rule": s.rec.rule or "",
            "hits": s.hits, "misses": s.misses,
            "disk_loads": s.disk_loads, "executables": len(s._table),
            "degraded": s._degraded,
        })
    out.sort(key=lambda r: (r["op"], r["rule"]))
    return out


# ------------------------------------------------------------- the wrapper
def _fast_key(args: tuple, kwargs: dict) -> tuple:
    """Executable-table key: hashable twin of devwatch._arg_signature
    (arrays by (dtype, shape), statics by value). Kept allocation-light —
    this runs on the hot fold path where the jit dispatch used to be."""
    import jax

    key: List[Any] = []
    for leaf in jax.tree_util.tree_leaves((args, kwargs)):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            key.append((dtype, tuple(shape)))
        else:
            try:
                hash(leaf)
                key.append(leaf)
            except TypeError:
                key.append(repr(leaf)[:48])
    return tuple(key)


class _AotJit:
    """The callable aot_jit returns. Semantically a jax.jit(fn,
    **jit_kwargs) — identical outputs, identical donation — but dispatch
    rides an explicit signature→Compiled table so executables can be
    installed from disk before the first call ever traces."""

    def __init__(self, fn: Callable, rec, jit_kwargs: dict) -> None:
        import jax

        self.rec = rec  # devwatch.OpWatch — shared accounting spine
        self._fn = fn
        self._jit_kwargs = dict(jit_kwargs)
        static = jit_kwargs.get("static_argnums", ())
        if isinstance(static, int):
            static = (static,)
        self._static = frozenset(static)
        self._jit = jax.jit(fn, **jit_kwargs)  # lowering seam only
        self._table: Dict[tuple, Any] = {}  # fast key -> Compiled
        self._lock = threading.Lock()
        self._fallback = None  # devwatch._WatchedJit, built on first need
        self._degraded = False  # AOT machinery failed — plain jit path
        self.hits = 0
        self.misses = 0
        self.disk_loads = 0
        _sites.register(self)

    # ------------------------------------------------------------ helpers
    def _strip_static(self, args: tuple) -> tuple:
        if not self._static:
            return args
        return tuple(a for i, a in enumerate(args)
                     if i not in self._static)

    def _ensure_fallback(self):
        if self._fallback is None:
            from ..observability import devwatch

            self._fallback = devwatch._WatchedJit.__new__(
                devwatch._WatchedJit)
            devwatch._WatchedJit.__init__(
                self._fallback, self._fn, self.rec, self._jit_kwargs)
        return self._fallback

    def _signature(self, args: tuple, kwargs: dict) -> str:
        from ..observability import devwatch

        try:
            return devwatch._arg_signature(args, kwargs)
        except Exception:
            return "<unavailable>"

    def _load_from_disk(self, sig: str):
        """Deserialize one persisted executable, or None. A corrupt or
        foreign entry is unlinked and treated as a miss — never a
        stale-executable load (the key already pins op × signature ×
        toolchain × topology; the meta check is belt and braces)."""
        root = cache_dir()
        if root is None:
            return None
        path = _entry_path(root, cache_key(self.rec.op, sig))
        if not os.path.exists(path):
            return None
        try:
            with open(path, "rb") as fh:
                blob = pickle.load(fh)
            meta = blob.get("meta", {})
            if (meta.get("fingerprint") != fingerprint()
                    or meta.get("op") != self.rec.op
                    or meta.get("signature") != sig):
                raise ValueError("cache entry metadata mismatch")
            import jax
            from jax.experimental import serialize_executable

            # load onto the devices the executable was compiled for: the
            # loader's default is EVERY device of the backend, and a
            # one-chip program loaded across a 4- or 8-device host then
            # refuses its own arguments ("expected N shards")
            by_id = {d.id: d for d in jax.devices()}
            compiled = serialize_executable.deserialize_and_load(
                blob["payload"], blob["in_tree"], blob["out_tree"],
                execution_devices=[by_id[i] for i in meta["device_ids"]])
        except Exception:
            try:
                os.unlink(path)
            except OSError:
                pass
            return None
        cost = meta.get("cost")
        if cost:
            try:
                self.rec.kern.set_cost(cost.get("flops"),
                                       cost.get("bytes"))
            except Exception:
                pass
        return compiled

    def _persist(self, compiled, sig: str, compile_s: float,
                 cost: Optional[dict]) -> None:
        root = cache_dir()
        if root is None:
            return
        try:
            from jax.experimental import serialize_executable

            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled)
            blob = {
                "payload": payload, "in_tree": in_tree,
                "out_tree": out_tree,
                "meta": {
                    "op": self.rec.op, "signature": sig,
                    "fingerprint": fingerprint(),
                    "device_ids": [
                        d.id for d in
                        compiled.runtime_executable().local_devices()],
                    "compile_s": round(compile_s, 4), "cost": cost,
                },
            }
            os.makedirs(root, exist_ok=True)
            path = _entry_path(root, cache_key(self.rec.op, sig))
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as fh:
                pickle.dump(blob, fh)
            os.replace(tmp, path)  # atomic: concurrent builders race safely
        except Exception as exc:
            from ..utils.infra import logger

            logger.debug("aot persist failed for %s (non-fatal): %s",
                         self.rec.op, exc)

    def _build(self, key: tuple, sig: str, args: tuple, kwargs: dict):
        """The true-miss path: lower (accepts ShapeDtypeStruct leaves in
        place of arrays), compile, persist, account. Returns Compiled."""
        rec = self.rec
        t0 = _time.perf_counter()
        lowered = self._jit.lower(*args, **kwargs)
        compiled = lowered.compile()
        dt = _time.perf_counter() - t0
        rec.on_compile(dt * 1e6, args, kwargs)
        rec.kern.on_compile(_Prelowered(lowered), args, kwargs)
        cost = None
        try:
            ca = lowered.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
            if isinstance(ca, dict):
                cost = {"flops": ca.get("flops"),
                        "bytes": ca.get("bytes accessed")}
        except Exception:
            pass
        self._persist(compiled, sig, dt, cost)
        with _stats.lock:
            _stats.misses += 1
            _stats.builds += 1
            _stats.build_seconds += dt
            serve = not in_build()
            if serve:
                _stats.serve_misses += 1
        self.misses += 1
        if serve:
            # a compile AFTER warm boot is the bug this cache exists to
            # kill — paper trail, not just a counter
            from .events import recorder

            recorder().record(
                "aot_cache_miss", rule=rec.rule or "", severity="warn",
                op=rec.op, signature=sig[:256],
                compile_ms=round(dt * 1e3, 1),
                disk=cache_dir() is not None)
        self._install(key, compiled)
        return compiled

    def _install(self, key: tuple, compiled) -> None:
        with self._lock:
            if len(self._table) < TABLE_CAP:
                self._table[key] = compiled

    # ------------------------------------------------------------ dispatch
    def probe(self, *args, **kwargs) -> str:
        """Ensure the executable for this argument signature exists
        WITHOUT executing anything — leaves may be ShapeDtypeStructs.
        This is what nodes_fused warmup runs at worker start: a warm
        disk cache makes it a deserialization sweep (tens of ms); a cold
        one makes it the build. Returns "mem" | "disk" | "built"
        ("jit" when AOT is degraded/disabled for the site)."""
        if self._degraded:
            return "jit"
        key = _fast_key(args, kwargs)
        with self._lock:
            if key in self._table:
                return "mem"
        sig = self._signature(args, kwargs)
        try:
            compiled = self._load_from_disk(sig)
            if compiled is not None:
                self.disk_loads += 1
                with _stats.lock:
                    _stats.disk_loads += 1
                self._install(key, compiled)
                return "disk"
            self._build(key, sig, args, kwargs)
            return "built"
        except Exception as exc:
            self._degrade(exc)
            return "jit"

    def _degrade(self, exc: BaseException) -> None:
        """AOT machinery failure (serializer gap, backend quirk): fall
        back to the plain watched jit path for this site, permanently
        and loudly — correctness first, zero-compile second."""
        from ..utils.infra import logger
        from .events import recorder

        self._degraded = True
        logger.warning("aot cache degraded for %s (plain jit path): %s",
                       self.rec.op, exc)
        recorder().record(
            "aot_degraded", rule=self.rec.rule or "", severity="warn",
            op=self.rec.op, error=f"{type(exc).__name__}: {exc}"[:256])

    def __call__(self, *args, **kwargs):
        rec = self.rec
        if self._degraded:
            return self._ensure_fallback()(*args, **kwargs)
        kern = rec.kern
        sampled = kern.tick()
        key = _fast_key(args, kwargs)
        compiled = self._table.get(key)
        if compiled is None:
            sig = self._signature(args, kwargs)
            try:
                compiled = self._load_from_disk(sig)
                if compiled is not None:
                    self.disk_loads += 1
                    with _stats.lock:
                        _stats.disk_loads += 1
                    self._install(key, compiled)
                else:
                    compiled = self._build(key, sig, args, kwargs)
            except Exception as exc:
                self._degrade(exc)
                return self._ensure_fallback()(*args, **kwargs)
        t0 = _time.perf_counter()
        try:
            with rec.annotate():
                out = compiled(*self._strip_static(args), **kwargs)
        except TypeError as exc:
            # calling-convention drift (args/kwargs split differs from
            # the lowered structure) surfaces as a pytree mismatch BEFORE
            # dispatch — donation has not fired; degrade, don't crash
            self._degrade(exc)
            return self._ensure_fallback()(*args, **kwargs)
        t1 = _time.perf_counter()
        rec.calls += 1
        self.hits += 1
        with _stats.lock:
            _stats.hits += 1
        if sampled:
            kern.sample(out, t0, t1, args, kwargs)
        return out


class _Prelowered:
    """Adapter handing kernwatch.on_compile an already-lowered program
    (its contract is `jitted.lower(*args, **kwargs).cost_analysis()`;
    re-lowering here would double the trace cost of every build)."""

    def __init__(self, lowered) -> None:
        self._lowered = lowered

    def lower(self, *args, **kwargs):
        return self._lowered


def aot_jit(fn: Callable, op: str, kind: str = "hot",
            **jit_kwargs) -> Callable:
    """Drop-in watched_jit with AOT-cached dispatch. Same accounting
    (devwatch OpWatch, kernwatch record), same jit semantics (donation,
    static argnums), plus: executables install from the on-disk cache
    before any trace, and serve-time compiles leave a flight event.
    KUIPER_AOT=0 returns the plain watched path."""
    from ..observability import devwatch

    if not enabled():
        return devwatch.watched_jit(fn, op, kind=kind, **jit_kwargs)
    from ..utils.rulelog import current_rule

    rec = devwatch.registry().register(op, current_rule(), kind)
    return _AotJit(fn, rec, jit_kwargs)


# ------------------------------------------------------------ admission
def plan_compile_price(certs) -> Dict[str, Any]:
    """Admission's compile ledger for one candidate plan: how many
    certified signatures its kernels may trace, and how many already
    have a persisted executable. Admission prices the DIFFERENCE — a
    warm fleet image admits rules against near-zero compile debt.
    `certs` is a list of jitcert.SiteCert."""
    fp = fingerprint()
    root = cache_dir()
    certified = cached = 0
    truncated = False
    sites = []
    for c in certs:
        n_cached = 0
        if root is not None and not c.truncated:
            n_cached = sum(1 for s in c.signatures if is_cached(c.op, s, fp))
        certified += c.full_count
        cached += n_cached
        truncated = truncated or c.truncated
        sites.append({"op": c.op, "certified": c.full_count,
                      "cached": n_cached})
    return {
        "enabled": root is not None,
        "certified": certified,
        "cached": cached,
        "uncached": max(certified - cached, 0),
        "truncated": truncated,
        "sites": sites,
    }


# ----------------------------------------------------------- observability
def render_prometheus(out: List[str], esc) -> None:
    """Append the kuiper_aot_* families (+ the warmup-failure counter)
    to a /metrics scrape."""
    snap = _stats.snapshot()
    fams = (
        ("kuiper_aot_hits_total", "counter",
         "calls served by a pre-built AOT executable", snap["hits"]),
        ("kuiper_aot_misses_total", "counter",
         "jit sites lowered+compiled at runtime (build or serve)",
         snap["misses"]),
        ("kuiper_aot_serve_misses_total", "counter",
         "AOT compiles OUTSIDE a build/warmup scope — warm-boot bugs",
         snap["serve_misses"]),
        ("kuiper_aot_disk_loads_total", "counter",
         "executables deserialized from the on-disk AOT cache",
         snap["disk_loads"]),
        ("kuiper_aot_build_seconds", "counter",
         "cumulative XLA compile seconds spent building AOT executables",
         snap["build_seconds"]),
        ("kuiper_aot_executables", "gauge",
         "pre-built executables pinned across live jit sites",
         snap["executables"]),
        ("kuiper_warmup_failures_total", "counter",
         "worker warmup/cache-probe failures (future serve-time "
         "compile stalls)", snap["warmup_failures"]),
    )
    for name, mtype, help_txt, value in fams:
        out.append(f"# TYPE {name} {mtype}")
        out.append(f"# HELP {name} {help_txt}")
        out.append(f"{name} {value}")


def reset() -> None:
    """Test hook: drop all counters and site registrations (the sites
    themselves live on their kernels and keep working)."""
    global _stats
    _stats = _Stats()
    _sites.clear()
