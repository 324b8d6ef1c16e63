#!/usr/bin/env python
"""Metrics-exposition lint — run from the tier-1 suite (like
tools/check_native.py): renders a full synthetic Prometheus scrape and
fails loudly when any emitted metric

  1. is not `kuiper_`-prefixed,
  2. lacks a `# TYPE` or `# HELP` header, or
  3. is missing from the docs/OBSERVABILITY.md catalog,

and — the reverse direction — when any family with a catalog row in
docs/OBSERVABILITY.md fails to render a sample in the synthetic scrape
(dead doc rows for renamed/removed metrics; see RENDER_EXEMPT).

The synthetic registry exercises every family render() can emit: a rule
with a staged + pooled node, a shared subtopo node, and a populated
end-to-end histogram — so a new metric added without docs or headers
cannot slip through a scrape that simply never hit its branch.

Exit 0 = clean; exit 1 prints one line per violation.
"""
from __future__ import annotations

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DOCS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "docs", "OBSERVABILITY.md")

_SAMPLE_RE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{|\s)")


#: catalog families the synthetic scrape legitimately cannot render —
#: every entry must carry a reason; an undocumented reason is a lint bug
RENDER_EXEMPT: dict = {}


def catalog_families(docs_text: str) -> set:
    """Families with a ROW in the docs/OBSERVABILITY.md catalog table
    (`| \\`kuiper_...\\` | type | ...`) — prose mentions and label
    examples do not count. This is the reverse lint's contract set."""
    return set(re.findall(r"^\|\s*`(kuiper_[a-z0-9_]+)`", docs_text,
                          re.MULTILINE))


def rendered_families(text: str) -> set:
    """Base family names with at least one sample line in a scrape."""
    types = set()
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) >= 3:
                types.add(parts[2])
    seen = set()
    for line in text.splitlines():
        if not line.strip() or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            continue
        name = m.group(1)
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[: -len(suffix)] in types:
                name = name[: -len(suffix)]
                break
        seen.add(name)
    return seen


def reverse_lint(text: str, docs_text: str) -> list:
    """The catalog must stay honest in BOTH directions: every documented
    family must actually render a sample in the synthetic scrape, or the
    doc row is dead (a renamed/removed metric nobody pruned) and the
    forward lint can never catch it."""
    missing = catalog_families(docs_text) - rendered_families(text) \
        - set(RENDER_EXEMPT)
    return [f"{fam}: documented in docs/OBSERVABILITY.md but never "
            "rendered by the synthetic scrape (dead catalog row, or the "
            "synthetic registry lost its branch)"
            for fam in sorted(missing)]


def documented_families(docs_path: str = DOCS) -> set:
    """Every kuiper_* family named in docs/OBSERVABILITY.md — the
    catalog this lint (and kuiperlint's static metric-hygiene pass)
    treats as the registered set. Empty when the catalog is missing."""
    try:
        with open(docs_path) as f:
            text = f.read()
    except OSError:
        return set()
    return set(re.findall(r"kuiper_[a-z0-9_]+", text))


def _synthetic_scrape() -> str:
    """Render a scrape covering every metric family."""
    from ekuiper_tpu.observability.histogram import LatencyHistogram
    from ekuiper_tpu.observability.prometheus import render
    from ekuiper_tpu.utils.metrics import StatManager

    class FakeQueue:
        @staticmethod
        def qsize():
            return 2

    class Node:
        def __init__(self, name, op_type="op", pooled=False):
            self.name = name
            self.op_type = op_type
            self.inq = FakeQueue()
            self.stats = StatManager(op_type, name)
            self.stats.rule_id = "lint_rule"
            self.stats.inc_in(3)
            self.stats.inc_out(2)
            self.stats.inc_dropped("buffer_full")
            self.stats.observe_stage("decode", 120.0, 3)
            self.stats.observe_queue_wait(42.0)
            self.stats.process_begin()
            self.stats.process_end()
            if pooled:
                self.pool_depths = lambda: (1, 0)
                # kuiper_source_decode_{fields,bytes}_total render from it
                self.decode_tally = {"kept": 3, "skipped": 18, "bytes": 750}
            # kuiper_sliding_triggers_total renders from this attribute
            self.sliding_triggers = {"fast": 2, "flip": 1, "dyn": 1}
            # ... and kuiper_sliding_tail_total from this one
            self.sliding_tails = {"device": 3, "host": 1}
            # ... and kuiper_keytable_encode_rows_total from this method
            self.keytable_encode_rows = lambda: {"native_int": 3, "sorted": 0}
            # ... and kuiper_fold_transfers_total from this attribute
            self.fold_transfers = 18
            # ... and kuiper_fold_resident_args_total from this one
            self.fold_resident_args = 36
            # ... and kuiper_shadow_fold_compact_rows_total from this one
            self.shadow_fold_compact_rows = 2048
            # kuiper_fold_int_exact_rows_total renders for a node with
            # exact BIGINT partials, kuiper_agg_int_fallback_total from
            # its counted reasons
            self._exact = True
            self.fold_int_exact_rows = 4096
            self.agg_int_fallback = {"not_declared_bigint": 2}
            # kuiper_orderstat_{rows_total,buffer_rows,grow_total} render
            # for a node with order-statistic row buffers
            self._orderstat = True
            self.orderstat_rows = 4096
            self.orderstat_buffer_rows = 131072
            self.orderstat_grows = 1

    class SubTopo:
        nodes = [Node("shared_src", op_type="source", pooled=True)]

    # one REAL watermark node so the health evaluator's event-time probe
    # (and with it kuiper_watermark_lag_ms) renders a sample
    from ekuiper_tpu.runtime.nodes_window import WatermarkNode

    wm_node = WatermarkNode("wm_lint")
    wm_node.max_ts = 1  # watermark established → lag is reportable

    class Topo:
        e2e_hist = LatencyHistogram()
        boundary_hists = {"emit": LatencyHistogram()}

        def all_nodes(self):
            return [Node("src", "source"), Node("op1"), wm_node,
                    Node("sink", "sink")]

        def live_shared(self):
            return [(SubTopo(), None)]

    Topo.e2e_hist.record(7)
    Topo.e2e_hist.record(42)
    Topo.boundary_hists["emit"].record(3400)

    class State:
        topo = Topo()

    class Registry:
        def list(self):
            return [{"id": "lint_rule", "status": "running"}]

        def state(self, rid):
            return State()

    # a pooled shared fold so the kuiper_shared_fold_* families render
    from ekuiper_tpu.runtime import nodes_sharedfold

    class FakeStore:
        name = "shared_fold[lint]"
        windows_emitted = 3

        def member_count(self):
            return 2

        def fold_dedup_ratio(self):
            return 0.5

    nodes_sharedfold._stores["__lint__"] = FakeStore()
    # engine-health families: one populated compile watch (with a compile
    # sample so kuiper_xla_compile_seconds renders buckets) and one memory
    # probe — render() reads the module registries directly
    from ekuiper_tpu.observability import devwatch, kernwatch, memwatch

    watch = devwatch.registry().register("lint.fold", "lint_rule")
    watch.calls = 5
    watch.on_compile(12_000.0, (), {})
    # kernel observatory (observability/kernwatch.py): one sampled site
    # with a synthetic XLA cost so all five kuiper_kernel_* families
    # (device/dispatch time counters, flops/bytes gauges, roofline
    # utilization) render samples
    watch.kern.set_cost(flops=2e6, bytes_=1.12e7)
    watch.kern.record_sample(dispatch_us=50.0, total_us=850.0)
    # the roofline family renders only against a device peak spec, and
    # the CPU this lint runs on has none — pin a synthetic device for the
    # synthetic site (kernwatch.reset() below drops it)
    kernwatch._device_spec_cache[:] = [{"kind": "lintdev", "spec": {
        "name": "lint", "peak_flops": 1e12, "hbm_gbs": 100.0,
        "h2d_gbs": 10.0}}]

    class MemOwner:
        pass

    owner = MemOwner()
    memwatch.register("lint_component", owner, lambda o: 4096,
                      rule="lint_rule")
    # tiered key state (ops/tierstore.py): one registered manager so all
    # four kuiper_spill_*/kuiper_tier_host_bytes families render samples
    from ekuiper_tpu.ops import tierstore

    class FakeTierStore:
        def __len__(self):
            return 2

        def nbytes(self):
            return 4096

    class FakeTier:
        demoted_total = 3
        promoted_total = 1
        prefetch_hits = 0
        store = FakeTierStore()

    tier_mgr = FakeTier()
    tierstore.registry().register(tier_mgr, "lint_rule")
    # multi-chip sharded serving (parallel/sharded.py): one registered
    # fake kernel so kuiper_shard_rows_total / kuiper_shard_keys render
    from ekuiper_tpu.parallel import sharded as sharded_mod

    class FakeSharded:
        mesh_tag = "1x2"
        capacity = 64

        def shard_stats(self):
            # >= KUIPER_MESH_SKEW_MIN_ROWS total so the fleet
            # observatory computes a skew ratio on the first observe
            return [{"shard": 0, "rows": 300, "keys": 3, "slots": 32,
                     "state_bytes": 128},
                    {"shard": 1, "rows": 100, "keys": 1, "slots": 32,
                     "state_bytes": 128}]

        def collective_bytes_per_fold(self):
            return 192

    shard_kernel = FakeSharded()
    sharded_mod.registry().register(shard_kernel, "lint_rule")
    # fleet observatory (observability/meshwatch.py): one sampled
    # sharded fold site + an observe pass so all four kuiper_mesh_*
    # families render samples
    from ekuiper_tpu.observability import meshwatch

    meshwatch.reset()
    mesh_site = devwatch.registry().register("sharded.fold_step",
                                             "lint_rule")
    mesh_site.kern.set_cost(flops=1e6, bytes_=1e6)
    mesh_site.kern.record_sample(dispatch_us=10.0, total_us=500.0)
    meshwatch.observe()
    # durable telemetry timeline (observability/timeline.py): install
    # over a throwaway dir + one snapshot so kuiper_timeline_* render
    import shutil
    import tempfile

    from ekuiper_tpu.observability import timeline as timeline_mod

    tl_dir = tempfile.mkdtemp(prefix="lint_timeline_")
    tl = timeline_mod.install(scrape_fn=lambda: "kuiper_rule_status 1\n",
                              base_dir=tl_dir, interval_ms=0)
    tl.snapshot()
    # relational tier (ops/joinring.py / ops/segscan.py): one fake ring
    # and one fake scan kernel so the kuiper_join_* / kuiper_segscan_*
    # families all render samples
    from ekuiper_tpu.ops import joinring as joinring_mod
    from ekuiper_tpu.ops import segscan as segscan_mod

    class FakeRing:
        rows_total = {"l": 5, "r": 4}
        matches_total = 3
        fallback_windows_total = 1

        @staticmethod
        def nbytes():
            return 2048

    class FakeSegScan:
        rows_total = 7
        spills_total = 2

    join_ring = FakeRing()
    seg_kernel = FakeSegScan()
    joinring_mod.registry().register(join_ring, "lint_rule")
    segscan_mod.registry().register(seg_kernel, "lint_rule")
    # health plane: an installed evaluator with one ticked verdict so the
    # kuiper_rule_health / kuiper_slo_burn_rate / kuiper_watermark_lag_ms
    # / kuiper_bottleneck_stage families all render samples
    from ekuiper_tpu.observability import health

    hev = health.install(lambda: [("lint_rule", Topo(), {})], start=False)
    hev.tick()
    # QoS control plane (runtime/control.py): an installed controller
    # with one decision of each kind, a shed total, and an autosize
    # event so kuiper_admission_total / kuiper_shed_total /
    # kuiper_autosize_events_total all render samples
    from ekuiper_tpu.runtime import control

    ctl = control.install(lambda: [], start=False)
    for decision in ("accept", "reject", "queue"):
        ctl.note_admission(decision)
    ctl._shed_totals[("lint_rule", "standard")] = 42
    ctl.autosize_events = 1
    try:
        return render(Registry())
    finally:
        control.reset()
        health.reset()
        nodes_sharedfold._stores.pop("__lint__", None)
        devwatch.registry().clear()
        kernwatch.reset()
        memwatch.registry().clear()
        tierstore.reset()
        sharded_mod.reset()
        joinring_mod.reset()
        segscan_mod.reset()
        meshwatch.reset()
        timeline_mod.reset()
        shutil.rmtree(tl_dir, ignore_errors=True)
        del owner
        del tier_mgr
        del shard_kernel
        del join_ring
        del seg_kernel


def lint(text: str, docs_text: str) -> list:
    errors = []
    types: dict = {}
    helps: set = set()
    seen: dict = {}  # base family name -> first sample line no
    for i, line in enumerate(text.splitlines(), 1):
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) >= 4:
                types[parts[2]] = parts[3]
            continue
        if line.startswith("# HELP "):
            parts = line.split(None, 3)
            if len(parts) >= 3:
                helps.add(parts[2])
            continue
        if not line.strip() or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            errors.append(f"line {i}: unparseable sample line: {line!r}")
            continue
        name = m.group(1)
        base = name
        # histogram/summary series roll up to their family name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[: -len(suffix)] in types:
                base = name[: -len(suffix)]
                break
        seen.setdefault(base, i)
    for base, line_no in sorted(seen.items(), key=lambda kv: kv[1]):
        if not base.startswith("kuiper_"):
            errors.append(f"{base}: not kuiper_-prefixed (line {line_no})")
        if base not in types:
            errors.append(f"{base}: no # TYPE header (line {line_no})")
        if base not in helps:
            errors.append(f"{base}: no # HELP header (line {line_no})")
        # word-boundary match: a family must appear as a whole name —
        # substring hits (kuiper_op_stage_us inside kuiper_op_stage_us_total)
        # must not count as documentation
        if not re.search(rf"(?<![A-Za-z0-9_]){re.escape(base)}(?![A-Za-z0-9_])",
                         docs_text):
            errors.append(
                f"{base}: not documented in docs/OBSERVABILITY.md "
                f"(line {line_no})")
    return errors


def main() -> int:
    try:
        with open(DOCS) as f:
            docs_text = f.read()
    except FileNotFoundError:
        print(f"check_metrics: missing {DOCS}")
        return 1
    text = _synthetic_scrape()
    errors = lint(text, docs_text) + reverse_lint(text, docs_text)
    if errors:
        print(f"check_metrics: {len(errors)} violation(s)")
        for e in errors:
            print("  " + e)
        return 1
    n = len({ln.split()[2] for ln in text.splitlines()
             if ln.startswith("# TYPE ")})
    print(f"check_metrics: OK ({n} metric families, all prefixed, "
          "typed, helped, documented)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
