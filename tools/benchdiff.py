#!/usr/bin/env python
"""benchdiff — the bench-trajectory regression gate.

Compares two or more driver bench artifacts
metric-by-metric: the LAST file is the candidate round, the metric
baseline is the most recent EARLIER round carrying that metric (phases
come and go across rounds; a metric new in the candidate has no baseline
and is reported as such, never gated). Each artifact is the driver's
record: `{n, cmd, rc, tail, parsed}` where `parsed` is bench.py's final
stdout JSON line (`{metric, value, unit, vs_baseline, phases: {...}}`).

Why this exists: a round once came back `rc=124, parsed: null` and nothing
noticed — the perf trajectory was blind, so no PR could prove it didn't
regress the headline. This gate makes two failure classes
loud and machine-checkable:

- a candidate round that FAILED to produce an artifact (`parsed` null /
  nonzero rc) exits nonzero by itself — a dead bench is a regression;
- a HEADLINE metric (the tumbling rows/s line, full-pipe rows/s, e2e
  p99) regressing beyond its noise tolerance exits nonzero.

Everything else — per-phase rows/s, latency percentiles, degradation —
is compared with the same direction-aware noise tolerance and flagged in
the report, but only headline metrics gate (phase metrics on a shared CI
box are noisy; the gate must not cry wolf).

Usage:
  python tools/benchdiff.py OLD.json NEW.json
  python tools/benchdiff.py ROUND_*.json            # trajectory view
  python tools/benchdiff.py --tolerance 0.15 A.json B.json
  python tools/benchdiff.py --smoke                 # tier-1 self-test

Exit codes: 0 ok; 1 headline regression or failed candidate round;
2 usage/artifact error.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

#: metrics that GATE (exit 1 on regression): (flat key, tolerance).
#: Tolerances are per-metric noise allowances measured off the recorded
#: round-to-round jitter — throughput on a quiet box swings ~10%, e2e
#: tail latency much more (one GC pause moves a p99), so the p99 gate
#: only catches step-function regressions, not jitter.
HEADLINE = (
    ("headline.value", 0.10),
    ("phases.full_pipe.rows_per_sec", 0.15),
    ("phases.full_pipe.e2e_p99_ms", 0.50),
    # QoS churn soak (ISSUE 9): healthy-rule emit p99 under sustained
    # rule churn + skew shifts + backpressure — same loose tail
    # tolerance as the full-pipe p99 (one GC pause moves a p99)
    ("phases.churn_soak.soak_p99_ms", 0.50),
    # sliding DABA rings (ISSUE 11): trigger→sink emit tail on the
    # constant-time sliding path, saturated + paced twins — a sliding
    # latency regression gates ci_gate every round, not report-only
    ("phases.sliding_saturated.emit_p99_ms", 0.50),
    ("phases.sliding_paced.emit_p99_ms", 0.50),
    # compiled expression IR (ISSUE 12): a filter-heavy rule must stay
    # fold-limited — its throughput gates alongside the tumbling line,
    # and the predicate-lifted shared fold's dedup ratio must hold
    ("phases.filter_heavy.rows_per_sec", 0.15),
    # device relational tier (ISSUE 19): interval-join match throughput
    # and the per-window emission tail through the join ring — a kernel
    # or emission-reconstruction regression gates every round
    ("phases.join_heavy.rows_per_sec", 0.15),
    ("phases.join_heavy.emit_p99_ms", 0.50),
    ("phases.multi_rule_shared_mixed.mixed_where_dedup_ratio", 0.10),
    # tiered key state (ISSUE 13): sustained rows/s and emit tail while
    # the cold tier absorbs a 1M->10M cardinality sweep under a fixed
    # HBM budget — a tiering-policy regression (demote storms stalling
    # folds, promote misses) shows up in exactly these two
    ("phases.key_cardinality.rows_per_sec", 0.15),
    ("phases.key_cardinality.emit_p99_ms", 0.50),
    # multi-chip sharded serving (ISSUE 15): the saturated tumbling full
    # pipe on the device mesh gates every round instead of a dryrun —
    # same throughput tolerance as the single-chip full-pipe line
    ("phases.multichip_full_pipe.rows_per_sec", 0.15),
    # AOT executable cache (ISSUE 16): rule-create→first-fold on a warm
    # disk cache is the zero-compile-restart claim — a serve-path
    # compile sneaking back in moves this from tens of ms to seconds,
    # far past any tolerance; ordinary scheduler jitter stays inside it
    ("phases.cold_start.warm.rule_create_to_first_fold_ms", 0.50),
)

#: default noise tolerance for every non-headline comparison
DEFAULT_TOLERANCE = 0.10

#: flat-key suffixes where LOWER is better; everything else numeric that
#: we compare is higher-better (throughput-shaped). Order matters only
#: for readability — first suffix match wins.
LOWER_IS_BETTER = ("_ms", "_us", "us_per_call", "_pct", "_bytes_peak",
                   # fleet observatory (ISSUE 20), report-only — shard
                   # imbalance and priced collective time should trend
                   # down (observatory_overhead_pct rides the _pct rule)
                   "skew_ratio", "collective_ms_p50")

#: suffixes compared at all — a flat key must end in one of these (either
#: direction) to be diffed; other numeric leaves (counts, booleans,
#: config echoes like pool/shards/burners) are context, not performance
HIGHER_IS_BETTER = ("_per_sec", "_per_s", "rows_per_sec", "dedup_ratio",
                    "roofline_util", "_util")


def classify(key: str) -> Optional[str]:
    """'higher' | 'lower' | None (not a perf metric)."""
    if key == "headline.value":  # the tumbling rows/s line
        return "higher"
    leaf = key.rsplit(".", 1)[-1]
    for suf in LOWER_IS_BETTER:
        if leaf.endswith(suf):
            return "lower"
    for suf in HIGHER_IS_BETTER:
        if leaf.endswith(suf):
            return "higher"
    return None


def flatten(artifact: Dict[str, Any]) -> Dict[str, float]:
    """Numeric perf metrics of one round as flat dotted keys:
    `headline.value` plus every classified leaf under `parsed.phases`."""
    parsed = artifact.get("parsed") or {}
    out: Dict[str, float] = {}
    v = parsed.get("value")
    if isinstance(v, (int, float)) and not isinstance(v, bool):
        out["headline.value"] = float(v)

    def walk(prefix: str, node: Any) -> None:
        if isinstance(node, dict):
            for k, sub in node.items():
                walk(f"{prefix}.{k}", sub)
        elif (isinstance(node, (int, float))
              and not isinstance(node, bool)
              and math.isfinite(float(node))
              and classify(prefix) is not None):
            out[prefix] = float(node)

    walk("phases", parsed.get("phases") or {})
    return out


def round_ok(artifact: Dict[str, Any]) -> Tuple[bool, str]:
    """(usable, reason). A round is usable when it carries a parsed
    artifact; rc is reported but only a MISSING artifact disqualifies
    (the bench's own watchdogs exit rc=3 WITH a valid final JSON)."""
    rc = artifact.get("rc")
    if not isinstance(artifact.get("parsed"), dict):
        return False, f"parsed is null (rc={rc}) — the r05 failure class"
    if not flatten(artifact):
        return False, f"parsed carries no comparable metrics (rc={rc})"
    return True, f"rc={rc}"


def compare(rounds: List[Tuple[str, Dict[str, Any]]],
            tolerance: float = DEFAULT_TOLERANCE) -> Dict[str, Any]:
    """Diff the last round against per-metric baselines from the earlier
    ones. Returns {candidate, baseline_names, rows, regressions,
    headline_regressions, candidate_ok, candidate_reason}; `rows` is one
    entry per metric present in the candidate or any baseline."""
    cand_name, cand = rounds[-1]
    ok, reason = round_ok(cand)
    out: Dict[str, Any] = {
        "candidate": cand_name, "candidate_ok": ok,
        "candidate_reason": reason,
        "baselines": [n for n, _ in rounds[:-1]],
        "rows": [], "regressions": [], "headline_regressions": [],
    }
    if not ok:
        return out
    flats = [(name, flatten(a)) for name, a in rounds]
    cand_flat = flats[-1][1]
    headline_tol = dict(HEADLINE)
    keys = sorted({k for _, f in flats for k in f})
    for key in keys:
        cur = cand_flat.get(key)
        base = base_name = None
        for name, f in reversed(flats[:-1]):  # most recent earlier round
            if key in f:
                base, base_name = f[key], name
                break
        row: Dict[str, Any] = {"metric": key, "baseline": base,
                               "baseline_round": base_name,
                               "candidate": cur}
        if base is None or cur is None:
            row["status"] = ("new" if base is None else "dropped")
            if cur is None and key in headline_tol:
                # a HEADLINE metric that VANISHES gates like a regression:
                # a partially-dead bench (full_pipe child timed out, the
                # tumbling headline still printed) must not pass the
                # trajectory gate on whole-artifact survival alone
                out["regressions"].append(row)
                out["headline_regressions"].append(row)
            out["rows"].append(row)
            continue
        direction = classify(key)
        tol = headline_tol.get(key, tolerance)
        if base == 0.0:
            # no ratio exists over a zero baseline: a nonzero value
            # appearing is a full-size change, never inside tolerance
            # (a 0ms stall becoming 500ms must flag, not divide by zero)
            delta = math.inf if cur > 0 else (
                -math.inf if cur < 0 else 0.0)
            row["delta_pct"] = None if cur else 0.0
        else:
            delta = (cur - base) / abs(base)
            row["delta_pct"] = round(delta * 100.0, 1)
        worse = -delta if direction == "higher" else delta
        if worse > tol:
            row["status"] = "REGRESSION"
            out["regressions"].append(row)
            if key in headline_tol:
                out["headline_regressions"].append(row)
        elif worse < -tol:
            row["status"] = "improved"
        else:
            row["status"] = "ok"
        row["tolerance_pct"] = round(tol * 100.0, 1)
        out["rows"].append(row)
    return out


def report(cmp: Dict[str, Any], verbose: bool = False) -> None:
    """Human-readable diff on stdout (the gate's evidence trail)."""
    base = ", ".join(cmp["baselines"]) or "(none)"
    print(f"benchdiff: {base} -> {cmp['candidate']}")
    if not cmp["candidate_ok"]:
        print(f"  CANDIDATE ROUND FAILED: {cmp['candidate_reason']}")
        return
    for row in cmp["rows"]:
        status = row.get("status")
        gates = row in cmp["headline_regressions"]
        if status in ("ok", "new", "dropped") and not verbose and not gates:
            continue
        if status in ("new", "dropped"):
            print(f"  {'!! ' if gates else ''}{status:<10} {row['metric']}"
                  + (" (HEADLINE vanished — gates)" if gates else ""))
            continue
        mark = {"REGRESSION": "!!", "improved": "++"}.get(status, "  ")
        dp = row["delta_pct"]
        delta_txt = f"{dp:+.1f}%" if dp is not None else "from zero"
        print(f"  {mark} {status:<10} {row['metric']}: "
              f"{row['baseline']:g} -> {row['candidate']:g} "
              f"({delta_txt}, tol ±{row['tolerance_pct']}%)")
    n_reg = len(cmp["regressions"])
    n_head = len(cmp["headline_regressions"])
    print(f"  {len(cmp['rows'])} metrics compared, {n_reg} regression(s), "
          f"{n_head} headline")


def gate(cmp: Dict[str, Any]) -> int:
    """Exit code for one comparison: 1 on failed candidate or headline
    regression, else 0 (non-headline regressions are report-only)."""
    if not cmp["candidate_ok"]:
        return 1
    return 1 if cmp["headline_regressions"] else 0


def _load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        d = json.load(f)
    if not isinstance(d, dict):
        raise ValueError(f"{path}: artifact must be a JSON object")
    return d


# --------------------------------------------------------------------- smoke
def smoke() -> int:
    """Tier-1 self-test (like kuiperdiag --smoke): synthetic artifacts
    exercise the pass / headline-regression / failed-round paths without
    touching real BENCH files."""

    def art(value, phases=None, rc=0, parsed=True):
        return {"n": 1, "cmd": "bench", "rc": rc, "tail": "",
                "parsed": ({"metric": "t", "value": value, "unit": "rows/s",
                            "phases": phases or {}} if parsed else None)}

    base = art(2_800_000, {
        "full_pipe": {"rows_per_sec": 1_000_000.0, "e2e_p99_ms": 4.0,
                      "decoder": "native"},
        "sliding_saturated": {"fold_stall_p50_ms": 50.0}})
    problems = []
    # 1) small wobble inside tolerance + a phase improvement -> exit 0
    good = art(2_700_000, {
        "full_pipe": {"rows_per_sec": 1_050_000.0, "e2e_p99_ms": 4.2,
                      "decoder": "native"},
        "sliding_saturated": {"fold_stall_p50_ms": 20.0}})
    cmp1 = compare([("r1", base), ("r2", good)])
    if gate(cmp1) != 0 or cmp1["regressions"]:
        problems.append(f"clean round flagged: {cmp1['regressions']}")
    if not any(r["status"] == "improved" for r in cmp1["rows"]):
        problems.append("sliding stall improvement not detected")
    # 2) headline collapse -> exit 1, named in headline_regressions
    bad = art(1_500_000, {"full_pipe": {"rows_per_sec": 990_000.0,
                                        "e2e_p99_ms": 4.0}})
    cmp2 = compare([("r1", base), ("r2", bad)])
    if gate(cmp2) != 1:
        problems.append("headline -46% did not gate")
    if [r["metric"] for r in cmp2["headline_regressions"]] != \
            ["headline.value"]:
        problems.append(f"wrong headline set: {cmp2['headline_regressions']}")
    # 3) non-headline regression alone -> flagged but exit 0
    slow = art(2_800_000, {
        "full_pipe": {"rows_per_sec": 1_000_000.0, "e2e_p99_ms": 4.0},
        "sliding_saturated": {"fold_stall_p50_ms": 400.0}})
    cmp3 = compare([("r1", base), ("r2", slow)])
    if gate(cmp3) != 0 or len(cmp3["regressions"]) != 1:
        problems.append(f"phase-only regression mishandled: "
                        f"{cmp3['regressions']}")
    # 4) the r05 class: candidate parsed null -> exit 1
    cmp4 = compare([("r1", base), ("r2", art(0, rc=124, parsed=False))])
    if gate(cmp4) != 1 or cmp4["candidate_ok"]:
        problems.append("parsed-null candidate did not gate")
    # 5) metric baseline skips rounds that lack it (r05-shaped hole)
    hole = art(2_750_000)  # no phases at all, still has headline
    cmp5 = compare([("r1", base), ("r2", hole), ("r3", good)])
    row = next(r for r in cmp5["rows"]
               if r["metric"] == "phases.full_pipe.rows_per_sec")
    if row.get("baseline_round") != "r1":
        problems.append(f"baseline did not skip the hole: {row}")
    # 6) a HEADLINE metric vanishing (full_pipe child died, tumbling
    # headline survived) gates even though the artifact parsed fine
    gone = art(2_800_000)  # headline only, no phases
    cmp6 = compare([("r1", base), ("r2", gone)])
    if gate(cmp6) != 1 or not any(
            r["status"] == "dropped" for r in cmp6["headline_regressions"]):
        problems.append("vanished headline metric did not gate")
    if problems:
        print("benchdiff --smoke: FAILED: " + "; ".join(problems))
        return 1
    print("benchdiff --smoke: OK (6 scenarios)")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("artifacts", nargs="*",
                    help="driver bench artifacts (JSON), oldest first; "
                         "the last is the candidate")
    ap.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                    help="noise tolerance for non-headline metrics "
                         f"(fraction, default {DEFAULT_TOLERANCE})")
    ap.add_argument("--verbose", action="store_true",
                    help="also print unchanged/new/dropped metrics")
    ap.add_argument("--smoke", action="store_true",
                    help="run the self-test and exit")
    args = ap.parse_args(argv)
    if args.smoke:
        return smoke()
    if len(args.artifacts) < 2:
        ap.error("need at least two artifacts (or --smoke)")
    try:
        rounds = [(os.path.basename(p), _load(p)) for p in args.artifacts]
    except (OSError, ValueError) as exc:
        print(f"benchdiff: {exc}", file=sys.stderr)
        return 2
    cmp = compare(rounds, tolerance=args.tolerance)
    report(cmp, verbose=args.verbose)
    return gate(cmp)


if __name__ == "__main__":
    sys.exit(main())
