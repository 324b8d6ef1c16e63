#!/usr/bin/env python3
"""The fused worker's cycle in one benchmark cell, from the program's own
counters: how the wall of the worker that folds divides between idle, each
stage it closed and no stage, per micro-batch, wall and thread-CPU, beside
the device's busy time (PERF.md section 5's table).

    python3 tools/cycle_ledger.py --workload <cell> --seed <n> [--seconds 40]

One traced run of the cell through `benchmark/run.py`'s own `run_cell` (on
the attached TPU, as the benchmark runs it); the ledger is read from the
`/metrics` texts that run takes at its window's open and close. Prints the
ledger as one JSON line, then the run's result line as `run.py` prints it;
with `--out DIR` the ledger is also written to DIR/<cell>.<seed>.json.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]

_SAMPLE = re.compile(r'^(kuiper_\w+)\{([^}]*)\} (\S+)$')
#: the worker's ledger, and the mirror of its idle on the senders' side
FAMILIES = {"idle": "kuiper_op_idle_us_total",
            "busy": "kuiper_op_busy_us_total",
            "busy_cpu": "kuiper_op_busy_cpu_us_total",
            "unstaged": "kuiper_op_unstaged_us_total",
            "unstaged_cpu": "kuiper_op_unstaged_cpu_us_total",
            "backpressure": "kuiper_op_backpressure_us_total"}
STAGE = {"wall": "kuiper_op_stage_us_total",
         "cpu": "kuiper_op_stage_cpu_us_total",
         "calls": "kuiper_op_stage_calls_total",
         "rows": "kuiper_op_stage_rows_total"}
#: stages of a window node that only its worker opens; `emit` is opened by
#: the `<node>-emit` thread too, so the worker's part of it is what is
#: left of staged
WORKER_ONLY = ("upload", "fold", "shadow_fold", "slide_ring", "slide_edge",
               "slide_advance", "boundary_reset", "release")


def samples(text: str) -> dict:
    """{(family, frozenset of label pairs): value} of a `/metrics` text."""
    out = {}
    for line in text.splitlines():
        m = _SAMPLE.match(line)
        if m:
            labels = frozenset(re.findall(r'(\w+)="([^"]*)"', m.group(2)))
            out[m.group(1), labels] = float(m.group(3))
    return out


def ledger(marks0: dict, marks1: dict) -> dict:
    """The division of the worker's wall between the two marks, for the op
    that reports `fold` (every figure in microseconds of the window)."""
    s0, s1 = samples(marks0["metrics"]), samples(marks1["metrics"])
    op = next(dict(labels)["op"] for (fam, labels) in s1
              if fam == STAGE["wall"] and ("stage", "fold") in labels)

    def grew(family: str, **want) -> float:
        want = set(want.items()) | {("op", op)}
        return sum(v - s0.get(key, 0.0) for key, v in s1.items()
                   if key[0] == family and want <= key[1])

    wall = (marks1["t"] - marks0["t"]) * 1e6
    out = {"op": op, "wall_us": wall}
    out.update({k: grew(fam) for k, fam in FAMILIES.items()})
    stages = sorted({dict(labels)["stage"] for (fam, labels) in s1
                     if fam == STAGE["wall"] and ("op", op) in labels})
    out["stages"] = {
        st: {k: grew(fam, stage=st) for k, fam in STAGE.items()}
        for st in stages}
    staged = out["busy"] - out["unstaged"]
    own = sum(out["stages"].get(st, {}).get("wall", 0.0)
              for st in WORKER_ONLY)
    out["staged"] = staged
    out["emit_on_worker"] = staged - own
    out["staged_cpu"] = out["busy_cpu"] - out["unstaged_cpu"]
    out["emit_on_worker_cpu"] = out["staged_cpu"] - sum(
        out["stages"].get(st, {}).get("cpu", 0.0) for st in WORKER_ONLY)
    # the identity, idle + staged + unstaged = wall: what is left over
    # (between two scrapes; `identity_gap` reads it without their latency)
    out["identity_gap_share"] = (
        (out["idle"] + staged + out["unstaged"]) / wall - 1.0)
    out["micro_batches"] = out["stages"]["fold"]["calls"]
    out["fold_rows_per_s"] = out["stages"]["fold"]["rows"] / (wall / 1e6)
    for key, fam in (("transfers", "kuiper_fold_transfers_total"),
                     ("resident", "kuiper_fold_resident_args_total")):
        if any(f == fam for f, _ in s1):
            out[key] = grew(fam)
    return out


def direct_marks(rest_marks):
    """`run.rest_marks`, with the worker's ledger also read straight off the
    fused node beside a clock reading: a `/metrics` scrape takes tens to
    hundreds of milliseconds under load, a different time at the window's
    open and at its close, which the identity should not be charged."""
    import time

    def marks(engine, rule):
        out = rest_marks(engine, rule)
        st = rule.fused.stats
        out["direct"] = {"t_us": time.perf_counter_ns() // 1000,
                         "idle": st.idle_us_total,
                         "busy": st.process_time_us_total}
        return out
    return marks


def identity_gap(marks0: dict, marks1: dict) -> float:
    """(idle + busy) / wall - 1 between two direct readings."""
    d0, d1 = marks0["direct"], marks1["direct"]
    return ((d1["idle"] - d0["idle"] + d1["busy"] - d0["busy"])
            / (d1["t_us"] - d0["t_us"]) - 1.0)


def table(led: dict, busy_s=None, window_s=None) -> str:
    """The ledger per micro-batch, in milliseconds."""
    n = max(led["micro_batches"], 1.0)

    def ms(us: float) -> str:
        return f"{us / n / 1000.0:8.3f}"

    rows = [f"op {led['op']}: {int(n)} micro-batches in "
            f"{led['wall_us'] / 1e6:.2f} s, {led['fold_rows_per_s']:.0f} rows/s"
            + (f" ({led['traced_fold_rows_per_s']:.0f} under the profiler)"
               if "traced_fold_rows_per_s" in led else "")
            + "; ms a micro-batch, wall / cpu",
            f"  cycle (wall / n)      {ms(led['wall_us'])}",
            f"  idle                  {ms(led['idle'])}"]
    for st in WORKER_ONLY:
        if st in led["stages"]:
            s = led["stages"][st]
            rows.append(f"  {st:<22}{ms(s['wall'])} {ms(s['cpu'])}"
                        f"   ({s['calls'] / n:.2f} calls)")
            if st == "fold" and "fold_h2d" in led["stages"]:
                h = led["stages"]["fold_h2d"]
                rows.append(f"    of which fold_h2d   {ms(h['wall'])} "
                            f"{ms(h['cpu'])}")
    rows += [f"  emit on the worker    {ms(led['emit_on_worker'])} "
             f"{ms(led['emit_on_worker_cpu'])}",
             f"  unstaged              {ms(led['unstaged'])} "
             f"{ms(led['unstaged_cpu'])}",
             f"  busy                  {ms(led['busy'])} "
             f"{ms(led['busy_cpu'])}",
             f"  identity: idle + staged + unstaged = wall "
             f"{100 * led['identity_gap_share']:+.3f} % between the scrapes"
             + (f", {100 * led['identity_gap_direct']:+.3f} % read directly"
                if "identity_gap_direct" in led else ""),
             f"  senders blocked on its queue {ms(led['backpressure'])}"]
    if "transfers" in led:
        calls = max(led["stages"].get("fold_h2d", {}).get("calls", 0.0), 1.0)
        rows.append(f"  runtime calls a staging "
                    f"{led['transfers'] / calls:.2f}")
        if "resident" in led:  # the parent's program has no such family
            served = led["resident"] + led["transfers"]
            rows.append(
                f"  resident arguments a staging "
                f"{led['resident'] / calls:.2f} (hit share "
                f"{100 * led['resident'] / max(served, 1.0):.1f} %)")
    if busy_s is not None:
        rows.append(f"  device busy {busy_s:.3f} s of {window_s:.2f} s "
                    f"traced")
    return "\n".join(rows)


def main(argv=None) -> int:
    import run as bench_run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = bench_run.load_cell(args.workload, True)
    try:
        device = bench_run.require_chip(cell.chips)
    except bench_run.NoChip as exc:
        print(f"no chip: {exc}", file=sys.stderr, flush=True)
        return 2
    keep: dict = {}
    bench_run.rest_marks = direct_marks(bench_run.rest_marks)
    result = bench_run.run_cell(
        cell, args.seed, args.seconds, True, device,
        os.path.join(ROOT, ".bench_run", cell.name), keep=keep)
    ctx = keep["ctx"]
    led = ledger(ctx.marks0, ctx.marks1)
    led.update(cell=cell.name, seed=args.seed,
               identity_gap_direct=identity_gap(ctx.marks0, ctx.marks1),
               identity_gap_direct_traced=identity_gap(
                   ctx.trace_marks0, ctx.trace_marks1),
               device_busy_s=result["device"].get("busy_s"),
               device_window_s=result["device"].get("window_s"),
               **{"traced_" + k: v for k, v in ledger(
                   ctx.trace_marks0, ctx.trace_marks1).items()
                  if k in ("micro_batches", "fold_rows_per_s")})
    print(table(led, led["device_busy_s"], led["device_window_s"]),
          file=sys.stderr, flush=True)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(
                args.out, f"{cell.name}.{args.seed}.json"), "w") as fh:
            json.dump({"ledger": led, "result": result}, fh)
    print(json.dumps({"ledger": led}), flush=True)
    bench_run.report(result)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # as benchmark/run.py: engine threads outlive main()
