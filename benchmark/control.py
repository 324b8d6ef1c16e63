#!/usr/bin/env python3
"""The control of a cell, on the chip, beside the program's own reading.

    python benchmark/control.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as `run.py` does (a short window at the cell's own load), then
puts each of the reference's controls in the program's place — the reference
with one stated guarantee broken, or computed one precision below the one the
configuration states — and holds it to the same comparison. Prints one JSON
line: the program's numbers, each control's numbers, and whether each control
came out not correct (it has to). The limits in the configurations were set
between these two sets of readings (PERF.md section 2); `run.py` never runs
this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload, False)
    try:
        device = run.require_chip(cell.chips)
    except run.NoChip as exc:
        print(f"no chip: {exc}", file=sys.stderr, flush=True)
        return 2
    seen: dict = {}
    result = run.run_cell(
        cell, args.seed, args.seconds, False, device,
        os.path.join(run.ROOT, ".bench_run", cell.name + ".control"),
        keep=seen)
    line = {"workload": cell.name, "seed": args.seed,
            "program": {"correct": result["correct"],
                        "checks": result["checks"]},
            "controls": {}}
    for name, control in seen["ref"].CONTROLS.items():
        verdict = control(seen["pool"], seen["sent"], seen["windows"],
                          seen["ref_params"])
        numbers = {k: [v, lim] for k, (v, lim) in verdict["numbers"].items()}
        line["controls"][name] = {
            "not_correct": any(v > lim for v, lim in numbers.values()),
            "numbers": numbers}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
