"""From a profiler trace (`.xplane.pb`) to the numbers the readers use.

Reads the file with `jax.profiler.ProfileData` and nothing else. A device
plane is one whose name starts with `/device:TPU:`; on it, the line named
`XLA Ops` holds one event per operation that ran on the device and the line
`XLA Modules` one per program (jitted function). Busy time is the union of
the op intervals, averaged over the device planes; a program's time is the
sum of its events' durations.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"


def _union_ns(intervals) -> tuple:
    """(total covered ns, gaps as (start, end, index of the interval that
    ends the gap)) of intervals given as (start, end)."""
    covered = 0.0
    gaps = []
    end = None
    order = sorted(range(len(intervals)), key=lambda i: intervals[i][0])
    for i in order:
        s, e = intervals[i]
        if end is None or s > end:
            if end is not None:
                gaps.append((end, s, i))
            covered += e - s
            end = e
        elif e > end:
            covered += e - end
            end = e
    return covered, gaps


def _program_name(event_name: str) -> str:
    """`jit_fold_impl(123456789)` -> `jit_fold_impl`: the fingerprint in
    brackets changes with every compile."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _op_name(event_name: str) -> str:
    """The profiler names an op by its whole HLO line; keep what stands
    before the ` = ` (`%fusion.1`)."""
    return event_name.split(" = ", 1)[0]


def _within(programs, start: int) -> str:
    """The program (name, start, end sorted by start) running at `start`."""
    i = bisect.bisect_right(programs, (start, float("inf"), "")) - 1
    if i >= 0 and programs[i][0] <= start < programs[i][1]:
        return programs[i][2]
    return "?"


def reduce_planes(planes, window_s: float | None = None) -> dict:
    """`planes` is an iterable of (plane name, [(line name, [(event name,
    start ns, duration ns)])])."""
    busy = []
    ops: dict = {}
    programs: dict = {}
    gaps_by: dict = {}
    span = [None, None]
    for pname, lines in planes:
        if not pname.startswith(DEVICE_PLANE):
            continue
        running = sorted(
            (start, start + dur, _program_name(name))
            for lname, events in lines if lname == PROGRAMS_LINE
            for name, start, dur in events)
        for _s, _e, key in running:
            programs[key] = programs.get(key, 0.0) + (_e - _s) / 1e9
        for lname, events in lines:
            if lname != OPS_LINE:
                continue
            iv = [(s, s + d) for _n, s, d in events]
            # an op is named by the program it ran in and its own name
            names = [_within(running, s) + "/" + _op_name(n)
                     for n, s, _d in events]
            covered, gaps = _union_ns(iv)
            busy.append(covered / 1e9)
            for name, (_n, _s, dur) in zip(names, events):
                ops[name] = ops.get(name, 0.0) + dur / 1e9
            for g0, g1, i in gaps:
                key = "before " + names[i]
                gaps_by[key] = gaps_by.get(key, 0.0) + (g1 - g0) / 1e9
            if iv:
                lo, hi = min(s for s, _ in iv), max(e for _, e in iv)
                span[0] = lo if span[0] is None else min(span[0], lo)
                span[1] = hi if span[1] is None else max(span[1], hi)
    n = max(len(busy), 1)
    if window_s is None:  # no host clock given: first op to last op
        window_s = (span[1] - span[0]) / 1e9 if span[0] is not None else 0.0

    def top(d):
        return [[k, v / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"busy_s": sum(busy) / n, "window_s": window_s,
            "device_planes": len(busy),
            "ops": {k: v / n for k, v in ops.items()},
            "programs": {k: v / n for k, v in programs.items()},
            "top_ops": top(ops), "top_gaps": top(gaps_by)}


def read_planes(path: str):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        yield plane.name, [
            (line.name, [(ev.name, ev.start_ns, ev.duration_ns)
                         for ev in line.events])
            for line in plane.lines]


def reduce_file(path: str, window_s: float | None = None) -> dict:
    return reduce_planes(read_planes(path), window_s)


def reduce_dir(trace_dir: str, window_s: float | None = None):
    """The newest trace under a `jax.profiler.start_trace` directory."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return reduce_file(found[-1], window_s) if found else None
