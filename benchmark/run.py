#!/usr/bin/env python3
"""One run of one benchmark cell on the attached TPU.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of `workloads` in BENCHMARK.json) names a configuration and a
traffic mix; everything that belongs to either, or to one metric, is a file
found by that name under this directory (README.md here says which). The run
starts the engine through `server.main.start_up`, creates the stream and the
rule over REST, publishes pre-encoded JSON byte payloads to the memory source,
reads the memory sink, warms up (set-up), measures for `--seconds`, then holds
every answer to the plain reference. Its last line on standard output is one
JSON object: `correct`, `attempted`, `failed`, `metrics`, `device`, with
`--trace 1` `breakdown`, and last `checks` — each number compared beside its
limit. There is no CPU mode: without a TPU it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from peaks import peaks_for  # noqa: E402

# the traced stretch, at the end of the measured window: longer than one
# count window of the slowest cell takes, so it always holds device work
TRACE_SECONDS = 8.0
DRAIN_WAIT_S = 60.0  # how long past the close a due answer is waited for


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


def load_cell(name: str, trace: bool) -> SimpleNamespace:
    """The cell, its configuration and mix, and the metrics this run owes:
    with `--trace 0` the cell's end-to-end metrics, with `--trace 1` its
    per-layer ones. A metric without a `workloads` key belongs to every
    cell (per-layer: every cell that reports what it moves)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    e2e = [m["name"] for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    layer = [m["name"] for m in bench["per_layer"]
             if name in m.get("workloads", [name]) and m["moves"] in e2e]
    return SimpleNamespace(
        name=name, chips=int(cell["chips"]),
        cfg=load_json("configs", cell["config"] + ".json"),
        mix=load_json("traffic", cell["traffic"] + ".json"),
        metrics=[("layers", n) for n in layer] if trace
        else [("end_to_end", n) for n in e2e])


def require_chip(chips: int) -> dict:
    """A TPU with enough chips, known to the peaks table, or nothing; the
    compile cache placed before any compile; the native decoder built."""
    import jax

    from ekuiper_tpu.io import fastjson
    from ekuiper_tpu.utils import jaxcache

    jaxcache.setup()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"jax found platform {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} chips visible, {chips} needed")
    if not fastjson.ensure_native(background=False):
        raise NoChip("the native JSON decoder did not build")
    peaks_for(devs[0].device_kind)  # an unknown kind is an error
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


class Sink:
    """The memory sink's subscriber: stamps every window's arrival, its
    group count and how many sent rows it answers (`rows_of`, the
    reference's), and keeps the payload of all windows or of a seeded
    reservoir of `keep` of them (a million-group payload cannot all be
    held)."""

    def __init__(self, keep, seed: int, rows_of) -> None:
        import numpy as np

        self.keep = keep  # "all" or a count
        self.rng = np.random.default_rng(seed + 1)
        self.rows_of = rows_of
        self.answered_rows = 0
        self.windows: list = []
        self._kept: list = []

    def __call__(self, _topic, payload) -> None:
        now = time.time()
        msgs = payload if isinstance(payload, list) else [payload]
        w = SimpleNamespace(index=len(self.windows), t=now,
                            n_groups=len(msgs), rows=self.rows_of(msgs),
                            payload=None)
        self.answered_rows += w.rows
        if self.keep == "all" or len(self._kept) < self.keep:
            w.payload = msgs
            self._kept.append(w)
        else:  # reservoir: window k replaces a kept one with chance keep/k
            j = int(self.rng.integers(0, w.index + 1))
            if j < self.keep:
                self._kept[j].payload = None
                self._kept[j] = w
                w.payload = msgs
        self.windows.append(w)


class Feed:
    """The generator's side of the run: every publish since the rule began,
    in order — which drain, when it was due, when it went out."""

    def __init__(self, rule, topic: str, pool, mix: dict, sink) -> None:
        from ekuiper_tpu.io import memory

        self.publish_to = lambda drain: memory.publish(topic, drain)
        self.rule, self.pool, self.mix, self.sink = rule, pool, mix, sink
        self.sent: list = []
        self.due: list = []
        self.out: list = []

    def _publish(self, due: float) -> None:
        i = len(self.sent) % len(self.pool.drains)
        self.due.append(due)
        self.out.append(time.time())
        self.publish_to(self.pool.drains[i])
        self.sent.append(i)

    def closed(self, until) -> None:
        """Closed loop: a bounded amount of work outstanding. The next drain
        goes out once the fused node's input queue is shallow again and no
        more than `max_unanswered_rows` sent rows are still without their
        window at the sink — so what was accepted in a stretch was also
        answered, up to that bound. A drain is due when it is sent."""
        depth = int(self.mix["queue_depth"])
        ahead = int(self.mix["max_unanswered_rows"])
        while not until():
            self._publish(time.time())
            self.rule.wait_shallow(depth)
            stuck = time.time() + 120
            while (len(self.sent) * self.pool.drain_rows
                   - self.sink.answered_rows > ahead) and not until():
                time.sleep(0.002)
                if time.time() > stuck:
                    raise RuntimeError(
                        f"over {ahead} sent rows unanswered for 120 s")

    def open(self, seconds: float) -> None:
        """Open loop: drain i is due at start + i·interval and goes out then
        or as soon after as the generator can, whether or not the engine
        keeps up."""
        interval = self.pool.drain_rows / float(self.mix["rate_rows_per_s"])
        start = time.time()
        for i in range(int(seconds / interval)):
            due = start + i * interval
            wait = due - time.time()
            if wait > 0:
                time.sleep(wait)
            self._publish(due)
        wait = start + seconds - time.time()
        if wait > 0:
            time.sleep(wait)

    def window(self, seconds: float) -> None:
        if self.mix["loop"] == "closed":
            end = time.time() + seconds
            self.closed(lambda: time.time() >= end)
        else:
            self.open(seconds)


def rest_marks(engine, rule) -> dict:
    return {"t": time.time(),
            "metrics": engine.rest("GET", "/metrics", raw=True),
            "status": rule.status()}


def run_cell(cell, seed: int, seconds: float, trace: bool, device: dict,
             out_dir: str, t_start: float = T_START, keep=None) -> dict:
    """Everything after the look for a chip: set-up, the measured window,
    the comparison, the metrics. Returns the result object; `keep`, a dict,
    receives what the comparison saw (for control.py and the self-tests)."""
    import jax

    from ekuiper_tpu.io import memory

    import engine as eng

    cfg, mix = cell.cfg, cell.mix
    generator = importlib.import_module("generators." + cfg["generator"])
    ref = importlib.import_module("references." + cfg["reference"])
    rows = {**cfg["rows"], **mix.get("rows", {})}
    ref_params = {**rows, **cfg["reference_params"]}
    split = {"to_run_cell": time.time() - t_start}  # imports, device, native
    mark = time.time()

    def lap(name: str) -> None:
        nonlocal mark
        split[name] = time.time() - mark
        mark = time.time()

    pool = generator.make(seed, rows)
    lap("row_pool")
    engine = eng.Engine(out_dir)
    engine.create_stream("bench_in", cfg["stream_fields"], "bench/in")
    sink = Sink(cfg["sink_keep"], seed,
                lambda msgs: ref.window_rows(msgs, ref_params))
    memory.subscribe("bench/out", sink)
    rule = engine.create_rule(
        "bench_rule", cfg["sql"].format(stream="bench_in"), "bench/out",
        cfg["options"])
    lap("start_up_and_rule")
    feed = Feed(rule, "bench/in", pool, mix, sink)

    # warm-up, closed loop whatever the mix: until the configured number of
    # windows has come out, every program of the steady path has run
    warm_deadline = time.time() + 900
    feed.closed(lambda: len(sink.windows) >= int(cfg["warmup_windows"])
                or time.time() > warm_deadline)
    if len(sink.windows) < int(cfg["warmup_windows"]):
        raise eng.EngineFailure("warm-up windows did not come within 900 s")
    if mix["loop"] != "closed":
        rule.topo.wait_idle(60.0)  # an open loop starts on an empty queue
    warm = eng.compile_marks()
    lap("warm_up")
    split["compile_s"] = warm["compile_s"]
    ctx = SimpleNamespace(cell=cell, cfg=cfg, mix=mix, rows=rows, pool=pool,
                          device=device, trace=None, marks0=None, marks1=None,
                          trace_marks0=None, trace_marks1=None)
    tracer = None
    if trace:
        ctx.marks0 = rest_marks(engine, rule)
        trace_dir = os.path.join(out_dir, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        delay = max(0.0, seconds - TRACE_SECONDS)

        def start_trace() -> None:
            time.sleep(delay)
            ctx.trace_marks0 = rest_marks(engine, rule)
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            ctx.trace_t0 = time.time()

        tracer = threading.Thread(target=start_trace, daemon=True)

    # ---- the measured window
    first = len(feed.sent)
    ctx.t_open = time.time()
    ctx.setup_s = ctx.t_open - t_start
    if tracer:
        tracer.start()
    feed.window(seconds)
    ctx.t_close = time.time()
    ctx.first_publish, ctx.end_publish = first, len(feed.sent)
    ctx.rows_in_window = (len(feed.sent) - first) * pool.drain_rows
    if tracer:
        tracer.join()
        ctx.trace_marks1 = rest_marks(engine, rule)
        ctx.trace_t1 = time.time()
        jax.profiler.stop_trace()
        ctx.marks1 = ctx.trace_marks1
    hot = eng.compile_marks()
    stats = jax.devices()[0].memory_stats() or {}
    ctx.peak_bytes = int(stats.get("peak_bytes_in_use", 0))

    # ---- every answer that is due, waited for up to a minute past the close
    due_rows = ref.rows_due(len(feed.sent) * pool.drain_rows, ref_params)
    deadline = time.time() + DRAIN_WAIT_S
    while sink.answered_rows < due_rows and time.time() < deadline:
        time.sleep(0.1)
    windows = list(sink.windows)
    ctx.windows = windows
    ctx.due, ctx.out = feed.due, feed.out

    facts = eng.fallback_facts(engine, rule)
    facts["compiles_in_window"] = hot["compiles"] - warm["compiles"]
    t_ref = time.time()
    verdict = ref.check(pool, feed.sent, windows, ref_params)
    reference_s = time.time() - t_ref  # after the window; not in setup_s
    if keep is not None:
        keep.update(ref=ref, ref_params=ref_params, pool=pool,
                    sent=feed.sent, windows=windows, rule=rule, ctx=ctx)
    checks = {k: [v, 0] for k, v in facts.items()}
    checks.update({k: [v, lim] for k, (v, lim) in
                   verdict["numbers"].items()})
    correct = all(v <= lim for v, lim in checks.values())

    if trace:
        import trace_reduce

        ctx.trace = trace_reduce.reduce_dir(
            trace_dir, ctx.trace_t1 - ctx.trace_t0)
        shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    for folder, name in cell.metrics:
        spec = load_json(folder, name + ".json")
        reader = importlib.import_module("readers." + spec["reader"])
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:  # nothing to read: the metric is left out
            metrics[name] = {"value": value, "unit": spec["unit"]}
    dev = dict(device, memory_peak_bytes=ctx.peak_bytes)
    result = {"correct": bool(correct), "attempted": verdict["attempted"],
              "failed": int(verdict["failed"] + facts["dropped_items"]),
              "metrics": metrics, "device": dev}
    if ctx.trace is not None:
        dev["busy_s"] = ctx.trace["busy_s"]
        dev["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = {"device_ops": ctx.trace["top_ops"],
                               "idle_gaps": ctx.trace["top_gaps"]}
    result["seed"] = seed
    result["setup_split"] = split
    result["reference_s"] = reference_s
    result["windows"] = len(windows)
    result["checks"] = checks
    return result


def report(result: dict) -> None:
    """The numbers compared, each beside its limit, as the last lines on
    standard error; the result as the last line on standard output."""
    sys.stdout.flush()
    for name, (value, limit) in result["checks"].items():
        print(f"check {name}: {value} (limit {limit})"
              f"{'' if value <= limit else '  <-- NOT WITHIN'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, bool(args.trace))
    try:
        device = require_chip(cell.chips)
    except NoChip as exc:
        print(f"no chip: {exc}", file=sys.stderr, flush=True)
        return 2
    # the server's store and the trace, inside the checkout, one per cell
    out_dir = os.path.join(ROOT, ".bench_run", cell.name)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          device, out_dir)
    except Exception:  # a run that broke prints no result
        traceback.print_exc()
        sys.stderr.flush()
        return 1
    report(result)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # engine worker threads and jax state outlive main(); leave without
    # running interpreter teardown over them (as chip_smoke.py does)
    os._exit(code)
