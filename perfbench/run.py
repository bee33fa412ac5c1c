"""Run one cell of the chip benchmark once.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout. The run enables the persistent compile cache
at its fixed path, refuses any platform but a TPU with the chips the cell
asks for, builds the cell from the seed, warms up its shapes (all of that is
set-up), then measures for `--seconds` seconds. `--trace 0` reports the
cell's end-to-end metrics; `--trace 1` profiles a short steady window and
reports its per-layer metrics. Once the window has closed, the program's
state is freed and the plain reference checks what the timed path produced.
The last line of standard output is one JSON object (`correct`,
`attempted`, `failed`, `metrics`, `device`, with a trace `breakdown`, and
last `checks`: each number compared with its limit); the same checks are
the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH_DIR]
# The persistent compile cache lives at one fixed path inside the checkout,
# whatever the environment says, so that two checkouts share nothing; the
# program's `compile_cache.enable()` takes the directory from this variable.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

from benchlib import cells, compare, peaks, trace as trace_lib  # noqa: E402

# Length of the profiled window of a traced run: short, so the trace stays
# small and its reduction quick, and at least MIN_TRACE_STEPS steps.
TRACE_SECONDS = 2.0
MIN_TRACE_STEPS = 3


class Run:
    """What a metric reader sees: the window's step times and work, the
    set-up time, the chips and their peaks, the cell, and with a trace the
    reduced device trace."""

    def __init__(self, cell, chips, device_kind):
        self.cell = cell
        self.chips = chips
        self.peaks = peaks.peaks(device_kind)
        self.step_s = []
        self.work = 0
        self.window_s = 0.0
        self.setup_s = 0.0
        self.trace = None


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:8.2f}s] {msg}", file=sys.stderr,
          flush=True)


def run_window(cell, run: Run, seconds: float, min_steps: int = 1,
               traced: bool = False) -> int:
    """Drive the cell's timed step until `seconds` have passed and at least
    `min_steps` steps ran, each in a step span when traced; -> steps whose
    outputs were not finite."""
    failed = 0
    t0 = time.perf_counter()
    while run.window_s < seconds or len(run.step_s) < min_steps:
        a = time.perf_counter()
        if traced:
            with trace_lib.step_span(len(run.step_s)):
                work, ok = cell.step()
        else:
            work, ok = cell.step()
        run.step_s.append(time.perf_counter() - a)
        run.work += work
        failed += 0 if ok else 1
        run.window_s = time.perf_counter() - t0
    return failed


def peak_memory(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def check_platform(devices, chips: int):
    """Why this run cannot measure, or None: only TPU chips, as many as the
    cell asks for, are measured; there is no fallback to the CPU."""
    if devices[0].platform != "tpu" or len(devices) < chips:
        return (f"this benchmark runs on TPU chips only; found "
                f"{len(devices)} {devices[0].platform!r} device(s), the cell "
                f"needs {chips} TPU chip(s)")
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = cells.benchmark()
    entry = cells.cell_entry(bench, args.workload)
    traffic = cells.workload(args.workload)
    cfg, cfg_mod = cells.config(entry["config"])
    wanted = cells.metrics_of(bench, args.workload, traced=bool(args.trace))
    readers = {m["name"]: cells.metric(m["name"]) for m in wanted}

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    from repro import compile_cache
    log(f"compile cache: {compile_cache.enable()}")
    # cache every program, however quick its compile, so that set-up after
    # the first run in a checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    chips = int(entry["chips"])
    refusal = check_platform(devices, chips)
    if refusal:
        print(refusal, file=sys.stderr)
        return 2
    devices = devices[:chips]
    kind = devices[0].device_kind
    cell = cfg_mod.Cell(cfg, traffic["traffic"], seed=args.seed,
                        devices=devices, limits=traffic["limits"], log=log)
    run = Run(cell, chips, kind)
    cell.setup()
    run.setup_s = time.perf_counter() - T_START
    log(f"set-up {run.setup_s:.2f}s")

    # XLA compilations after set-up; the window should see none
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)

    breakdown = None
    busy = None
    if args.trace:
        with tempfile.TemporaryDirectory(prefix="bench_trace_") as d:
            with jax.profiler.trace(d):
                failed = run_window(cell, run,
                                    min(args.seconds, TRACE_SECONDS),
                                    MIN_TRACE_STEPS, traced=True)
            log(f"traced {len(run.step_s)} steps in {run.window_s:.3f}s")
            run.trace, host_spans = trace_lib.load(d)
        busy = run.trace.mean_busy_s()
        breakdown = {"device_ops": run.trace.top_ops(10),
                     "idle_gaps": run.trace.idle_gaps(host_spans, 10)}
        log(f"trace reduced: window {run.trace.window_s:.4f}s, busy "
            f"{busy:.4f}s, {sum(len(v) for v in run.trace.ops.values())} ops")
    else:
        failed = run_window(cell, run, args.seconds)
        log(f"window: {len(run.step_s)} steps in {run.window_s:.3f}s")

    log(f"compilations after set-up: {len(compiles)}")
    memory = peak_memory(devices)
    cell.release()
    t_check = time.perf_counter()
    numbers = cell.check()
    log(f"check took {time.perf_counter() - t_check:.2f}s")
    # a number whose limit is null is read but not compared: no limit can
    # hold it (PERF.md says why, with its readings)
    checks = [c for c in numbers if c["limit"] is not None]
    correct = compare.all_within(checks) and failed == 0

    metrics = {}
    for m in wanted:
        value = readers[m["name"]].read(run)
        if value is None:
            continue
        if not math.isfinite(value):
            correct = False
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory}
    if args.trace:
        device.update(busy_s=busy, window_s=run.trace.window_s)
    result = {"correct": bool(correct), "attempted": len(run.step_s),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    for c in numbers:
        if c["limit"] is None:
            print(f"reading {c['name']} {c['value']!r} (not compared)",
                  file=sys.stderr)
    for c in checks:
        print(f"check {c['name']} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
