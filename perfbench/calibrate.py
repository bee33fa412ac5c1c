"""Read the control's and the planted faults' numbers for a cell.

    python3 perfbench/calibrate.py --workload <cell> --seeds <n> ... \
        [--program]

For each seed this makes the cell's inputs and weights, runs the plain
reference, and puts in the program's place (a) the control: the reference
one precision below what the configuration states (its module's
`control`), and (b) the reference with the second half of every batch left
out; with `--program`, also the program's own first steps through the
timed call, as a run's set-up drives them. Each prints the numbers `check`
compares against the float32 reference, one JSON line per seed. The limits in a
cell's traffic file lie between the program's readings and these. A step
that returns its state unchanged reads 1 on the update and gradient gaps by
construction, and needs no run. A cell whose traffic file is not in
`BENCHMARK.json` yet reads as a one-chip cell. The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH_DIR), "src"), BENCH_DIR]

from benchlib import cells  # noqa: E402


def readings(mod, cfg, traffic, limits, seeds, program=False):
    refs = {"reference": mod.Reference(cfg), "control": mod.control(cfg),
            "half_batch": mod.Reference(cfg, half_batch=True)}
    for seed in seeds:
        t0 = time.perf_counter()
        line = {"seed": seed}
        if program:
            import jax
            cell = mod.Cell(cfg, traffic, seed=seed,
                            devices=jax.devices()[:1], limits=limits,
                            log=lambda msg: None)
            cell.setup()
            cell.release()
            data, prog = cell.data, cell.prog
        else:
            data, prog = mod.Data(cfg, traffic, seed), None
        out = {k: r.run(data) for k, r in refs.items()}
        if prog is not None:
            out["program"] = prog
        for k in ("program", "control", "half_batch"):
            if k in out:
                line[k] = {c["name"]: c["value"] for c in
                           mod.compare_summaries(out[k], out["reference"],
                                                 limits)}
        line["seconds"] = time.perf_counter() - t0
        yield line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true",
                    help="also read the program itself: its first steps "
                         "through the timed call, on each seed")
    args = ap.parse_args(argv)
    bench = cells.benchmark()
    entry = cells.staged_entry(bench, args.workload)
    traffic = cells.workload(args.workload)
    cfg, mod = cells.config(entry["config"])
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        os.path.dirname(BENCH_DIR), ".jax_cache")
    import jax
    from repro import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for line in readings(mod, cfg, traffic["traffic"], traffic["limits"],
                         args.seeds, program=args.program):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
