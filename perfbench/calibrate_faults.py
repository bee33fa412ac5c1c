"""Read the planted faults a configuration declares against its plain
reference.

    python3 perfbench/calibrate_faults.py --workload <cell> --seeds <n> ... \
        [--only <name> ...]

For each seed this makes the cell's inputs and weights, runs the plain
reference, and puts in the program's place the reference with each fault
of the configuration module's `FAULTS` (name -> the reference's keyword
arguments, such as a part of the model left out), and, where the module
has `AT_DTYPE` (the reference's keyword arguments for the program's own
precision), that reference as `reference_at_dtype`: what rounding alone
reads. `--only` reads the named ones alone. It prints the numbers `check`
compares, one JSON line per seed and variant as soon as it is read, as
`calibrate.py` prints the control's and the half batch's. A cell's limits
should fail each fault: the readings say which limits do. The benchmark's
own runs never run this.
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


def variants(mod, cfg, only=None):
    kwargs = dict(mod.FAULTS)
    if hasattr(mod, "AT_DTYPE"):
        kwargs["reference_at_dtype"] = mod.AT_DTYPE
    if only:
        kwargs = {k: kwargs[k] for k in only}
    return {k: mod.Reference(cfg, **kw) for k, kw in kwargs.items()}


def readings(mod, cfg, traffic, limits, seeds, only=None):
    ref = mod.Reference(cfg)
    others = variants(mod, cfg, only)
    for seed in seeds:
        data = mod.Data(cfg, traffic, seed)
        want = ref.run(data)
        for name, other in others.items():
            t0 = time.perf_counter()
            line = {"seed": seed, "name": name}
            line.update({c["name"]: c["value"] for c in
                         mod.compare_summaries(other.run(data), want, limits)})
            line["seconds"] = time.perf_counter() - t0
            yield line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--only", nargs="+",
                    help="read these faults (or reference_at_dtype) alone")
    args = ap.parse_args(argv)
    entry = cells.staged_entry(cells.benchmark(), args.workload)
    traffic = cells.workload(args.workload)
    cfg, mod = cells.config(entry["config"])
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        os.path.dirname(BENCH_DIR), ".jax_cache")
    import jax
    from repro import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for line in readings(mod, cfg, traffic["traffic"], traffic["limits"],
                         args.seeds, args.only):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
