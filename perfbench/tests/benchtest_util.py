"""Helpers shared by the benchmark's tests: import the harness from the
benchmark directory, and run a cell at a size a CPU test can hold with the
harness's look for a chip skipped."""
from __future__ import annotations

import importlib.util
import io
import json
import os
import sys
from contextlib import redirect_stderr, redirect_stdout

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (os.path.join(ROOT, "src"), BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import cells, peaks  # noqa: E402

# Tiny shapes of each configuration and traffic mix: the same code paths,
# at a size a CPU test can hold.
SMALL_TRAFFIC = {
    "fleet-lenet5-n256": dict(clients=4, samples_per_client=64,
                              test_images=32),
    "lm-xlstm125m-4x2048": dict(batch=2, seq_len=64, distinct_batches=4),
}
SMALL_CONFIG = {
    "lenet5-fleet": {},
    "xlstm-125m": dict(num_layers=2, d_model=64, d_feature=64,
                       vocab_size=512, block_pattern=["mlstm", "slstm"],
                       ssm_chunk=16, disc_tokens=96, num_negatives=63),
}


def load_run_module():
    spec = importlib.util.spec_from_file_location(
        "perfbench_run", os.path.join(BENCH_DIR, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_config, _workload = cells.config, cells.workload


def small_config(name):
    cfg, mod = _config(name)
    cfg.update(SMALL_CONFIG[name])
    return cfg, mod


def small_workload(name):
    w = _workload(name)
    w["traffic"].update(SMALL_TRAFFIC[name])
    return w


def run_small(monkeypatch, cell: str, seed: int, trace: int = 0):
    """Drive a whole run of `cell` at tiny size on this host's device, the
    look for a chip skipped. -> (exit code, last stdout line as a dict or
    None, stderr)."""
    import jax
    run = load_run_module()
    bench = cells.benchmark()
    if cell not in [w["name"] for w in bench["workloads"]]:
        # a traffic file not (yet) in BENCHMARK.json runs as a one-chip cell
        bench["workloads"].append({"name": cell, "traffic": cell, "chips": 1,
                                   "config": _workload(cell)["config"],
                                   "why": "test"})
    monkeypatch.setattr(cells, "benchmark", lambda root=cells.ROOT: bench)
    monkeypatch.setattr(run, "check_platform", lambda devices, chips: None)
    monkeypatch.setattr(cells, "workload", small_workload)
    monkeypatch.setattr(cells, "config", small_config)
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        dict(peaks.PEAKS["TPU v5 lite"]))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.environ.get("JAX_COMPILATION_CACHE_DIR", ""))
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = run.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", "0.5", "--trace", str(trace)])
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
