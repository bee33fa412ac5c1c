"""Benchmark entry point: one module per paper table/figure.

  python -m benchmarks.run [--quick|--full]

CSV lines go to stdout: ``name,us_per_call,derived`` for micro-benches;
per-table CSVs for the paper reproductions. REPRO_BENCH_ROUNDS controls the
round budget of the utility tables (default 12 here; EXPERIMENTS.md numbers
use the dedicated longer runs recorded there).
"""
from __future__ import annotations

import os
import sys
import time

from repro import compile_cache


def main() -> None:
    quick = "--full" not in sys.argv
    if quick and "REPRO_BENCH_ROUNDS" not in os.environ:
        os.environ["REPRO_BENCH_ROUNDS"] = "12"
    from benchmarks import (comm_cost, fig3_ablation, fig4_convergence,
                            kernel_bench, roofline_table, scaling_clients,
                            table1_utility)
    t0 = time.time()
    print("== comm_cost (paper §Communication) ==")
    comm_cost.main()
    print("\n== kernel micro-benchmarks ==")
    kernel_bench.main()
    print("\n== roofline table (deliverable g, from dry-run artifacts) ==")
    roofline_table.main()
    print("\n== table1_utility (paper Table 1) ==")
    table1_utility.main(n_values=(2, 5) if quick else (2, 5, 10))
    print("\n== fig4_convergence (paper Fig. 4) ==")
    fig4_convergence.main(n_clients=5)
    print("\n== scaling_clients (vectorized engine vs sequential oracle) ==")
    scaling_clients.main(clients=(2, 8, 32) if quick else (2, 8, 32, 128))
    print("\n== participation sweep (partial client rounds, k/N savings) ==")
    scaling_clients.participation_sweep(n_clients=16 if quick else 32)
    if not quick:
        print("\n== fig3_ablation (paper Fig. 3) ==")
        fig3_ablation.main(n_clients=5)
    print(f"\n== benchmarks done in {time.time()-t0:.0f}s ==")


if __name__ == '__main__':
    compile_cache.enable()
    main()
