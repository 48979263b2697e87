"""Benchmark entry point — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run           # quick mode
    PYTHONPATH=src python -m benchmarks.run --full    # paper-scale panels

Prints ``name,us_per_call,derived`` CSV lines per benchmark, plus summary
sections.  Figure/table data land in experiments/ as CSVs.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax

jax.config.update("jax_enable_x64", True)


def main() -> None:
    from repro.utils.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale panels (slow)")
    args = ap.parse_args()
    quick = not args.full
    enable_compile_cache(__file__)

    print("name,us_per_call,derived")

    # ---- kernel microbenchmarks -------------------------------------------
    from benchmarks import kernels_bench

    for name, us, derived in kernels_bench.run(quick=quick):
        print(f"kernel/{name},{us:.1f},{derived}")
    sys.stdout.flush()

    # ---- Figure 1 (the paper's main empirical claim) ----------------------
    from benchmarks import fig1

    t0 = time.perf_counter()
    results = fig1.run(quick=quick)
    dt = (time.perf_counter() - t0) * 1e6
    for panel, summary in results.items():
        best_baseline = min(
            (v for k, v in summary.items() if k != "svrp" and v == v), default=float("nan")
        )
        print(
            f"fig1/{panel},{dt / max(len(results), 1):.0f},"
            f"svrp={summary['svrp']:.2e};best_baseline={best_baseline:.2e}"
        )
    sys.stdout.flush()

    # ---- Figure 2 (Section 9: logistic regression through the engine) ------
    from benchmarks import fig2

    t0 = time.perf_counter()
    results = fig2.run(quick=quick)
    dt = (time.perf_counter() - t0) * 1e6
    for panel, summary in results.items():
        best_baseline = min(
            (v for k, v in summary.items() if k != "svrp" and v == v), default=float("nan")
        )
        print(
            f"fig2/{panel},{dt / max(len(results), 1):.0f},"
            f"svrp={summary['svrp']:.2e};best_baseline={best_baseline:.2e}"
        )
    sys.stdout.flush()

    # ---- Table 1 (comm-to-eps grid) ---------------------------------------
    from benchmarks import table1_comm

    t0 = time.perf_counter()
    rows = table1_comm.run(quick=quick)
    dt = (time.perf_counter() - t0) * 1e6
    for M, delta, method, nbytes in rows:
        print(f"table1/M{M}_d{delta:g}/{method},{dt / max(len(rows), 1):.0f},bytes_to_eps={nbytes:.3g}")
    sys.stdout.flush()

    # ---- beyond-paper: federated deep-LM comparison ------------------------
    from benchmarks import deep_fed

    for name, us, derived in deep_fed.run(quick=quick):
        print(f"deep_fed/{name},{us:.0f},{derived}")
    sys.stdout.flush()

    # ---- batched sweep engine vs per-trial python loop ---------------------
    from benchmarks import sweep_bench

    for name, us, derived in sweep_bench.run(quick=quick):
        print(f"sweep/{name},{us:.0f},{derived}")
    sys.stdout.flush()

    # ---- beyond-paper: client-minibatch scaling ----------------------------
    from benchmarks import minibatch_sweep

    t0 = time.perf_counter()
    mb_rows = minibatch_sweep.run(quick=quick)
    dt = (time.perf_counter() - t0) * 1e6
    for b, rounds, comm in mb_rows:
        print(f"minibatch/b{b},{dt / max(len(mb_rows), 1):.0f},rounds={rounds};comm={comm}")
    sys.stdout.flush()

    # ---- roofline table (from dry-run artifacts, if present) ---------------
    from benchmarks import roofline_table

    rows = roofline_table.run()
    if rows:
        print(f"roofline/combos,0,n={len(rows)} (see experiments/dryrun)")
    else:
        print("roofline/combos,0,run `python -m repro.launch.dryrun --all` first")


if __name__ == "__main__":
    main()
