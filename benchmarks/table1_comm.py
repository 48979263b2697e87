"""Table 1 realized empirically — in BYTES: wire bytes to reach eps for every
method, across a (delta, M) grid — the complexity separations the paper
proves (SVRP's M + delta^2/mu^2 vs the sqrt(delta/mu) M family), priced the
way a deployment pays for them.

Every method runs through the batched experiment engine (`run_batch`) like
fig1/fig2: the stochastic methods (SVRP / Catalyzed SVRP / SVRG) are
multi-seed sweeps — one jit per method per panel, bytes-to-eps is the MEDIAN
over the seed axis with the IQR recorded alongside — and the deterministic
full-participation baselines (DANE / Accelerated Extragradient) are
single-trial engine runs, now that all five share the ALGOS registry.

Bytes come from the engine's int64 ledger (`BatchResult.comm_bytes` /
`bytes_to_accuracy`), predictions from `core.theory.predict_comm_bytes_for`
(Section-4.2 exchange counts x the channel's static wire price) — the two
sides are exactly commensurable because every counted exchange is one
d-vector.  The vector-count column (`comm_to_eps`) is kept as the derived
view; the quantized-wire frontier itself (quant8 vs float32 bytes-per-round)
lives in BENCH_sweep.json via benchmarks/sweep_bench.py.

    PYTHONPATH=src python -m benchmarks.table1_comm [--quick]

Writes experiments/table1/comm_to_eps.csv with columns
M,delta,method,comm_to_eps,comm_q25,comm_q75,predicted_comm,bytes_to_eps,
bytes_q25,bytes_q75,predicted_bytes (medians over seeds; inf = never
reached).  `--quick` is the CI smoke configuration (two panels, reduced seed
count).
"""
from __future__ import annotations

import os

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np

from repro.core import (
    THEORY,
    catalyst_inner_iterations,
    measure_constants,
    predict_comm_bytes_for,
    predict_comm_for,
)
from repro.experiments import run_batch
from repro.problems import make_synthetic_quadratic

EPS = 1e-12
OUT = "experiments/table1"
SEEDS_QUICK = 2
SEEDS_FULL = 5


def comm_to_eps(prob, seeds: int):
    """{method: (median, q25, q75, predicted) steps AND (median, q25, q75,
    predicted) BYTES to reach EPS} — predicted from the `core.theory` table
    where the paper states a rate (NaN for the baselines), so the CSV doubles
    as the predicted-vs-measured record on both axes."""
    mu = float(prob.strong_convexity())
    dmax = float(prob.similarity_max())
    L = float(prob.smoothness_max())
    M = prob.num_clients
    consts = measure_constants(prob)
    inner = catalyst_inner_iterations(mu, consts.delta, M)

    runs = {}
    # SVRP at the Theorem-2 grid (resolved from the theory table); spectral
    # prox is the engine fast path.
    runs["svrp"] = run_batch(
        "svrp", prob, stepsize="theory", theory_constants=consts,
        seeds=seeds, num_steps=12_000, prox_solver="spectral",
    )
    # Catalyzed SVRP with the proof's parameter choices (Theorem 3).
    runs["catalyzed_svrp"] = run_batch(
        "catalyzed_svrp", prob, stepsize="theory", theory_constants=consts,
        seeds=seeds, num_outer=30, inner_steps=inner, prox_solver="spectral",
    )
    runs["svrg"] = run_batch(
        "svrg", prob, grid={"stepsize": 1 / (6 * L), "p": 1 / M},
        seeds=seeds, num_steps=100_000,
    )
    # Deterministic full-participation baselines: a single trial suffices.
    runs["dane"] = run_batch("dane", prob, grid={"theta": dmax}, num_rounds=400)
    runs["acc_extragradient"] = run_batch(
        "acc_extragradient", prob, grid={"theta": dmax, "mu": mu}, num_rounds=400
    )

    out = {}
    for method, res in runs.items():
        c2a = res.comm_to_accuracy(EPS)  # (B,), inf if never reached
        b2a = res.bytes_to_accuracy(EPS)  # (B,) wire bytes, same convention
        has_rate = method in THEORY and THEORY[method].comm is not None
        predicted = (
            predict_comm_for(prob, method, eps=EPS, constants=consts)
            if has_rate else float("nan")
        )
        predicted_bytes = (
            predict_comm_bytes_for(prob, method, eps=EPS, constants=consts)
            if has_rate else float("nan")
        )
        out[method] = (
            float(np.median(c2a)),
            float(np.percentile(c2a, 25)),
            float(np.percentile(c2a, 75)),
            predicted,
            float(np.median(b2a)),
            float(np.percentile(b2a, 25)),
            float(np.percentile(b2a, 75)),
            predicted_bytes,
        )
    return out


def run(quick: bool = False):
    """Returns [(M, delta, method, median comm-to-eps), ...] and writes the
    CSV (with IQR columns) under experiments/table1/."""
    grid = [(20, 5.0), (20, 60.0)] if quick else [
        (20, 5.0), (20, 60.0), (100, 5.0), (100, 60.0), (400, 20.0)
    ]
    seeds = SEEDS_QUICK if quick else SEEDS_FULL
    os.makedirs(OUT, exist_ok=True)
    rows = []
    csv_rows = []
    for M, delta in grid:
        prob = make_synthetic_quadratic(num_clients=M, dim=30, mu=1.0, L=1500.0,
                                        delta=delta, seed=0)
        res = comm_to_eps(prob, seeds=seeds)
        for method, vals in res.items():
            rows.append((M, delta, method, vals[4]))  # median bytes-to-eps
            csv_rows.append((M, delta, method, *vals))
    with open(os.path.join(OUT, "comm_to_eps.csv"), "w") as f:
        f.write("M,delta,method,comm_to_eps,comm_q25,comm_q75,predicted_comm,"
                "bytes_to_eps,bytes_q25,bytes_q75,predicted_bytes\n")
        for row in csv_rows:
            f.write(",".join(str(v) for v in row) + "\n")
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache(__file__)
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="CI smoke configuration")
    args = ap.parse_args()
    for row in run(quick=args.quick):
        print(row)
