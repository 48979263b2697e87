"""Figure 2 reproduction: the paper's LOGISTIC-regression experiment (Section 9)
through the batched experiment engine — squared distance to optimum vs
communication steps on a9a-style l2-regularized logistic regression.

This is the NON-QUADRATIC validation of SVRP: every prox is approximate (the
guarded Newton of `repro.core.prox`), the similarity constant delta is
MEASURED at the optimum (statistical similarity from i.i.d. client
subsampling, Section 9), and SVRP's theory stepsize mu/(2 delta^2) is used
as-is.  Methods mirror fig1: SVRP vs SVRG, SCAFFOLD, Accelerated
Extragradient — each multi-seed through `run_batch` (one jit per method per
panel; SVRP sweeps with `prox_solver="newton"`).

    PYTHONPATH=src python -m benchmarks.fig2 [--quick]

Writes experiments/fig2/<panel>.csv with columns method,comm,dist_sq
(median trajectories over seeds).  `--quick` is the CI smoke configuration
(one small panel, reduced pool and budget).
"""
from __future__ import annotations

import os

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp

from repro.experiments import run_batch
from repro.problems import make_a9a_like_problem

OUT_DIR = "experiments/fig2"
SEEDS_QUICK = 2
SEEDS_FULL = 5


def _run_panel(prob, label: str, seeds: int, budget: int):
    mu = float(prob.strong_convexity())
    L = float(prob.smoothness_max())
    x_star = prob.minimizer()
    delta = float(prob.similarity_at(x_star))  # measured, as the paper reports
    dmax = float(prob.similarity_max_at(x_star))
    M = prob.num_clients
    x0 = jnp.zeros(prob.dim)
    common = dict(x0=x0, x_star=x_star, seeds=seeds)
    print(f"{label}: M={M}  measured L={L:.3f}  delta={delta:.4f}  mu={mu:.3f}")

    runs = {}
    # SVRP through the engine's non-quadratic solver: guarded Newton prox,
    # E[comm/iter] = 5 at p = 1/M; the Theorem-2 grid (eta = mu/(2 delta^2)
    # at the MEASURED delta, p = 1/M) resolves from the core.theory table.
    runs["svrp"] = run_batch(
        "svrp", prob, stepsize="theory",
        num_steps=max(budget // 5, 200), prox_solver="newton", **common,
    )
    runs["svrg"] = run_batch(
        "svrg", prob, grid={"stepsize": 1.0 / (6.0 * L), "p": 1.0 / M},
        num_steps=max(budget // 5, 200), **common,
    )
    runs["scaffold"] = run_batch(
        "scaffold", prob, grid={"local_lr": 1.0 / (4.0 * L), "global_lr": 1.0},
        num_rounds=budget // 2, local_steps=5, **common,
    )
    # deterministic (full participation; surrogate solved by guarded Newton)
    runs["acc_extragradient"] = run_batch(
        "acc_extragradient", prob, grid={"theta": dmax, "mu": mu},
        num_rounds=max(budget // (4 * M + 2), 3), x0=x0, x_star=x_star,
    )

    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{label}.csv")
    with open(path, "w") as f:
        f.write("method,comm,dist_sq\n")
        for name, res in runs.items():
            s = res.summary()
            comm = s["comm_median"]
            d2 = s["dist_sq_median"]
            keep = comm <= budget
            for c, d in zip(comm[keep], d2[keep]):
                f.write(f"{name},{int(c)},{d:.6e}\n")
    return {name: res.final_at_budget(budget) for name, res in runs.items()}


def run(quick: bool = False):
    """Returns {panel: {method: median final dist_sq at the comm budget}}."""
    seeds = SEEDS_QUICK if quick else SEEDS_FULL
    budget = 2000 if quick else 10_000
    a9a_Ms = [10] if quick else [20, 40, 60]
    n_pool = 2000 if quick else 32561
    n_per = 200 if quick else 2000
    results = {}
    for M in a9a_Ms:
        prob = make_a9a_like_problem(
            num_clients=M, n_per_client=n_per, n_pool=n_pool, lam=0.1, seed=0
        )
        results[f"a9a_logistic_M{M}"] = _run_panel(
            prob, f"a9a_logistic_M{M}", seeds=seeds, budget=budget
        )
    return results


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache(__file__)
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="CI smoke configuration")
    args = ap.parse_args()
    print(json.dumps(run(quick=args.quick), indent=1))
