"""Figure 1 reproduction: squared distance to optimum vs communication steps.

Top row (synthetic): M in {1000, 2000, 3000}, L ~= 3330, delta ~= 10, lam = 1.
Bottom row (a9a): M in {20, 40, 60}, lam = 0.1 — ridge regression on an
a9a-statistics-matched pool (offline container; see DESIGN.md §8), n = 2000
samples per client drawn i.i.d. from the pool exactly as in the paper.

Methods: SVRP (ours) vs SVRG, SCAFFOLD, Accelerated Extragradient — each with
its theory stepsize, 10_000 communication steps, as in the paper.

Multi-seed: every stochastic method runs SEEDS trials through the batched
experiment engine (`repro.experiments.run_batch`) — one jit per method per
panel instead of a Python loop — and the plotted/written trajectory is the
per-step MEDIAN over seeds (the paper plots seed-averaged curves).

Writes experiments/fig1/<panel>.csv with columns method,comm,dist_sq
(comm/dist_sq = median trajectories).
"""
from __future__ import annotations

import os

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

from repro.core import theorem2_stepsize
from repro.experiments import run_batch
from repro.problems import make_synthetic_quadratic, make_ridge_problem
from repro.problems.logistic import make_a9a_like_problem

COMM_BUDGET = 10_000
OUT_DIR = "experiments/fig1"
SEEDS_QUICK = 2
SEEDS_FULL = 5


def _run_panel(prob, label: str, seeds: int = SEEDS_QUICK):
    mu = float(prob.strong_convexity())
    delta = float(prob.similarity())
    dmax = float(prob.similarity_max())
    L = float(prob.smoothness_max())
    M = prob.num_clients
    x_star = prob.minimizer()
    x0 = jnp.zeros(prob.dim)
    common = dict(x0=x0, x_star=x_star, seeds=seeds)

    runs = {}
    # SVRP: E[comm/iter] = 5 at p=1/M.  Spectral prox = the engine fast path
    # (same operator as the LU solve up to factorization round-off).
    runs["svrp"] = run_batch(
        "svrp", prob, grid={"eta": theorem2_stepsize(mu, delta), "p": 1.0 / M},
        num_steps=max(COMM_BUDGET // 5, 200), prox_solver="spectral", **common,
    )
    runs["svrg"] = run_batch(
        "svrg", prob, grid={"stepsize": 1.0 / (6.0 * L), "p": 1.0 / M},
        num_steps=max(COMM_BUDGET // 5, 200), **common,
    )
    runs["scaffold"] = run_batch(
        "scaffold", prob, grid={"local_lr": 1.0 / (4.0 * L), "global_lr": 1.0},
        num_rounds=COMM_BUDGET // 2, local_steps=5, **common,
    )
    # deterministic (full participation): a single trial suffices
    runs["acc_extragradient"] = run_batch(
        "acc_extragradient", prob, grid={"theta": dmax, "mu": mu},
        num_rounds=max(COMM_BUDGET // (4 * M + 2), 3), x0=x0, x_star=x_star,
    )

    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{label}.csv")
    with open(path, "w") as f:
        f.write("method,comm,dist_sq\n")
        for name, res in runs.items():
            s = res.summary()
            comm = s["comm_median"]
            d2 = s["dist_sq_median"]
            keep = comm <= COMM_BUDGET
            for c, d in zip(comm[keep], d2[keep]):
                f.write(f"{name},{int(c)},{d:.6e}\n")
    return {name: res.final_at_budget(COMM_BUDGET) for name, res in runs.items()}


def run(quick: bool = False):
    """Returns {panel: {method: median final dist_sq at the comm budget}}."""
    seeds = SEEDS_QUICK if quick else SEEDS_FULL
    results = {}
    synth_Ms = [200] if quick else [1000, 2000, 3000]
    for M in synth_Ms:
        prob = make_synthetic_quadratic(
            num_clients=M, dim=40, mu=1.0, L=3330.0, delta=10.0, seed=0
        )
        results[f"synthetic_M{M}"] = _run_panel(prob, f"synthetic_M{M}", seeds=seeds)

    a9a_Ms = [20] if quick else [20, 40, 60]
    n_pool = 4000 if quick else 32561
    n_per = 500 if quick else 2000
    for M in a9a_Ms:
        lp = make_a9a_like_problem(num_clients=M, n_per_client=n_per, n_pool=n_pool, seed=0)
        # the paper's a9a experiment is RIDGE regression on these features
        Z = np.asarray(lp.Z)
        y = np.asarray(lp.y)
        prob = make_ridge_problem(Z, y, lam=0.1)
        results[f"a9a_M{M}"] = _run_panel(prob, f"a9a_M{M}", seeds=seeds)
    return results


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache(__file__)
    import json

    print(json.dumps(run(quick=True), indent=1))
