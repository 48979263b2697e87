"""The paper's real-data experiments on a9a-style data: ridge regression
(Fig. 1 bottom row) AND l2-regularized logistic regression (Section 9), both
driven through the batched experiment engine — every method is a multi-seed
`run_batch` sweep in one jit, not a per-trial Python loop.

    PYTHONPATH=src python examples/fed_a9a.py --clients 20 --seeds 3

The container is offline, so features are re-synthesized with a9a's published
statistics (123 binary features, ~14 nnz/row) and clients subsample a common
pool i.i.d. — exactly the mechanism that makes delta small (Section 9).  The
logistic track sweeps SVRP with the guarded-Newton prox solver
(`prox_solver="newton"`) from `repro.core.prox`'s registry.
"""
import argparse

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

from repro.core import theorem2_stepsize
from repro.experiments import run_batch
from repro.problems import make_ridge_problem
from repro.problems.logistic import make_a9a_like_problem


def _report(title: str, runs: dict, budget: int) -> None:
    print(f"\n{title}")
    print(f"{'method':10s} {'median dist^2 @ comm budget':>28s}")
    for name, res in runs.items():
        print(f"{name:10s} {res.final_at_budget(budget):28.3e}")


def run_panel(prob, *, budget: int, seeds: int, prox_solver: str, label: str):
    mu = float(prob.strong_convexity())
    L = float(prob.smoothness_max())
    M = prob.num_clients
    x_star = prob.minimizer()
    if hasattr(prob, "similarity"):
        delta = float(prob.similarity())
    else:
        delta = float(prob.similarity_at(x_star))  # measured at x_* (logistic)
    print(f"{label}: M={M}  measured L={L:.2f}  delta={delta:.3f}  mu={mu:.2f}")

    common = dict(x0=jnp.zeros(prob.dim), x_star=x_star, seeds=seeds)
    runs = {
        "svrp": run_batch(
            "svrp", prob, grid={"eta": theorem2_stepsize(mu, delta), "p": 1 / M},
            num_steps=budget // 5, prox_solver=prox_solver, **common,
        ),
        "svrg": run_batch(
            "svrg", prob, grid={"stepsize": 1 / (6 * L), "p": 1 / M},
            num_steps=budget // 5, **common,
        ),
        "scaffold": run_batch(
            "scaffold", prob, grid={"local_lr": 1 / (4 * L), "global_lr": 1.0},
            local_steps=5, num_rounds=budget // 2, **common,
        ),
    }
    _report(label, runs, budget)
    return runs


def main():
    ap = argparse.ArgumentParser()
    # Defaults are sized for a ~1-minute CPU demo; the paper's setup is
    # --comm-budget 10000 --n-per-client 2000.
    ap.add_argument("--clients", type=int, default=20)
    ap.add_argument("--comm-budget", type=int, default=5000)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--n-per-client", type=int, default=500)
    args = ap.parse_args()

    lp = make_a9a_like_problem(num_clients=args.clients, n_per_client=args.n_per_client,
                               n_pool=8000, lam=0.1, seed=0)

    # Track 1 — ridge regression on the a9a features (quadratic: spectral prox).
    ridge = make_ridge_problem(np.asarray(lp.Z), np.asarray(lp.y), lam=0.1)
    run_panel(ridge, budget=args.comm_budget, seeds=args.seeds,
              prox_solver="spectral", label="a9a-like ridge")

    # Track 2 — the actual logistic problem (non-quadratic: guarded Newton prox).
    run_panel(lp, budget=args.comm_budget, seeds=args.seeds,
              prox_solver="newton", label="a9a-like logistic")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache(__file__)
    main()
