"""Quickstart: SVRP vs SGD/SVRG on a synthetic federated quadratic.

    PYTHONPATH=src python examples/quickstart.py

Reproduces the paper's headline effect in ~10 seconds on CPU: with high
second-order similarity (delta << L), SVRP reaches machine precision in a
fraction of the communication any L-dependent method needs.
"""
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp

from repro.core import run_sgd, run_svrg, run_svrp, theorem2_stepsize
from repro.problems import make_synthetic_quadratic


def main():
    M, dim = 100, 30
    prob = make_synthetic_quadratic(num_clients=M, dim=dim, mu=1.0, L=2000.0,
                                    delta=8.0, seed=0)
    mu = float(prob.strong_convexity())
    delta = float(prob.similarity())
    L = float(prob.smoothness_max())
    print(f"problem: M={M} d={dim}  mu={mu:.2f}  delta={delta:.2f}  L={L:.0f}")
    print(f"SVRP's favourable regime: delta={delta:.1f} << sqrt(L*mu)={ (L*mu)**0.5 :.1f}\n")

    x_star = prob.minimizer()
    x0 = jnp.zeros(dim)
    key = jax.random.key(0)

    res_svrp = run_svrp(prob, x0, x_star, eta=theorem2_stepsize(mu, delta), p=1 / M,
                        num_steps=4000, key=key)
    res_svrg = run_svrg(prob, x0, x_star, stepsize=1 / (6 * L), p=1 / M,
                        num_steps=40_000, key=key)
    res_sgd = run_sgd(prob, x0, x_star, stepsize=1 / (2 * L), num_steps=40_000, key=key)

    eps = 1e-10
    print(f"{'method':12s} {'final dist^2':>14s} {'comm to 1e-10':>14s}")
    for name, res in [("SVRP", res_svrp), ("SVRG", res_svrg), ("SGD", res_sgd)]:
        c = float(res.comm_to_accuracy(eps))
        c_str = f"{int(c)}" if c == c and c != float("inf") else "never"
        print(f"{name:12s} {float(res.dist_sq[-1]):14.2e} {c_str:>14s}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache(__file__)
    main()
