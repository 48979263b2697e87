"""DP-ERM in ~40 lines: the paper's headline application end to end.

Builds the a9a-style logistic problem, privatizes it (row clipping + per-client
Gaussian objective perturbation), runs a multi-seed SVRP sweep at the
theorem-prescribed stepsize through the batched engine, and prints what the
three new layers say about the run:

* the zCDP accountant's (eps, delta) for the round schedule,
* the clip-composed O(1/sqrt(n)) similarity bound next to the measured delta,
* the theory table's predicted communication next to the engine's measurement.

    PYTHONPATH=src python examples/fed_dp.py
"""
import jax

jax.config.update("jax_enable_x64", True)

import numpy as np

from repro.core import measure_constants, predict_comm_for
from repro.experiments import run_batch
from repro.problems import make_dp_a9a_problem
from repro.utils.compile_cache import enable_compile_cache

enable_compile_cache(__file__)

M = 10
NUM_STEPS = 400
SEEDS = 4

for sigma in (1.0, 4.0):
    prob = make_dp_a9a_problem(
        M, sigma=sigma, clip=1.0, n_per_client=200, n_pool=2000, lam=0.1
    )
    x_star = prob.base_problem().minimizer()  # the NON-private comparator
    consts = measure_constants(prob, x_star=x_star)

    res = run_batch(
        "svrp", prob, stepsize="theory", theory_constants=consts,
        seeds=SEEDS, num_steps=NUM_STEPS,
        prox_solver="newton-cg", x_star=x_star,
    )
    p = float(res.hparams["p"][0])
    eps, delta_dp = prob.privacy_spent(NUM_STEPS, p)
    final = float(np.median(np.asarray(res.dist_sq)[:, -1]))
    eps_opt = 2.0 * final  # a reachable target for the comm comparison
    measured_comm = float(np.median(res.comm_to_accuracy(eps_opt)))
    predicted_comm = predict_comm_for(prob, "svrp", eps=eps_opt, constants=consts)

    print(f"sigma={sigma:g}:")
    print(f"  privacy:    ({eps:.2f}, {delta_dp:g})-DP after {NUM_STEPS} rounds at p={p:.2f}")
    print(f"  similarity: measured delta={consts.delta:.4f}  "
          f"clip-composed bound={prob.similarity_bound():.4f}")
    print(f"  utility:    median final dist to non-private optimum = {final:.3e}")
    print(f"  comm to {eps_opt:.1e}: measured {measured_comm:.0f}, "
          f"theory bound {predicted_comm:.0f}")
