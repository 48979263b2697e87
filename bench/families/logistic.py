"""l2-regularized logistic regression on an a9a-shaped dataset.

The data comes from the reference's generator, seeded by the run's seed; the
optimum and the per-client smoothness bound from the reference's float64
solve on the host.  The program sees them as inputs, as a user's sweep
script hands them over.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from bench.reference import logistic as ref


@dataclasses.dataclass
class Logistic:
    Z: np.ndarray  # (M, n, d) float32
    y: np.ndarray  # (M, n) float32, +-1
    lam: float
    x_star: np.ndarray  # (d,) float32
    L: float  # max over clients of the smoothness bound
    problem: object  # the program's LogisticProblem on the device

    @property
    def d0(self) -> float:
        """||x0 - x_*||^2 from the start x0 = 0."""
        return float(np.sum(self.x_star.astype(np.float64) ** 2))


def build(config: dict, traffic: dict, seed: int) -> Logistic:
    from repro.problems.logistic import LogisticProblem

    Z, y = ref.a9a_like(
        seed, num_clients=config["num_clients"], n_per_client=config["n_per_client"],
        n_pool=config["n_pool"], dim=config["dim"], nnz_per_row=config["nnz_per_row"])
    lam = float(config["lam"])
    x_star = ref.minimizer(Z, y, lam).astype(np.float32)
    L = ref.smoothness_max(Z, lam)
    problem = LogisticProblem(Z=jnp.asarray(Z), y=jnp.asarray(y), lam=lam)
    return Logistic(Z, y, lam, x_star, L, problem)
