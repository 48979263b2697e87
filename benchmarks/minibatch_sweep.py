"""Beyond-paper: client-minibatch sweep for SVRP — rounds-to-eps vs b.

Shows the datacenter trade the DeepSVRP cohort design exploits: total
communication stays roughly flat in b while the number of ROUNDS (wall-clock
under parallel clients) drops.

Seeds within each cohort size run through the batched engine
(`run_batch("svrp_minibatch", ...)`) — one jit per b (the cohort size is a
static shape).  Reported rounds/comm are medians over seeds.
"""
from __future__ import annotations

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np

from repro.core import theorem2_stepsize
from repro.experiments import run_batch
from repro.problems import make_synthetic_quadratic

EPS = 1e-12


def run(quick: bool = False):
    M = 64
    prob = make_synthetic_quadratic(num_clients=M, dim=24, mu=1.0, L=800.0,
                                    delta=8.0, seed=0)
    mu = float(prob.strong_convexity())
    delta = float(prob.similarity())
    eta = theorem2_stepsize(mu, delta)
    seeds = 3 if quick else 8

    rows = []
    bs = [1, 4, 16] if quick else [1, 2, 4, 8, 16, 32, 64]
    for b in bs:
        # scaling laws for minibatch clients: variance ~ delta^2/b allows
        # eta*b; refresh can afford p*b (its 3pM cost grows like the 2b
        # per-round cost).  Measured: rounds drop ~b-fold, comm stays flat.
        res = run_batch(
            "svrp_minibatch", prob,
            grid={"eta": eta * b, "p": min(b / M, 1.0)},
            seeds=seeds, num_steps=4000, batch_clients=b, prox_solver="spectral",
        )
        d2 = np.asarray(res.dist_sq)
        comm = np.asarray(res.comm)
        per_rounds, per_comm = [], []
        for i in range(d2.shape[0]):
            hit = np.nonzero(d2[i] <= EPS)[0]
            if len(hit):
                per_rounds.append(int(hit[0]) + 1)
                per_comm.append(int(comm[i, hit[0]]))
        # median over the trials that reached EPS; -1 if none did
        if per_rounds:
            rows.append((b, int(np.median(per_rounds)), int(np.median(per_comm))))
        else:
            rows.append((b, -1, -1))
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache(__file__)
    print("b,rounds_to_eps,comm_to_eps")
    for b, r, c in run(quick=True):
        print(f"{b},{r},{c}")
