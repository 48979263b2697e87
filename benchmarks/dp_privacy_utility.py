"""DP-ERM through the experiment engine: the privacy-utility frontier and the
predicted-vs-measured communication panel.

The paper's abstract names differentially private empirical risk minimization
as a regime where second-order similarity holds (delta ~ O(1/sqrt(n)) per
client); this benchmark realizes that workload end-to-end:

1. **Privacy-utility frontier** — the a9a-style logistic problem privatized
   by `repro.problems.dp_erm` (row clipping + per-client Gaussian objective
   perturbation) across a noise-multiplier sweep.  Each sigma runs a
   multi-seed SVRP sweep through `run_batch(..., stepsize="theory")`; the
   zCDP accountant prices the run's (steps, p) schedule — the fresh-noise
   schedule it corresponds to, NOT a certificate for the replayed one-shot
   simulation (see the noise-reuse caveat in problems/dp_erm.py) — and the
   utility is the median final squared distance to the NON-PRIVATE optimum
   (`base_problem().minimizer()`).  Output: eps vs utility — the frontier.

2. **Predicted-vs-measured communication** — `core.theory.predict_comm`
   curves overlaid on engine measurements (`comm_to_accuracy`) for SPPM and
   SVRP across a similarity grid on exact-constant quadratics, including the
   Theorem-3 separation: SVRP wins when delta/mu is small, SPPM's
   sigma_*^2-driven rate wins when delta/mu is large.  The same panel is
   recorded on the BYTES ledger: `predict_comm_bytes_for` (Section-4.2
   counts x the static wire price) against `BatchResult.bytes_to_accuracy`
   — exactly commensurable, since every counted exchange is one d-vector
   priced at the same `channel.wire_vector_bytes` the engine uses, so the
   predicted/measured ratio must be IDENTICAL on both axes (asserted in the
   smoke run).

    PYTHONPATH=src python -m benchmarks.dp_privacy_utility [--quick]

Writes experiments/dp/privacy_utility.csv and
experiments/dp/predicted_vs_measured.csv.  `--quick` is the CI smoke
configuration (reduced pool, seeds, and step budgets).
"""
from __future__ import annotations

import os

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

from repro.core import measure_constants, predict_comm_bytes_for, predict_comm_for
from repro.experiments import run_batch
from repro.problems import make_dp_a9a_problem, make_synthetic_quadratic

OUT = "experiments/dp"


# ------------------------------------------------------- privacy-utility side
def privacy_utility_frontier(quick: bool) -> list[dict]:
    """One row per noise multiplier: (sigma, eps, delta_dp, utility quartiles)."""
    sigmas = [1.0, 8.0] if quick else [0.5, 1.0, 2.0, 4.0, 8.0, 16.0]
    M = 10 if quick else 20
    n_per = 200 if quick else 2000
    n_pool = 2000 if quick else 32561
    seeds = 2 if quick else 5
    num_steps = 300 if quick else 2000

    rows = []
    for sigma in sigmas:
        prob = make_dp_a9a_problem(
            M, sigma=sigma, clip=1.0, n_per_client=n_per, n_pool=n_pool,
            lam=0.1, seed=0, noise_seed=1,
        )
        x_star = prob.base_problem().minimizer()
        res = run_batch(
            "svrp", prob, stepsize="theory", seeds=seeds, num_steps=num_steps,
            prox_solver="newton-cg", x_star=x_star,
        )
        p = float(res.hparams["p"][0])
        eps, delta_dp = prob.privacy_spent(num_steps, p)
        final = np.asarray(res.dist_sq)[:, -1]
        rows.append({
            "sigma": sigma,
            "eps": eps,
            "delta_dp": delta_dp,
            "similarity_bound": prob.similarity_bound(),
            "dist_sq_median": float(np.median(final)),
            "dist_sq_q25": float(np.percentile(final, 25)),
            "dist_sq_q75": float(np.percentile(final, 75)),
        })
        print(
            f"sigma={sigma:<5g} eps={eps:9.3f} "
            f"median final dist_sq={rows[-1]['dist_sq_median']:.3e}"
        )
    return rows


# ------------------------------------------- predicted-vs-measured comm panel
def predicted_vs_measured(quick: bool) -> list[dict]:
    """SPPM/SVRP communication-to-eps: theory table prediction next to the
    engine measurement, across a similarity grid (exact-constant quadratics,
    small gradient noise so the SPPM side is measurable)."""
    deltas = [2.0, 40.0] if quick else [1.0, 2.0, 5.0, 10.0, 25.0, 60.0]
    eps = 1e-3 if quick else 1e-4
    seeds = 2 if quick else 5
    M, dim = 20, 25
    sppm_steps = 30_000 if quick else 120_000
    svrp_steps = 50_000 if quick else 200_000

    rows = []
    for delta in deltas:
        prob = make_synthetic_quadratic(
            num_clients=M, dim=dim, mu=1.0, L=300.0, delta=delta,
            noise=0.3, seed=0,
        )
        # Start far from x_* so r0_sq/eps is the theorems' non-degenerate
        # regime (the synthetic b keeps |x_*| small; x0=0 would mean r0~eps).
        x0 = 2.0 * jnp.ones(dim)
        consts = measure_constants(prob, x0=x0)
        for algo, steps in (("sppm", sppm_steps), ("svrp", svrp_steps)):
            predicted = predict_comm_for(prob, algo, eps=eps, constants=consts)
            predicted_bytes = predict_comm_bytes_for(
                prob, algo, eps=eps, constants=consts
            )
            res = run_batch(
                algo, prob, stepsize="theory", target_eps=eps,
                theory_constants=consts, seeds=seeds,
                num_steps=steps, prox_solver="spectral", x0=x0,
            )
            c2a = res.comm_to_accuracy(eps)
            b2a = res.bytes_to_accuracy(eps)
            rows.append({
                "delta": delta,
                "algo": algo,
                "eps": eps,
                "predicted_comm": float(predicted),
                "measured_comm_median": float(np.median(c2a)),
                "measured_comm_q25": float(np.percentile(c2a, 25)),
                "measured_comm_q75": float(np.percentile(c2a, 75)),
                "predicted_bytes": float(predicted_bytes),
                "measured_bytes_median": float(np.median(b2a)),
                "measured_bytes_q25": float(np.percentile(b2a, 25)),
                "measured_bytes_q75": float(np.percentile(b2a, 75)),
            })
            print(
                f"delta={delta:<5g} {algo:<5} predicted={predicted:12.0f} "
                f"measured={rows[-1]['measured_comm_median']:10.0f}"
            )
        # The Theorem-3 story in one line per delta: do prediction and
        # measurement agree on the winner?
        sp, sv = rows[-2], rows[-1]
        pred_winner = "svrp" if sv["predicted_comm"] < sp["predicted_comm"] else "sppm"
        meas_winner = (
            "svrp" if sv["measured_comm_median"] < sp["measured_comm_median"]
            else "sppm"
        )
        agree = "agree" if pred_winner == meas_winner else "DISAGREE"
        print(f"delta={delta:<5g} winner: predicted={pred_winner} "
              f"measured={meas_winner} ({agree})")
    return rows


def _write_csv(path: str, rows: list[dict]) -> None:
    with open(path, "w") as f:
        cols = list(rows[0])
        f.write(",".join(cols) + "\n")
        for r in rows:
            f.write(",".join(str(r[c]) for c in cols) + "\n")


def run(quick: bool = False) -> dict:
    os.makedirs(OUT, exist_ok=True)
    frontier = privacy_utility_frontier(quick)
    panel = predicted_vs_measured(quick)
    _write_csv(os.path.join(OUT, "privacy_utility.csv"), frontier)
    _write_csv(os.path.join(OUT, "predicted_vs_measured.csv"), panel)
    return {"frontier": frontier, "panel": panel}


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache(__file__)
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="CI smoke configuration")
    args = ap.parse_args()
    out = run(quick=args.quick)
    # The frontier must actually trade off: more noise = more privacy
    # (smaller eps) and worse utility.  Hold that shape in the smoke too.
    eps_list = [r["eps"] for r in out["frontier"]]
    assert eps_list == sorted(eps_list, reverse=True), "eps must fall as sigma grows"
    # The bytes panel is the comm panel under one static wire price, on BOTH
    # sides — so predicted/measured must agree between axes wherever finite.
    for r in out["panel"]:
        if np.isfinite(r["measured_comm_median"]):
            scale = r["measured_bytes_median"] / r["measured_comm_median"]
            np.testing.assert_allclose(
                r["predicted_bytes"], r["predicted_comm"] * scale, rtol=1e-12
            )
