"""Streaming federated simulation server example.

Clients churn on a `ClientStream`; cohorts form on the fly from whoever is
resident; SVRP rounds run continuously with pipelined stats readback.  The
round body is the SAME registry definition (`repro.core.rounds.ROUND_DEFS`)
the batch engine scans over — only the client-sampling hooks are masked to
the resident set.

    PYTHONPATH=src python examples/serve_fed.py              # full demo
    PYTHONPATH=src python examples/serve_fed.py --quick      # CI smoke
    PYTHONPATH=src python examples/serve_fed.py --pool       # multi-tenant

`--pool` serves MANY federations at once through `repro.serve.SessionPool`:
several tenants (distinct problems, hyperparameters, horizons) packed into
one stacked device state, every running tenant advanced by ONE jitted
dispatch per tick via `FedRoundServer(pool=...)`; tenants whose horizon runs
out freeze mid-run while the rest keep serving.

In CI the --quick runs append a rounds/sec + latency-percentile table (and,
for --pool, a per-tenant table) to `$GITHUB_STEP_SUMMARY`.  The incremental
single-sweep counterpart (step a `run_batch` sweep round by round) is
`repro.serve.open_session`; the model DECODE batch server lives in
`repro.launch.serve` (see examples/serve.py).
"""
import argparse
import os

import numpy as np

from repro.core import theorem2_stepsize
from repro.problems import make_synthetic_quadratic
from repro.serve import ClientStream, FedRoundServer, SessionPool


def _append_step_summary(text: str) -> None:
    summary_path = os.environ.get("GITHUB_STEP_SUMMARY")
    if summary_path:
        with open(summary_path, "a") as f:
            f.write(text)


def run_stream(args) -> None:
    M = args.clients or (10 if args.quick else 32)
    rounds = args.rounds or (120 if args.quick else 600)
    prob = make_synthetic_quadratic(num_clients=M, dim=8, mu=1.0, L=80.0,
                                    delta=4.0, seed=1)
    eta = theorem2_stepsize(1.0, float(prob.similarity()))
    hparams = {"svrp": {"eta": eta, "p": 0.2},
               "sppm": {"eta": 0.05},
               "svrp_minibatch": {"eta": 3 * eta, "p": 0.25}}[args.algo]
    extra = {"batch_clients": max(2, M // 4)} if args.algo == "svrp_minibatch" else {}

    stream = ClientStream(M, churn=args.churn, seed=args.seed + 1)
    srv = FedRoundServer(args.algo, prob, hparams=hparams, stream=stream,
                         seed=args.seed, **extra)
    print(f"serving {args.algo}: {M} clients, churn={args.churn}, "
          f"{rounds} continuous rounds ...")
    stats = srv.run(rounds)
    print(stats.report())
    _append_step_summary(stats.markdown(f"Federated round server ({args.algo})"))

    # Sanity for the CI smoke: rounds completed, percentiles populated.
    s = stats.summary()
    assert s["rounds"] == rounds
    assert s["p95_ms"] == s["p95_ms"], "latency percentiles must be populated"


def run_pool(args) -> None:
    M = args.clients or (10 if args.quick else 32)
    rounds = args.rounds or (60 if args.quick else 400)
    P = 4 if args.quick else 8
    pool = SessionPool(capacity=P)
    tenants = []  # (tenant id, horizon)
    for i in range(P):
        prob = make_synthetic_quadratic(num_clients=M, dim=8, mu=1.0, L=80.0,
                                        delta=4.0, seed=args.seed + i + 1)
        eta = theorem2_stepsize(1.0, float(prob.similarity()))
        # Mixed horizons on purpose: odd tenants exhaust halfway through the
        # run and freeze (masked lanes) while even tenants keep serving.
        horizon = rounds if i % 2 == 0 else max(2, rounds // 2)
        tid = pool.admit("svrp", prob, grid={"eta": eta, "p": 0.2},
                         seeds=2, num_steps=horizon)
        tenants.append((tid, horizon))
    srv = FedRoundServer(pool=pool)
    print(f"serving {P} pooled svrp tenants ({M} clients each, mixed "
          f"horizons, one dispatch per tick), up to {rounds} ticks ...")
    stats = srv.run(rounds)
    print(stats.report())

    elapsed = stats.elapsed_s[-1]
    agg = pool.total_rounds / elapsed if elapsed > 0 else float("inf")
    lines = [
        f"### Multi-tenant session pool ({P} tenants, svrp)",
        "",
        f"aggregate: {pool.total_rounds} tenant-rounds in {elapsed:.2f}s "
        f"= {agg:.0f} rounds/sec across the pool "
        f"({stats.summary()['rounds_per_sec']:.0f} ticks/sec)",
        "",
        "| tenant | horizon | rounds served | final median dist^2 |",
        "|---:|---:|---:|---:|",
    ]
    for tid, horizon in tenants:
        ses = pool.session(tid)
        final = float(np.median(np.asarray(ses.dist_sq)[:, -1]))
        lines.append(f"| {tid} | {horizon} | {ses.t} | {final:.3e} |")
        # Sanity for the CI smoke: every tenant served its whole horizon
        # (the server freezes exhausted tenants instead of erroring) and
        # made progress.
        assert ses.t == horizon, (tid, ses.t, horizon)
        assert final < float(np.median(np.asarray(ses.dist_sq)[:, 0]))
    assert pool.freeze_exhausted(1) == 0, "no tenant should have rounds left"
    table = "\n".join(lines) + "\n"
    print(table)
    _append_step_summary(table)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small population / few rounds (CI smoke)")
    ap.add_argument("--pool", action="store_true",
                    help="multi-tenant SessionPool serving demo")
    ap.add_argument("--algo", choices=["svrp", "sppm", "svrp_minibatch"],
                    default="svrp")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--clients", type=int, default=None)
    ap.add_argument("--churn", type=float, default=0.15)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.pool:
        run_pool(args)
    else:
        run_stream(args)


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache(__file__)
    main()
