"""A researcher's sweep: `run_batch` calls issued back to back.

Each call runs every (stepsize, seed) trial of the traffic file for a fixed
number of rounds, with fresh seeds, and its trajectories are read back to
the host before the next call starts: a closed loop, like a sweep script.
Every call does the same work, so the seed changes only which trials run.

End to end: `trial_rounds_per_s`, the trials x rounds of every call in the
window over the window's wall time (calls run to completion).

Correctness: calls drawn from the seed among the window's are replayed by
the plain SVRP reference from the same seeds: each trial's `dist_sq` where
its trajectory ends (`dist_sq_floor_gap`, in decades; float32's floor for
the trials that reach it, which the precision of the products sets), and
`comm` and `comm_bytes` exactly.
"""
from __future__ import annotations

import dataclasses
import time

import jax.numpy as jnp
import numpy as np

from bench import counts
from bench.harness import Check, Window
from bench.reference import logistic as ref


@dataclasses.dataclass
class State:
    data: object  # families.logistic.Logistic
    t: dict  # the traffic file
    seed_base: int
    calls: list  # [(seeds, dist_sq, comm, comm_bytes)] of the window
    check_rng: np.random.Generator
    control: str | None = None  # a precision: the reference runs in the program's place


def trial_order(etas, seeds):
    """The program's trial order: seed-major, stepsizes fastest."""
    return np.tile(np.asarray(etas, np.float64), len(seeds)), np.repeat(np.asarray(seeds), len(etas))


def call_seeds(st: State, i: int) -> list:
    n = st.t["seeds_per_call"]
    return [(st.seed_base + n * i + j) % 2**32 for j in range(n)]


def one_call(st: State, seeds: list, rec):
    from repro.experiments import run_batch

    t, data = st.t, st.data
    if st.control:  # a control run: the reference at a lower precision
        return replay(st, seeds, st.control)
    with rec.span("run_batch"):
        res = run_batch(
            t["algo"], data.problem,
            grid={"eta": list(t["etas"]), "p": t["p"], "smoothness": data.L},
            seeds=seeds, num_steps=t["rounds"], prox_solver=t["prox_solver"],
            prox_steps=t["prox_steps"], fused=t["fused"],
            x0=jnp.zeros(data.Z.shape[-1], jnp.float32), x_star=jnp.asarray(data.x_star))
    with rec.span("readback"):
        d2 = np.asarray(res.dist_sq)
    etas, seed_arr = trial_order(t["etas"], seeds)
    if not (np.array_equal(res.seeds, seed_arr) and np.allclose(res.hparams["eta"], etas)):
        raise RuntimeError("run_batch returned its trials in an unexpected order")
    return d2, np.asarray(res.comm), np.asarray(res.comm_bytes)


def replay(st: State, seeds: list, precision: str):
    """The reference's (dist_sq, comm, comm_bytes) for one call."""
    t, data = st.t, st.data
    etas, seed_arr = trial_order(t["etas"], seeds)
    d2, comm = ref.svrp(data.Z, data.y, data.lam, data.x_star, data.L, etas, seed_arr,
                        p=t["p"], rounds=t["rounds"], prox_steps=t["prox_steps"],
                        precision=precision)
    comm = np.asarray(comm, np.int64)
    return np.asarray(d2), comm, comm * (data.Z.shape[-1] * 4)  # identity wire: f32 vectors


def setup(cell, rec, control: str | None = None) -> State:
    data = cell.family.build(cell.config, cell.traffic, cell.seed)
    rng = np.random.default_rng(cell.seed)
    st = State(data, cell.traffic, int(rng.integers(0, 2**31)), [], rng, control)
    one_call(st, call_seeds(st, 10**6), rec)  # warm-up: compiles every shape
    return st


def window(st: State, cell, rec) -> Window:
    start = time.perf_counter()
    i = 0
    while time.perf_counter() - start < cell.seconds:
        seeds = call_seeds(st, i)
        st.calls.append((seeds, *one_call(st, seeds, rec)))
        i += 1
    end = time.perf_counter()
    t = st.t
    trials = len(t["etas"]) * t["seeds_per_call"]
    failed = sum(int(not np.all(np.isfinite(c[1]))) for c in st.calls)
    M, n, d = st.data.Z.shape
    refreshes = sum(int(np.sum([counts.svrp_refreshes(c, M, t["rounds"]) for c in call[2][:, -1]]))
                    for call in st.calls)
    flops = (len(st.calls) * counts.svrp_trials_flops(M, n, d, t["prox_steps"], t["rounds"], trials, 0)
             + refreshes * counts.svrp_refresh_flops(M, n, d))
    return Window(start, end, attempted=len(st.calls), failed=failed,
                  end_to_end={"trial_rounds_per_s": len(st.calls) * trials * t["rounds"] / (end - start)},
                  counters={"flops": flops, "trials": trials})


def release(st: State) -> None:
    st.data.problem = None


def check(st: State, cell) -> list:
    """Replay `check_calls` calls drawn from the seed with the reference."""
    k = min(cell.limits["check_calls"], len(st.calls))
    picked = sorted(st.check_rng.choice(len(st.calls), size=k, replace=False))
    lim = cell.limits
    floor_gap, comm_bad, bytes_bad = 0.0, 0, 0
    for i in picked:
        seeds, d2, comm, comm_bytes = st.calls[i]
        want_d2, want_comm, want_bytes = replay(st, seeds, "highest")
        floor_gap = max(floor_gap, ref.floor_gap(d2, want_d2))
        comm_bad += int(np.sum(comm != want_comm))
        bytes_bad += int(np.sum(comm_bytes != want_bytes))
    return [Check("dist_sq_floor_gap", floor_gap, lim["dist_sq_floor_gap"]),
            Check("comm_mismatches", comm_bad, 0), Check("comm_bytes_mismatches", bytes_bad, 0)]
