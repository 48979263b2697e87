"""Federated fine-tuning: `run_batch("deep_svrp")` calls back to back.

Each call runs `rounds_per_call` DeepSVRP rounds of the model over the
clients' fixed token batches through the traffic file's channel, and the
next call starts from the iterate the last one returned, as a fine-tuning
job continues.  A call's seed draws its rounds' anchor-refresh coins, and a
refresh is a full gradient over the clients: so the window's i-th call takes
the seed `WINDOW_SEED + i` in every run, and every run's window does the
same work.  The weights, the data and the first call's seed come from the
run's seed.

Set-up builds the problem and makes the first call (the compile and the
job's first rounds); the window continues that same job.

End to end: `fed_round_s`, the elapsed time of every call that started in
the window over the rounds those calls ran (calls run to completion).

Correctness: the reference replays the first call (set-up's) from the same
weights, data and seed in float32 at HIGHEST precision.  Compared: the mean
client loss after each of its first `LOSS_ROUNDS` rounds (`loss_rel_gap`,
the worst of them), the norm of every leaf's change over the call
(`update_norm_gap`: each layer of a stacked leaf on its own, against the
larger of that leaf's and the median leaf's reference norm; leaves whose
reference gradient is nought but for rounding are left out,
`reference.deep_svrp.moved_leaves`), and `comm` after every round, exactly.
Later rounds' losses are not compared: rounding alone, in the reference
too, moves the job's trajectory, and on some seeds the tenth round's loss
reads as far from the reference's as a lower precision's does.

The driver knows no model: the configuration's family (`bench/families/`,
whose docstring gives what a built family has) supplies the weights, the
data, the program's problem, the reference loss that `reference.deep_svrp`
runs the algorithm over, and the forward FLOPs that `bench/counts.py`'s
round and refresh counts multiply.

A new model cell on this driver is new files and new entries only:

1. `bench/families/<family>.py`: `build`, returning what that docstring
   lists, the forward FLOPs counted there;
2. `bench/reference/<family>.py`: the plain reference and its loss,
   importing nothing of the program;
3. `bench/configs/<config>.json`: the sizes as run, naming the family;
4. `bench/limits/<cell>.json`: `loss_rel_gap` and `update_norm_gap`, set
   from the program's and the control's readings on the chip;
5. `bench/traffic/<traffic>.json`, where the traffic is new;
6. `bench/metrics/<metric>.py` for each per-layer metric the cell adds;
7. in `BENCHMARK.json`: the configuration and the cell, and the cell's name
   in `fed_round_s`'s `workloads` (and in each per-layer metric's it reports);
8. `tests/bench/tiny/<cell>.json`: the cell's size on the CPU, which the
   tests find by name.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from bench import counts
from bench.harness import Check, Window
from bench.reference import deep_svrp

# 15 refreshes in the window's first 6 calls of 10 rounds: the expectation at
# anchor_prob 0.25.
WINDOW_SEED = 1000
# The rounds whose losses are compared: the first three steps of the job.
LOSS_ROUNDS = 3


@dataclasses.dataclass
class State:
    lm: object  # what the configuration's family built (bench/families/)
    t: dict
    seed: int
    first_seed: int
    first_loss: np.ndarray = None  # the first call's reported losses
    first_comm: np.ndarray = None
    first_norms: np.ndarray = None  # its per-leaf change norms
    x: jax.Array = None  # the job's current iterate
    calls: list = dataclasses.field(default_factory=list)  # [(start, end, rounds, comm_final)]
    control: str | None = None


def spec(st: State, x, seed: int):
    from repro.experiments import RunSpec

    t = st.t
    return RunSpec("deep_svrp",
                   grid={"eta": t["eta"], "local_lr": t["local_lr"], "anchor_prob": t["anchor_prob"]},
                   seeds=[seed], x0=x, x_star=x,
                   static={"num_steps": t["rounds_per_call"], "local_steps": t["local_steps"],
                           "channel": t["channel"]})


def one_call(st: State, x, seed: int, rec):
    """(losses (rounds,), comm (rounds,), final iterate) of one call."""
    from repro.experiments import run_batch

    with rec.span("run_batch"):
        res = run_batch(spec(st, x, seed), st.lm.problem)
    with rec.span("readback"):
        loss = np.asarray(res.dist_sq)[0]
    return loss, np.asarray(res.comm)[0], res.x_final[0]


def replay(st: State, precision: str):
    """The reference's (losses, comm, per-leaf change norms, per-leaf norms
    of the first anchor gradient) of the first call."""
    t, lm = st.t, st.lm
    params0 = lm.weights(st.seed)
    loss, comm, params, grad0 = deep_svrp.deep_svrp(
        lm.loss, params0, lm.tokens, lm.labels, eta=t["eta"], local_lr=t["local_lr"],
        local_steps=t["local_steps"], anchor_prob=t["anchor_prob"],
        rounds=t["rounds_per_call"], seed=st.first_seed, precision=precision)
    return (loss, comm, deep_svrp.leaf_change_norms(params, params0)[1],
            deep_svrp.leaf_norms(grad0)[1])


def setup(cell, rec, control: str | None = None) -> State:
    lm = cell.family.build(cell.config, cell.traffic, cell.seed)
    rng = np.random.default_rng(cell.seed)
    st = State(lm, cell.traffic, cell.seed, int(rng.integers(0, 2**31)), control=control)
    if control:  # the reference at a lower precision in the program's place
        st.first_loss, st.first_comm, st.first_norms, _ = replay(st, control)
        st.x = lm.x0
        return st
    x0 = lm.x0
    st.first_loss, st.first_comm, x = one_call(st, x0, st.first_seed, rec)
    st.first_norms = deep_svrp.leaf_change_norms(lm.unravel(x), lm.unravel(x0))[1]
    lm.x0 = None
    del x0
    st.x = x
    return st


def window(st: State, cell, rec) -> Window:
    start = time.perf_counter()
    i = 0
    while st.control is None and time.perf_counter() - start < cell.seconds:
        t0 = time.perf_counter()
        loss, comm, st.x = one_call(st, st.x, WINDOW_SEED + i, rec)
        st.calls.append((t0, time.perf_counter(), len(loss), int(comm[-1]), bool(np.all(np.isfinite(loss)))))
        i += 1
    end = time.perf_counter()
    t, lm = st.t, st.lm
    rounds = sum(c[2] for c in st.calls)
    M, S, Lseq = lm.tokens.shape
    fwd = lm.forward_flops(S, Lseq)
    fl_round = counts.deep_svrp_round_flops(fwd, M, t["local_steps"])
    fl_refresh = counts.deep_svrp_refresh_flops(fwd, M)
    flops = sum(fl_refresh * (1 + counts.deep_svrp_refreshes(c[3], M, c[2])) + fl_round * c[2]
                for c in st.calls)
    elapsed = sum(c[1] - c[0] for c in st.calls)
    return Window(start, end, attempted=len(st.calls), failed=sum(not c[4] for c in st.calls),
                  end_to_end={"fed_round_s": elapsed / rounds if rounds else float("nan")},
                  counters={"flops": flops, "rounds": rounds})


def release(st: State) -> None:
    st.x = None
    st.lm.problem = None


def check(st: State, cell) -> list:
    want_loss, want_comm, want_norms, grad_norms = replay(st, "highest")
    got, want = st.first_loss[:LOSS_ROUNDS], want_loss[:LOSS_ROUNDS]
    loss_gap = float(np.max(np.abs(got - want) / np.abs(want)))
    norm_gap, _ = deep_svrp.norm_gap(st.first_norms, want_norms, deep_svrp.moved_leaves(grad_norms))
    comm_bad = int(np.sum(np.asarray(st.first_comm) != want_comm))
    lim = cell.limits
    return [Check("loss_rel_gap", loss_gap, lim["loss_rel_gap"]),
            Check("update_norm_gap", norm_gap, lim["update_norm_gap"]),
            Check("comm_mismatches", comm_bad, 0)]
