"""A churning session pool: tenants admitted, stepped and evicted each tick.

A `SessionPool` of `capacity` lanes is kept full in a closed loop.  Each
tick evicts the tenants that have finished (their horizon reached, or their
`stop_eps` met, which freezes them), admits new tenants into the free
lanes, steps every lane `rounds_per_tick` rounds with one dispatch, and
reads the tick's stats back to the host.  Tenant i runs `seeds_per_tenant`
trials at stepsize eta_base * 2^-(i mod eta_levels) for a horizon drawn from
`horizons` by the seed; every `stop_eps_every`-th tenant stops once all its
trials are `stop_decades` decades under their start.

Set-up warms the pool until a tenant of every horizon has been admitted and
one of each kind (horizon reached, stop_eps met) evicted, so the window
builds no executable; the window continues that same pool.

End to end: `tick_p95_ms`, the 95th percentile over the window's ticks of
the time from a tick's start (evictions and admissions included) to its
stats on the host; `tenant_rounds_per_s`, the lane trial-rounds stepped
over the window's wall time.

Correctness: tenants drawn from the seed among those evicted in the window,
with the one that ran longest, are replayed by the plain SVRP reference:
`dist_sq` where each trajectory ends, as in `drivers/sweep.py`, and `comm`
and `comm_bytes` exactly.
"""
from __future__ import annotations

import dataclasses
import time

import jax.numpy as jnp
import numpy as np

from bench.harness import Check, Window
from bench.reference import logistic as ref


@dataclasses.dataclass
class Tenant:
    index: int
    seeds: list
    eta: float
    horizon: int
    stop_eps: float | None
    rounds: int = 0
    id: int | None = None
    result: tuple | None = None  # (dist_sq, comm, comm_bytes) once evicted


@dataclasses.dataclass
class State:
    data: object
    t: dict
    pool: object
    horizon_draws: np.ndarray
    seed_base: int
    check_rng: np.random.Generator
    resident: dict = dataclasses.field(default_factory=dict)  # tenant id -> Tenant
    next_index: int = 0
    evicted: list = dataclasses.field(default_factory=list)  # Tenants evicted in the window
    ticks: list = dataclasses.field(default_factory=list)  # tick latencies, seconds
    lane_trial_rounds: int = 0
    in_window: bool = False
    control: str | None = None


def plan(st: State, i: int, horizon: int | None = None) -> Tenant:
    t = st.t
    B = t["seeds_per_tenant"]
    stops = (i % t["stop_eps_every"]) == t["stop_eps_every"] - 1
    return Tenant(
        index=i, seeds=[(st.seed_base + B * i + j) % 2**32 for j in range(B)],
        eta=t["eta_base"] * 2.0 ** -(i % t["eta_levels"]),
        horizon=horizon or int(st.horizon_draws[i % len(st.horizon_draws)]),
        stop_eps=st.data.d0 * 10.0 ** -t["stop_decades"] if stops else None)


def admit(st: State, ten: Tenant) -> None:
    t, data = st.t, st.data
    ten.id = st.pool.admit(
        t["algo"], data.problem, grid={"eta": ten.eta, "p": t["p"], "smoothness": data.L},
        seeds=ten.seeds, num_steps=ten.horizon, prox_solver=t["prox_solver"],
        prox_steps=t["prox_steps"], x0=jnp.zeros(data.Z.shape[-1], jnp.float32),
        x_star=jnp.asarray(data.x_star), stop_eps=ten.stop_eps)
    st.resident[ten.id] = ten


def finished(st: State, ten: Tenant) -> bool:
    return ten.rounds >= ten.horizon or st.pool.is_frozen(ten.id)


def tick(st: State, rec) -> None:
    n = st.t["rounds_per_tick"]
    t0 = time.perf_counter()
    with rec.span("evict"):
        for tid in [tid for tid, ten in st.resident.items() if finished(st, ten)]:
            ten = st.resident.pop(tid)
            res = st.pool.evict(tid).result()
            ten.result = (res.dist_sq, res.comm, res.comm_bytes)
            if st.in_window:
                st.evicted.append(ten)
    with rec.span("admit"):
        while len(st.resident) < st.t["capacity"]:
            admit(st, plan(st, st.next_index))
            st.next_index += 1
    running = [ten for ten in st.resident.values() if not st.pool.is_frozen(ten.id)]
    with rec.span("step"):
        d2, _ = st.pool.step(n)
    with rec.span("readback"):
        np.asarray(d2)
    if st.in_window:
        st.ticks.append(time.perf_counter() - t0)
        st.lane_trial_rounds += len(running) * st.t["seeds_per_tenant"] * n
    for ten in running:
        ten.rounds += n


def setup(cell, rec, control: str | None = None) -> State:
    from repro.serve import SessionPool

    data = cell.family.build(cell.config, cell.traffic, cell.seed)
    rng = np.random.default_rng(cell.seed)
    t = cell.traffic
    st = State(data, t, SessionPool(capacity=t["capacity"]),
               rng.choice(t["horizons"], size=4096), int(rng.integers(0, 2**31)), rng,
               control=control)
    if control:  # the reference at a lower precision in the program's place
        tenants = [plan(st, i) for i in range(cell.limits["check_tenants"] + 1)]
        for ten in tenants:
            ten.rounds = ten.horizon
        for ten, res in zip(tenants, replay(st, tenants, control)):
            ten.result = res
        st.evicted = tenants
        return st
    # Warm-up tenants: every horizon, longest first (it sizes the key
    # buffer), with and without stop_eps; then tick until both kinds of
    # eviction have happened.
    for h in sorted(t["horizons"], reverse=True):
        for _ in range(t["stop_eps_every"]):
            if len(st.resident) < t["capacity"]:
                admit(st, plan(st, st.next_index, horizon=h))
                st.next_index += 1
    kinds = set()
    for _ in range(4 * max(t["horizons"])):
        if kinds == {"horizon", "stop_eps"}:
            break
        for ten in st.resident.values():
            if finished(st, ten):
                kinds.add("stop_eps" if st.pool.is_frozen(ten.id) else "horizon")
        tick(st, rec)
    tick(st, rec)
    return st


def window(st: State, cell, rec) -> Window:
    st.in_window = True
    start = time.perf_counter()
    while st.control is None and time.perf_counter() - start < cell.seconds:
        tick(st, rec)
    end = time.perf_counter()
    st.in_window = False
    ticks = np.asarray(st.ticks)
    e2e = {"tick_p95_ms": float(np.percentile(ticks, 95) * 1e3) if len(ticks) else float("nan"),
           "tenant_rounds_per_s": st.lane_trial_rounds / (end - start)}
    host = sum(v for k, v in rec.span_s.items() if k in ("evict", "admit", "step"))
    return Window(start, end, attempted=len(ticks), failed=0, end_to_end=e2e,
                  counters={"ticks": len(ticks), "host_s": host, "compiles": rec.compiles})


def release(st: State) -> None:
    st.pool = None
    st.resident = {}
    st.data.problem = None


def replay(st: State, tenants: list, precision: str):
    """The reference's (dist_sq, comm, comm_bytes) per tenant, each cut to
    the rounds that tenant ran."""
    t, data = st.t, st.data
    B = t["seeds_per_tenant"]
    etas = np.repeat([ten.eta for ten in tenants], B)
    seeds = np.concatenate([ten.seeds for ten in tenants])
    d2, comm = ref.svrp(data.Z, data.y, data.lam, data.x_star, data.L, etas, seeds,
                        p=t["p"], rounds=max(t["horizons"]), prox_steps=t["prox_steps"],
                        precision=precision)
    d2, comm = np.asarray(d2), np.asarray(comm, np.int64)
    wire = data.Z.shape[-1] * 4  # identity wire: one float32 vector
    out = []
    for k, ten in enumerate(tenants):
        r = ten.rounds
        rows = slice(k * B, (k + 1) * B)
        out.append((d2[rows, :r], comm[rows, :r], comm[rows, :r] * wire))
    return out


def check(st: State, cell) -> list:
    pool = st.evicted
    k = min(cell.limits["check_tenants"], len(pool))
    picked = set(st.check_rng.choice(len(pool), size=k, replace=False).tolist()) if k else set()
    if pool:
        picked.add(max(range(len(pool)), key=lambda i: pool[i].rounds))
    tenants = [pool[i] for i in sorted(picked)]
    lim = cell.limits
    floor_gap, comm_bad, bytes_bad, shape_bad = 0.0, 0, 0, 0
    replayed = replay(st, tenants, "highest") if tenants else []
    for ten, (want_d2, want_comm, want_bytes) in zip(tenants, replayed):
        d2, comm, comm_bytes = (np.asarray(a) for a in ten.result)
        if d2.shape != want_d2.shape:
            shape_bad += 1
            continue
        floor_gap = max(floor_gap, ref.floor_gap(d2, want_d2))
        comm_bad += int(np.sum(comm != want_comm))
        bytes_bad += int(np.sum(comm_bytes != want_bytes))
    return [Check("dist_sq_floor_gap", floor_gap, lim["dist_sq_floor_gap"]),
            Check("comm_mismatches", comm_bad, 0), Check("comm_bytes_mismatches", bytes_bad, 0),
            Check("length_mismatches", shape_bad, 0), Check("tenants_checked_missing",
                                                             int(not tenants), 0)]
