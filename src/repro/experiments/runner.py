"""Batched multi-trial experiment engine.

Every figure/table in the paper averages SVRP/SPPM/Catalyzed-SVRP over many
seeds and sweeps stepsizes/cohorts.  Driving `run_svrp` one trial at a time
from Python recompiles nothing (hyperparameters are traced) but still pays one
device dispatch per scan step per trial and leaves the device idle on these
tiny bandwidth-bound problems.  `run_batch` instead vmaps the pure
`*_scan(problem, x0, x_star, key, hparams)` drivers over a `(B,)` axis of
seeds x hyperparameters and runs the WHOLE sweep as one jitted scan —
compile once, batch every per-step linear solve / gradient across trials.

    from repro.experiments import run_batch

    res = run_batch(
        "svrp", problem,
        grid={"eta": [1e-3, 3e-3, 1e-2], "p": 1 / M},
        seeds=8,
        num_steps=2000,
    )
    res.dist_sq            # (24, 2000) per-trial trajectories
    res.summary()          # median/IQR over the batch axis
    res.trial(5)           # plain RunResult, bitwise-comparable to run_svrp

Design rules enforced by the core refactor this engine relies on:

* all per-trial hyperparameters are traced scalars carried in a NamedTuple
  (`SVRPParams` etc.) — the vmap axis;
* anything that changes trace structure (num_steps, prox-solver choice,
  cohort size) is static config shared by the whole batch;
* per-trial PRNG keys are built with `vmap(jax.random.key)`, so trial
  `(seed=s)` reproduces `run_*(..., key=jax.random.key(s))` exactly.

Substrates (see `repro.core.rounds`, where each algorithm's round body is
defined exactly once): for the rounds-defined algorithms (membership in
`rounds.ROUND_DEFS`) the engine's DEFAULT batched execution is
`rounds.registry_batched_scan` — a batch-level scan with the per-trial
sampling and registry prox solve vmapped inside the round, which makes the
anchor refresh batch-aware (`lax.cond(jnp.any(c))`: the full-gradient
recompute only runs on steps where some trial actually refreshes — the >=1x
caveat-track CI gate rests on this).  Algorithms outside `ROUND_DEFS`
(baselines, composite, catalyzed's non-fused path) run as plain `jax.vmap`
of their sequential `*_scan` over the `(B,)` trial axis.  `fused=True`
switches rounds-defined algos to `rounds.batched_scan`: the same hand-batched
state with the Algorithm-7 local solves routed through the batched Pallas
kernels.  Which algorithms fuse, and which static keys supply their
inner-loop/round counts, is declared on their `AlgoSpec` (`fusable` /
`fused_inner_steps` / `fused_round_steps`).

`shard="data"` lays the `(B,)` trial axis over the local device mesh via
shard_map (one group of trials per device), padding B up to a multiple of the
device count with duplicate trials and masking the pad out of the returned
result — each device runs its own vmapped (or fused-Pallas) block of the
sweep with zero cross-device collectives.

What to run (the `ALGOS` table, `AlgoSpec`, and the shared `RunSpec` all
three entry points consume) lives in `repro.experiments.spec` and is
re-exported here; `run_batch(RunSpec(...), problem)` and the legacy keyword
style resolve through the same `RunSpec.resolve`.  A fourth substrate — the
incremental session layer (`repro.serve.open_session` / `FedSession`), which
steps the SAME round bodies n rounds at a time with device-resident donated
state — is what `stop_eps=` routes through for early stopping.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Mapping, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.channel import wire_vector_bytes
from repro.core.rounds import (
    ROUND_DEFS,
    batched_scan,
    client_sharded_scan,
    fused_oracle_kind,
    registry_batched_scan,
)
from repro.core.types import RunResult
from repro.experiments.grid import trial_labels
from repro.experiments.spec import (  # noqa: F401  (re-exported API)
    ALGOS,
    AlgoSpec,
    ResolvedRun,
    RunSpec,
    _REQUIRED,
    _device_hparams,
    _keys_for,
    as_runspec,
    check_pool_entry,
    check_substrate,
    pool_entry_signature,
    resolve_algo,
)
from repro.utils.shard import shard_map_compat


class BatchResult(NamedTuple):
    """Stacked `RunResult`s for a sweep batch, plus per-trial labels.

    `stopped_round` is populated only by the early-stopping path
    (`run_batch(..., stop_eps=...)` / `FedSession.run_until`): per trial, the
    1-based round at which dist_sq first reached the threshold, or -1 if the
    trial never reached it within the rounds executed.  K is then the number
    of rounds actually run (<= the configured horizon); the trajectories are
    the identical prefix of the full run's.
    """

    dist_sq: jax.Array  # (B, K)
    comm: jax.Array  # (B, K)
    x_final: jax.Array  # (B, d)
    hparams: dict[str, np.ndarray]  # each (B,)
    seeds: np.ndarray  # (B,)
    stopped_round: np.ndarray | None = None  # (B,) — early-stopping path only
    comm_bytes: np.ndarray | None = None  # (B, K) int64 wire-bytes ledger

    @property
    def num_trials(self) -> int:
        return self.dist_sq.shape[0]

    def trial(self, i: int) -> RunResult:
        """Trial i as a plain RunResult (comparable to the sequential driver)."""
        cb = None if self.comm_bytes is None else self.comm_bytes[i]
        return RunResult(self.dist_sq[i], self.comm[i], self.x_final[i], cb)

    def labels(self) -> list[dict[str, float]]:
        return trial_labels(self.hparams, self.seeds)

    def comm_to_accuracy(self, eps: float) -> np.ndarray:
        """(B,) first cumulative-comm count at which dist_sq <= eps (inf if never)."""
        return np.asarray(
            jax.vmap(lambda d, c: RunResult(d, c, c[:0]).comm_to_accuracy(eps))(
                self.dist_sq, self.comm
            )
        )

    def bytes_to_accuracy(self, eps: float) -> np.ndarray:
        """(B,) first cumulative WIRE BYTES at which dist_sq <= eps (inf if
        never) — the bytes-ledger analog of `comm_to_accuracy`."""
        if self.comm_bytes is None:
            raise ValueError(
                "comm_bytes is not populated; run through run_batch/"
                "run_sequential/open_session, which attach the bytes ledger"
            )
        d2 = np.asarray(self.dist_sq)
        by = np.asarray(self.comm_bytes, dtype=np.float64)
        hit = d2 <= eps
        out = np.full(d2.shape[0], np.inf)
        for i in range(d2.shape[0]):
            if hit[i].any():
                out[i] = by[i, int(np.argmax(hit[i]))]
        return out

    def final_at_budget(self, budget: int) -> float:
        """Median over trials of dist_sq at the LAST step with comm <= budget
        (inclusive: a step landing exactly on the budget counts); NaN if no
        trial has any step within budget."""
        comm = np.asarray(self.comm)
        d2 = np.asarray(self.dist_sq)
        finals = [
            d2[i, np.searchsorted(comm[i], budget, side="right") - 1]
            for i in range(comm.shape[0])
            if comm[i, 0] <= budget
        ]
        return float(np.median(finals)) if finals else float("nan")

    def summary(self, q: tuple[float, float] = (25.0, 75.0)) -> dict[str, np.ndarray]:
        """Median/IQR trajectories over the batch axis (the paper's shaded bands)."""
        d2 = np.asarray(self.dist_sq)
        comm = np.asarray(self.comm)
        lo, hi = q
        out = {
            "dist_sq_median": np.median(d2, axis=0),
            "dist_sq_q_lo": np.percentile(d2, lo, axis=0),
            "dist_sq_q_hi": np.percentile(d2, hi, axis=0),
            "comm_median": np.median(comm, axis=0),
        }
        if self.comm_bytes is not None:
            out["comm_bytes_median"] = np.median(
                np.asarray(self.comm_bytes), axis=0
            )
        return out


def ledger_bytes(cfg: Mapping[str, Any], x0: jax.Array, comm) -> np.ndarray:
    """The integer bytes-on-the-wire ledger for a (B, K) (or (K,)) cumulative
    comm trajectory: every counted exchange in the rounds family is one
    d-vector, so bytes = comm x the channel's wire size for that vector.

    Computed HOST-SIDE in int64 by the entry points (run_batch /
    run_sequential / FedSession / FedRoundServer) rather than inside the
    traced scan: an in-trace int32 ledger overflows within a handful of
    rounds at 20m-model payloads (~6e7 wire bytes per vector), and the
    product is exact because the wire size is static per (channel, d, dtype).
    Algorithms without a channel knob price at the identity wire size
    (d x itemsize)."""
    wire = wire_vector_bytes(
        cfg.get("channel"), int(np.prod(x0.shape)), x0.dtype.itemsize
    )
    return np.asarray(comm, dtype=np.int64) * np.int64(wire)


def _one_trial_fn(scan_fn: Callable, static_items: tuple) -> Callable:
    static = dict(static_items)

    def one_trial(problem, x0, x_star, key, hp):
        return scan_fn(problem, x0, x_star, key, hp, **static)

    return one_trial


@functools.lru_cache(maxsize=None)
def _vmapped_trials(scan_fn: Callable, static_items: tuple) -> Callable:
    """The unjitted `(B,)`-vmapped driver — shared by the single-device jit
    path (`_batched_runner`) and the per-device body of the sharded path."""
    return jax.vmap(_one_trial_fn(scan_fn, static_items), in_axes=(None, None, None, 0, 0))


@functools.lru_cache(maxsize=None)
def _registry_body(algo: str, static_items: tuple) -> Callable:
    """The rounds-defined algorithms' default batched driver: the shared round
    definition hand-batched with its registry prox solver vmapped per trial
    (`rounds.registry_batched_scan`).  Numerically identical to vmapping the
    whole per-trial scan, but the anchor refresh is BATCH-AWARE — the
    full-gradient recompute only runs on steps where some trial refreshes,
    instead of for every trial every step (the old ~0.5x logistic caveat)."""
    cfg = dict(static_items)

    def run(problem, x0, x_star, keys, hp):
        return registry_batched_scan(algo, problem, x0, x_star, keys, hp, **cfg)

    return run


@functools.lru_cache(maxsize=None)
def _registry_runner(algo: str, static_items: tuple) -> Callable:
    return jax.jit(_registry_body(algo, static_items))


@functools.lru_cache(maxsize=None)
def _batched_runner(scan_fn: Callable, static_items: tuple) -> Callable:
    """One jitted vmapped driver per (scan_fn, static-config) pair.

    The returned callable takes `(problem, x0, x_star, keys, hp)` with a
    leading `(B,)` axis on `keys` and every `hp` leaf; jax's jit cache then
    keys on shapes/dtypes, so repeated sweeps of the same size compile once.
    """
    return jax.jit(_vmapped_trials(scan_fn, static_items))


@functools.lru_cache(maxsize=None)
def _single_runner(scan_fn: Callable, static_items: tuple) -> Callable:
    """The per-trial (un-vmapped) jitted driver `run_sequential` loops over."""
    return jax.jit(_one_trial_fn(scan_fn, static_items))


def run_batch(
    algo: str | RunSpec,
    problem,
    grid: Mapping[str, Any] | None = None,
    seeds: int | Sequence[int] = 1,
    *,
    x0: jax.Array | None = None,
    x_star: jax.Array | None = None,
    stepsize: str | None = None,
    target_eps: float = 1e-6,
    theory_constants=None,
    fused: bool = False,
    interpret: bool | None = None,
    shard: str | None = None,
    devices: Sequence[Any] | None = None,
    stop_eps: float | None = None,
    **static,
) -> BatchResult:
    """Run `seeds x grid` trials of `algo` on `problem` in ONE jitted vmap.

    `grid` maps hparam names (fields of the algo's params NamedTuple, e.g.
    eta/p for "svrp") to scalars or sequences; sequences are crossed
    cartesian-product style and the whole thing is crossed with the seed axis
    (seed-major).  Remaining kwargs are the algo's static config (num_steps,
    prox_solver, ...), shared by every trial.

    `stepsize="theory"` resolves the grid from the paper's theorem table
    (`repro.core.theory.theory_grid`): measured mu/delta/sigma_*^2 feed the
    Theorem-1/2/3 stepsizes (`target_eps` sets the accuracy the Theorem-1
    rule is calibrated to); explicit grid entries override the resolved ones,
    and `theory_constants` (a `ProblemConstants`) skips the per-call
    measurement when the caller already holds one.

    `fused=True` (fusable algos running Algorithm 7: svrp/sppm/
    svrp_minibatch/catalyzed_svrp with prox_solver="gd", and deep_svrp
    always) switches to the fused substrate (`rounds.batched_scan`):
    hand-batched `(B, d)` state, local solves through the batched Pallas
    kernels, batch-aware anchor refresh.  The kernels are compiled on a TPU
    and run in the Pallas interpreter on every other backend
    (`kernels.backend.resolve_interpret`); `interpret` (fused-only) overrides
    that rule and is rarely needed.

    `shard="data"` additionally lays the `(B,)` trial axis over the device
    mesh (`devices` defaults to all local devices): B is padded up to a
    multiple of the device count with duplicates of the last trial, each
    device runs its own contiguous block of trials as a fully local vmapped
    (or fused-Pallas) sweep — no cross-device collectives — and the pad is
    masked out of the returned BatchResult, so `summary()` and per-trial
    access see exactly the requested B trials.

    `stop_eps` enables early stopping: the sweep is executed on the
    incremental session substrate (`repro.serve`) — the same jitted round
    bodies, stepped chunk-at-a-time — and halts once EVERY trial has reached
    `dist_sq <= stop_eps` (or the configured horizon runs out).  The returned
    trajectories are the identical prefix of the full run's, and
    `BatchResult.stopped_round` records each trial's first-hit round.

    Per-trial outputs match the sequential `run_<algo>` driver for the same
    (seed, hparams) to float tolerance — see tests/test_experiments.py and
    tests/test_sharded.py.
    """
    spec_ = as_runspec(algo, grid=grid, seeds=seeds, x0=x0, x_star=x_star,
                       stepsize=stepsize, target_eps=target_eps,
                       theory_constants=theory_constants, static=static)
    rr = spec_.resolve(problem)
    algo, spec = rr.algo, rr.aspec
    hparams, seed_arr, cfg, x0, x_star = rr.hparams, rr.seeds, rr.cfg, rr.x0, rr.x_star

    if stop_eps is not None:
        if fused or shard is not None or interpret is not None or devices is not None:
            raise ValueError(
                "stop_eps runs on the incremental session substrate; it cannot "
                "be combined with fused=, interpret=, shard= or devices="
            )
        import dataclasses

        from repro.serve import open_session  # lazy: serve imports this module

        sess = open_session(dataclasses.replace(spec_, substrate="batched"), problem)
        return sess.run_until(stop_eps)

    hp = spec.params_cls(**_device_hparams(hparams))
    keys = _keys_for(seed_arr)

    if shard not in (None, "data", "clients"):
        raise ValueError(
            f"unknown shard mode {shard!r}; supported: 'data', 'clients'"
        )
    if devices is not None and shard is None:
        raise ValueError(
            "devices= only applies with shard='data'/'clients' (did you forget it?)"
        )
    if shard == "clients":
        from repro.problems.client_shard import check_client_shardable

        check_client_shardable(problem)
        if fused:
            if algo not in ROUND_DEFS:
                raise ValueError(
                    "fused=True with shard='clients' supports only the "
                    f"rounds-defined algorithms {sorted(ROUND_DEFS)}; run "
                    f"{algo!r} with fused=False"
                )
            if not (spec.fusable and cfg.get("prox_solver", "gd") == "gd"):
                raise ValueError(
                    f"{algo}: fused=True requires a fusable algo with prox_solver='gd'"
                )
            fused_oracle_kind(problem)
        elif interpret is not None:
            raise ValueError("interpret only applies to the fused=True Pallas path")
        res = _run_client_sharded(
            algo, tuple(sorted(cfg.items())), problem, x0, x_star, keys, hp,
            devices=devices, fused=fused, interpret=interpret,
        )
        return BatchResult(
            dist_sq=res.dist_sq,
            comm=res.comm,
            x_final=res.x_final,
            hparams=hparams,
            seeds=seed_arr,
            comm_bytes=ledger_bytes(cfg, x0, res.comm),
        )
    if fused:
        # Registry-prox algos fuse only their "gd" path; deep_svrp's local
        # solver IS Algorithm 7, so it has no prox_solver switch to check.
        if not (spec.fusable and cfg.get("prox_solver", "gd") == "gd"):
            raise ValueError(
                f"{algo}: fused=True requires a fusable algo with prox_solver='gd'"
            )
        fused_oracle_kind(problem)  # clear trace-time error for unsupported problems
        static_items = tuple(sorted(cfg.items()))
        body = _fused_body(algo, static_items, interpret)
        runner = _fused_runner(algo, static_items, interpret)
    else:
        if interpret is not None:
            raise ValueError("interpret only applies to the fused=True Pallas path")
        if algo in ROUND_DEFS:
            static_items = tuple(sorted(cfg.items()))
            body = _registry_body(algo, static_items)
            runner = _registry_runner(algo, static_items)
        else:
            body = _vmapped_trials(spec.scan_fn, tuple(sorted(cfg.items())))
            runner = _batched_runner(spec.scan_fn, tuple(sorted(cfg.items())))

    if shard is None:
        res = runner(problem, x0, x_star, keys, hp)
    else:
        res = _run_sharded(body, problem, x0, x_star, keys, hp, devices)

    return BatchResult(
        dist_sq=res.dist_sq,
        comm=res.comm,
        x_final=res.x_final,
        hparams=hparams,
        seeds=seed_arr,
        comm_bytes=ledger_bytes(cfg, x0, res.comm),
    )


def run_sequential(
    algo: str | RunSpec,
    problem,
    grid: Mapping[str, Any] | None = None,
    seeds: int | Sequence[int] = 1,
    *,
    x0: jax.Array | None = None,
    x_star: jax.Array | None = None,
    stepsize: str | None = None,
    target_eps: float = 1e-6,
    theory_constants=None,
    **static,
) -> BatchResult:
    """The per-trial Python loop `run_batch` replaces.

    Same trial set and per-trial numerics, one jitted call PER TRIAL — kept as
    the equivalence oracle for tests and the baseline for
    benchmarks/sweep_bench.py.  Accepts the same `RunSpec` as run_batch and
    `open_session` (or the legacy keyword style via the `as_runspec` shim).
    """
    spec_ = as_runspec(algo, grid=grid, seeds=seeds, x0=x0, x_star=x_star,
                       stepsize=stepsize, target_eps=target_eps,
                       theory_constants=theory_constants, static=static)
    rr = spec_.resolve(problem)
    algo, spec = rr.algo, rr.aspec
    hparams, seed_arr, cfg, x0, x_star = rr.hparams, rr.seeds, rr.cfg, rr.x0, rr.x_star

    single = _single_runner(spec.scan_fn, tuple(sorted(cfg.items())))
    dev_hp = _device_hparams(hparams)
    results = []
    for i in range(seed_arr.shape[0]):
        hp = spec.params_cls(**{k: v[i] for k, v in dev_hp.items()})
        results.append(single(problem, x0, x_star, jax.random.key(int(seed_arr[i])), hp))
    comm = jnp.stack([r.comm for r in results])
    return BatchResult(
        dist_sq=jnp.stack([r.dist_sq for r in results]),
        comm=comm,
        x_final=jnp.stack([r.x_final for r in results]),
        hparams=hparams,
        seeds=seed_arr,
        comm_bytes=ledger_bytes(cfg, x0, comm),
    )


# ------------------------------------------------------------- sharded sweeps
def _pad_rows(a: jax.Array, n_total: int) -> jax.Array:
    """Pad the leading axis to n_total by repeating the last row (dup trials)."""
    pad = n_total - a.shape[0]
    if pad == 0:
        return a
    return jnp.concatenate([a, jnp.repeat(a[-1:], pad, axis=0)], axis=0)


@functools.lru_cache(maxsize=None)
def _sharded_runner(body: Callable, devices: tuple) -> Callable:
    """shard_map `body` (a `(B,)`-vmapped or hand-batched sweep driver) over a
    1-D ('data',) mesh of `devices`, one contiguous block of trials per device.

    The body runs fully locally on each device's trial block — the lowered
    module contains ZERO cross-device collectives; PRNG keys travel as uint32
    key-data (`jax.random.key_data`).  Cached per (body, devices) so repeated sweeps of the same shape
    compile once, mirroring `_batched_runner`.
    """
    from repro.launch.mesh import make_sweep_mesh

    mesh = make_sweep_mesh(devices)

    def local_block(problem, x0, x_star, key_data, hp):
        keys = jax.random.wrap_key_data(key_data)
        return body(problem, x0, x_star, keys, hp)

    smapped = shard_map_compat(
        local_block,
        mesh=mesh,
        in_specs=(P(), P(), P(), P("data"), P("data")),
        out_specs=P("data"),
        manual_axes=("data",),
    )
    return jax.jit(smapped)


def _run_sharded(body, problem, x0, x_star, keys, hp, devices) -> RunResult:
    devs = tuple(jax.devices()) if devices is None else tuple(devices)
    n = len(devs)
    B = keys.shape[0]
    B_pad = B + (-B) % n
    key_data = _pad_rows(jax.random.key_data(keys), B_pad)
    hp_pad = jax.tree.map(lambda a: _pad_rows(jnp.asarray(a), B_pad), hp)
    res = _sharded_runner(body, devs)(problem, x0, x_star, key_data, hp_pad)
    # Mask the pad back out: callers (summary/trial/labels) only ever see the
    # B requested trials.
    return jax.tree.map(lambda a: a[:B], res)


# ------------------------------------------------------ client-sharded sweeps
#
# shard="clients": the CLIENT axis over the mesh instead of the trial axis
# (docs/SCALING.md).  Rounds-defined algorithms run `ClientShardedOps` — the
# owner-masked prox assembly with ONE psum per round and one per anchor
# refresh event (HLO-asserted in tests/test_client_sharded.py); algorithms
# outside ROUND_DEFS run their UNCHANGED sequential drivers against the
# per-oracle `ClientShardedProblem` view (correct, but one collective per
# oracle call — the documented non-scaling fallback).  Keys and hparams are
# replicated (every device plays all trials over its resident clients), so
# PRNG draws are device-identical and comm parity stays integer-exact.


@functools.lru_cache(maxsize=None)
def _client_body(
    algo: str, static_items: tuple, num_clients: int, fused: bool,
    interpret: bool | None,
) -> Callable:
    """The per-device body of the client-sharded path: `(local_problem,
    valid, x0, x_star, keys, hp) -> RunResult`, already inside shard_map."""
    cfg = dict(static_items)
    if algo in ROUND_DEFS:
        if fused:
            spec = ALGOS[algo]
            inner_steps = cfg[spec.fused_inner_steps]
            num_steps = cfg[spec.fused_round_steps]
            extra = {k: cfg[k] for k in ("batch_clients", "channel") if k in cfg}

            def run(local_problem, valid, x0, x_star, keys, hp):
                return client_sharded_scan(
                    algo, local_problem, x0, x_star, keys, hp,
                    axis="clients", num_clients=num_clients, valid=valid,
                    num_steps=num_steps, fused=True, inner_steps=inner_steps,
                    interpret=interpret, **extra,
                )

            return run

        def run(local_problem, valid, x0, x_star, keys, hp):
            return client_sharded_scan(
                algo, local_problem, x0, x_star, keys, hp,
                axis="clients", num_clients=num_clients, valid=valid, **cfg,
            )

        return run

    from repro.problems.client_shard import ClientShardedProblem

    one = _one_trial_fn(ALGOS[algo].scan_fn, static_items)

    def run(local_problem, valid, x0, x_star, keys, hp):
        view = ClientShardedProblem(local_problem, valid, "clients", num_clients)
        return jax.vmap(lambda k, h: one(view, x0, x_star, k, h))(keys, hp)

    return run


@functools.lru_cache(maxsize=None)
def _client_runner(body: Callable, devices: tuple, treedef) -> Callable:
    """shard_map `body` over a 1-D ('clients',) mesh: every client-major
    problem leaf is sharded into contiguous blocks; x0/x_star/keys/hparams
    are replicated; outputs are replicated (any device's copy is returned).
    Cached per (body, devices, problem-structure) like `_sharded_runner`."""
    from repro.launch.mesh import make_client_mesh

    mesh = make_client_mesh(devices)
    prob_specs = jax.tree.unflatten(
        treedef, [P("clients")] * treedef.num_leaves
    )

    def local_block(problem, valid, x0, x_star, key_data, hp):
        keys = jax.random.wrap_key_data(key_data)
        return body(problem, valid, x0, x_star, keys, hp)

    smapped = shard_map_compat(
        local_block,
        mesh=mesh,
        in_specs=(prob_specs, P("clients"), P(), P(), P(), P()),
        out_specs=P(),
        manual_axes=("clients",),
    )
    return jax.jit(smapped)


def _run_client_sharded(
    algo, static_items, problem, x0, x_star, keys, hp, *,
    devices, fused, interpret,
) -> RunResult:
    from repro.problems.client_shard import pad_clients

    devs = tuple(jax.devices()) if devices is None else tuple(devices)
    M = problem.num_clients
    padded = pad_clients(problem, M + (-M) % len(devs))
    valid = jnp.arange(padded.num_clients) < M
    body = _client_body(algo, static_items, M, fused, interpret)
    runner = _client_runner(body, devs, jax.tree.structure(padded))
    return runner(padded, valid, x0, x_star, jax.random.key_data(keys), hp)


# -------------------------------------------------------- fused substrate path
#
# The hand-written per-algorithm fused step bodies that used to live here
# (_svrp_step_fused / _sppm_step_fused / _deep_svrp_step_fused) are gone:
# every fused algo now executes its ONE shared round definition
# (`repro.core.rounds.ROUND_DEFS`) on the fused substrate via
# `rounds.batched_scan` — per-trial sampling vmapped (bit-identical key usage
# to the sequential drivers), Algorithm-7 local solves through the batched
# Pallas kernels, anchor refresh batch-aware.  This driver only resolves the
# AlgoSpec's static config into batched_scan's arguments and caches the
# jitted/shard-mappable callables.


@functools.lru_cache(maxsize=None)
def _fused_body(algo: str, static_items: tuple, interpret: bool | None) -> Callable:
    """The unjitted fused-substrate driver (jitted by `_fused_runner`;
    shard-mapped raw by the sharded path so each device runs its own fused
    block).  `static_items` is the algo's full sorted static config — the
    AlgoSpec's `fused_inner_steps` names which entry feeds the Algorithm-7
    inner loop, so no per-algo special-casing here."""
    spec = ALGOS[algo]
    cfg = dict(static_items)
    inner_steps = cfg[spec.fused_inner_steps]
    num_steps = cfg[spec.fused_round_steps]
    extra = {k: cfg[k] for k in ("batch_clients", "num_outer", "channel") if k in cfg}

    def run(problem, x0, x_star, keys, hp):
        return batched_scan(
            algo, problem, x0, x_star, keys, hp,
            num_steps=num_steps, inner_steps=inner_steps, interpret=interpret,
            **extra,
        )

    return run


@functools.lru_cache(maxsize=None)
def _fused_runner(algo: str, static_items: tuple, interpret: bool | None) -> Callable:
    return jax.jit(_fused_body(algo, static_items, interpret))
