"""Incremental round engine: `open_session` / `FedSession`.

The batch engine (`repro.experiments.run_batch`) executes a whole sweep as
one jitted `lax.scan`.  This module exposes the SAME round bodies — every
algorithm's single-round `StepDef` (`core.rounds.registry_step_def` for the
rounds-defined algorithms, the per-module `*_step_def` builders for the
rest) — as an *incremental* API:

    from repro.serve import open_session

    session = open_session("svrp", problem,
                           grid={"eta": 1e-2, "p": 0.1}, seeds=8,
                           num_steps=2000)
    session.step()            # one round, all trials
    session.step(n=50)        # fifty more, one jitted chunk
    res = session.run_until(eps=1e-8)   # early stopping -> BatchResult

Semantics are scan-equivalence by construction: `k` incremental rounds
produce the first `k` columns of `run_batch`'s trajectories (same PRNG keys,
same round bodies, same substrate) — `run_batch` is now just "scan over the
round body the session steps".  Three substrates (docs/ARCHITECTURE.md):

* ``substrate="batched"`` (default): ONE device-resident state for all B
  trials, stepped by the same batch-aware registry path run_batch uses
  (rounds algos) or a vmapped per-trial step (everything else).
* ``substrate="sequential"``: one state per trial, stepped by the per-trial
  round body — the run_sequential oracle, steppable.
* ``substrate="clients"``: the client-axis-sharded substrate
  (docs/SCALING.md) — the problem's client blocks live sharded over a 1-D
  device mesh and each chunk is one shard_mapped dispatch; trial state stays
  replicated so `step()`/`x()`/`result()` behave identically.

State stays on device between `step()` calls and is donated back to each
chunk, so incremental stepping costs one dispatch per chunk, not per round.  The PRNG schedule is the one place
incrementality needs care: `jax.random.split(key, n)` is NOT prefix-stable
in `n`, so the session materializes the FULL key schedule for the configured
horizon at open time and refuses to step past it.

The streaming simulation server built on the same round bodies lives in
`repro.serve.server`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.baselines import (
    acc_extragradient_step_def,
    dane_step_def,
    scaffold_step_def,
    sgd_step_def,
    svrg_step_def,
)
from repro.core.catalyst import catalyzed_step_def
from repro.core.composite import composite_step_def
from repro.core.rounds import ROUND_DEFS, client_sharded_step_def, registry_step_def
from repro.core.types import StepDef
from repro.experiments.runner import BatchResult, ledger_bytes
from repro.experiments.spec import (
    RunSpec,
    _device_hparams,
    as_runspec,
    check_substrate,
    horizon_rounds,
)

# Static-config keys that parameterize the registry round binding (subset
# present per algo: prox trio for the registry-prox algos, cohort size for
# minibatch, local-loop length for deep_svrp, comm channel for all of them).
_REGISTRY_BINDING = (
    "prox_solver", "prox_steps", "prox_tol", "batch_clients", "local_steps",
    "channel",
)

# The chunk fns donate their state argument (position 4; 5 for the
# client-sharded chunk, which takes the padded problem and valid mask first):
# every backend of the installed jax honours donation, CPU included, so the
# CPU suite runs the same buffer lifecycle as the chip.  A state handed to a
# chunk must therefore own its buffers (no leaf shared with another leaf or
# with x0) and is never read again after the call.

# Post-round state dtype signatures, keyed on the full config+shape signature
# (see FedSession._canonicalize).
_CANONICAL_DTYPES: dict = {}


def trial_step_def(algo: str, problem, x0, x_star, hp, cfg: Mapping[str, Any]) -> StepDef:
    """The per-trial (scalar-hparam) StepDef for ANY `ALGOS` entry.

    Safe to call inside a trace with traced `hp` leaves — every builder is a
    cheap closure construction."""
    if algo in ROUND_DEFS:
        binding = {k: cfg[k] for k in _REGISTRY_BINDING if k in cfg}
        return registry_step_def(algo, problem, x0, x_star, hp, batched=False, **binding)
    if algo == "catalyzed_svrp":
        return catalyzed_step_def(
            problem, x0, x_star, hp,
            num_outer=cfg["num_outer"], inner_steps=cfg["inner_steps"],
            prox_solver=cfg["prox_solver"], prox_steps=cfg["prox_steps"],
            prox_tol=cfg["prox_tol"], channel=cfg.get("channel"),
        )
    if algo == "sgd":
        return sgd_step_def(problem, x0, x_star, hp)
    if algo == "svrg":
        return svrg_step_def(problem, x0, x_star, hp)
    if algo == "scaffold":
        return scaffold_step_def(problem, x0, x_star, hp, local_steps=cfg["local_steps"])
    if algo == "dane":
        return dane_step_def(problem, x0, x_star, hp, surrogate_client=cfg["surrogate_client"])
    if algo == "acc_extragradient":
        return acc_extragradient_step_def(
            problem, x0, x_star, hp, surrogate_client=cfg["surrogate_client"]
        )
    if algo == "composite":
        return composite_step_def(
            problem, x0, x_star, hp, prox_R=cfg["prox_R"], prox_steps=cfg["prox_steps"]
        )
    raise KeyError(f"no incremental step definition for algo {algo!r}")


def _key_schedule(algo: str, cfg: Mapping[str, Any], keys: jax.Array) -> jax.Array:
    """(B, horizon) per-trial key schedule, identical to what the scan
    substrates consume (trial-major `split`, or Catalyst's per-stage splits)."""
    horizon = horizon_rounds(cfg)
    if algo == "catalyzed_svrp":
        num_outer, inner_steps = cfg["num_outer"], cfg["inner_steps"]

        def per_trial(k):
            stage_keys = jax.random.split(k, num_outer)
            per_stage = jax.vmap(lambda s: jax.random.split(s, inner_steps))(stage_keys)
            return per_stage.reshape(horizon)

    else:

        def per_trial(k):
            return jax.random.split(k, horizon)

    return jax.vmap(per_trial)(keys)


@functools.lru_cache(maxsize=None)
def _schedule_fn(algo: str, static_items: tuple):
    """Jitted seeds -> (B, horizon) key schedule.

    The schedule is recomputed at every `open_session`; tracing the nested
    vmaps eagerly costs several ms per open (it dominates open time for the
    serving open-step-close pattern), so the whole pipeline is one cached jit
    per (algo, config)."""
    cfg = dict(static_items)

    def schedule(seeds):
        keys = jax.vmap(jax.random.key)(seeds)
        return _key_schedule(algo, cfg, keys)

    return jax.jit(schedule)


@functools.lru_cache(maxsize=None)
def _seq_chunk_fn(algo: str, static_items: tuple):
    cfg = dict(static_items)

    def chunk(problem, x0, x_star, hp, state, keys):
        sd = trial_step_def(algo, problem, x0, x_star, hp, cfg)
        return jax.lax.scan(sd.step, state, keys)

    return jax.jit(chunk, donate_argnums=4)


def batched_scan_body(algo: str, static_items: tuple):
    """The batched substrate's n-round scan body, shared by the single-session
    chunk (`_batched_chunk_fn`) and the pool-axis binding
    (`core.rounds.registry_pool_scan` / `repro.serve.pool.SessionPool`):

        scan_chunk(problem, x0, x_star, hp, state, keys) -> (state, (d2, comm))

    with `keys` in the registry scan's `(n, B)` layout and outputs `(n, B)`.
    The StepDef is constructed INSIDE the caller's trace but OUTSIDE the scan,
    so per-binding setup (e.g. `solver.prepare`'s eigendecomposition) is
    hoisted once per chunk, never per round."""
    cfg = dict(static_items)
    if algo in ROUND_DEFS:
        binding = {k: cfg[k] for k in _REGISTRY_BINDING if k in cfg}

        def scan_chunk(problem, x0, x_star, hp, state, keys):
            # keys: (n, B) — the registry scan's key layout; num_trials is
            # concrete inside the trace.
            sd = registry_step_def(
                algo, problem, x0, x_star, hp,
                batched=True, num_trials=keys.shape[1], **binding,
            )
            return jax.lax.scan(sd.step, state, keys)

    else:

        def scan_chunk(problem, x0, x_star, hp, state, keys):
            def one(h, s, k):
                return trial_step_def(algo, problem, x0, x_star, h, cfg).step(s, k)

            vstep = jax.vmap(one)
            return jax.lax.scan(lambda s, krow: vstep(hp, s, krow), state, keys)

    return scan_chunk


@functools.lru_cache(maxsize=None)
def _batched_chunk_fn(algo: str, static_items: tuple):
    scan_chunk = batched_scan_body(algo, static_items)

    def chunk(problem, x0, x_star, hp, state, keys_bn):
        # Keys arrive (B, n) (the session's storage layout) and outputs leave
        # (B, n): both transposes happen INSIDE the jit, so a step() chunk is
        # a single dispatch with no host-side relayout ops.
        fin, (d2, comm) = scan_chunk(
            problem, x0, x_star, hp, state, jnp.swapaxes(keys_bn, 0, 1)
        )
        return fin, (jnp.swapaxes(d2, 0, 1), jnp.swapaxes(comm, 0, 1))

    return jax.jit(chunk, donate_argnums=4)


@functools.lru_cache(maxsize=None)
def _seq_init_fn(algo: str, static_items: tuple):
    cfg = dict(static_items)

    def init(problem, x0, x_star, hp):
        return trial_step_def(algo, problem, x0, x_star, hp, cfg).init()

    return jax.jit(init)


@functools.lru_cache(maxsize=None)
def _batched_init_fn(algo: str, static_items: tuple, num_trials: int):
    cfg = dict(static_items)
    if algo in ROUND_DEFS:
        binding = {k: cfg[k] for k in _REGISTRY_BINDING if k in cfg}

        def init(problem, x0, x_star, hp):
            sd = registry_step_def(
                algo, problem, x0, x_star, hp,
                batched=True, num_trials=num_trials, **binding,
            )
            return sd.init()

    else:

        def init(problem, x0, x_star, hp):
            return jax.vmap(
                lambda h: trial_step_def(algo, problem, x0, x_star, h, cfg).init()
            )(hp)

    return jax.jit(init)


@functools.lru_cache(maxsize=None)
def _seq_final_fn(algo: str, static_items: tuple):
    cfg = dict(static_items)

    def final(problem, x0, x_star, hp, state):
        return trial_step_def(algo, problem, x0, x_star, hp, cfg).final(state)

    return jax.jit(final)


@functools.lru_cache(maxsize=None)
def _batched_final_fn(algo: str, static_items: tuple, num_trials: int):
    cfg = dict(static_items)
    if algo in ROUND_DEFS:
        binding = {k: cfg[k] for k in _REGISTRY_BINDING if k in cfg}

        def final(problem, x0, x_star, hp, state):
            sd = registry_step_def(
                algo, problem, x0, x_star, hp,
                batched=True, num_trials=num_trials, **binding,
            )
            return sd.final(state)

    else:

        def final(problem, x0, x_star, hp, state):
            return jax.vmap(
                lambda h, s: trial_step_def(algo, problem, x0, x_star, h, cfg).final(s)
            )(hp, state)

    return jax.jit(final)


# --------------------------------------------------- client-sharded substrate
# The session analogue of runner._run_client_sharded (docs/SCALING.md): the
# padded problem's client-major leaves live sharded over a 1-D ('clients',)
# mesh; x0/x_star/hparams/keys/state stay replicated, so every chunk is one
# shard_mapped dispatch whose outputs are device-identical.  Keys cross the
# shard_map boundary as raw uint32 (`jax.random.key_data`) because typed PRNG
# keys cannot be partitioned arguments.


def _client_shard_map(fn, treedef, n_state_specs: int):
    """shard_map `fn(local_problem, valid, *replicated)` over all devices:
    problem leaves and the valid mask are split on 'clients', everything else
    (x0, x_star, hparams, state, keys) is replicated in and out."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_client_mesh
    from repro.utils.shard import shard_map_compat

    mesh = make_client_mesh()
    prob_specs = jax.tree.unflatten(treedef, [P("clients")] * treedef.num_leaves)
    return shard_map_compat(
        fn,
        mesh=mesh,
        in_specs=(prob_specs, P("clients")) + (P(),) * n_state_specs,
        out_specs=P(),
        manual_axes=("clients",),
    )


@functools.lru_cache(maxsize=None)
def _client_chunk_fn(algo: str, static_items: tuple, num_clients: int, treedef):
    cfg = dict(static_items)
    if algo in ROUND_DEFS:
        binding = {k: cfg[k] for k in _REGISTRY_BINDING if k in cfg}

        def scan_chunk(local_problem, valid, x0, x_star, hp, state, keys):
            sd = client_sharded_step_def(
                algo, local_problem, x0, x_star, hp,
                axis="clients", num_clients=num_clients, valid=valid,
                num_trials=keys.shape[1], **binding,
            )
            return jax.lax.scan(sd.step, state, keys)

    else:

        def scan_chunk(local_problem, valid, x0, x_star, hp, state, keys):
            from repro.problems.client_shard import ClientShardedProblem

            view = ClientShardedProblem(local_problem, valid, "clients", num_clients)

            def one(h, s, k):
                return trial_step_def(algo, view, x0, x_star, h, cfg).step(s, k)

            vstep = jax.vmap(one)
            return jax.lax.scan(lambda s, krow: vstep(hp, s, krow), state, keys)

    def local_block(local_problem, valid, x0, x_star, hp, state, raw_bn):
        keys_bn = jax.random.wrap_key_data(raw_bn)
        fin, (d2, comm) = scan_chunk(
            local_problem, valid, x0, x_star, hp, state,
            jnp.swapaxes(keys_bn, 0, 1),
        )
        return fin, (jnp.swapaxes(d2, 0, 1), jnp.swapaxes(comm, 0, 1))

    mapped = _client_shard_map(local_block, treedef, n_state_specs=5)
    return jax.jit(mapped, donate_argnums=5)


@functools.lru_cache(maxsize=None)
def _client_init_fn(algo: str, static_items: tuple, num_trials: int,
                    num_clients: int, treedef):
    cfg = dict(static_items)
    if algo in ROUND_DEFS:
        binding = {k: cfg[k] for k in _REGISTRY_BINDING if k in cfg}

        def init(local_problem, valid, x0, x_star, hp):
            sd = client_sharded_step_def(
                algo, local_problem, x0, x_star, hp,
                axis="clients", num_clients=num_clients, valid=valid,
                num_trials=num_trials, **binding,
            )
            return sd.init()

    else:

        def init(local_problem, valid, x0, x_star, hp):
            from repro.problems.client_shard import ClientShardedProblem

            view = ClientShardedProblem(local_problem, valid, "clients", num_clients)
            return jax.vmap(
                lambda h: trial_step_def(algo, view, x0, x_star, h, cfg).init()
            )(hp)

    return jax.jit(_client_shard_map(init, treedef, n_state_specs=3))


@functools.lru_cache(maxsize=None)
def _client_final_fn(algo: str, static_items: tuple, num_trials: int,
                     num_clients: int, treedef):
    cfg = dict(static_items)
    if algo in ROUND_DEFS:
        binding = {k: cfg[k] for k in _REGISTRY_BINDING if k in cfg}

        def final(local_problem, valid, x0, x_star, hp, state):
            sd = client_sharded_step_def(
                algo, local_problem, x0, x_star, hp,
                axis="clients", num_clients=num_clients, valid=valid,
                num_trials=num_trials, **binding,
            )
            return sd.final(state)

    else:

        def final(local_problem, valid, x0, x_star, hp, state):
            from repro.problems.client_shard import ClientShardedProblem

            view = ClientShardedProblem(local_problem, valid, "clients", num_clients)
            return jax.vmap(
                lambda h, s: trial_step_def(algo, view, x0, x_star, h, cfg).final(s)
            )(hp, state)

    return jax.jit(_client_shard_map(final, treedef, n_state_specs=4))


class FedSession:
    """A sweep held open: device-resident state, stepped n rounds at a time.

    Construct via `open_session`.  All trials advance together; `step(n)`
    returns the `(B, n)` dist-sq / comm block for the rounds just run, and the
    session accumulates the full trajectory so `result()` yields the same
    `BatchResult` a `run_batch` of the rounds-so-far would."""

    def __init__(self, spec: RunSpec, problem) -> None:
        rr = spec.resolve(problem)
        substrate = check_substrate(spec.substrate or "batched")
        self._spec = spec
        self._problem = problem
        self._substrate = substrate
        self._algo = rr.algo
        self._cfg = rr.cfg
        self._static_items = tuple(sorted(rr.cfg.items()))
        self._hparams, self._seeds = rr.hparams, rr.seeds
        self._x0, self._x_star = rr.x0, rr.x_star
        self._hp = rr.aspec.params_cls(**_device_hparams(rr.hparams))
        self._keys = _schedule_fn(rr.algo, self._static_items)(
            jnp.asarray(rr.seeds, dtype=jnp.uint32)
        )  # (B, horizon); trial s's row reproduces jax.random.key(s)'s splits
        self._horizon = horizon_rounds(rr.cfg)
        self._B = int(rr.seeds.shape[0])
        self._t = 0
        self._d2: list[jax.Array] = []  # (B, n) chunks
        self._comm: list[jax.Array] = []
        if substrate == "clients":
            from repro.problems.client_shard import check_client_shardable, pad_clients

            check_client_shardable(problem)
            devs = jax.devices()
            self._M = problem.num_clients
            self._padded = pad_clients(problem, self._M + (-self._M) % len(devs))
            self._valid = jnp.arange(self._padded.num_clients) < self._M
            self._treedef = jax.tree.structure(self._padded)
            state = _client_init_fn(
                self._algo, self._static_items, self._B, self._M, self._treedef
            )(self._padded, self._valid, self._x0, self._x_star, self._hp)
            self._state = self._canonicalize(state, self._keys[:, :1])
        elif substrate == "batched":
            state = _batched_init_fn(self._algo, self._static_items, self._B)(
                problem, self._x0, self._x_star, self._hp
            )
            self._state = self._canonicalize(state, self._keys[:, :1])
        else:
            init = _seq_init_fn(self._algo, self._static_items)
            self._state = [
                self._canonicalize(
                    init(problem, self._x0, self._x_star, self._hp_i(i)),
                    self._keys[i, :1], trial=i,
                )
                for i in range(self._B)
            ]

    def _canonicalize(self, state, keys1, trial: int | None = None):
        """Cast the init state to the dtypes one round of stepping produces.

        Init-time counters are weak-typed (plain Python ints through
        `jnp.asarray`); after one round they promote to strong dtypes.  Left
        alone, that changes the jit signature between the first and second
        `step()` chunk and silently recompiles the chunk fn.  An `eval_shape`
        of the chunk against its own output pins the post-round avals without
        compiling anything; the dtype list is cached per config signature so
        repeated opens (the serving pattern) skip even the trace."""
        if trial is None:
            hp = self._hp

            def call(s):
                return self._chunk_call(s, keys1)

        else:
            chunk = _seq_chunk_fn(self._algo, self._static_items)
            hp = self._hp_i(trial)

            def call(s):
                return chunk(self._problem, self._x0, self._x_star, hp, s, keys1)

        leaves, treedef = jax.tree.flatten(state)
        sig = tuple(
            (jnp.shape(a), str(jnp.result_type(a)))
            for tree in (state, hp, self._x0, self._x_star, self._problem, keys1)
            for a in jax.tree.leaves(tree)
        )
        cache_key = (self._algo, self._static_items, self._substrate, trial is None, sig)
        dtypes = _CANONICAL_DTYPES.get(cache_key)
        if dtypes is None:
            out_state, _ = jax.eval_shape(call, state)
            dtypes = tuple(av.dtype for av in jax.tree.leaves(out_state))
            _CANONICAL_DTYPES[cache_key] = dtypes
        return jax.tree.unflatten(
            treedef, [jnp.asarray(a, dt) for a, dt in zip(leaves, dtypes)]
        )

    # ------------------------------------------------------------ inspection
    @property
    def t(self) -> int:
        """Rounds executed so far."""
        return self._t

    @property
    def horizon(self) -> int:
        """Total rounds the key schedule covers (fixed at open)."""
        return self._horizon

    @property
    def num_trials(self) -> int:
        return self._B

    @property
    def substrate(self) -> str:
        return self._substrate

    @property
    def dist_sq(self) -> jax.Array:
        """(B, t) trajectory so far."""
        if not self._d2:
            return jnp.zeros((self._B, 0))
        return jnp.concatenate(self._d2, axis=1)

    @property
    def comm(self) -> jax.Array:
        if not self._comm:
            return jnp.zeros((self._B, 0), dtype=jnp.int32)
        return jnp.concatenate(self._comm, axis=1)

    @property
    def comm_bytes(self) -> np.ndarray:
        """(B, t) cumulative wire-bytes ledger (host int64; see
        `experiments.runner.ledger_bytes`)."""
        return ledger_bytes(self._cfg, self._x0, self.comm)

    @property
    def flops(self) -> np.ndarray:
        """(B, t) cumulative analytic-FLOPs ledger — the compute mirror of
        `comm_bytes`, exact per trial (refresh rounds reconstructed from the
        comm trajectory; see `repro.core.flops.ledger_flops` and
        docs/PERFORMANCE.md)."""
        from repro.core.flops import ledger_flops

        return ledger_flops(self._algo, self._cfg, self._problem, self.comm)

    def _chunk_call(self, state, keys_bn):
        """One batch-of-trials chunk on the session's device substrate
        (batched: plain jit; clients: shard_mapped over the padded problem)."""
        if self._substrate == "clients":
            chunk = _client_chunk_fn(
                self._algo, self._static_items, self._M, self._treedef
            )
            return chunk(
                self._padded, self._valid, self._x0, self._x_star, self._hp,
                state, jax.random.key_data(keys_bn),
            )
        chunk = _batched_chunk_fn(self._algo, self._static_items)
        return chunk(self._problem, self._x0, self._x_star, self._hp, state, keys_bn)

    def x(self) -> jax.Array:
        """(B, d) current iterates."""
        if self._substrate == "clients":
            return _client_final_fn(
                self._algo, self._static_items, self._B, self._M, self._treedef
            )(self._padded, self._valid, self._x0, self._x_star, self._hp, self._state)
        if self._substrate == "batched":
            return _batched_final_fn(self._algo, self._static_items, self._B)(
                self._problem, self._x0, self._x_star, self._hp, self._state
            )
        fin = _seq_final_fn(self._algo, self._static_items)
        return jnp.stack(
            [
                fin(self._problem, self._x0, self._x_star, self._hp_i(i), self._state[i])
                for i in range(self._B)
            ]
        )

    def _hp_i(self, i: int):
        return jax.tree.map(lambda a: a[i], self._hp)

    # -------------------------------------------------------------- stepping
    def step(self, n: int = 1) -> tuple[jax.Array, jax.Array]:
        """Advance every trial `n` rounds (one jitted chunk); returns the
        `(B, n)` dist-sq and cumulative-comm block for those rounds."""
        if n < 1:
            raise ValueError(f"step(n={n}): n must be >= 1")
        if self._t + n > self._horizon:
            raise ValueError(
                f"session horizon exhausted: {self._t} rounds done, {n} more "
                f"requested, horizon {self._horizon}.  The PRNG key schedule "
                "is fixed at open (split is not prefix-stable) — open a new "
                "session with a larger round budget to continue."
            )
        sl = slice(self._t, self._t + n)
        if self._substrate in ("batched", "clients"):
            self._state, (d2, comm) = self._chunk_call(self._state, self._keys[:, sl])
        else:
            chunk = _seq_chunk_fn(self._algo, self._static_items)
            d2_rows, comm_rows = [], []
            for i in range(self._B):
                self._state[i], (d2_i, comm_i) = chunk(
                    self._problem, self._x0, self._x_star, self._hp_i(i),
                    self._state[i], self._keys[i, sl],
                )
                d2_rows.append(d2_i)
                comm_rows.append(comm_i)
            d2, comm = jnp.stack(d2_rows), jnp.stack(comm_rows)
        self._t += n
        self._d2.append(d2)
        self._comm.append(comm)
        return d2, comm

    def run_until(
        self, eps: float, *, max_rounds: int | None = None, chunk: int = 32
    ) -> BatchResult:
        """Step in chunks until EVERY trial has reached `dist_sq <= eps` at
        least once (or the horizon / `max_rounds` budget runs out); returns
        the accumulated `BatchResult` with per-trial `stopped_round` counts.

        The trajectories are the exact prefix of the full-horizon run — early
        stopping changes how far the scan goes, never what it computes."""
        limit = self._horizon if max_rounds is None else min(self._horizon, self._t + max_rounds)
        while self._t < limit and not self._all_reached(eps):
            self.step(min(chunk, limit - self._t))
        return self.result(stopped_round=self._first_hit(eps))

    def _first_hit(self, eps: float) -> np.ndarray:
        """(B,) 1-based round of first dist_sq <= eps, -1 if not yet reached."""
        d2 = np.asarray(self.dist_sq)
        if d2.shape[1] == 0:
            return np.full(self._B, -1, dtype=np.int64)
        hit = d2 <= eps
        return np.where(hit.any(axis=1), hit.argmax(axis=1) + 1, -1)

    def _all_reached(self, eps: float) -> bool:
        return bool((self._first_hit(eps) >= 0).all())

    # ---------------------------------------------------------------- result
    def result(self, stopped_round: np.ndarray | None = None) -> BatchResult:
        """The rounds-so-far as a `BatchResult` (same layout as run_batch)."""
        return BatchResult(
            dist_sq=self.dist_sq,
            comm=self.comm,
            x_final=self.x(),
            hparams=self._hparams,
            seeds=self._seeds,
            stopped_round=stopped_round,
            comm_bytes=self.comm_bytes,
        )


def open_session(
    algo: str | RunSpec,
    problem,
    substrate: str | None = None,
    grid: Mapping[str, Any] | None = None,
    seeds: int | Sequence[int] = 1,
    *,
    x0: jax.Array | None = None,
    x_star: jax.Array | None = None,
    stepsize: str | None = None,
    target_eps: float = 1e-6,
    theory_constants: Any = None,
    **static,
) -> FedSession:
    """Open an incremental session for the same sweep `run_batch` would run.

    Accepts a `RunSpec` (whose `substrate` field picks the execution mode) or
    the legacy keyword style — the identical `as_runspec` shim and
    `RunSpec.resolve` path as `run_batch` / `run_sequential`, so the trial
    table, defaults and every validation error match exactly."""
    spec = as_runspec(
        algo, grid=grid, seeds=seeds, x0=x0, x_star=x_star, stepsize=stepsize,
        target_eps=target_eps, theory_constants=theory_constants,
        substrate=substrate, static=static,
    )
    spec = dataclasses.replace(spec, substrate=check_substrate(spec.substrate or "batched"))
    return FedSession(spec, problem)
