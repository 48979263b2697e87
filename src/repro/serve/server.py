"""Streaming federated simulation server: continuous rounds over a churning
client population.

The batch engine answers "what would M fixed clients converge to"; a real
federated deployment looks different — clients connect and drop on a stream,
cohorts must form from whoever is CURRENTLY resident, and the server's job is
to keep rounds flowing while the population shifts under it.  This module
simulates exactly that on top of the shared round bodies:

* `ClientStream` — host-side churn: each tick, every client independently
  flips residency with probability `churn` (a departure or an arrival), with
  a minimum-resident guard so the round never starves.
* `FedRoundServer` — continuous SVRP/SPPM/minibatch/deep rounds.  The round
  body is the ONE registry definition (`core.rounds.ROUND_DEFS`); only the
  sampling hooks change: `RoundOps.uniform_client` / `sample_cohort` are
  overridden with resident-masked draws (masked categorical for the single
  sampled client, masked Gumbel-top-k for minibatch cohorts), so a round can
  only ever touch clients that are resident when it starts.
* Double-buffered host<->device transfer: the server keeps `pipeline_depth`
  rounds in flight — round t+1 is dispatched before round t's scalar stats
  are fetched back, so the host readback and the device round overlap (jax's
  async dispatch does the buffering; on the synchronous CPU backend the
  structure stands but overlap is limited).
* `ServeStats` — rounds/sec, p50/p95/p99 round latency, and the
  dist-to-opt-over-wall-clock trace.

Per-round keys derive as `fold_in(base_key, round_index)` — no split chain to
keep in lockstep with the stream, so server runs are reproducible given
(seed, churn seed) regardless of chunking.

Distinct from `repro.launch.serve.BatchServer`, which serves model DECODE
requests; this server serves optimization ROUNDS.
"""
from __future__ import annotations

import time
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.channel import wire_vector_bytes
from repro.core.flops import flops_at, round_model
from repro.core.rounds import ROUND_DEFS, make_registry_ops
from repro.experiments.spec import ALGOS, _REQUIRED
from repro.serve.stats import PipelinedReadback, ServeStats


class ClientStream:
    """Host-side residency churn over `num_clients` simulated clients.

    `tick()` advances one round: every client independently flips its
    residency with probability `churn`; if departures would leave fewer than
    `min_resident` clients, random absentees are revived first.  Returns the
    boolean residency mask for the round."""

    def __init__(
        self,
        num_clients: int,
        *,
        churn: float = 0.1,
        min_resident: int | None = None,
        seed: int = 0,
    ) -> None:
        if num_clients < 1:
            raise ValueError("num_clients must be >= 1")
        self.num_clients = num_clients
        self.churn = float(churn)
        self.min_resident = (
            max(1, num_clients // 2) if min_resident is None else int(min_resident)
        )
        if not 1 <= self.min_resident <= num_clients:
            raise ValueError(
                f"min_resident must be in [1, {num_clients}], got {self.min_resident}"
            )
        self._rng = np.random.default_rng(seed)
        self.mask = np.ones(num_clients, dtype=bool)

    def tick(self) -> np.ndarray:
        flips = self._rng.random(self.num_clients) < self.churn
        self.mask = self.mask ^ flips
        short = self.min_resident - int(self.mask.sum())
        if short > 0:
            absent = np.flatnonzero(~self.mask)
            revive = self._rng.choice(absent, size=short, replace=False)
            self.mask[revive] = True
        return self.mask.copy()


def _resolve_hparams(algo: str, hparams: Mapping[str, float] | None):
    """Scalar hparam NamedTuple from the ALGOS defaults + overrides."""
    aspec = ALGOS[algo]
    hp = dict(hparams or {})
    unknown = set(hp) - set(aspec.params_cls._fields)
    if unknown:
        raise ValueError(
            f"{algo}: unknown hparams {sorted(unknown)}; "
            f"fields: {list(aspec.params_cls._fields)}"
        )
    vals = {}
    for name in aspec.params_cls._fields:
        if name in hp:
            vals[name] = jnp.asarray(hp[name])
        elif aspec.defaults[name] is _REQUIRED:
            raise ValueError(f"{algo}: hparams must provide required hparam {name!r}")
        else:
            vals[name] = jnp.asarray(aspec.defaults[name])
    return aspec.params_cls(**vals)


class FedRoundServer:
    """Continuous federated rounds with on-the-fly cohorts from a client stream.

    Supports every rounds-defined algorithm (`core.rounds.ROUND_DEFS`:
    sppm / svrp / svrp_minibatch / deep_svrp).  `run(num_rounds)` keeps the
    server state device-resident, pipelines round dispatch against stats
    readback, and returns the accumulated `ServeStats`.  Repeated `run` calls
    continue the same trajectory (round indices keep counting, so the
    `fold_in` key sequence never repeats).

    Pool mode — `FedRoundServer(pool=SessionPool(...))` — serves MANY
    tenants' sessions instead of one churning stream: each served round is
    one pooled tick (`pool.step(1)`, a single dispatch advancing every
    running tenant), with the identical `pipeline_depth`-deep stats readback;
    tenants whose horizon runs out are frozen (masked from the chunk) rather
    than erroring, and `run` stops early once no tenant is left running."""

    def __init__(
        self,
        algo: str | None = None,
        problem=None,
        *,
        pool=None,
        hparams: Mapping[str, float] | None = None,
        stream: ClientStream | None = None,
        x0: jax.Array | None = None,
        x_star: jax.Array | None = None,
        seed: int = 0,
        pipeline_depth: int = 2,
        prox_solver: str = "exact",
        prox_steps: int = 50,
        prox_tol: float = 1e-10,
        batch_clients: int | None = None,
        local_steps: int | None = None,
        channel: str | None = None,
    ) -> None:
        if pool is not None:
            if algo is not None or problem is not None:
                raise ValueError(
                    "FedRoundServer(pool=...) serves the pool's tenants; "
                    "don't also pass algo/problem (admit tenants to the pool)"
                )
            if pipeline_depth < 1:
                raise ValueError("pipeline_depth must be >= 1")
            self._pool = pool
            self._depth = pipeline_depth
            self._round_idx = 0
            self.stats = ServeStats()
            return
        self._pool = None
        if algo not in ROUND_DEFS:
            raise ValueError(
                f"FedRoundServer serves rounds-defined algorithms "
                f"{sorted(ROUND_DEFS)}; got {algo!r}"
            )
        if pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        self.algo = algo
        self.problem = problem
        self._rdef = ROUND_DEFS[algo]
        self._hp = _resolve_hparams(algo, hparams)
        M = problem.num_clients
        if x0 is None:
            x0 = jnp.zeros(
                problem.dim,
                dtype=problem.A.dtype if hasattr(problem, "A") else None,
            )
        self._x0 = x0
        self._x_star = problem.minimizer() if x_star is None else x_star
        self._stream = stream if stream is not None else ClientStream(M, seed=seed + 1)
        if algo == "svrp_minibatch":
            if batch_clients is None:
                raise ValueError("svrp_minibatch needs batch_clients")
            if self._stream.min_resident < batch_clients:
                raise ValueError(
                    f"cohorts of {batch_clients} need min_resident >= "
                    f"{batch_clients} on the ClientStream "
                    f"(got {self._stream.min_resident})"
                )
        binding: dict[str, Any] = dict(
            prox_solver=prox_solver, prox_steps=prox_steps, prox_tol=prox_tol
        )
        if algo == "deep_svrp":
            binding = {"local_steps": 4 if local_steps is None else local_steps}
        elif batch_clients is not None:
            binding["batch_clients"] = batch_clients
        binding["channel"] = channel
        # Static wire price of one d-vector under this channel: the per-round
        # bytes ledger is comm x this (host int64 — see runner.ledger_bytes).
        self._wire_bytes = wire_vector_bytes(
            channel, int(np.prod(self._x0.shape)), self._x0.dtype.itemsize
        )
        # Analytic per-round FLOPs model: cumulative FLOPs are exactly
        # recoverable from (round index, cumulative comm) — see
        # repro.core.flops and docs/PERFORMANCE.md.
        self._flops_model = round_model(algo, problem, **binding)

        def _ops(mask):
            # Rebuilt inside the trace: same registry binding as the scan
            # substrates, with the sampling hooks masked to resident clients.
            neg_inf = jnp.where(mask, 0.0, -jnp.inf)

            def uniform_client(key):
                return jax.random.categorical(key, neg_inf).astype(jnp.int32)

            def sample_cohort(key):
                g = jax.random.gumbel(key, (M,)) + neg_inf
                return jax.lax.top_k(g, batch_clients)[1].astype(jnp.int32)

            return make_registry_ops(
                algo, problem, self._x0, self._x_star, self._hp, batched=False,
                uniform_client_fn=uniform_client, sample_cohort_fn=sample_cohort,
                **binding,
            )

        def _round(state, key, mask):
            return self._rdef.round(_ops(mask), state, key)

        self._round_fn = jax.jit(_round, donate_argnums=0)
        # Init is sampling-free (anchor setup / comm0), so a full mask is fine.
        # It runs eagerly and may return x0 itself, or one array in two
        # leaves; the round fn donates its state, so every leaf gets its own
        # buffer.
        self._state = jax.tree.map(
            jnp.copy, self._rdef.init(_ops(jnp.ones(M, dtype=bool)), self._x0)
        )
        self._base_key = jax.random.key(seed)
        self._round_idx = 0
        self._depth = pipeline_depth
        self.stats = ServeStats()

    @property
    def x(self) -> jax.Array:
        """The server's current iterate."""
        return self._state[0]

    @property
    def rounds_done(self) -> int:
        return self._round_idx

    def run(self, num_rounds: int) -> ServeStats:
        """Run `num_rounds` continuous rounds; cohorts re-form from the stream
        every round (stream mode) or every running tenant advances one pooled
        round (pool mode); stats readback is pipelined `pipeline_depth` deep."""
        if self._pool is not None:
            return self._run_pool(num_rounds)
        start = time.perf_counter()

        def drain_one(t0: float, round_idx: int, d2: Any, comm: Any) -> None:
            d2_host = float(d2)  # blocks until the round's result is ready
            now = time.perf_counter()
            comm_host = int(comm)
            self.stats.record(
                now - t0, now - start, d2_host, comm_host,
                comm_bytes=comm_host * self._wire_bytes,
                flops=float(flops_at(self._flops_model, round_idx, comm_host)),
            )

        readback = PipelinedReadback(self._depth, drain_one)
        for _ in range(num_rounds):
            mask = jnp.asarray(self._stream.tick())
            key_t = jax.random.fold_in(self._base_key, self._round_idx)
            t0 = time.perf_counter()
            self._state, (d2, comm) = self._round_fn(self._state, key_t, mask)
            self._round_idx += 1
            readback.push(t0, self._round_idx, d2, comm)
        readback.flush()
        return self.stats

    def _run_pool(self, num_rounds: int) -> ServeStats:
        """Pool mode: one pooled tick per served round, aggregate stats.

        The recorded dist^2 is the mean over running lanes' trials after the
        tick; comm/comm_bytes are the cumulative steps SERVED across runs —
        each tick attributes only its own per-lane increments, so the total
        stays monotone when a converged/exhausted tenant's lane freezes (its
        masked chunk outputs drop to zero, but its served rounds are kept)."""
        pool = self._pool
        start = time.perf_counter()
        # Per-lane cumulative comm already attributed, seeded from the rounds
        # tenants ran before this call (no chunk is in flight yet, so the
        # host conversion here cannot stall the pipeline).
        base = np.zeros((pool.capacity,), dtype=np.int64)
        rounds_base = np.zeros((pool.capacity,), dtype=np.int64)
        for tid in pool.tenant_ids(resident_only=True):
            ses = pool.session(tid)
            if ses.t:
                slot = pool._tenants[tid].slot
                base[slot] = int(np.asarray(ses.comm[:, -1]).sum())
                rounds_base[slot] = ses.t
        served = getattr(self, "_comm_served", 0)
        flops_served = getattr(self, "_flops_served", 0.0)
        model = getattr(pool, "flops_model", None)

        def drain_one(t0: float, active: np.ndarray, d2: Any, comm: Any) -> None:
            nonlocal served, flops_served
            d2_host = np.asarray(d2)  # blocks until the tick's result is ready
            now = time.perf_counter()
            comm_host = np.asarray(comm)  # (P, B, 1) cumulative, masked lanes 0
            mean_d2 = float(d2_host[active, :, -1].mean())
            lane_totals = comm_host[:, :, -1].sum(axis=1).astype(np.int64)
            delta = int((lane_totals - base)[active].sum())
            served += delta
            base[active] = lane_totals[active]
            if model is not None:
                # Exact aggregate FLOPs of this tick: each active lane ran B
                # trials 1 round; inits are charged to trials at round 0 (or
                # at a Catalyst stage boundary), then the refresh count falls
                # out of the comm delta — see repro.core.flops.tick_flops.
                B = comm_host.shape[1]
                if model.stage_rounds:
                    init_lanes = active & (rounds_base % model.stage_rounds == 0)
                elif model.comm_init:
                    init_lanes = active & (rounds_base == 0)
                else:
                    init_lanes = np.zeros_like(active)
                inits = int(np.sum(init_lanes)) * B
                trial_rounds = int(np.sum(active)) * B
                if model.comm_refresh:
                    refreshes = max(round(
                        (delta - inits * model.comm_init
                         - trial_rounds * model.comm_base) / model.comm_refresh
                    ), 0)
                else:
                    refreshes = 0
                flops_served += (
                    inits * model.init_flops
                    + trial_rounds * model.base_flops
                    + refreshes * model.refresh_flops
                )
            rounds_base[active] += 1
            self.stats.record(
                now - t0, now - start, mean_d2, served,
                comm_bytes=served * pool.wire_bytes_per_vector,
                flops=flops_served if model is not None else None,
            )

        readback = PipelinedReadback(self._depth, drain_one)
        for _ in range(num_rounds):
            if pool.freeze_exhausted(1) == 0:
                break  # every tenant converged, evicted, or out of horizon
            active = pool.active_mask
            t0 = time.perf_counter()
            d2, comm = pool.step(1)
            self._round_idx += 1
            readback.push(t0, active, d2, comm)
        readback.flush()
        self._comm_served = served
        self._flops_served = flops_served
        return self.stats
