"""Round-step substrate layer: every algorithm defined ONCE, executed four ways.

The substrate contract (equivalence guarantees, which tests hold which seam)
is documented in docs/ARCHITECTURE.md; the client-sharded collective model in
docs/SCALING.md.

The whole SPPM/SVRP family in this repo is one shape — sample a cohort, solve
a local prox, maybe refresh the anchor, account communication.  Before this
layer that shape was written up to three times per algorithm (the sequential
``*_scan`` in ``core/``, a hand-batched ``_*_step_fused`` copy in
``experiments/runner.py``, and the DeepSVRP pod step in ``launch/steps.py``).
Here each algorithm is a single ``RoundDef``:

* ``init(ops, x0) -> state``            — round-0 state (iterate, anchor,
  cached anchor gradient, communication counter), built through the substrate
  primitives so the SAME definition yields ``(d,)`` or ``(B, d)`` state;
* ``round(ops, state, key) -> (state, (dist_sq, comm))`` — one communication
  round, written against the abstract client-sampling / prox-oracle / anchor
  interface ``RoundOps``.

``RoundOps`` is the substrate: a bundle of execution primitives that decide
HOW the round runs.

==============  ==============================================================
substrate       execution
==============  ==============================================================
sequential      per-trial ``lax.scan`` — bit-preserves the historical
                ``run_*`` drivers and their PRNG key schedules; consumed by
                the thin ``*_scan`` wrappers in ``core/svrp.py`` etc.
batched         the experiment engine's DEFAULT for rounds-defined algos
                (``registry_batched_scan``): a batch-level scan with the
                per-trial sampling + registry prox solve vmapped INSIDE the
                round — numerically identical to vmapping the whole scan,
                but the anchor refresh is BATCH-AWARE (below).  Algorithms
                outside ``ROUND_DEFS`` still run as plain vmap-of-scan
                (``experiments.runner._vmapped_trials``).
fused           hand-batched ``(B, d)`` state with the Algorithm-7 local
                solves routed through the batched Pallas kernels; same
                vmapped per-trial sampling (bit-identical key usage) and
                batch-aware refresh.  Entry point: ``batched_scan``.
client-sharded  the CLIENT axis laid over a 1-D device mesh
                (``make_client_sharded_ops`` / ``client_sharded_scan``):
                per-client oracles are owner-masked (zeros elsewhere, no
                collective), the round's single masked ``psum`` assembles the
                prox result, and the anchor refresh is ONE ``psum`` per
                refresh EVENT — docs/SCALING.md#one-psum-per-refresh-event.
                Trial state stays replicated; only problem blocks (and DP
                noise shifts) shard.  Entry: ``run_batch(shard="clients")``.
incremental     the SAME sequential/batched/client-sharded bindings
                stepped one chunk at a time instead of scanned to a fixed
                horizon: ``registry_step_def`` / ``client_sharded_step_def``
                expose each ``(init, round)``
                pair as a `core.types.StepDef` consumed by the online session
                layer (`repro.serve.FedSession` — ``open_session`` /
                ``session.step(n)`` / ``run_until(eps)``) and the streaming
                federated server (`repro.serve.FedRoundServer`, which swaps
                the sampling fns to draw cohorts from resident clients only).
==============  ==============================================================

Batch-aware anchor refresh
--------------------------
Under plain vmap the per-trial refresh ``lax.cond`` linearizes into a select
that evaluates ``full_grad`` for every trial at every step — the recorded
~0.5x SVRP-on-logistic caveat.  The fused substrate instead gates ONE
batch-level ``lax.cond(jnp.any(c))``: the full-gradient recompute only
materializes on steps where at least one trial actually refreshes (a
``(1-p)^B`` fraction of steps costs nothing), and the per-trial selection
``where(c, full_grad(w'), gbar)`` is unchanged, so the fused trajectories are
bitwise-identical to the always-pay version.  Every refresh-bearing algorithm
(svrp, svrp_minibatch, deep_svrp, catalyzed_svrp's inner loop) inherits the
fix from the one shared definition.

PRNG contract: the fused substrate consumes keys exactly like the sequential
drivers — per-trial ``split``/``randint``/``choice``/``bernoulli`` under
``vmap`` — so trial b of a fused sweep replays the sequential trial's coin
flips bit-for-bit, and the sequential-vs-batched equivalence oracles
(tests/test_substrates.py) gate the whole layer.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.channel import get_channel
from repro.core.types import RunResult


class RoundDef(NamedTuple):
    """One algorithm as an (init, round) pair over the substrate interface."""

    name: str
    init: Callable  # (ops, x0) -> state
    round: Callable  # (ops, state, key) -> (state, (dist_sq, comm))


class RoundOps:
    """Substrate execution primitives the round definitions are written against.

    One instance = one (problem, hparams, substrate) binding.  ``batched=False``
    runs a single trial (scalars, ``(d,)`` vectors, per-trial ``lax.cond``);
    ``batched=True`` runs a hand-batched ``(B,)`` sweep (per-trial sampling
    vmapped, ``(B, d)`` state, batch-level anchor refresh).

    The local prox solve is algorithm-/substrate-specific and injected by the
    caller: ``prox(m, z)`` for single-client rounds (sppm/svrp),
    ``cohort_prox(ms, z)`` for minibatch cohorts, ``local_prox_gd(z, y0)`` for
    DeepSVRP's explicit-stepsize Algorithm-7 loop.
    """

    def __init__(
        self,
        problem,
        hp,
        x_star,
        dtype,
        *,
        batched: bool,
        num_trials: int | None = None,
        prox: Callable | None = None,
        cohort_prox: Callable | None = None,
        cohort_size: int | None = None,
        local_prox_gd: Callable | None = None,
        grad: Callable | None = None,
        full_grad: Callable | None = None,
        uniform_client_fn: Callable | None = None,
        sample_cohort_fn: Callable | None = None,
        channel=None,
    ):
        self.problem = problem
        self.hp = hp
        self.x_star = x_star
        self.dtype = dtype
        self.batched = batched
        self.B = num_trials
        self.M = problem.num_clients
        # The comm channel every client<->server transfer flows through
        # (None -> identity: bit-exact passthrough).  Static per binding.
        self.channel = get_channel(channel)
        self.prox = prox
        self.cohort_prox = cohort_prox
        self.cohort_size = cohort_size
        self.local_prox_gd = local_prox_gd
        # Substrate-level sampling overrides: the streaming server restricts
        # client/cohort draws to the currently RESIDENT clients (a (M,) mask)
        # by swapping these, leaving the round definitions untouched.
        self._uniform_client_fn = uniform_client_fn
        self._sample_cohort_fn = sample_cohort_fn
        # Substrate-level oracle overrides (already batched when batched=True):
        # Catalyst's inner rounds substitute per-trial SHIFTED gradients here.
        self._grad = problem.grad
        self._full_grad = problem.full_grad
        self.oracle_overridden = grad is not None or full_grad is not None
        if grad is not None:
            self.grad = grad
        if full_grad is not None:
            self.full_grad = full_grad

    # ---------------------------------------------------------------- PRNG
    def schedule_keys(self, key, num_steps: int):
        """The scan's per-step key array — identical to the sequential
        drivers' ``jax.random.split(key, num_steps)`` per trial."""
        if not self.batched:
            return jax.random.split(key, num_steps)
        return jnp.swapaxes(
            jax.vmap(lambda k: jax.random.split(k, num_steps))(key), 0, 1
        )

    def split(self, key):
        if not self.batched:
            key_a, key_b = jax.random.split(key)
            return key_a, key_b
        s = jax.vmap(jax.random.split)(key)  # (B, 2) keys
        return s[:, 0], s[:, 1]

    def uniform_client(self, key):
        if self._uniform_client_fn is not None:
            return self._uniform_client_fn(key)
        if not self.batched:
            return jax.random.randint(key, (), 0, self.M)
        return jax.vmap(lambda k: jax.random.randint(k, (), 0, self.M))(key)

    def sample_cohort(self, key):
        """``cohort_size`` clients without replacement (minibatch SVRP)."""
        if self._sample_cohort_fn is not None:
            return self._sample_cohort_fn(key)
        b = self.cohort_size
        if not self.batched:
            return jax.random.choice(key, self.M, shape=(b,), replace=False)
        return jax.vmap(
            lambda k: jax.random.choice(k, self.M, shape=(b,), replace=False)
        )(key)

    def bernoulli(self, key, p):
        p = jnp.asarray(p, self.dtype)
        if not self.batched:
            return jax.random.bernoulli(key, p)
        return jax.vmap(jax.random.bernoulli)(key, jnp.broadcast_to(p, (self.B,)))

    # ------------------------------------------------------------- oracles
    def grad(self, m, y):
        if not self.batched:
            return self._grad(m, y)
        return jax.vmap(self._grad)(m, y)

    def full_grad(self, w):
        if not self.batched:
            return self._full_grad(w)
        return jax.vmap(self._full_grad)(w)

    def cohort_grad(self, ms, y):
        """Per-cohort-client gradients at the shared iterate: (b, d) / (B, b, d).

        A 1-D ``ms`` under the batched substrate is a trial-SHARED cohort
        (DeepSVRP's full participation); 2-D is per-trial sampled clients."""
        if self.oracle_overridden:
            # Substrate-level grad/full_grad closures cannot be decomposed
            # back into the per-client primitive this needs — extend the
            # override mechanism before routing a cohort round through it.
            raise NotImplementedError(
                "cohort_grad does not support substrate-level oracle overrides"
            )
        per_trial = jax.vmap(self._grad, in_axes=(0, None))
        if not self.batched:
            return per_trial(ms, y)
        if ms.ndim == 1:
            return jax.vmap(per_trial, in_axes=(None, 0))(ms, y)
        return jax.vmap(per_trial)(ms, y)

    def init_full_grad(self, x0):
        """Round-0 anchor gradient for a trial-SHARED ``x0``: computed once on
        the raw problem oracle and tiled to per-trial state.  A substrate
        hook (rather than an inline ``problem.full_grad`` call in
        ``_svrp_init``) so the client-sharded substrate can route the init
        anchor through the same masked-sum + single-``psum`` assembly as its
        refresh events."""
        return self.tile(self._full_grad(x0))

    def refresh_grad(self, c, w_next, gbar):
        """Anchor-gradient refresh.  Sequential: the historical lazy
        ``lax.cond`` (full gradient paid only on refresh steps).  Batched: the
        batch-aware form — one ``lax.cond(jnp.any(c))`` so the vmapped
        full-gradient sweep only runs on steps where some trial refreshes,
        with the per-trial ``where`` selection unchanged."""
        if not self.batched:
            return jax.lax.cond(c, lambda: self.full_grad(w_next), lambda: gbar)
        return jax.lax.cond(
            jnp.any(c),
            lambda: jnp.where(c[:, None], self.full_grad(w_next), gbar),
            lambda: gbar,
        )

    # ------------------------------------------------------- shape algebra
    def tile(self, v):
        """Trial-shared array -> per-trial state (identity / (B,)-broadcast)."""
        if not self.batched:
            return v
        return jnp.broadcast_to(v, (self.B,) + v.shape)

    def vec(self, h):
        """Per-trial scalar hparam as a multiplier for state-shaped arrays."""
        h = jnp.asarray(h, self.dtype)
        if not self.batched:
            return h
        return jnp.broadcast_to(h, (self.B,))[:, None]

    def cvec(self, h):
        """Like ``vec`` but broadcasting against cohort-shaped arrays."""
        h = jnp.asarray(h, self.dtype)
        if not self.batched:
            return h
        return jnp.broadcast_to(h, (self.B,))[:, None, None]

    def expand(self, v):
        """Add the cohort axis: (d,) -> (1, d)  /  (B, d) -> (B, 1, d)."""
        return v[None, :] if not self.batched else v[:, None, :]

    def where_vec(self, c, a, b):
        return jnp.where(c if not self.batched else c[:, None], a, b)

    def as_count(self, c):
        return c.astype(jnp.int32)

    def comm0(self, n: int):
        if not self.batched:
            return jnp.asarray(n)
        return jnp.full((self.B,), n)

    # ------------------------------------------------------------- channel
    # The transfer seams every round body routes its payloads through.  With
    # the identity channel all four are passthrough, so default trajectories
    # are bit-identical to the pre-channel engine.

    def chan_init(self, xB):
        """Round-0 channel state (quant8's EF residual), shaped like the
        broadcast payload.  Replicated per-trial state on every substrate."""
        return self.channel.init_state(xB)

    def chan_down(self, ch, x):
        """Server -> client iterate broadcast — the one stateful link: the
        quant8 channel transmits ``Q(x + e)`` and carries the residual."""
        return self.channel.down(ch, x)

    def chan_up(self, v):
        """Client -> server payloads (prox results), stateless, compressed
        row-independently along the last axis."""
        return self.channel.up(v)

    def chan_bcast(self, v):
        """Anchor broadcast on refresh events, stateless: clients store the
        anchor AS RECEIVED, so the cached anchor gradient stays consistent
        with the anchor the clients actually hold."""
        return self.channel.bcast(v)

    def client_mean(self, y):
        """Mean over the client axis of full-participation rows (DeepSVRP).
        A substrate primitive so the client-sharded binding can assemble the
        GLOBAL mean from resident rows with its one masked ``psum``."""
        return jnp.mean(y, axis=-2)

    def dist_sq(self, x):
        metric = getattr(self.problem, "metric", None)
        if metric is not None:
            # Problems without a computable minimizer (real-model federated
            # fine-tunes) report their own scalar metric (e.g. full loss)
            # in place of squared distance to x_star.
            return metric(x) if not self.batched else jax.vmap(metric)(x)
        if not self.batched:
            return jnp.sum((x - self.x_star) ** 2)
        return jnp.sum((x - self.x_star[None]) ** 2, axis=-1)

    def out(self, traj):
        """Scan-stacked trajectory -> RunResult layout ((K,) / (B, K))."""
        return traj if not self.batched else jnp.swapaxes(traj, 0, 1)


def scan_rounds(rdef: RoundDef, ops: RoundOps, x0, key, num_steps: int) -> RunResult:
    """Execute ``num_steps`` rounds of one definition on one substrate."""
    state0 = rdef.init(ops, x0)
    keys = ops.schedule_keys(key, num_steps)
    final, (d2s, comms) = jax.lax.scan(
        lambda s, k: rdef.round(ops, s, k), state0, keys
    )
    return RunResult(dist_sq=ops.out(d2s), comm=ops.out(comms), x_final=final[0])


# ============================================================ round definitions
#
# Communication accounting follows Section 4.2 exactly (audited against the
# sequential drivers by tests/test_substrates.py): one vector exchange
# server<->client = 1 step; the initial anchor setup (broadcast w_0, gather M
# gradients, broadcast the average) = 3M; a refresh re-runs that round.
# Every counted vector is priced on the wire by the bound comm channel —
# ``comm`` stays the step count, and the entry points derive the int64 bytes
# ledger as steps x the channel's static per-vector wire size.
#
# Channel seams: the server iterate broadcast goes through ``chan_down`` (the
# stateful/EF link — clients form their prox targets from the compressed
# iterate they actually received), client->server prox results through
# ``chan_up``, and the refresh anchor broadcast through ``chan_bcast`` (the
# stored anchor is the compressed one the clients hold, so the cached anchor
# gradient matches it).  The refresh event's client->server gradient gather
# is PRICED in the 3M accounting but modeled lossless numerically — the
# masked-sum + psum assembly stays one collective on the sharded substrate.


def _sppm_init(ops: RoundOps, x0):
    xB = ops.tile(x0)
    return (xB, ops.comm0(0), ops.chan_init(xB))


def _sppm_round(ops: RoundOps, s, key_k):
    x, comm, ch = s
    m = ops.uniform_client(key_k)
    ch, x_d = ops.chan_down(ch, x)
    x_next = ops.chan_up(ops.prox(m, x_d))
    comm = comm + 2  # server -> client (x_k), client -> server (x_{k+1})
    return (x_next, comm, ch), (ops.dist_sq(x_next), comm)


def _svrp_init(ops: RoundOps, x0):
    xB = ops.tile(x0)
    if ops.oracle_overridden:
        gbar = ops.full_grad(xB)  # the override sees per-trial state
    else:
        # x0 is trial-shared: compute the anchor gradient once and tile it.
        gbar = ops.init_full_grad(x0)
    return (xB, xB, gbar, ops.comm0(3 * ops.M), ops.chan_init(xB))


def _svrp_round(ops: RoundOps, s, key_k):
    x, w, gbar, comm, ch = s
    key_m, key_c = ops.split(key_k)
    m = ops.uniform_client(key_m)

    ch, x_d = ops.chan_down(ch, x)
    g_k = gbar - ops.grad(m, w)
    z = x_d - ops.vec(ops.hp.eta) * g_k
    x_next = ops.chan_up(ops.prox(m, z))

    c = ops.bernoulli(key_c, ops.hp.p)
    w_next = ops.where_vec(c, ops.chan_bcast(x_next), w)
    gbar_next = ops.refresh_grad(c, w_next, gbar)
    comm = comm + 2 + 3 * ops.M * ops.as_count(c)
    return (x_next, w_next, gbar_next, comm, ch), (ops.dist_sq(x_next), comm)


def _svrp_minibatch_round(ops: RoundOps, s, key_k):
    x, w, gbar, comm, ch = s
    key_m, key_c = ops.split(key_k)
    ms = ops.sample_cohort(key_m)

    ch, x_d = ops.chan_down(ch, x)
    g_k = ops.expand(gbar) - ops.cohort_grad(ms, w)
    z = ops.expand(x_d) - ops.cvec(ops.hp.eta) * g_k
    ys = ops.chan_up(ops.cohort_prox(ms, z))
    x_next = jnp.mean(ys, axis=-2)

    c = ops.bernoulli(key_c, ops.hp.p)
    w_next = ops.where_vec(c, ops.chan_bcast(x_next), w)
    gbar_next = ops.refresh_grad(c, w_next, gbar)
    comm = comm + 2 * ops.cohort_size + 3 * ops.M * ops.as_count(c)
    return (x_next, w_next, gbar_next, comm, ch), (ops.dist_sq(x_next), comm)


def _deep_svrp_round(ops: RoundOps, s, key_k):
    """DeepSVRP's full-participation pod round: every client is a cohort and
    all M step concurrently; the local solver is Algorithm 7 at an explicit
    stepsize (hp.local_lr), injected as ``ops.local_prox_gd``."""
    x, w, gbar, comm, ch = s
    clients = jnp.arange(ops.M)

    ch, x_d = ops.chan_down(ch, x)
    g_k = ops.expand(gbar) - ops.cohort_grad(clients, w)
    z = ops.expand(x_d) - ops.cvec(ops.hp.eta) * g_k
    y = ops.local_prox_gd(z, x_d)
    x_next = ops.client_mean(ops.chan_up(y))

    c = ops.bernoulli(key_k, ops.hp.anchor_prob)
    w_next = ops.where_vec(c, ops.chan_bcast(x_next), w)
    gbar_next = ops.refresh_grad(c, w_next, gbar)
    # Full participation: 2M per round (x down / y up for all cohorts) + a
    # Bernoulli-gated 2M for the anchor-gradient all-reduce.
    comm = comm + 2 * ops.M + 2 * ops.M * ops.as_count(c)
    return (x_next, w_next, gbar_next, comm, ch), (ops.dist_sq(x_next), comm)


ROUND_DEFS: dict[str, RoundDef] = {
    "sppm": RoundDef("sppm", _sppm_init, _sppm_round),
    "svrp": RoundDef("svrp", _svrp_init, _svrp_round),
    "svrp_minibatch": RoundDef("svrp_minibatch", _svrp_init, _svrp_minibatch_round),
    "deep_svrp": RoundDef("deep_svrp", _svrp_init, _deep_svrp_round),
}


# ========================================== batched (registry-prox) substrate
#
# The engine's default batched execution for the rounds-defined algorithms:
# a BATCH-LEVEL scan whose per-trial pieces (sampling, registry prox solve)
# are vmapped inside the round, rather than a vmap of the whole per-trial
# scan.  Numerically identical to vmap-of-scan (the same primitives are
# vmapped either way), but the anchor refresh becomes batch-aware: under
# vmap-of-scan the per-trial `lax.cond` linearizes into a select that pays
# `full_grad` for EVERY trial EVERY step (the recorded ~0.5x
# SVRP-on-logistic caveat); here the shared `refresh_grad` gates one
# `lax.cond(jnp.any(c))` and the recompute only runs on steps where some
# trial actually refreshes.


def make_registry_ops(
    algo: str, problem, x0, x_star, hp, *,
    batched: bool, num_trials: int | None = None,
    prox_solver: str = "exact", prox_steps: int = 50,
    prox_tol: float = 1e-10, batch_clients: int | None = None,
    local_steps: int | None = None, prox_factors=None,
    uniform_client_fn: Callable | None = None,
    sample_cohort_fn: Callable | None = None,
    channel=None,
) -> RoundOps:
    """Bind one rounds-defined algorithm's substrate: registry prox solve +
    Algorithm-7 local loop, per trial (``batched=False``, the historical
    ``*_scan`` binding) or vmapped over a ``(B,)`` sweep (``batched=True``).

    The ONE binding every entry point shares: the sequential ``*_scan``
    wrappers (core/svrp.py etc.), the engine's default batched path
    (`registry_batched_scan`), the incremental session (`registry_step_def`)
    and the streaming server (which additionally swaps the sampling fns to
    draw from resident clients only) all call this — so the prox/oracle
    wiring can never drift between drivers.

    ``prox_factors`` passes pre-hoisted solver state (Catalyst's per-stage
    shifted spectral factors); otherwise the solver's own ``prepare`` runs
    here, once, outside any scan.
    """
    from repro.core.prox import get_prox_solver

    B = num_trials
    dtype = x0.dtype
    kw: dict[str, Any] = {
        "uniform_client_fn": uniform_client_fn,
        "sample_cohort_fn": sample_cohort_fn,
        "channel": channel,
    }

    if algo == "deep_svrp":
        M = problem.num_clients
        clients = jnp.arange(M)
        if batched:
            from repro.kernels.ref import prox_update_batched as _prox_update_ref_b

            beta = jnp.broadcast_to(jnp.asarray(hp.local_lr, dtype), (B,))
            inv_eta = 1.0 / jnp.broadcast_to(jnp.asarray(hp.eta, dtype), (B,))
            grad_cohort = jax.vmap(jax.vmap(problem.grad))

            def local_prox_gd(z, x):  # (B, M, d) targets, (B, d) shared start
                ms = jnp.broadcast_to(clients, (B, M))

                def local(y, _):
                    # The canonical Algorithm-7 update (kernels.ref), the same
                    # single source the sequential driver scans.
                    return (
                        _prox_update_ref_b(y, grad_cohort(ms, y), z, beta, inv_eta),
                        None,
                    )

                y0 = jnp.broadcast_to(x[:, None, :], z.shape)
                y, _ = jax.lax.scan(local, y0, None, length=local_steps)
                return y
        else:
            from repro.kernels.ref import prox_update as _prox_update_ref

            beta = jnp.asarray(hp.local_lr, dtype)
            inv_eta = 1.0 / jnp.asarray(hp.eta, dtype)
            grad_rows = jax.vmap(problem.grad)  # (M,), (M, d) -> (M, d)

            def local_prox_gd(z, x):  # (M, d) targets, shared start x -> (M, d)
                def local(y, _):
                    return _prox_update_ref(y, grad_rows(clients, y), z, beta, inv_eta), None

                y0 = jnp.broadcast_to(x, z.shape)
                y, _ = jax.lax.scan(local, y0, None, length=local_steps)
                return y

        kw["local_prox_gd"] = local_prox_gd
    else:
        solver = get_prox_solver(prox_solver, problem)
        factors = prox_factors if prox_factors is not None else solver.prepare(problem)
        if batched:
            eta = jnp.broadcast_to(jnp.asarray(hp.eta, dtype), (B,))
            L = jnp.broadcast_to(
                jnp.asarray(getattr(hp, "smoothness", 0.0), dtype), (B,)
            )

            def solve_one(m, z, e, s):
                return solver.solve(
                    problem, factors, m, z, e,
                    smoothness=s, steps=prox_steps, tol=prox_tol,
                )

            if algo == "svrp_minibatch":
                def cohort_prox(ms, z):  # (B, b), (B, b, d) -> (B, b, d)
                    per_trial = jax.vmap(solve_one, in_axes=(0, 0, None, None))
                    return jax.vmap(per_trial)(ms, z, eta, L)

                kw["cohort_prox"] = cohort_prox
                kw["cohort_size"] = batch_clients
            else:
                kw["prox"] = lambda m, z: jax.vmap(solve_one)(m, z, eta, L)
        else:
            eta = jnp.asarray(hp.eta, dtype)

            def solve_one_seq(m, z_m):
                return solver.solve(
                    problem, factors, m, z_m, eta,
                    smoothness=hp.smoothness, steps=prox_steps, tol=prox_tol,
                )

            if algo == "svrp_minibatch":
                kw["cohort_prox"] = lambda ms, z: jax.vmap(solve_one_seq)(ms, z)
                kw["cohort_size"] = batch_clients
            else:
                kw["prox"] = solve_one_seq

    return RoundOps(
        problem, hp, x_star, dtype, batched=batched, num_trials=B, **kw
    )


def registry_step_def(
    algo: str, problem, x0, x_star, hp, *,
    batched: bool, num_trials: int | None = None, **binding,
):
    """The rounds-defined algorithms' incremental unit (`core.types.StepDef`):
    the SAME `(init, round)` pair `scan_rounds` scans, exposed step-at-a-time
    for `repro.serve.FedSession`.  `binding` is forwarded to
    `make_registry_ops` (prox_solver/prox_steps/prox_tol/batch_clients/
    local_steps and the server's sampling overrides)."""
    from repro.core.types import StepDef

    ops = make_registry_ops(
        algo, problem, x0, x_star, hp,
        batched=batched, num_trials=num_trials, **binding,
    )
    rdef = ROUND_DEFS[algo]
    return StepDef(
        init=lambda: rdef.init(ops, x0),
        step=lambda s, k: rdef.round(ops, s, k),
        final=lambda s: s[0],
    )


def registry_batched_scan(
    algo: str, problem, x0, x_star, keys, hp, *,
    num_steps: int, prox_solver: str = "exact", prox_steps: int = 50,
    prox_tol: float = 1e-10, batch_clients: int | None = None,
    local_steps: int | None = None, channel=None,
) -> RunResult:
    """Run one rounds-defined algorithm hand-batched with its registry prox
    solver vmapped per trial (per-trial eta/smoothness ride the vmap)."""
    ops = make_registry_ops(
        algo, problem, x0, x_star, hp,
        batched=True, num_trials=keys.shape[0],
        prox_solver=prox_solver, prox_steps=prox_steps, prox_tol=prox_tol,
        batch_clients=batch_clients, local_steps=local_steps, channel=channel,
    )
    return scan_rounds(ROUND_DEFS[algo], ops, x0, keys, num_steps)


def registry_pool_scan(
    algo: str, problems, x0, x_star, hp, state, keys, *,
    num_trials: int, **binding,
):
    """Pool-axis binding: the batched registry scan lifted over a leading
    TENANT axis — many same-shaped federations stepped by one dispatch.

    Every argument carries a leading ``(P,)`` pool axis (`problems` is the
    stacked problem pytree, `hp` the stacked per-trial hparams, `state` the
    stacked ``(P, B, ...)`` round state, `keys` ``(P, n, B)``); the per-tenant
    body is EXACTLY `registry_step_def`'s round scanned `n` steps, so a pooled
    lane replays its standalone session bit-for-bit in expectation and within
    vmap-reassociation tolerance in floats (held at <= 1e-5 with integer-exact
    comm by tests/test_pool.py).  The StepDef — including the prox solver's
    `prepare` (e.g. the spectral eigendecomposition, which vmap batches over
    tenants) — is constructed inside the vmap but OUTSIDE the scan, so
    per-binding setup happens once per chunk, never per round.

    One substrate-level caveat: vmap linearizes the batch-aware anchor-refresh
    `lax.cond(jnp.any(c))` into a select, so a pooled chunk pays the full
    gradient recompute every round (the always-pay form the gate replaces —
    numerically bitwise-identical, see docs/ARCHITECTURE.md).
    """
    rdef = ROUND_DEFS[algo]

    def one(problem, x0_t, x_star_t, hp_t, s, keys_nb):
        ops = make_registry_ops(
            algo, problem, x0_t, x_star_t, hp_t,
            batched=True, num_trials=num_trials, **binding,
        )
        return jax.lax.scan(lambda st, k: rdef.round(ops, st, k), s, keys_nb)

    return jax.vmap(one)(problems, x0, x_star, hp, state, keys)


# ------------------------------------------------- pod (pytree) local solver
def local_prox_gd_tree(
    grad_fn: Callable,
    z,
    y0,
    local_lr,
    inv_eta,
    num_steps: int,
    *,
    update_fn: Callable | None = None,
    g0=None,
):
    """DeepSVRP's K local Algorithm-7 steps over a parameter PYTREE.

    The one local-solve loop the pod step (launch/steps.py), the pytree round
    (`core.deep.deep_svrp_round`) and — in array form — the convex scan/fused
    substrates all execute:  ``y <- update_fn(y, grad_fn(y), z, lr, 1/eta)``.
    ``update_fn`` defaults to `kernels.ops.prox_update_tree`, which fuses the
    whole-tree elementwise update into one batched Pallas launch per dtype
    group when the Pallas path is enabled.  Returns ``(y_K, g_{K-1})`` — the
    last local gradient feeds the pod step's "reuse_local" refresh mode;
    ``g0`` seeds that carry for ``num_steps == 0``.
    """
    if update_fn is None:
        from repro.kernels import ops as kops

        update_fn = kops.prox_update_tree
    if g0 is None:
        g0 = jax.tree.map(jnp.zeros_like, y0)

    def local_step(carry, _):
        y, _g = carry
        g = grad_fn(y)
        return (update_fn(y, g, z, local_lr, inv_eta), g), None

    (y, g_last), _ = jax.lax.scan(local_step, (y0, g0), None, length=num_steps)
    return y, g_last


# ===================================================== fused (Pallas) substrate
#
# Hand-batched execution of the round definitions for the approximate-prox
# (Algorithm 7) solvers: state is (B, d), sampling is vmapped per trial, and
# the local solves go through the batched Pallas kernels so each GD step is
# one fused launch for the whole sweep (per device, under shard="data").
#
# Two per-problem oracles: quadratic-family problems batch the generic
# gradient through the ELEMENTWISE kernel (`kernels.prox_update_batched`, one
# launch per GD step); logistic problems go one level deeper through
# `kernels.logistic_prox_gd_batched`, which keeps the sampled client data
# VMEM-resident and runs the entire Algorithm-7 loop in ONE launch.


def fused_oracle_kind(problem) -> str:
    """Which fused Algorithm-7 oracle this problem supports ("quadratic" /
    "logistic"), raising a clear trace-time error otherwise."""
    if hasattr(problem, "A") and hasattr(problem, "b"):
        return "quadratic"
    if hasattr(problem, "Z") and hasattr(problem, "lam"):
        return "logistic"
    raise ValueError(
        f"fused=True has no batched Pallas prox path for {type(problem).__name__}: "
        "supported oracles are the quadratic family (A/b attrs; generic gradient "
        "through kernels.prox_update_batched) and the logistic family (Z/y/lam "
        "attrs; kernels.logistic_prox_gd_batched) — run with fused=False instead"
    )


def prox_gd_fused(problem, m, z, eta, L, prox_steps: int, interpret: bool | None):
    """The batched Algorithm-7 solve of one fused round: per-row sampled
    client ``m`` (R,), targets ``z`` (R, d), per-row eta/L scalars.  Rows are
    trials for single-client rounds and trial x cohort pairs for minibatch.

    DP-ERM noise fold: a problem exposing ``dp_linear_term(m)`` (the
    per-client objective-perturbation gradient shift s_m) solves
    prox_{eta f^DP}(z) = prox_{eta f}(z - eta s_m) through the SAME kernel —
    shifted target, unshifted start y0 = z, so the iterates match the
    sequential registry solver's (whose oracle carries s_m additively).
    The quadratic branch needs no fold: its noise rides ``problem.grad``."""
    from repro.core.prox import prox_gd_batched

    if fused_oracle_kind(problem) == "logistic":
        from repro.kernels.logistic_prox import logistic_prox_gd_batched

        A = jnp.take(problem.Z, m, axis=0) * jnp.take(problem.y, m, axis=0)[:, :, None]
        beta = 1.0 / (L + 1.0 / eta)
        y0 = None
        z_solve = z
        if hasattr(problem, "dp_linear_term"):
            z_solve = z - eta[:, None] * problem.dp_linear_term(m)
            y0 = z
        return logistic_prox_gd_batched(
            A, z_solve, beta, 1.0 / eta, problem.lam, prox_steps,
            y0=y0, interpret=interpret,
        )
    grad_b = jax.vmap(problem.grad)
    return prox_gd_batched(
        lambda y: grad_b(m, y), z, eta, L, prox_steps,
        use_kernel=True, interpret=interpret,
    )


def _rows(a):
    """(B, b, d) cohort block -> (B*b, d) kernel rows."""
    B, b, d = a.shape
    return a.reshape(B * b, d)


def _fused_ops(algo: str, problem, hp, x_star, x0, B: int, *,
               inner_steps: int, interpret: bool | None,
               cohort_size: int | None = None, channel=None) -> RoundOps:
    """Bind one algorithm's fused substrate: vmapped sampling + Pallas prox."""
    dtype = x0.dtype
    eta = jnp.broadcast_to(jnp.asarray(hp.eta, dtype), (B,))
    L = jnp.broadcast_to(jnp.asarray(getattr(hp, "smoothness", 0.0), dtype), (B,))
    kw: dict[str, Any] = {"cohort_size": cohort_size, "channel": channel}

    if algo in ("sppm", "svrp"):
        kw["prox"] = lambda m, z: prox_gd_fused(
            problem, m, z, eta, L, inner_steps, interpret
        )
    elif algo == "svrp_minibatch":
        def cohort_prox(ms, z):
            b = ms.shape[-1]
            y = prox_gd_fused(
                problem, ms.reshape(-1), _rows(z),
                jnp.repeat(eta, b), jnp.repeat(L, b), inner_steps, interpret,
            )
            return y.reshape(z.shape)

        kw["cohort_prox"] = cohort_prox
    elif algo == "deep_svrp":
        from repro.kernels.prox_update import prox_update_batched

        M = problem.num_clients
        beta_rows = jnp.repeat(
            jnp.broadcast_to(jnp.asarray(hp.local_lr, dtype), (B,)), M
        )
        inv_eta_rows = jnp.repeat(1.0 / eta, M)
        m_rows = jnp.tile(jnp.arange(M), B)
        grad_rows = jax.vmap(problem.grad)

        def local_prox_gd(z, x):
            """All B x M cohort prox loops, one batched Pallas launch per
            GD step (per-row scalars: trial b's local_lr / 1/eta)."""
            z_rows = _rows(z)
            y0 = _rows(jnp.broadcast_to(x[:, None, :], z.shape))

            def body(_, y):
                return prox_update_batched(
                    y, grad_rows(m_rows, y), z_rows, beta_rows, inv_eta_rows,
                    interpret=interpret,
                )

            y = jax.lax.fori_loop(0, inner_steps, body, y0)
            return y.reshape(z.shape)

        kw["local_prox_gd"] = local_prox_gd
    else:
        raise ValueError(f"no fused substrate for algo {algo!r}")

    return RoundOps(problem, hp, x_star, dtype, batched=True, num_trials=B, **kw)


def batched_scan(
    algo: str, problem, x0, x_star, keys, hp, *,
    num_steps: int, inner_steps: int, interpret: bool | None = None, **static,
) -> RunResult:
    """The fused substrate's sweep driver: one hand-batched scan over (B, d)
    state for the whole trial batch.  ``inner_steps`` is the algorithm's
    Algorithm-7 step count (resolved from its AlgoSpec's ``fused_inner_steps``
    static key by the engine, so no caller can pick the wrong one)."""
    B = keys.shape[0]
    if algo == "catalyzed_svrp":
        return _catalyzed_batched_scan(
            problem, x0, x_star, keys, hp,
            num_outer=static["num_outer"], num_steps=num_steps,
            inner_steps=inner_steps, interpret=interpret,
            channel=static.get("channel"),
        )
    ops = _fused_ops(
        algo, problem, hp, x_star, x0, B,
        inner_steps=inner_steps, interpret=interpret,
        cohort_size=static.get("batch_clients"),
        channel=static.get("channel"),
    )
    return scan_rounds(ROUND_DEFS[algo], ops, x0, keys, num_steps)


def _catalyzed_batched_scan(
    problem, x0, x_star, keys, hp, *,
    num_outer: int, num_steps: int, inner_steps: int, interpret: bool | None,
    channel=None,
) -> RunResult:
    """Catalyzed SVRP on the fused substrate: the outer Catalyst recurrence
    hand-batched over (B,) with the inner loop running the SHARED SVRP round
    definition on per-trial shifted oracles.

    The per-trial shift  h_t,m(x) = f_m(x) + gamma_b/2 ||x - y_b||^2  cannot
    be expressed as one shifted problem object (gamma and the prox center
    differ per trial), so the substrate supplies the inner rounds with shifted
    grad/full_grad closures and routes the prox-GD solve through the generic
    elementwise Pallas kernel (`prox_gd_batched`) — the same Algorithm-7 math
    the vmapped substrate runs via the "gd" registry solver on
    ``problem.shifted``.
    """
    from repro.core.prox import prox_gd_batched

    fused_oracle_kind(problem)  # clear trace-time error for unsupported problems
    B = keys.shape[0]
    dtype = x0.dtype
    d = x0.shape[-1]
    mu = jnp.broadcast_to(jnp.asarray(hp.mu, dtype), (B,))
    gamma = jnp.broadcast_to(jnp.asarray(hp.gamma, dtype), (B,))
    eta = jnp.broadcast_to(jnp.asarray(hp.eta, dtype), (B,))
    L = jnp.broadcast_to(jnp.asarray(hp.smoothness, dtype), (B,))
    q = mu / (mu + gamma)
    M = problem.num_clients
    grad_b = jax.vmap(problem.grad)
    full_grad_b = jax.vmap(problem.full_grad)

    stage_keys = jnp.swapaxes(
        jax.vmap(lambda k: jax.random.split(k, num_outer))(keys), 0, 1
    )

    def outer(carry, keys_t):
        x_prev, y_prev, alpha_prev, comm0 = carry

        def grad_sh(m, y):
            return grad_b(m, y) + gamma[:, None] * (y - y_prev)

        def full_grad_sh(w):
            return full_grad_b(w) + gamma[:, None] * (w - y_prev)

        def prox(m, z):
            return prox_gd_batched(
                lambda y: grad_sh(m, y), z, eta, L, inner_steps,
                use_kernel=True, interpret=interpret,
            )

        ops = RoundOps(
            problem, hp, x_star, dtype, batched=True, num_trials=B,
            prox=prox, grad=grad_sh, full_grad=full_grad_sh, channel=channel,
        )

        # Channel state (quant8's EF residual) re-initializes per stage,
        # matching the sequential driver whose inner svrp_scan re-runs
        # _svrp_init each stage.
        state0 = (
            x_prev, x_prev, full_grad_sh(x_prev),
            ops.comm0(3 * M), ops.chan_init(x_prev),
        )
        step_keys = ops.schedule_keys(keys_t, num_steps)
        final, (d2s, comms) = jax.lax.scan(
            lambda s, k: _svrp_round(ops, s, k), state0, step_keys
        )
        x_t = final[0]

        from repro.core.catalyst import catalyst_extrapolate

        alpha_t, beta_t = catalyst_extrapolate(alpha_prev, q)
        y_t = x_t + beta_t[:, None] * (x_t - x_prev)

        comm = comms + comm0[None, :]
        return (x_t, y_t, alpha_t, comm[-1]), (d2s, comm)

    xB = jnp.broadcast_to(x0, (B, d))
    # comm offsets anchor to int32 like the sequential accounting (the inner
    # rounds' `c.astype(int32)` fixes the dtype regardless of x64).
    init = (xB, xB, jnp.sqrt(q), jnp.zeros((B,), dtype=jnp.int32))
    (x_fin, _, _, _), (d2s, comms) = jax.lax.scan(outer, init, stage_keys)
    # (T, K, B) stage-major trajectories -> (B, T*K), matching the sequential
    # driver's concatenated stages.
    to_flat = lambda a: jnp.transpose(a, (2, 0, 1)).reshape(B, -1)
    return RunResult(dist_sq=to_flat(d2s), comm=to_flat(comms), x_final=x_fin)


# =============================================== client-sharded substrate
#
# The fourth substrate: the CLIENT axis (not the trial axis) laid over a 1-D
# device mesh.  Each device holds a contiguous block of client state — data
# rows, DP noise shifts, per-client spectral factors — and the round bodies
# run unchanged inside `shard_map` against `ClientShardedOps` (docs/SCALING.md
# derives the communication model; docs/ARCHITECTURE.md places it in the
# substrate table).
#
# Collective model (held by an HLO assertion in tests/test_client_sharded.py):
#
# * per-client oracles (``grad``/``cohort_grad``) are computed by the OWNER
#   device only and masked to zero elsewhere — NO collective.  The wrong-z
#   prox inputs this leaves on non-owner devices are discarded by the mask
#   below, so correctness never depends on them.
# * the prox result is assembled by ONE ``psum`` per round: owner value plus
#   zeros from everyone else, which is floating-point EXACT (adding zeros),
#   so per-round iterates are bit-identical to the unsharded substrates.
# * the anchor refresh is THE one extra cross-device ``psum`` per refresh
#   event: a masked local sum of per-client gradients inside the batch-aware
#   ``lax.cond`` branch, all-reduced once and divided by the GLOBAL M.  Only
#   here (and in the identical init anchor) does the cross-device summation
#   order differ from the unsharded oracle — the 1e-5 equivalence tolerance
#   of tests/test_substrates.py covers exactly this term.
#
# Non-divisible M pads the client axis with zero blocks: sampling draws from
# the TRUE M (pads are never owners) and ``valid`` masks pads out of every
# client mean, so padding never reaches a result (tests/test_client_sharded.py
# pins this with an M that leaves whole devices pad-only).


class ClientShardedOps(RoundOps):
    """`RoundOps` over a device-resident client block inside ``shard_map``.

    ``local_problem`` is this device's contiguous block of ``M_local``
    clients (global clients ``[axis_index * M_local, ...)``); ``num_clients``
    is the GLOBAL M, so sampling and the Section-4.2 communication accounting
    are identical to every other substrate (comm parity stays integer-exact).
    ``valid`` masks padding rows appended when M does not divide the mesh.
    Keys are replicated, so all devices draw the same clients/coins and the
    PRNG schedule matches the sequential drivers bit-for-bit.
    """

    def __init__(
        self, local_problem, hp, x_star, dtype, *,
        axis: str, num_clients: int, valid, num_trials: int,
        cohort_size: int | None = None, channel=None,
    ):
        super().__init__(
            local_problem, hp, x_star, dtype,
            batched=True, num_trials=num_trials, cohort_size=cohort_size,
            channel=channel,
        )
        self.axis = axis
        self.M_local = local_problem.num_clients
        self.M = num_clients  # GLOBAL M: sampling + comm accounting
        self.valid = valid  # (M_local,) False on padding rows

    def local_index(self, m):
        """Global client ids -> (clamped local row, this-device-owns-it mask)."""
        off = jax.lax.axis_index(self.axis) * self.M_local
        local = m - off
        resident = (local >= 0) & (local < self.M_local)
        return jnp.clip(local, 0, self.M_local - 1), resident

    def masked_psum(self, value, resident):
        """Assemble owner-computed rows: zeros elsewhere make the all-reduce
        exact.  ``resident`` broadcasts against ``value``'s leading axes."""
        resident = jnp.expand_dims(resident, -1)
        return jax.lax.psum(
            jnp.where(resident, value, jnp.zeros_like(value)), self.axis
        )

    def mean_clients(self, y):
        """(B, M_local, d) resident rows -> the GLOBAL client mean broadcast
        back over the local block (so round bodies' ``jnp.mean(axis=-2)``
        reproduces the unsharded mean).  One ``psum``."""
        s = jnp.sum(jnp.where(self.valid[None, :, None], y, 0.0), axis=1)
        ybar = jax.lax.psum(s, self.axis) / self.M
        return jnp.broadcast_to(ybar[:, None, :], y.shape)

    def client_mean(self, y):
        """DeepSVRP's client mean over RESIDENT rows: masked local sum, the
        round's one ``psum``, divide by the global M.  Channel compression of
        the uplink commutes with this assembly: rows are compressed
        independently BEFORE the mean on every substrate, and padding rows
        are masked out of the sum here exactly as in the unsharded mean."""
        s = jnp.sum(jnp.where(self.valid[None, :, None], y, 0.0), axis=1)
        return jax.lax.psum(s, self.axis) / self.M

    # ------------------------------------------------------------- oracles
    def grad(self, m, y):
        """Owner-masked sampled-client gradient — deliberately NOT psummed:
        it only feeds the same client's prox input, whose result the round's
        single ``masked_psum`` assembles."""
        local, resident = self.local_index(m)
        g = jax.vmap(self._grad)(local, y)
        return jnp.where(resident[:, None], g, jnp.zeros_like(g))

    def cohort_grad(self, ms, y):
        if ms.ndim == 1:
            # Full participation (DeepSVRP): the resident client block.  The
            # global ``arange(M)`` ids are implicit — rows here are local.
            local_ids = jnp.arange(self.M_local)
            per_trial = jax.vmap(self._grad, in_axes=(0, None))
            return jax.vmap(per_trial, in_axes=(None, 0))(local_ids, y)
        local, resident = self.local_index(ms)  # (B, b)
        per_trial = jax.vmap(self._grad, in_axes=(0, None))
        g = jax.vmap(per_trial)(local, y)
        return jnp.where(resident[..., None], g, jnp.zeros_like(g))

    def full_grad(self, w):
        """Anchor gradient at per-trial ``w``: masked local client sum, ONE
        ``psum``, divide by the global M.  Exact for every supported oracle
        (the per-client mean IS full_grad, pads contribute nothing)."""
        local_ids = jnp.arange(self.M_local)
        per_trial = jax.vmap(self._grad, in_axes=(0, None))
        rows = jax.vmap(per_trial, in_axes=(None, 0))(local_ids, w)  # (B, M_l, d)
        s = jnp.sum(jnp.where(self.valid[None, :, None], rows, 0.0), axis=1)
        return jax.lax.psum(s, self.axis) / self.M

    def init_full_grad(self, x0):
        """The round-0 anchor: same masked-sum + one-psum assembly as the
        refresh events, on the trial-shared ``x0``."""
        rows = jax.vmap(self._grad, in_axes=(0, None))(
            jnp.arange(self.M_local), x0
        )
        s = jnp.sum(jnp.where(self.valid[:, None], rows, 0.0), axis=0)
        return self.tile(jax.lax.psum(s, self.axis) / self.M)


def make_client_sharded_ops(
    algo: str, local_problem, x0, x_star, hp, *,
    axis: str, num_clients: int, valid, num_trials: int,
    fused: bool = False, inner_steps: int | None = None, interpret: bool | None = None,
    prox_solver: str = "exact", prox_steps: int = 50, prox_tol: float = 1e-10,
    batch_clients: int | None = None, local_steps: int | None = None,
    channel=None,
) -> ClientShardedOps:
    """Bind one rounds-defined algorithm to the client-sharded substrate.

    Mirrors `make_registry_ops` (registry prox solvers prepared on the LOCAL
    block — e.g. the spectral eigh factorizes only resident clients) and
    `_fused_ops` (``fused=True``: the batched Pallas kernels launched
    per-device over resident client tiles), wrapping every local solve in the
    owner-mask + single-``psum`` assembly described above.
    """
    from repro.core.prox import get_prox_solver

    B = num_trials
    dtype = x0.dtype
    ops = ClientShardedOps(
        local_problem, hp, x_star, dtype,
        axis=axis, num_clients=num_clients, valid=valid, num_trials=B,
        cohort_size=batch_clients, channel=channel,
    )
    eta = jnp.broadcast_to(jnp.asarray(hp.eta, dtype), (B,))

    if algo == "deep_svrp":
        if fused:
            from repro.kernels.prox_update import prox_update_batched

            M_l = ops.M_local
            beta_rows = jnp.repeat(
                jnp.broadcast_to(jnp.asarray(hp.local_lr, dtype), (B,)), M_l
            )
            inv_eta_rows = jnp.repeat(1.0 / eta, M_l)
            m_rows = jnp.tile(jnp.arange(M_l), B)
            grad_rows = jax.vmap(local_problem.grad)

            def local_prox_gd(z, x):
                # Resident tile rows through the batched Pallas kernel — one
                # launch per GD step per device, no collective inside.
                z_rows = _rows(z)
                y0 = _rows(jnp.broadcast_to(x[:, None, :], z.shape))

                def body(_, y):
                    return prox_update_batched(
                        y, grad_rows(m_rows, y), z_rows, beta_rows,
                        inv_eta_rows, interpret=interpret,
                    )

                y = jax.lax.fori_loop(0, inner_steps, body, y0)
                # Raw resident rows: the round body's ``ops.client_mean``
                # (one masked psum) assembles the global mean AFTER the
                # uplink channel compresses each row.
                return y.reshape(z.shape)
        else:
            from repro.kernels.ref import prox_update_batched as _prox_ref_b

            beta = jnp.broadcast_to(jnp.asarray(hp.local_lr, dtype), (B,))
            inv_eta = 1.0 / eta
            grad_cohort = jax.vmap(jax.vmap(local_problem.grad))
            local_ids = jnp.arange(ops.M_local)

            def local_prox_gd(z, x):  # (B, M_local, d) targets
                ms = jnp.broadcast_to(local_ids, (B, ops.M_local))

                def local(y, _):
                    return _prox_ref_b(y, grad_cohort(ms, y), z, beta, inv_eta), None

                y0 = jnp.broadcast_to(x[:, None, :], z.shape)
                y, _ = jax.lax.scan(local, y0, None, length=local_steps)
                return y  # rows; ops.client_mean assembles the global mean

        ops.local_prox_gd = local_prox_gd
        return ops

    if fused:
        def solve_rows(m_r, z_r, eta_r, L_r):
            return prox_gd_fused(
                local_problem, m_r, z_r, eta_r, L_r, inner_steps, interpret
            )
    else:
        solver = get_prox_solver(prox_solver, local_problem)
        factors = solver.prepare(local_problem)

        def solve_rows(m_r, z_r, eta_r, L_r):
            def one(m, z, e, s):
                return solver.solve(
                    local_problem, factors, m, z, e,
                    smoothness=s, steps=prox_steps, tol=prox_tol,
                )

            return jax.vmap(one)(m_r, z_r, eta_r, L_r)

    L = jnp.broadcast_to(jnp.asarray(getattr(hp, "smoothness", 0.0), dtype), (B,))

    if algo == "svrp_minibatch":
        def cohort_prox(ms, z):  # (B, b), (B, b, d)
            local, resident = ops.local_index(ms)
            b = ms.shape[-1]
            y = solve_rows(
                local.reshape(-1), _rows(z), jnp.repeat(eta, b), jnp.repeat(L, b)
            ).reshape(z.shape)
            return ops.masked_psum(y, resident)

        ops.cohort_prox = cohort_prox
    else:
        def prox(m, z):
            local, resident = ops.local_index(m)
            return ops.masked_psum(solve_rows(local, z, eta, L), resident)

        ops.prox = prox
    return ops


def client_sharded_scan(
    algo: str, local_problem, x0, x_star, keys, hp, *,
    axis: str, num_clients: int, valid, num_steps: int, **binding,
) -> RunResult:
    """Run one rounds-defined algorithm on the client-sharded substrate (the
    per-device body of ``run_batch(shard="clients")`` — already inside
    ``shard_map``; ``binding`` forwards to `make_client_sharded_ops`)."""
    ops = make_client_sharded_ops(
        algo, local_problem, x0, x_star, hp,
        axis=axis, num_clients=num_clients, valid=valid,
        num_trials=keys.shape[0], **binding,
    )
    return scan_rounds(ROUND_DEFS[algo], ops, x0, keys, num_steps)


def client_sharded_step_def(
    algo: str, local_problem, x0, x_star, hp, *,
    axis: str, num_clients: int, valid, num_trials: int, **binding,
):
    """The client-sharded substrate's incremental unit for the session layer
    (`repro.serve.FedSession` with ``substrate="clients"``)."""
    from repro.core.types import StepDef

    ops = make_client_sharded_ops(
        algo, local_problem, x0, x_star, hp,
        axis=axis, num_clients=num_clients, valid=valid,
        num_trials=num_trials, **binding,
    )
    rdef = ROUND_DEFS[algo]
    return StepDef(
        init=lambda: rdef.init(ops, x0),
        step=lambda s, k: rdef.round(ops, s, k),
        final=lambda s: s[0],
    )
