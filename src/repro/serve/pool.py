"""Multi-tenant session pool: many federations, ONE dispatch per tick.

A `FedSession` keeps one sweep device-resident and steps it a chunk at a
time — but every concurrently-open session costs its own jitted dispatch per
chunk, so N tenants run at ~1/N device utilization on these small
bandwidth-bound rounds.  `SessionPool` packs up to `capacity` tenants'
sessions (same algorithm + problem SHAPES; independent problems,
hyperparameters, seeds, horizons and `stop_eps`) into one stacked
device-resident state with a leading `(P,)` pool axis, and advances ALL of
them with a single jitted, donated chunk per `step(n)`:

    pool = SessionPool(capacity=8)
    a = pool.admit("svrp", problem_a, grid={"eta": 1e-2, "p": 0.1},
                   seeds=4, num_steps=500)
    b = pool.admit("svrp", problem_b, grid={"eta": 3e-3, "p": 0.1},
                   seeds=4, num_steps=200, stop_eps=1e-9)
    pool.step(50)          # one dispatch advances BOTH tenants 50 rounds
    pool.result(a)         # per-tenant BatchResult, == standalone session

The per-tenant round body is EXACTLY the batched substrate's
(`session.batched_scan_body` / `core.rounds.registry_pool_scan` — the pool
axis is a vmap over it), so a pooled lane reproduces its standalone
`FedSession` trajectory to <= 1e-5 with integer-exact `comm`/`comm_bytes`
(held for every `ALGOS` entry by tests/test_pool.py).

The tick is ONE dispatch for real, not just one jit call among host chores:
the per-tenant key schedules live in a device-resident `(P, B, Hmax)` buffer
whose n-round windows are sliced INSIDE the jit from a traced cursor array,
and the tick's pooled (d2, comm) outputs are drained into per-tenant
trajectories lazily (`session()`/`result()`/`evict`, or per tick only for
tenants with a `stop_eps` to check) — the serving loop itself does no
per-tenant host work at all.

Lane lifecycle: slots are admitted and evicted freely mid-run; an admitted
tenant starts its OWN key schedule at round 0 (schedules are per-session,
materialized at open — joining late never shifts anyone's randomness).
Unoccupied and frozen lanes are zero-padded and carried through the chunk
under one traced `(P,)` active mask — their outputs are masked to zero
(nothing reaches any tenant's stats or the bytes ledger) and their state is
held, so eviction, per-tenant `stop_eps` freezing, and admission never change
the chunk's trace signature — no recompile, after the first step at a given
chunk length, with ONE exception: admitting a tenant whose horizon exceeds
every earlier tenant's grows the key buffer (one retrace).

Serving integration: `FedRoundServer(pool=...)` drives the pool tick-by-tick
with the same `pipeline_depth`-deep stats readback the streaming server uses.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.channel import wire_vector_bytes
from repro.core.rounds import ROUND_DEFS, registry_pool_scan
from repro.experiments.runner import (
    BatchResult,
    check_pool_entry,
    pool_entry_signature,
)
from repro.experiments.spec import _POOL_HORIZON_KEYS, as_runspec
from repro.serve.session import _REGISTRY_BINDING, FedSession, batched_scan_body


def _is_key_dtype(a) -> bool:
    return jnp.issubdtype(jnp.result_type(a), jax.dtypes.prng_key)


def _zero_lanes(leaf, capacity: int):
    """A `(capacity,) + leaf.shape` all-zero stack (zero key-data for typed
    PRNG leaves — a valid, if degenerate, key; inactive lanes are masked out
    regardless of what they compute)."""
    if _is_key_dtype(leaf):
        raw = jax.random.key_data(leaf)
        return jax.random.wrap_key_data(
            jnp.zeros((capacity,) + raw.shape, raw.dtype)
        )
    return jnp.zeros((capacity,) + jnp.shape(leaf), jnp.result_type(leaf))


def _lane_set(stacked, slot: int, value):
    return jax.tree.map(lambda a, v: a.at[slot].set(v), stacked, value)


def _lane_get(stacked, slot: int):
    return jax.tree.map(lambda a: a[slot], stacked)


def _select_lanes(active, new, old):
    """Per-lane select: active lanes take the chunk's new state, inactive
    lanes hold their old (zero-padded) state bit-for-bit."""

    def sel(n, o):
        if _is_key_dtype(n):
            rn, ro = jax.random.key_data(n), jax.random.key_data(o)
            m = active.reshape((active.shape[0],) + (1,) * (rn.ndim - 1))
            return jax.random.wrap_key_data(jnp.where(m, rn, ro))
        m = active.reshape((active.shape[0],) + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)

    return jax.tree.map(sel, new, old)


@functools.lru_cache(maxsize=None)
def _pool_chunk_fn(algo: str, pool_static_items: tuple):
    """The ONE jitted pool dispatch: every tenant's n-round scan under a
    pool-axis vmap, inactive lanes masked.  Cached per (algo, round-body
    static config) — the horizon keys are excluded from `pool_static_items`
    (tenants step different horizons through the same compilation).

    The per-lane key schedules live in a device-resident `(P, B, Hmax)`
    buffer and each lane's n-round window is sliced INSIDE the jit from a
    traced `(P,)` cursor array — the serving tick does no host-side key
    slicing/stacking, so one `step` really is one dispatch."""
    if algo in ROUND_DEFS:
        cfg = dict(pool_static_items)
        binding = {k: cfg[k] for k in _REGISTRY_BINDING if k in cfg}

        def stacked(problems, x0, x_star, hp, state, keys_pnb):
            return registry_pool_scan(
                algo, problems, x0, x_star, hp, state, keys_pnb,
                num_trials=keys_pnb.shape[2], **binding,
            )

    else:
        scan_chunk = batched_scan_body(algo, pool_static_items)
        stacked = jax.vmap(scan_chunk)

    def chunk(n, problems, x0, x_star, hp, state, keys_buf, cursors, active):
        # Each lane's (B, n) key window, from its own cursor (frozen and
        # empty lanes slice in-bounds garbage — their outputs are masked).
        kd = jax.random.key_data(keys_buf)
        keys_pbn = jax.random.wrap_key_data(
            jax.vmap(
                lambda lane, c: jax.lax.dynamic_slice_in_dim(lane, c, n, axis=1)
            )(kd, cursors)
        )
        new_state, (d2, comm) = stacked(
            problems, x0, x_star, hp, state, jnp.swapaxes(keys_pbn, 1, 2)
        )
        d2 = jnp.swapaxes(d2, 1, 2)
        comm = jnp.swapaxes(comm, 1, 2)
        # The active mask is TRACED data: admission, eviction and stop_eps
        # freezing flip lanes without changing the trace signature.  Cursors
        # advance on-device too (parked for inactive lanes) — steady-state
        # ticks upload nothing.
        new_state = _select_lanes(active, new_state, state)
        new_cursors = jnp.where(active, cursors + n, cursors)
        d2 = jnp.where(active[:, None, None], d2, jnp.zeros_like(d2))
        comm = jnp.where(active[:, None, None], comm, jnp.zeros_like(comm))
        return new_state, new_cursors, (d2, comm)

    return jax.jit(chunk, static_argnums=0, donate_argnums=(5, 7))


@dataclasses.dataclass
class PoolTenant:
    """One admitted session's pool-side bookkeeping (internal)."""

    id: int
    slot: int
    session: FedSession
    stop_eps: float | None = None
    frozen: bool = False  # stop_eps reached (or frozen by the server): lane
    #                        masked out, key cursor parked — resumable state
    evicted: bool = False
    # Indices (absolute, pool-lifetime) of pooled (d2, comm) blocks this
    # tenant's session has not yet sliced its lane out of — the serving tick
    # appends an index here; the per-tenant readback happens on demand
    # (`SessionPool._drain`), never inside the tick.
    pending: list[int] = dataclasses.field(default_factory=list)

    @property
    def running(self) -> bool:
        return not self.frozen and not self.evicted


class SessionPool:
    """Up to `capacity` tenants' sessions stepped by one dispatch per tick.

    See the module docstring for the contract.  `admit` accepts exactly what
    `open_session` accepts (a `RunSpec` or the legacy keyword style) — the
    tenant is validated through the same `as_runspec`/`RunSpec.resolve` path,
    then checked for pool compatibility (`experiments.spec.check_pool_entry`):
    every tenant shares the pool's single jitted chunk, so the algorithm,
    round-body static config, trial count and problem/x0/x_star shapes must
    match the first admit; hyperparameters, problems, seeds, horizons and
    `stop_eps` vary freely."""

    def __init__(self, capacity: int, *, pipeline_depth: int = 2) -> None:
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        self.capacity = capacity
        self.pipeline_depth = pipeline_depth
        self._slots: list[PoolTenant | None] = [None] * capacity
        self._tenants: dict[int, PoolTenant] = {}  # every tenant ever admitted
        self._next_id = 0
        self._signature: tuple | None = None
        self._algo: str | None = None
        self._pool_static_items: tuple | None = None
        # Stacked (P,)-leading pytrees; built lazily on the first admit.
        self._problems = None
        self._x0 = None
        self._x_star = None
        self._hp = None
        self._state = None
        self._keys_buf = None  # (P, B, Hmax) typed-key buffer, device-resident
        self._hmax = 0  # the buffer's horizon axis (max over admitted tenants)
        # Pooled (d2, comm) output blocks not yet drained into every tenant's
        # session (see PoolTenant.pending); `_block_offset` maps the absolute
        # pending indices into this list after compaction.
        self._blocks: list[tuple[jax.Array, jax.Array]] = []
        self._block_offset = 0
        # Device mirrors of the lanes' (cursor, active) rows — rebuilt from
        # the tenant table only when a lifecycle event (admit/evict/freeze)
        # dirties them; steady-state ticks reuse the chunk's own outputs.
        self._cursors_dev = None
        self._active_dev = None
        self._lanes_dirty = True

    # ------------------------------------------------------------- admission
    def admit(
        self,
        algo,
        problem=None,
        grid: Mapping[str, Any] | None = None,
        seeds: int | Sequence[int] = 1,
        *,
        stop_eps: float | None = None,
        x0=None,
        x_star=None,
        stepsize: str | None = None,
        target_eps: float = 1e-6,
        theory_constants: Any = None,
        **static,
    ) -> int:
        """Admit one tenant into a free slot; returns its tenant id.

        Mid-run admission is safe by construction: the new tenant's key
        schedule is its own (materialized at open, starting at round 0), and
        existing lanes' state is untouched."""
        spec = as_runspec(
            algo, grid=grid, seeds=seeds, x0=x0, x_star=x_star,
            stepsize=stepsize, target_eps=target_eps,
            theory_constants=theory_constants, substrate=None, static=static,
        )
        if spec.substrate not in (None, "batched"):
            raise ValueError(
                f"SessionPool packs the batched substrate only; "
                f"got substrate={spec.substrate!r}"
            )
        spec = dataclasses.replace(spec, substrate="batched")
        session = FedSession(spec, problem)
        sig = pool_entry_signature(
            session._algo, session._cfg, session._B,
            session._problem, session._x0, session._x_star,
        )
        if self._signature is None:
            self._install_signature(sig, session)
        else:
            check_pool_entry(self._signature, sig)
        slot = next(
            (i for i, t in enumerate(self._slots) if t is None), None
        )
        if slot is None:
            raise ValueError(
                f"pool is full ({self.capacity} slots); evict a tenant first"
            )
        tenant = PoolTenant(
            id=self._next_id, slot=slot, session=session, stop_eps=stop_eps
        )
        self._next_id += 1
        self._slots[slot] = tenant
        self._tenants[tenant.id] = tenant
        self._problems = _lane_set(self._problems, slot, session._problem)
        self._x0 = self._x0.at[slot].set(session._x0)
        self._x_star = self._x_star.at[slot].set(session._x_star)
        self._hp = _lane_set(self._hp, slot, session._hp)
        self._state = _lane_set(self._state, slot, session._state)
        self._write_key_lane(slot, session)
        self._lanes_dirty = True
        return tenant.id

    def _write_key_lane(self, slot: int, session: FedSession) -> None:
        """Copy the tenant's whole key schedule into its buffer lane
        (zero-padded if shorter than the buffer's horizon).  A tenant whose
        horizon EXCEEDS every earlier tenant's re-pads the buffer — the one
        admission event that changes the chunk's trace signature (one
        retrace); same-or-shorter horizons, eviction, and freezing never do."""
        buf = jax.random.key_data(self._keys_buf)
        lane = jax.random.key_data(session._keys)
        h = lane.shape[1]
        if h > self._hmax:
            pad = [(0, 0)] * buf.ndim
            pad[2] = (0, h - self._hmax)
            buf = jnp.pad(buf, pad)
            self._hmax = h
        elif h < self._hmax:
            pad = [(0, 0)] * lane.ndim
            pad[1] = (0, self._hmax - h)
            lane = jnp.pad(lane, pad)
        self._keys_buf = jax.random.wrap_key_data(buf.at[slot].set(lane))

    def _install_signature(self, sig: tuple, session: FedSession) -> None:
        self._signature = sig
        self._algo = session._algo
        self._pool_static_items = tuple(
            (k, v)
            for k, v in session._static_items
            if k not in _POOL_HORIZON_KEYS
        )
        P = self.capacity
        self._problems = jax.tree.map(
            lambda a: _zero_lanes(a, P), session._problem
        )
        self._x0 = _zero_lanes(session._x0, P)
        self._x_star = _zero_lanes(session._x_star, P)
        self._hp = jax.tree.map(lambda a: _zero_lanes(a, P), session._hp)
        self._state = jax.tree.map(lambda a: _zero_lanes(a, P), session._state)
        raw = jax.random.key_data(session._keys)
        self._hmax = session.horizon
        self._keys_buf = jax.random.wrap_key_data(
            jnp.zeros((P,) + raw.shape, raw.dtype)
        )
        d = int(np.prod(np.asarray(jnp.shape(session._x0))))
        self.wire_bytes_per_vector = wire_vector_bytes(
            session._cfg.get("channel"), d, session._x0.dtype.itemsize
        )
        # Analytic per-round FLOPs model (repro.core.flops) — valid for every
        # tenant, because admission requires the same (algo, statics, problem
        # shapes) signature the model is derived from.
        from repro.core.flops import round_model

        self.flops_model = round_model(
            self._algo, session._problem,
            **{k: v for k, v in session._cfg.items() if k != "prox_R"},
        )

    # -------------------------------------------------------------- stepping
    def step(self, n: int = 1) -> tuple[jax.Array, jax.Array]:
        """Advance every running tenant `n` rounds with ONE jitted dispatch;
        returns the pooled `(P, B, n)` dist-sq and cumulative-comm blocks
        (inactive lanes zero).  Raises the session's past-horizon error,
        prefixed with the offending tenant id, if any running tenant's key
        schedule cannot cover `n` more rounds."""
        if n < 1:
            raise ValueError(f"step(n={n}): n must be >= 1")
        running = [t for t in self._slots if t is not None and t.running]
        if not running:
            raise ValueError(
                "pool has no running tenants — admit() one (or un-freeze via "
                "evict+admit) before stepping"
            )
        for t in running:
            ses = t.session
            if ses.t + n > ses.horizon:
                raise ValueError(
                    f"pool tenant {t.id}: session horizon exhausted: "
                    f"{ses.t} rounds done, {n} more requested, horizon "
                    f"{ses.horizon}.  The PRNG key schedule is fixed at open "
                    "(split is not prefix-stable) — evict the tenant and "
                    "admit a new session with a larger round budget."
                )
        if self._lanes_dirty:
            cursors = np.zeros(self.capacity, dtype=np.int32)
            active = np.zeros(self.capacity, dtype=bool)
            for slot in range(self.capacity):
                t = self._slots[slot]
                if t is not None and t.running:
                    active[slot] = True
                    cursors[slot] = t.session.t
            self._cursors_dev = jnp.asarray(cursors)
            self._active_dev = jnp.asarray(active)
            self._lanes_dirty = False
        chunk = _pool_chunk_fn(self._algo, self._pool_static_items)
        self._state, self._cursors_dev, (d2, comm) = chunk(
            n, self._problems, self._x0, self._x_star, self._hp,
            self._state, self._keys_buf, self._cursors_dev, self._active_dev,
        )
        self._blocks.append((d2, comm))
        idx = self._block_offset + len(self._blocks) - 1
        for t in running:
            t.pending.append(idx)
            t.session._t += n
            if t.stop_eps is not None:
                self._drain(t)
                if t.session._all_reached(t.stop_eps):
                    t.frozen = True  # lane masked from the next chunk on
                    self._lanes_dirty = True
        return d2, comm

    def freeze_exhausted(self, n: int = 1) -> int:
        """Freeze every running tenant whose key schedule cannot cover `n`
        more rounds (the serving loop's graceful alternative to `step`'s
        past-horizon error); returns how many tenants remain running."""
        count = 0
        for t in self._slots:
            if t is None or not t.running:
                continue
            if t.session.t + n > t.session.horizon:
                t.frozen = True
                self._lanes_dirty = True
            else:
                count += 1
        return count

    # ------------------------------------------------------------- lifecycle
    def evict(self, tenant_id: int) -> FedSession:
        """Release a tenant's slot (state written back into its standalone
        `FedSession`, which is returned fully usable); the lane is zeroed and
        contributes nothing until re-admitted."""
        t = self._require(tenant_id)
        if t.evicted:
            raise ValueError(f"tenant {tenant_id} already evicted")
        self._sync(t)
        zero = jax.tree.map(lambda a: _zero_lanes(a, 1)[0], t.session._state)
        self._state = _lane_set(self._state, t.slot, zero)
        t.evicted = True
        self._slots[t.slot] = None
        self._lanes_dirty = True
        return t.session

    def session(self, tenant_id: int) -> FedSession:
        """The tenant's `FedSession`, state synced from its pool lane."""
        t = self._require(tenant_id)
        self._sync(t)
        return t.session

    def result(self, tenant_id: int) -> BatchResult:
        """The tenant's rounds-so-far as a `BatchResult` — same layout (and,
        per tests/test_pool.py, same values) as its standalone session's."""
        return self.session(tenant_id).result()

    def _sync(self, t: PoolTenant) -> None:
        self._drain(t)
        if not t.evicted:
            t.session._state = _lane_get(self._state, t.slot)

    def _drain(self, t: PoolTenant) -> None:
        """Slice the tenant's lane out of every pooled block it is still
        pending on, into its session's trajectory — the on-demand half of the
        tick's deferred readback."""
        if not t.pending:
            return
        for idx in t.pending:
            d2, comm = self._blocks[idx - self._block_offset]
            t.session._d2.append(d2[t.slot])
            t.session._comm.append(comm[t.slot])
        t.pending.clear()
        self._compact()

    def _compact(self) -> None:
        """Drop pooled blocks every tenant has drained."""
        live = [t.pending[0] for t in self._tenants.values() if t.pending]
        keep_from = min(live) if live else self._block_offset + len(self._blocks)
        drop = keep_from - self._block_offset
        if drop > 0:
            del self._blocks[:drop]
            self._block_offset = keep_from

    def _require(self, tenant_id: int) -> PoolTenant:
        if tenant_id not in self._tenants:
            raise KeyError(
                f"unknown tenant id {tenant_id}; "
                f"known: {sorted(self._tenants)}"
            )
        return self._tenants[tenant_id]

    # ------------------------------------------------------------ inspection
    @property
    def num_resident(self) -> int:
        return sum(t is not None for t in self._slots)

    @property
    def num_running(self) -> int:
        return sum(t is not None and t.running for t in self._slots)

    @property
    def active_mask(self) -> np.ndarray:
        """(P,) — which lanes the next chunk will actually advance."""
        return np.asarray(
            [t is not None and t.running for t in self._slots], dtype=bool
        )

    def tenant_ids(self, *, resident_only: bool = False) -> list[int]:
        if resident_only:
            return sorted(t.id for t in self._slots if t is not None)
        return sorted(self._tenants)

    def is_frozen(self, tenant_id: int) -> bool:
        return self._require(tenant_id).frozen

    @property
    def total_rounds(self) -> int:
        """Rounds executed across every tenant ever admitted."""
        return sum(t.session.t for t in self._tenants.values())

    @property
    def total_comm_bytes(self) -> int:
        """Wire bytes across every tenant ever admitted (each tenant's own
        int64 ledger, summed over trials) — masked lanes contributed zero."""
        total = 0
        for t in self._tenants.values():
            self._drain(t)
            if t.session.t:
                total += int(t.session.comm_bytes[:, -1].sum())
        return total

    @property
    def total_flops(self) -> float:
        """Analytic FLOPs across every tenant ever admitted — the compute
        mirror of `total_comm_bytes` (exact per trial; see
        `repro.core.flops.ledger_flops` and docs/PERFORMANCE.md)."""
        total = 0.0
        for t in self._tenants.values():
            self._drain(t)
            if t.session.t:
                total += float(t.session.flops[:, -1].sum())
        return total
