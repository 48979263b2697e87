"""Multi-tenant SessionPool suite: one dispatch, per-tenant exactness.

The pool (`repro.serve.SessionPool`) packs many same-shaped sessions into one
stacked device state and steps them all with a single jitted chunk.  This
suite is the gate that makes pooling invisible to every tenant — for EVERY
`ALGOS` entry:

    pooled lane  ==  standalone FedSession

to <= 1e-5 in values with `comm`/`comm_bytes` integer- and dtype-EXACT,
with two tenants on different hyperparameters packed together and stepped in
deliberately uneven chunks.  On top of that contract:

* mid-run admission starts the new tenant's OWN key schedule at round 0
  (joining late shifts nobody's randomness);
* unoccupied and evicted lanes contribute exactly zero to the pooled outputs
  and to the bytes ledger;
* per-tenant `stop_eps` freezes only its own lane, without a recompile;
* mixed-horizon stepping raises the session's past-horizon error per tenant;
* admission validation (shared `RunSpec` path + `check_pool_entry`) rejects
  un-poolable tenants field by field;
* every state-carrying dispatch (session, pool, server) really donates its
  input state, so the CPU suite runs the chip's buffer lifecycle;
* `FedRoundServer(pool=...)` multiplexes tenants with pipelined readback.

A new ALGOS entry fails `test_every_algo_has_a_pool_case` until wired in.
"""
import copy

import jax
import numpy as np
import pytest

from repro.core import (
    catalyst_inner_iterations,
    composite_minimizer_pgd,
    prox_l2ball,
    theorem2_stepsize,
    theorem3_gamma,
)
from repro.experiments import ALGOS
from repro.experiments.spec import check_pool_entry, pool_entry_signature
from repro.problems import make_synthetic_quadratic
from repro.serve import (
    FedRoundServer,
    SessionPool,
    open_session,
)

M = 10
SEEDS = 2


@pytest.fixture(scope="module")
def prob():
    return make_synthetic_quadratic(num_clients=M, dim=6, mu=1.0, L=80.0,
                                    delta=4.0, seed=1)


@pytest.fixture(scope="module")
def prob2():
    """Same SHAPES as `prob`, different data — poolable by construction."""
    return make_synthetic_quadratic(num_clients=M, dim=6, mu=1.0, L=80.0,
                                    delta=4.0, seed=7)


@pytest.fixture(scope="module")
def cases(prob):
    """Per-algorithm tenant configs (the test_session case table, reused)."""
    mu = float(prob.strong_convexity())
    delta = float(prob.similarity())
    dmax = float(prob.similarity_max())
    L = float(prob.smoothness_max())
    eta = theorem2_stepsize(mu, delta)
    gamma = max(theorem3_gamma(mu, delta, M), 0.5)
    inner = min(catalyst_inner_iterations(mu, delta, M), 12)
    eta_in = theorem2_stepsize(mu + gamma, delta)
    beta_deep = 0.8 / (L + 2.0)
    prox_R = prox_l2ball(0.1)
    x_star_c = composite_minimizer_pgd(
        prob, prox_R, L=float(prob.smoothness()), num_steps=20_000
    )
    return {
        "sppm": dict(grid={"eta": [0.05, 0.1]}, seeds=SEEDS, num_steps=12),
        "svrp": dict(grid={"eta": [eta, eta / 2], "p": 0.2}, seeds=SEEDS,
                     num_steps=12),
        "svrp_minibatch": dict(grid={"eta": 3 * eta, "p": 0.25}, seeds=SEEDS,
                               num_steps=12, batch_clients=3),
        "catalyzed_svrp": dict(
            grid={"mu": mu, "gamma": gamma, "eta": eta_in, "p": 1 / M},
            seeds=SEEDS, num_outer=2, inner_steps=inner),
        "deep_svrp": dict(
            grid={"eta": 0.5, "local_lr": beta_deep, "anchor_prob": 0.25},
            seeds=SEEDS, num_steps=12, local_steps=4),
        "sgd": dict(grid={"stepsize": 1 / (3 * L)}, seeds=SEEDS, num_steps=12),
        "svrg": dict(grid={"stepsize": 1 / (6 * L), "p": 0.2}, seeds=SEEDS,
                     num_steps=12),
        "scaffold": dict(grid={"local_lr": 1 / (4 * L)}, seeds=SEEDS,
                         num_rounds=12, local_steps=4),
        "dane": dict(grid={"theta": dmax}, num_rounds=8),
        "acc_extragradient": dict(grid={"theta": dmax, "mu": mu}, num_rounds=8),
        "composite": dict(
            grid={"eta": [eta, eta / 2], "p": 0.2, "smoothness": L, "mu": mu},
            seeds=SEEDS, num_steps=12, prox_R=prox_R, x_star=x_star_c),
    }


def _variant(kw):
    """A second tenant config: same shapes/static config, different
    hyperparameters — scales the first grid axis by 0.9."""
    kw = copy.copy(kw)
    grid = dict(kw["grid"])
    name = next(iter(grid))
    v = grid[name]
    grid[name] = [x * 0.9 for x in v] if isinstance(v, list) else v * 0.9
    kw["grid"] = grid
    return kw


def _assert_tenant_equal(pool_res, session):
    np.testing.assert_allclose(
        np.asarray(pool_res.dist_sq), np.asarray(session.dist_sq),
        rtol=1e-5, atol=1e-24,
    )
    np.testing.assert_array_equal(
        np.asarray(pool_res.comm), np.asarray(session.comm)
    )
    assert pool_res.comm.dtype == session.comm.dtype
    np.testing.assert_array_equal(pool_res.comm_bytes, session.comm_bytes)
    assert pool_res.comm_bytes.dtype == session.comm_bytes.dtype
    np.testing.assert_allclose(
        np.asarray(pool_res.x_final), np.asarray(session.x()),
        rtol=1e-5, atol=1e-12,
    )


def test_every_algo_has_a_pool_case(cases):
    """A new ALGOS entry must be wired into this suite to land."""
    assert set(cases) == set(ALGOS)


# ---------------------------------------------------------------------------
# Tentpole contract: pooled lane == standalone FedSession, every algorithm.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", sorted(ALGOS))
def test_pooled_lane_matches_standalone_session(algo, prob, cases):
    kw, kw2 = cases[algo], _variant(cases[algo])
    pool = SessionPool(capacity=3)  # one lane deliberately left unoccupied
    a = pool.admit(algo, prob, **kw)
    b = pool.admit(algo, prob, **kw2)

    ref_a = open_session(algo, prob, **kw)
    ref_b = open_session(algo, prob, **kw2)
    horizon = ref_a.horizon

    # Uneven chunks so boundaries cross refreshes / catalyst stages.
    k1 = max(1, horizon // 3)
    d2, comm = pool.step(k1)
    assert d2.shape == (3, ref_a.num_trials, k1)
    assert comm.shape == d2.shape
    pool.step(horizon - k1)
    ref_a.step(k1)
    ref_a.step(horizon - k1)
    ref_b.step(horizon)

    _assert_tenant_equal(pool.result(a), ref_a)
    _assert_tenant_equal(pool.result(b), ref_b)
    # The unoccupied lane contributed nothing.
    np.testing.assert_array_equal(np.asarray(d2)[2], 0.0)
    np.testing.assert_array_equal(np.asarray(comm)[2], 0)


def test_pool_handles_distinct_problems(prob, prob2, cases):
    """Tenants solve DIFFERENT federations (same shapes) side by side."""
    kw = cases["svrp"]
    pool = SessionPool(capacity=2)
    a = pool.admit("svrp", prob, **kw)
    b = pool.admit("svrp", prob2, **kw)
    pool.step(12)
    ra = open_session("svrp", prob, **kw)
    rb = open_session("svrp", prob2, **kw)
    ra.step(12)
    rb.step(12)
    _assert_tenant_equal(pool.result(a), ra)
    _assert_tenant_equal(pool.result(b), rb)


def test_mid_run_admission_resumes_correct_key_schedule(prob, cases):
    """A tenant admitted after the pool has stepped replays its OWN schedule
    from round 0 — and the incumbents' trajectories are unchanged."""
    kw, kw2 = cases["svrp"], _variant(cases["svrp"])
    pool = SessionPool(capacity=2)
    a = pool.admit("svrp", prob, **kw)
    pool.step(7)
    b = pool.admit("svrp", prob, **kw2)
    pool.step(5)  # a reaches its 12-round horizon; b is at round 5

    ra = open_session("svrp", prob, **kw)
    ra.step(12)
    rb = open_session("svrp", prob, **kw2)
    rb.step(5)
    _assert_tenant_equal(pool.result(a), ra)
    _assert_tenant_equal(pool.result(b), rb)


# ---------------------------------------------------------------------------
# Masked lanes: zero contribution from empty/evicted slots, stop_eps freeze.
# ---------------------------------------------------------------------------

def test_evicted_lane_contributes_zero_bytes(prob, cases):
    kw, kw2 = cases["svrp"], _variant(cases["svrp"])
    pool = SessionPool(capacity=2)
    a = pool.admit("svrp", prob, **kw)
    b = pool.admit("svrp", prob, **kw2)
    pool.step(6)
    bytes_a = int(pool.session(a).comm_bytes[:, -1].sum())
    ses_a = pool.evict(a)
    d2, comm = pool.step(6)
    # The evicted lane's chunk outputs are exactly zero...
    np.testing.assert_array_equal(np.asarray(d2)[0], 0.0)
    np.testing.assert_array_equal(np.asarray(comm)[0], 0)
    # ...its ledger froze at eviction, and the pool totals account it once.
    assert int(ses_a.comm_bytes[:, -1].sum()) == bytes_a
    assert pool.total_comm_bytes == bytes_a + int(
        pool.session(b).comm_bytes[:, -1].sum()
    )
    # The evicted session is fully usable standalone (same state, same keys).
    assert ses_a.t == 6
    ses_a.step(6)
    ref = open_session("svrp", prob, **kw)
    ref.step(12)
    np.testing.assert_allclose(
        np.asarray(ses_a.dist_sq), np.asarray(ref.dist_sq),
        rtol=1e-5, atol=1e-24,
    )
    np.testing.assert_array_equal(np.asarray(ses_a.comm), np.asarray(ref.comm))


def test_stop_eps_freezes_only_its_lane(prob):
    eta = theorem2_stepsize(1.0, float(prob.similarity()))
    pool = SessionPool(capacity=2)
    fast = pool.admit("svrp", prob, grid={"eta": eta, "p": 0.2}, seeds=SEEDS,
                      num_steps=400, stop_eps=1e-10)
    slow = pool.admit("svrp", prob, grid={"eta": eta * 1e-4, "p": 0.2},
                      seeds=SEEDS, num_steps=400)
    while not pool.is_frozen(fast):
        pool.step(50)
    t_frozen = pool.session(fast).t
    assert t_frozen < 400  # actually converged early
    assert (np.asarray(pool.session(fast).dist_sq)[:, -1] <= 1e-10).all()
    d2, _ = pool.step(50)  # the frozen lane is masked out...
    np.testing.assert_array_equal(np.asarray(d2)[0], 0.0)
    assert pool.session(fast).t == t_frozen  # ...and its cursor is parked
    assert pool.session(slow).t == t_frozen + 50  # its peer kept stepping
    # The frozen prefix still matches the standalone run exactly.
    ref = open_session("svrp", prob, grid={"eta": eta, "p": 0.2}, seeds=SEEDS,
                       num_steps=400)
    ref.step(t_frozen)
    _assert_tenant_equal(pool.result(fast), ref)


def test_mixed_horizons_raise_per_tenant(prob, cases):
    kw_long = dict(cases["svrp"], num_steps=40)
    kw_short = dict(_variant(cases["svrp"]), num_steps=10)
    pool = SessionPool(capacity=2)
    pool.admit("svrp", prob, **kw_long)
    short = pool.admit("svrp", prob, **kw_short)
    pool.step(10)  # fits both
    with pytest.raises(ValueError, match=rf"tenant {short}: .*horizon exhausted"):
        pool.step(1)  # the short tenant is out of schedule
    # Nothing advanced on the failed call.
    assert pool.session(short).t == 10
    # Freezing the exhausted tenant lets the long one continue.
    assert pool.freeze_exhausted(1) == 1
    pool.step(30)
    assert pool.session(short).t == 10


# ---------------------------------------------------------------------------
# Admission validation: the shared RunSpec path + pool signature.
# ---------------------------------------------------------------------------

def test_unpoolable_tenants_rejected_field_by_field(prob, prob2, cases):
    pool = SessionPool(capacity=4)
    pool.admit("svrp", prob, **cases["svrp"])
    with pytest.raises(ValueError, match=r"(?s)not poolable.*algo"):
        pool.admit("sppm", prob, **cases["sppm"])
    with pytest.raises(ValueError, match=r"(?s)not poolable.*trial count"):
        pool.admit("svrp", prob, grid=cases["svrp"]["grid"], seeds=5,
                   num_steps=12)
    with pytest.raises(ValueError, match=r"(?s)not poolable.*static config"):
        pool.admit("svrp", prob, grid=cases["svrp"]["grid"], seeds=SEEDS,
                   num_steps=12, channel="quant8")
    small = make_synthetic_quadratic(num_clients=M, dim=4, mu=1.0, L=80.0,
                                     delta=4.0, seed=2)
    with pytest.raises(ValueError, match="not poolable"):
        pool.admit("svrp", small, **cases["svrp"])
    # Different horizon is NOT a mismatch (horizon keys are excluded)...
    pool.admit("svrp", prob2, grid=cases["svrp"]["grid"], seeds=SEEDS,
               num_steps=77)
    # ...and the shared RunSpec validation still guards every entry.
    with pytest.raises(ValueError, match="unknown static config"):
        pool.admit("svrp", prob, grid=cases["svrp"]["grid"], seeds=SEEDS,
                   num_steps=12, bogus=1)


def test_pool_admission_errors(prob, cases):
    kw = cases["svrp"]
    pool = SessionPool(capacity=1)
    a = pool.admit("svrp", prob, **kw)
    with pytest.raises(ValueError, match="pool is full"):
        pool.admit("svrp", prob, **_variant(kw))
    with pytest.raises(KeyError, match="unknown tenant id"):
        pool.result(a + 99)
    pool.evict(a)
    with pytest.raises(ValueError, match="already evicted"):
        pool.evict(a)
    with pytest.raises(ValueError, match="no running tenants"):
        pool.step(1)
    with pytest.raises(ValueError, match="capacity"):
        SessionPool(capacity=0)
    from repro.experiments import RunSpec
    with pytest.raises(ValueError, match="batched substrate only"):
        pool.admit(RunSpec("svrp", grid=kw["grid"], seeds=SEEDS,
                           substrate="sequential",
                           static={"num_steps": 12}), prob)


def test_pool_entry_signature_roundtrip(prob, prob2):
    sig = pool_entry_signature("svrp", {"num_steps": 10, "channel": None},
                               4, prob, prob.minimizer(), prob.minimizer())
    sig_same = pool_entry_signature("svrp", {"num_steps": 999, "channel": None},
                                    4, prob2, prob2.minimizer(),
                                    prob2.minimizer())
    check_pool_entry(sig, sig_same)  # horizons/data differ, signature equal
    sig_other = pool_entry_signature("svrp", {"num_steps": 10, "channel": "quant8"},
                                     4, prob, prob.minimizer(), prob.minimizer())
    with pytest.raises(ValueError, match=r"(?s)not poolable.*static config"):
        check_pool_entry(sig, sig_other)


# ---------------------------------------------------------------------------
# Donation: every state-carrying dispatch gives up its input state, on every
# backend (the CPU client of the installed jax honours donation too).
# ---------------------------------------------------------------------------

def _step_and_old_state(kind, prob):
    """Run one dispatch of `kind`; return (state leaves before it, result)."""
    kw = dict(grid={"eta": 1e-2, "p": 0.1}, seeds=SEEDS, num_steps=6)
    if kind in ("batched", "sequential", "clients"):
        sess = open_session("svrp", prob, substrate=kind, **kw)
        before = jax.tree.leaves(sess._state)
        sess.step(2)
        return before, sess.step(2)[0]
    if kind == "pool":
        pool = SessionPool(capacity=2)
        pool.admit("svrp", prob, **kw)
        before = jax.tree.leaves(pool._state)
        pool.step(2)
        return before, pool.step(2)[0]
    srv = FedRoundServer("svrp", prob, hparams={"eta": 1e-2, "p": 0.1})
    before = jax.tree.leaves(srv._state)
    return before, srv.run(3).dist_sq


@pytest.mark.parametrize("kind", ["batched", "sequential", "clients", "pool", "server"])
def test_dispatch_donates_state(prob, kind):
    before, out = _step_and_old_state(kind, prob)
    assert np.all(np.isfinite(np.asarray(out)))
    assert all(a.is_deleted() for a in before if isinstance(a, jax.Array))


# ---------------------------------------------------------------------------
# Serving integration: FedRoundServer(pool=...).
# ---------------------------------------------------------------------------

def test_server_pool_mode_multiplexes_tenants(prob, cases):
    kw = cases["svrp"]
    pool = SessionPool(capacity=2, pipeline_depth=2)
    a = pool.admit("svrp", prob, **dict(kw, num_steps=20))
    b = pool.admit("svrp", prob, **dict(_variant(kw), num_steps=8))
    srv = FedRoundServer(pool=pool)
    stats = srv.run(30)
    s = stats.summary()
    # Stops at the longest horizon; the short tenant froze at its own.
    assert s["rounds"] == 20
    assert pool.session(a).t == 20 and pool.session(b).t == 8
    assert pool.is_frozen(b)
    assert pool.num_running == 0  # everyone ran out of horizon and froze
    assert np.isfinite([s["p50_ms"], s["p95_ms"], s["p99_ms"]]).all()
    assert np.all(np.diff(stats.comm) >= 0) and s["total_comm"] > 0
    assert s["total_comm_bytes"] == s["total_comm"] * pool.wire_bytes_per_vector
    # Both tenants' trajectories are still exactly their standalone runs.
    ra = open_session("svrp", prob, **dict(kw, num_steps=20))
    ra.step(20)
    _assert_tenant_equal(pool.result(a), ra)


def test_server_pool_mode_rejects_mixed_construction(prob, cases):
    pool = SessionPool(capacity=1)
    with pytest.raises(ValueError, match="pool"):
        FedRoundServer("svrp", prob, pool=pool)
