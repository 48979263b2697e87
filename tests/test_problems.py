"""Problem substrate: exact constants and oracles of the quadratic family."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.problems import make_synthetic_quadratic, make_a9a_like_problem


@pytest.fixture(scope="module")
def prob():
    return make_synthetic_quadratic(num_clients=20, dim=12, mu=1.0, L=100.0, delta=5.0, seed=3)


def test_constants_match_construction(prob):
    assert np.isclose(float(prob.similarity()), 5.0, rtol=1e-6)
    assert float(prob.strong_convexity()) >= 1.0 - 1e-8
    assert float(prob.smoothness()) <= 100.0 + 5.0 + 1e-6


def test_prox_is_exact_minimizer(prob):
    """prox_{eta f_m}(z) must satisfy the stationarity condition."""
    z = jnp.ones(12)
    eta = 0.37
    for m in [0, 7, 19]:
        p = prob.prox(jnp.asarray(m), z, eta)
        # grad of f_m(y) + ||y-z||^2/(2 eta) at p should vanish
        g = prob.grad(jnp.asarray(m), p) + (p - z) / eta
        assert float(jnp.linalg.norm(g)) < 1e-9


def test_full_grad_is_mean_of_client_grads(prob):
    x = jnp.linspace(-1, 1, 12)
    gs = jnp.stack([prob.grad(jnp.asarray(m), x) for m in range(prob.num_clients)])
    np.testing.assert_allclose(np.asarray(jnp.mean(gs, 0)), np.asarray(prob.full_grad(x)), rtol=1e-10)


def test_minimizer_stationary(prob):
    x_star = prob.minimizer()
    assert float(jnp.linalg.norm(prob.full_grad(x_star))) < 1e-8


@settings(deadline=None, max_examples=10)
@given(
    delta=st.floats(0.5, 20.0),
    mu=st.floats(0.1, 2.0),
    seed=st.integers(0, 10_000),
)
def test_construction_properties_hold(delta, mu, seed):
    """Property: the synthetic generator always achieves the requested delta
    exactly and keeps every client mu-strongly convex (Assumption 2)."""
    p = make_synthetic_quadratic(num_clients=8, dim=6, mu=mu, L=50 * mu + 3 * delta,
                                 delta=delta, seed=seed)
    assert np.isclose(float(p.similarity()), delta, rtol=1e-5)
    assert float(p.strong_convexity()) >= mu - 1e-8


def test_shifted_problem_is_catalyst_surrogate(prob):
    y = jnp.ones(12) * 0.3
    gamma = 2.5
    h = prob.shifted(gamma, y)
    x = jnp.linspace(0, 1, 12)
    m = jnp.asarray(4)
    np.testing.assert_allclose(
        np.asarray(h.grad(m, x)),
        np.asarray(prob.grad(m, x) + gamma * (x - y)),
        rtol=1e-10,
    )
    # similarity is shift-invariant (the proof of Proposition 3)
    assert np.isclose(float(h.similarity()), float(prob.similarity()), rtol=1e-6)


def test_a9a_like_problem_basics():
    p = make_a9a_like_problem(num_clients=4, n_per_client=100, n_pool=500, seed=0)
    assert p.dim == 123
    x = jnp.zeros(123)
    m = jnp.asarray(1)
    # gradient of logistic loss at 0 is bounded and finite
    g = p.grad(m, x)
    assert bool(jnp.all(jnp.isfinite(g)))
    # prox solves the subproblem
    pr = p.prox(m, x, 0.5)
    stat = p.grad(m, pr) + (pr - x) / 0.5
    assert float(jnp.linalg.norm(stat)) < 1e-8


def _dot_precisions(jaxpr):
    """The `precision` of every dot_general in `jaxpr`, sub-jaxprs included
    (loop bodies, conds, the Pallas kernel body)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn.params["precision"]
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, ClosedJaxpr):
                    yield from _dot_precisions(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _dot_precisions(sub)


def _convex_contractions():
    from repro.core.prox import prox_newton_cg
    from repro.kernels.logistic_prox import logistic_prox_gd_batched

    q = make_synthetic_quadratic(num_clients=3, dim=8, mu=1.0, L=10.0, delta=1.0, seed=0)
    lg = make_a9a_like_problem(num_clients=3, n_per_client=16, n_pool=64, dim=8, nnz_per_row=3)
    m, x, eta = jnp.asarray(1), jnp.ones(8), jnp.asarray(0.5)
    return {
        "quadratic.grad": lambda: q.grad(m, x),
        "quadratic.full_grad": lambda: q.full_grad(x),
        "quadratic.local_oracle": lambda: q.local_oracle(m)[0](x),
        "quadratic.prox_spectral": lambda: q.prox_spectral(m, x, eta, q.prox_factors()),
        "logistic.grad": lambda: lg.grad(m, x),
        "logistic.full_grad": lambda: lg.full_grad(x),
        "logistic.local_oracle": lambda: [f(x) for f in lg.local_oracle(m)],
        "logistic.smoothness_max": lambda: lg.smoothness_max(),
        "logistic.minimizer": lambda: lg.minimizer(steps=3),
        "prox_newton_cg": lambda: prox_newton_cg(lg.local_oracle(m)[0], x, eta, max_steps=2),
        "logistic_prox_kernel": lambda: logistic_prox_gd_batched(
            jnp.ones((2, 16, 8)), jnp.ones((2, 8)), jnp.ones(2), jnp.ones(2), 0.1, 2),
    }


@pytest.mark.parametrize("name", sorted(_convex_contractions()))
def test_convex_contractions_are_highest_precision(name):
    """A TPU's default float32 matmul rounds operands to bfloat16; the convex
    oracles and solvers ask for full precision.  The Algorithm-7 kernel makes
    its products as float32 multiply-reduces and contracts nothing at all."""
    import jax

    from repro.core.precision import HIGHEST

    precisions = list(_dot_precisions(jax.make_jaxpr(_convex_contractions()[name])().jaxpr))
    if name == "logistic_prox_kernel":
        assert not precisions, precisions
        return
    assert precisions, name
    assert all(p == (HIGHEST, HIGHEST) for p in precisions), (name, precisions)
