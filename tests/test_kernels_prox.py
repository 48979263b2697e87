"""prox_update fused kernel (the paper's Algorithm 7 inner step).

`hypothesis` is optional: in clean envs conftest.py installs the deterministic
stub from tests/_hypothesis_stub.py before collection, so these property tests
always run (install the real package via the `[test]` extra for shrinking).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from hypothesis.extra import numpy as hnp  # noqa: F401  (exercises the stub's submodule path)

from repro.kernels import ref
from repro.kernels.prox_update import prox_update as prox_pallas
from repro.kernels.prox_update import prox_update_batched as prox_pallas_batched


@pytest.mark.parametrize("shape", [(7,), (3, 37, 11), (128, 128), (100_001,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_prox_update_matches_ref(shape, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    y = jax.random.normal(ks[0], shape, dtype)
    g = jax.random.normal(ks[1], shape, dtype)
    z = jax.random.normal(ks[2], shape, dtype)
    o_ref = ref.prox_update(y, g, z, 0.1, 2.0)
    o_pal = prox_pallas(y, g, z, 0.1, 2.0)
    tol = dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(o_pal, np.float32), np.asarray(o_ref, np.float32), **tol)
    assert o_pal.shape == shape and o_pal.dtype == dtype


@settings(deadline=None, max_examples=25)
@given(
    n=st.integers(1, 3000),
    lr=st.floats(1e-4, 1.0),
    inv_eta=st.floats(1e-3, 100.0),
    seed=st.integers(0, 99),
)
def test_prox_update_property(n, lr, inv_eta, seed):
    """Property: fixed point iff g + (y-z)/eta == 0; linear in inputs."""
    ks = jax.random.split(jax.random.key(seed), 2)
    y = jax.random.normal(ks[0], (n,))
    z = jax.random.normal(ks[1], (n,))
    # choose g to make it a fixed point
    g_fix = -(y - z) * inv_eta
    out = prox_pallas(y, g_fix, z, lr, inv_eta)
    np.testing.assert_allclose(np.asarray(out), np.asarray(y), atol=1e-5)


@pytest.mark.parametrize(
    "shape", [(4, 7), (3, 37, 11), (2, 128, 128), (5, 300), (2, 100_001), (6,)]
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_prox_update_batched_matches_ref(shape, dtype):
    """The sweep-batch kernel (grid over batch x row-blocks, per-trial scalars
    in the (B, 2) operand) must match the oracle on odd shapes/dtypes."""
    ks = jax.random.split(jax.random.key(1), 3)
    y = jax.random.normal(ks[0], shape, dtype)
    g = jax.random.normal(ks[1], shape, dtype)
    z = jax.random.normal(ks[2], shape, dtype)
    B = shape[0]
    lr = jnp.linspace(0.01, 0.9, B)  # distinct per-trial scalars
    inv_eta = jnp.linspace(0.5, 4.0, B)
    o_ref = ref.prox_update_batched(y, g, z, lr, inv_eta)
    o_pal = prox_pallas_batched(y, g, z, lr, inv_eta)
    tol = dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(o_pal, np.float32), np.asarray(o_ref, np.float32), **tol
    )
    assert o_pal.shape == shape and o_pal.dtype == dtype


def test_prox_update_batched_uses_per_trial_scalars():
    """Trial b must see ITS scalars: each batched row equals the single-trial
    kernel run with that row's (lr, inv_eta)."""
    ks = jax.random.split(jax.random.key(2), 3)
    B, n = 5, 77
    y = jax.random.normal(ks[0], (B, n))
    g = jax.random.normal(ks[1], (B, n))
    z = jax.random.normal(ks[2], (B, n))
    lr = jnp.asarray([0.1, 0.2, 0.3, 0.4, 0.5])
    inv_eta = jnp.asarray([2.0, 1.0, 0.5, 4.0, 3.0])
    out = prox_pallas_batched(y, g, z, lr, inv_eta)
    for b in range(B):
        single = prox_pallas(y[b], g[b], z[b], float(lr[b]), float(inv_eta[b]))
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(single), rtol=1e-12)


def test_prox_update_batched_broadcasts_scalars():
    y = jnp.ones((3, 40))
    g = jnp.ones((3, 40))
    z = jnp.zeros((3, 40))
    out = prox_pallas_batched(y, g, z, 0.1, 2.0)  # python scalars broadcast
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.prox_update(y, g, z, 0.1, 2.0)), rtol=1e-12
    )


def test_prox_update_batched_f64_and_traced_scalars():
    """The engine runs in f64 with traced per-trial scalars under jit."""
    B, n = 4, 33
    ks = jax.random.split(jax.random.key(3), 3)
    y = jax.random.normal(ks[0], (B, n), jnp.float64)
    g = jax.random.normal(ks[1], (B, n), jnp.float64)
    z = jax.random.normal(ks[2], (B, n), jnp.float64)

    @jax.jit
    def f(lr, inv_eta):
        return prox_pallas_batched(y, g, z, lr, inv_eta)

    lr = jnp.linspace(0.05, 0.4, B)
    inv_eta = jnp.linspace(1.0, 2.0, B)
    np.testing.assert_allclose(
        np.asarray(f(lr, inv_eta)),
        np.asarray(ref.prox_update_batched(y, g, z, lr, inv_eta)),
        rtol=1e-12,
    )


def test_prox_gd_batched_kernel_equals_jnp_path():
    """core.prox_gd_batched(use_kernel=True) == the plain jnp expression, and
    both == per-trial prox_gd."""
    from repro.core.prox import prox_gd, prox_gd_batched
    from repro.problems import make_synthetic_quadratic

    prob = make_synthetic_quadratic(num_clients=6, dim=12, mu=1.0, L=50.0, delta=3.0, seed=0)
    B = 4
    ms = jnp.asarray([0, 2, 4, 5])
    z = jax.random.normal(jax.random.key(0), (B, 12))
    eta = jnp.asarray([0.5, 0.2, 1.0, 0.1])
    L = jnp.full((B,), float(prob.smoothness_max()))
    grad_b = jax.vmap(prob.grad)

    out_k = prox_gd_batched(lambda y: grad_b(ms, y), z, eta, L, 30, use_kernel=True)
    out_j = prox_gd_batched(lambda y: grad_b(ms, y), z, eta, L, 30, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_j), rtol=1e-10, atol=1e-12)
    for b in range(B):
        single = prox_gd(
            lambda y: prob.grad(ms[b], y), z[b], float(eta[b]), float(L[b]), 30
        )
        np.testing.assert_allclose(np.asarray(out_k[b]), np.asarray(single), rtol=1e-8, atol=1e-10)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_prox_update_tree_matches_leafwise(use_pallas):
    """ops.prox_update_tree == leaf-wise prox_update on a mixed-dtype pytree.

    use_pallas=True exercises the per-dtype concat/split single-launch path
    (interpret mode) that the DeepSVRP pod step routes through — including
    the offset bookkeeping across multiple leaves of the same dtype."""
    from repro.kernels import ops as kops

    ks = jax.random.split(jax.random.key(4), 4)
    y = {
        "a": jax.random.normal(ks[0], (3, 37), jnp.float32),
        "b": jax.random.normal(ks[1], (129,), jnp.float32),
        "c": jax.random.normal(ks[2], (4, 5), jnp.bfloat16),
        "d": jax.random.normal(ks[3], (2, 2, 2), jnp.float32),
    }
    g = jax.tree.map(lambda x: (x * 0.3).astype(jnp.float32), y)  # f32 grads vs bf16 params
    z = jax.tree.map(lambda x: x - 0.25, y)
    want = jax.tree.map(
        lambda yy, gg, zz: ref.prox_update(yy, gg.astype(yy.dtype), zz, 0.1, 2.0), y, g, z
    )

    state = kops._USE_PALLAS
    try:
        kops.use_pallas(use_pallas)
        got = kops.prox_update_tree(y, g, z, 0.1, 2.0)
    finally:
        kops.use_pallas(state)
    for k in y:
        assert got[k].shape == y[k].shape and got[k].dtype == y[k].dtype, k
        tol = dict(atol=2e-2, rtol=2e-2) if y[k].dtype == jnp.bfloat16 else dict(rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(got[k], np.float32), np.asarray(want[k], np.float32), **tol
        )


def test_prox_update_under_jit_and_traced_scalars():
    """lr / inv_eta may be traced (come from schedules) — must not retrace-fail."""
    y = jnp.ones((64,))
    g = jnp.ones((64,))
    z = jnp.zeros((64,))

    @jax.jit
    def f(lr, inv_eta):
        return prox_pallas(y, g, z, lr, inv_eta)

    out = f(jnp.asarray(0.1), jnp.asarray(2.0))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.prox_update(y, g, z, 0.1, 2.0)),
                               rtol=1e-6)


# ------------------------------------------------------- logistic prox-GD kernel
@pytest.mark.parametrize("shape", [(2, 17, 5), (4, 64, 16), (3, 100, 123)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_logistic_prox_gd_batched_matches_ref(shape, dtype):
    """The in-kernel Algorithm-7 loop on the (B, n, d) logistic oracle must
    match the jnp oracle on odd (unaligned) shapes — row/col padding is free
    by the sign-folded-operand construction."""
    from repro.kernels.logistic_prox import logistic_prox_gd_batched

    B, n, d = shape
    ks = jax.random.split(jax.random.key(4), 2)
    A = jax.random.normal(ks[0], shape, dtype)
    z = jax.random.normal(ks[1], (B, d), dtype)
    beta = jnp.linspace(0.02, 0.3, B).astype(dtype)
    inv_eta = jnp.linspace(0.5, 3.0, B).astype(dtype)
    out = logistic_prox_gd_batched(A, z, beta, inv_eta, 0.1, 9)
    oracle = ref.logistic_prox_gd_batched(A, z, beta, inv_eta, 0.1, 9)
    tol = dict(rtol=1e-5, atol=1e-6) if dtype == jnp.float32 else dict(rtol=1e-12, atol=1e-13)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle), **tol)
    assert out.shape == (B, d) and out.dtype == dtype


def test_logistic_prox_gd_batched_matches_prox_gd():
    """Against the scalar Algorithm-7 solver on the real problem oracle: the
    kernel with A = y * Z_m is the same computation as prox_gd over
    problem.grad(m, .) for each trial."""
    from repro.core.prox import prox_gd
    from repro.kernels.logistic_prox import logistic_prox_gd_batched
    from repro.problems import make_a9a_like_problem

    lp = make_a9a_like_problem(
        num_clients=5, n_per_client=40, n_pool=300, dim=20, nnz_per_row=5, seed=1
    )
    B = 4
    m = jnp.asarray([0, 2, 3, 1])
    z = jax.random.normal(jax.random.key(5), (B, lp.dim), jnp.float64)
    L = float(lp.smoothness_max())
    eta = jnp.asarray([0.5, 1.0, 2.0, 4.0])
    beta = 1.0 / (L + 1.0 / eta)
    A = jnp.take(lp.Z, m, axis=0) * jnp.take(lp.y, m, axis=0)[:, :, None]
    out = logistic_prox_gd_batched(A, z, beta, 1.0 / eta, lp.lam, 15)
    for b in range(B):
        grad_fn, _ = lp.local_oracle(m[b])
        single = prox_gd(grad_fn, z[b], float(eta[b]), L, 15)
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(single), rtol=1e-10, atol=1e-12)
