"""l2-regularized logistic regression: the non-quadratic validation problem.

The paper's second experiment uses LIBSVM "a9a" with each client's data sampled
from the common training pool (n = 2000 per client), lam = 0.1, measured
L ~= 6.33 and delta ~= 0.22.  This container is offline, so `make_a9a_like_problem`
re-synthesizes a dataset matched to a9a's published statistics (123 binary
features, ~13.9 nonzeros/row, n_pool = 32561) with labels from a planted
logistic model; clients subsample the pool i.i.d. exactly as in the paper, which
is what produces the small delta (statistical similarity, Section 9).

The local prox (and the full-batch `minimizer`) use the GUARDED Newton from
`repro.core.prox` — backtracking line search plus a gradient-norm early exit.
Raw undamped Newton overshoots on the logistic subproblem whenever eta is
large: the Hessian bottoms out near (lam + 1/eta) I while the gradient stays
O(1), so the unguarded step length blows up and the iteration oscillates (see
tests/test_logistic_prox.py for the regression).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.precision import HIGHEST, mm
from repro.core.prox import prox_newton


def _sigmoid(t):
    return 0.5 * (jnp.tanh(0.5 * t) + 1.0)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LogisticProblem:
    """f_m(x) = (1/n) sum_i log(1 + exp(-y_i z_i'x)) + lam/2 ||x||^2, y in {-1,+1}."""

    Z: jax.Array  # (M, n, d)
    y: jax.Array  # (M, n), +-1
    lam: float = dataclasses.field(metadata=dict(static=True))

    # Client-axis sharding contract (repro.problems.client_shard): leaves are
    # client-major and a zero-padded client (Z_m = 0, y_m = 0) has benign
    # oracles — its loss degenerates to the ridge term, grad = lam x, and the
    # guarded-Newton prox stays well-posed.  Inherited by DPLogisticProblem
    # (`dp_shift` is client-major noise state, zero-padded like the data).
    client_shardable = True

    @property
    def num_clients(self) -> int:
        return self.Z.shape[0]

    @property
    def dim(self) -> int:
        return self.Z.shape[-1]

    # --- oracles -----------------------------------------------------------------
    def loss(self, m: jax.Array, x: jax.Array) -> jax.Array:
        Z_m = jnp.take(self.Z, m, axis=0)
        y_m = jnp.take(self.y, m, axis=0)
        t = y_m * mm(Z_m, x)
        return jnp.mean(jnp.logaddexp(0.0, -t)) + 0.5 * self.lam * mm(x, x)

    def grad(self, m: jax.Array, x: jax.Array) -> jax.Array:
        Z_m = jnp.take(self.Z, m, axis=0)
        y_m = jnp.take(self.y, m, axis=0)
        t = y_m * mm(Z_m, x)
        w = -y_m * _sigmoid(-t)  # d/dt log(1+e^-t) = -sigmoid(-t)
        return mm(Z_m.T, w) / Z_m.shape[0] + self.lam * x

    def full_loss(self, x: jax.Array) -> jax.Array:
        t = self.y * jnp.einsum("mnd,d->mn", self.Z, x, precision=HIGHEST)
        return jnp.mean(jnp.logaddexp(0.0, -t)) + 0.5 * self.lam * mm(x, x)

    def full_grad(self, x: jax.Array) -> jax.Array:
        t = self.y * jnp.einsum("mnd,d->mn", self.Z, x, precision=HIGHEST)
        w = -self.y * _sigmoid(-t)
        M, n, _ = self.Z.shape
        return jnp.einsum("mnd,mn->d", self.Z, w, precision=HIGHEST) / (M * n) + self.lam * x

    def hessian(self, m: jax.Array, x: jax.Array) -> jax.Array:
        Z_m = jnp.take(self.Z, m, axis=0)
        y_m = jnp.take(self.y, m, axis=0)
        t = y_m * mm(Z_m, x)
        s = _sigmoid(t) * _sigmoid(-t)
        d = self.dim
        return mm((Z_m * s[:, None]).T, Z_m) / Z_m.shape[0] + self.lam * jnp.eye(d, dtype=x.dtype)

    def local_oracle(self, m: jax.Array):
        """(grad_fn, hess_fn) of client m with the data gather HOISTED.

        `grad(m, .)` / `hessian(m, .)` re-gather (Z_m, y_m) on every call;
        inside an iterative prox solver that gather sits in the loop body, and
        under the experiment engine's vmap it becomes a (B, n, d) copy PER
        ITERATION.  Closing over the gathered slices once per solve keeps the
        client block resident across all Newton/GD iterations.
        """
        A = jnp.take(self.Z, m, axis=0) * jnp.take(self.y, m, axis=0)[:, None]
        n = A.shape[0]
        eye = self.lam * jnp.eye(self.dim, dtype=self.Z.dtype)

        def grad_fn(x):
            u = _sigmoid(-mm(A, x))  # sigmoid of minus-margins
            return -mm(A.T, u) / n + self.lam * x

        def hess_fn(x):
            t = mm(A, x)
            s = _sigmoid(t) * _sigmoid(-t)
            return mm((A * s[:, None]).T, A) / n + eye

        return grad_fn, hess_fn

    def prox(
        self,
        m: jax.Array,
        z: jax.Array,
        eta: jax.Array,
        newton_steps: int = 50,
        tol: float = 1e-11,
    ) -> jax.Array:
        """prox_{eta f_m}(z) via GUARDED Newton on the strongly convex subproblem.

        phi(x) = f_m(x) + 1/(2 eta) ||x - z||^2.  Backtracking keeps every step
        monotone in ||grad phi|| (raw Newton overshoots at large eta, where the
        subproblem Hessian bottoms out near (lam + 1/eta) I while the gradient
        stays O(1)); the while_loop exits as soon as ||grad phi|| <= tol, which
        quadratic local convergence reaches in a handful of iterations.
        """
        grad_fn, hess_fn = self.local_oracle(m)
        return prox_newton(grad_fn, hess_fn, z, eta, max_steps=newton_steps, tol=tol)

    def shifted(self, gamma: float, y_anchor: jax.Array) -> "ShiftedLogisticProblem":
        return ShiftedLogisticProblem(base=self, gamma=gamma, anchor=y_anchor)

    # --- measured constants (the paper reports measured L, delta) -----------------
    def smoothness(self) -> jax.Array:
        """L <= lambda_max((1/(4 M n)) sum Z'Z) + lam — the standard bound."""
        M, n, _ = self.Z.shape
        G = jnp.einsum("mni,mnj->ij", self.Z, self.Z, precision=HIGHEST) / (M * n)
        return 0.25 * jnp.linalg.eigvalsh(G)[-1] + self.lam

    def smoothness_max(self) -> jax.Array:
        """max_m L_m, the per-client smoothness bound the local solvers use:
        L_m <= lambda_max(Z_m'Z_m/(4 n)) + lam."""
        n = self.Z.shape[1]

        def client_L(Z_m):
            G = mm(Z_m.T, Z_m) / (4.0 * n)
            return jnp.linalg.eigvalsh(G)[-1] + self.lam

        return jnp.max(jax.vmap(client_L)(self.Z))

    def strong_convexity(self) -> float:
        return self.lam

    def similarity_at(self, x: jax.Array) -> jax.Array:
        """Measured delta(x): sqrt(lambda_max((1/M) sum (H_m(x) - Hbar(x))^2))."""
        H = jax.vmap(lambda m: self.hessian(m, x))(jnp.arange(self.num_clients))
        E = H - jnp.mean(H, axis=0, keepdims=True)
        S = jnp.mean(jnp.einsum("mij,mjk->mik", E, E, precision=HIGHEST), axis=0)
        return jnp.sqrt(jnp.linalg.eigvalsh(S)[-1])

    def similarity_max_at(self, x: jax.Array) -> jax.Array:
        """Per-client delta(x): max_m ||H_m(x) - Hbar(x)||_op — the stronger
        constant used by the surrogate baselines (DANE / extragradient)."""
        H = jax.vmap(lambda m: self.hessian(m, x))(jnp.arange(self.num_clients))
        E = H - jnp.mean(H, axis=0, keepdims=True)
        op = jax.vmap(lambda e: jnp.max(jnp.abs(jnp.linalg.eigvalsh(e))))(E)
        return jnp.max(op)

    def minimizer(self, steps: int = 200, tol: float = 1e-12) -> jax.Array:
        """Full-batch guarded Newton to machine precision (reference x_*)."""

        def full_hess(x):
            H = jax.vmap(lambda m: self.hessian(m, x))(jnp.arange(self.num_clients))
            return jnp.mean(H, axis=0)

        x0 = jnp.zeros((self.dim,), dtype=self.Z.dtype)
        # The full objective is its own prox subproblem in the eta -> inf
        # limit; reuse the guarded solver with a huge eta (1/eta ~ 0 extra
        # curvature — lam already makes the Hessian PD).
        return prox_newton(
            self.full_grad, full_hess, x0, jnp.asarray(1e12, x0.dtype),
            max_steps=steps, tol=tol,
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShiftedLogisticProblem:
    """Catalyst subproblem h_t: adds gamma/2 ||x - anchor||^2 to every client."""

    base: LogisticProblem
    gamma: float = dataclasses.field(metadata=dict(static=True))
    anchor: jax.Array = None

    @property
    def num_clients(self):
        return self.base.num_clients

    @property
    def dim(self):
        return self.base.dim

    def grad(self, m, x):
        return self.base.grad(m, x) + self.gamma * (x - self.anchor)

    def full_grad(self, x):
        return self.base.full_grad(x) + self.gamma * (x - self.anchor)

    def hessian(self, m, x):
        return self.base.hessian(m, x) + self.gamma * jnp.eye(self.dim, dtype=x.dtype)

    def local_oracle(self, m):
        grad0, hess0 = self.base.local_oracle(m)
        shift_eye = self.gamma * jnp.eye(self.dim, dtype=self.base.Z.dtype)

        def grad_fn(x):
            return grad0(x) + self.gamma * (x - self.anchor)

        def hess_fn(x):
            return hess0(x) + shift_eye

        return grad_fn, hess_fn

    def prox(self, m, z, eta, newton_steps: int = 50, tol: float = 1e-11):
        grad_fn, hess_fn = self.local_oracle(m)
        return prox_newton(grad_fn, hess_fn, z, eta, max_steps=newton_steps, tol=tol)


def make_a9a_like_problem(
    num_clients: int,
    n_per_client: int = 2000,
    lam: float = 0.1,
    n_pool: int = 32561,
    dim: int = 123,
    nnz_per_row: int = 14,
    seed: int = 0,
    dtype=jnp.float64,
) -> LogisticProblem:
    """a9a-statistics-matched synthetic pool + i.i.d. per-client subsampling."""
    rng = np.random.default_rng(seed)
    # Binary sparse features: a9a has 123 binary cols, ~13.9 nnz/row, with a
    # heavily skewed column popularity; use a Zipf-like column distribution.
    col_p = 1.0 / np.arange(1, dim + 1) ** 0.8
    col_p /= col_p.sum()
    # Without-replacement sampling of nnz columns per row, vectorized over the
    # whole pool via the Gumbel-top-k trick (same marginal column popularity
    # as a per-row rng.choice(..., replace=False, p=col_p) loop, ~100x faster
    # at the full n_pool = 32561).
    if nnz_per_row >= dim:  # dense rows: every column selected
        pool = np.ones((n_pool, dim), dtype=np.float64)
    else:
        gumbel = rng.gumbel(size=(n_pool, dim))
        cols = np.argpartition(-(np.log(col_p)[None, :] + gumbel), nnz_per_row, axis=1)
        pool = np.zeros((n_pool, dim), dtype=np.float64)
        np.put_along_axis(pool, cols[:, :nnz_per_row], 1.0, axis=1)
    x_true = rng.standard_normal(dim) / np.sqrt(nnz_per_row)
    logits = pool @ x_true
    y_pool = np.where(rng.uniform(size=n_pool) < 1.0 / (1.0 + np.exp(-logits)), 1.0, -1.0)

    idx = rng.integers(0, n_pool, size=(num_clients, n_per_client))
    Z = pool[idx]  # (M, n, d)
    y = y_pool[idx]
    return LogisticProblem(Z=jnp.asarray(Z, dtype), y=jnp.asarray(y, dtype), lam=lam)
