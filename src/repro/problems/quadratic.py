"""Finite-sum quadratic problems with controlled second-order similarity.

The paper's synthetic experiments (Section 5) use l2-regularized linear
regression.  Per-client losses are quadratics

    f_m(x) = 0.5 x^T A_m x - b_m^T x + c_m,

with A_m >= mu I.  For quadratics every quantity in the paper is available in
closed form, which is what makes them the canonical validation substrate:

* exact proximal operator:   prox_{eta f_m}(z) = (I + eta A_m)^{-1} (z + eta b_m)
* exact minimizer:           x_* = Abar^{-1} bbar
* exact similarity constant: delta^2 = lambda_max( (1/M) sum_m (A_m - Abar)^2 )
* exact smoothness/strong convexity: eigenvalues of the A_m / Abar.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.precision import mm


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuadraticProblem:
    """Finite-sum quadratic  f(x) = (1/M) sum_m [0.5 x'A_m x - b_m'x]."""

    A: jax.Array  # (M, d, d), symmetric, each >= mu I
    b: jax.Array  # (M, d)

    # Client-axis sharding contract (repro.problems.client_shard): every
    # array leaf is client-major and a zero-padded client (A_m = 0, b_m = 0)
    # has benign oracles — grad 0, prox solve (I + eta*0) y = z.  Inherited
    # by the DP-ERM subclass, whose noise already rides `b`.
    client_shardable = True

    # --- structural properties -------------------------------------------------
    @property
    def num_clients(self) -> int:
        return self.A.shape[0]

    @property
    def dim(self) -> int:
        return self.A.shape[-1]

    @property
    def A_bar(self) -> jax.Array:
        return jnp.mean(self.A, axis=0)

    @property
    def b_bar(self) -> jax.Array:
        return jnp.mean(self.b, axis=0)

    # --- oracle access ---------------------------------------------------------
    def grad(self, m: jax.Array, x: jax.Array) -> jax.Array:
        """Gradient of f_m at x (m may be a traced integer)."""
        A_m = jnp.take(self.A, m, axis=0)
        b_m = jnp.take(self.b, m, axis=0)
        return mm(A_m, x) - b_m

    def full_grad(self, x: jax.Array) -> jax.Array:
        return mm(self.A_bar, x) - self.b_bar

    def loss(self, m: jax.Array, x: jax.Array) -> jax.Array:
        A_m = jnp.take(self.A, m, axis=0)
        b_m = jnp.take(self.b, m, axis=0)
        return 0.5 * mm(x, mm(A_m, x)) - mm(b_m, x)

    def full_loss(self, x: jax.Array) -> jax.Array:
        return 0.5 * mm(x, mm(self.A_bar, x)) - mm(self.b_bar, x)

    def hessian(self, m: jax.Array, x: jax.Array) -> jax.Array:
        """Constant client Hessian A_m (uniform oracle for the Newton solvers,
        which then converge in a single guarded step on quadratics)."""
        del x
        return jnp.take(self.A, m, axis=0)

    def local_oracle(self, m: jax.Array):
        """(grad_fn, hess_fn) of client m with the (A_m, b_m) gather hoisted
        out of iterative prox solvers (see LogisticProblem.local_oracle)."""
        A_m = jnp.take(self.A, m, axis=0)
        b_m = jnp.take(self.b, m, axis=0)
        return (lambda x: mm(A_m, x) - b_m), (lambda x: A_m)

    def prox(self, m: jax.Array, z: jax.Array, eta: jax.Array) -> jax.Array:
        """Exact prox_{eta f_m}(z) = (I + eta A_m)^{-1}(z + eta b_m)."""
        A_m = jnp.take(self.A, m, axis=0)
        b_m = jnp.take(self.b, m, axis=0)
        H = jnp.eye(self.dim, dtype=z.dtype) + eta * A_m
        return jnp.linalg.solve(H, z + eta * b_m)

    def prox_factors(self) -> tuple[jax.Array, jax.Array]:
        """Per-client eigendecompositions A_m = Q_m diag(lam_m) Q_m^T.

        One-time O(M d^3) factorization that turns every subsequent prox into
        two matvecs + a diagonal solve (`prox_spectral`) — the scan-resident
        prox path of the batched experiment engine, which otherwise pays a
        serial LAPACK LU per trial per step on CPU.
        """
        lam, Q = jnp.linalg.eigh(self.A)
        return lam, Q

    def prox_spectral(
        self, m: jax.Array, z: jax.Array, eta: jax.Array, factors
    ) -> jax.Array:
        """prox via the cached spectral factors: Q ((Q^T (z + eta b)) / (1 + eta lam)).

        Same operator as `prox` up to factorization round-off (~eps * cond,
        |diff| ~ 1e-12 in f64 on the benchmark instances).
        """
        lam, Q = factors
        Q_m = jnp.take(Q, m, axis=0)
        lam_m = jnp.take(lam, m, axis=0)
        b_m = jnp.take(self.b, m, axis=0)
        rhs = z + eta * b_m
        return mm(Q_m, mm(Q_m.T, rhs) / (1.0 + eta * lam_m))

    def shifted(self, gamma: float, y: jax.Array) -> "QuadraticProblem":
        """Catalyst subproblem  h_t,m(x) = f_m(x) + gamma/2 ||x - y||^2."""
        eye = jnp.eye(self.dim, dtype=self.A.dtype)
        return QuadraticProblem(A=self.A + gamma * eye, b=self.b + gamma * y)

    # --- exact constants ---------------------------------------------------------
    def minimizer(self) -> jax.Array:
        return jnp.linalg.solve(self.A_bar, self.b_bar)

    def smoothness(self) -> jax.Array:
        """L of the average objective f."""
        return jnp.linalg.eigvalsh(self.A_bar)[-1]

    def smoothness_max(self) -> jax.Array:
        """max_m L_m — the per-client smoothness used by local solvers."""
        return jnp.max(jax.vmap(lambda A: jnp.linalg.eigvalsh(A)[-1])(self.A))

    def strong_convexity(self) -> jax.Array:
        """min over clients of the smallest eigenvalue (Assumption 2's mu)."""
        return jnp.min(jax.vmap(lambda A: jnp.linalg.eigvalsh(A)[0])(self.A))

    def similarity(self) -> jax.Array:
        """Exact delta:  delta^2 = lambda_max((1/M) sum (A_m - Abar)^2)."""
        E = self.A - self.A_bar[None]
        S = jnp.mean(jax.vmap(lambda e: mm(e, e))(E), axis=0)
        return jnp.sqrt(jnp.linalg.eigvalsh(S)[-1])

    def similarity_max(self) -> jax.Array:
        """Per-client (Hessian-similarity) delta: max_m ||A_m - Abar||_op.

        The stronger condition used by the surrogate baselines (DANE/SONATA/
        extragradient sliding); always >= `similarity()`."""
        E = self.A - self.A_bar[None]
        op = jax.vmap(lambda e: jnp.max(jnp.abs(jnp.linalg.eigvalsh(e))))(E)
        return jnp.max(op)

    def grad_noise_at_opt(self) -> jax.Array:
        """sigma_*^2 = E_m ||grad f_m(x_*)||^2 (Theorem 1's noise constant)."""
        x_star = self.minimizer()
        g = jax.vmap(lambda A, b: mm(A, x_star) - b)(self.A, self.b)
        return jnp.mean(jnp.sum(g * g, axis=-1))


def _random_orthogonal(rng: np.random.Generator, d: int) -> np.ndarray:
    q, r = np.linalg.qr(rng.standard_normal((d, d)))
    return q * np.sign(np.diag(r))


def make_synthetic_quadratic(
    num_clients: int,
    dim: int,
    mu: float = 1.0,
    L: float = 3330.0,
    delta: float = 10.0,
    noise: float = 1.0,
    seed: int = 0,
    dtype=jnp.float64,
) -> QuadraticProblem:
    """Synthetic family matching the paper's setup: delta << L forced by design.

    Construction: a shared base Hessian `Abar0` with spectrum spanning [mu+delta, L],
    plus client perturbations E_m with sum_m E_m = 0 and
    lambda_max((1/M) sum E_m^2) = delta^2 exactly (computed, then rescaled).
    """
    rng = np.random.default_rng(seed)
    # Shared base with spread spectrum (log-uniform in [mu + delta, L - delta]).
    lo, hi = mu + delta, max(L - delta, mu + 2 * delta)
    eigs = np.exp(rng.uniform(np.log(lo), np.log(hi), size=dim))
    eigs[0], eigs[-1] = lo, hi
    Q = _random_orthogonal(rng, dim)
    A_base = (Q * eigs) @ Q.T

    # Zero-sum symmetric perturbations.
    E = rng.standard_normal((num_clients, dim, dim))
    E = 0.5 * (E + np.swapaxes(E, 1, 2))
    E -= E.mean(axis=0, keepdims=True)
    # Rescale so that the exact similarity constant equals `delta`.
    S = np.mean(np.einsum("mij,mjk->mik", E, E), axis=0)
    cur = np.sqrt(np.linalg.eigvalsh(S)[-1])
    E *= delta / cur

    A = A_base[None] + E
    # Guarantee mu-strong convexity of *every* client despite perturbation:
    min_eig = min(np.linalg.eigvalsh(A_m)[0] for A_m in A)
    if min_eig < mu:
        A += (mu - min_eig) * np.eye(dim)[None]

    b = noise * rng.standard_normal((num_clients, dim))
    # Center b so the optimum stays O(1) in norm.
    return QuadraticProblem(A=jnp.asarray(A, dtype), b=jnp.asarray(b, dtype))


def make_ridge_problem(
    Z: np.ndarray,  # (M, n, d) per-client features
    y: np.ndarray,  # (M, n) per-client labels
    lam: float,
    dtype=jnp.float64,
) -> QuadraticProblem:
    """Ridge regression per the paper:  f_m(x) = (1/n)||Z_m x - y_m||^2 + lam/2 ||x||^2.

    Note the paper's loss uses mean squared error with factor 1/n (no 1/2), so
    A_m = (2/n) Z_m^T Z_m + lam I  and  b_m = (2/n) Z_m^T y_m.
    """
    M, n, d = Z.shape
    A = 2.0 / n * np.einsum("mni,mnj->mij", Z, Z) + lam * np.eye(d)[None]
    b = 2.0 / n * np.einsum("mni,mn->mi", Z, y)
    return QuadraticProblem(A=jnp.asarray(A, dtype), b=jnp.asarray(b, dtype))
