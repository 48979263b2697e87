"""Plain reference for l2-regularized logistic regression under SVRP.

Everything here follows the source paper (Khaled & Jin, ICLR 2023; §9 and
Algorithms 2 and 7) and imports nothing of the program:

* `a9a_like`: a dataset with LIBSVM a9a's statistics (binary features with a
  Zipf-like column popularity, a fixed number of ones per row, labels from a
  planted logistic model), split i.i.d. over the clients;
* `minimizer` and `smoothness_max`: the optimum and the per-client
  smoothness bound, in float64 on the host;
* `svrp`: SVRP with the Algorithm-7 gradient-descent prox, one trial per
  (stepsize, seed), with the client draws and refresh coins taken from
  `jax.random.key(seed)` in the order the paper's round consumes them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.precision import matmul


def a9a_like(seed: int, *, num_clients: int, n_per_client: int, n_pool: int,
             dim: int, nnz_per_row: int) -> tuple[np.ndarray, np.ndarray]:
    """(Z (M, n, d), y (M, n)) float32 with a9a's statistics."""
    rng = np.random.default_rng(seed)
    col_p = 1.0 / np.arange(1, dim + 1) ** 0.8
    col_p /= col_p.sum()
    # Gumbel-top-k: nnz distinct columns per row, drawn by popularity.
    gumbel = rng.gumbel(size=(n_pool, dim))
    cols = np.argpartition(-(np.log(col_p)[None, :] + gumbel), nnz_per_row, axis=1)
    pool = np.zeros((n_pool, dim))
    np.put_along_axis(pool, cols[:, :nnz_per_row], 1.0, axis=1)
    x_true = rng.standard_normal(dim) / np.sqrt(nnz_per_row)
    prob = 1.0 / (1.0 + np.exp(-(pool @ x_true)))
    y_pool = np.where(rng.uniform(size=n_pool) < prob, 1.0, -1.0)
    idx = rng.integers(0, n_pool, size=(num_clients, n_per_client))
    return pool[idx].astype(np.float32), y_pool[idx].astype(np.float32)


def minimizer(Z, y, lam: float, tol: float = 1e-13) -> np.ndarray:
    """The optimum of the full objective: damped Newton in float64."""
    A = (np.asarray(y, np.float64)[..., None] * np.asarray(Z, np.float64)).reshape(-1, Z.shape[-1])
    N, d = A.shape

    def f(x):
        return np.mean(np.logaddexp(0.0, -(A @ x))) + 0.5 * lam * x @ x

    x = np.zeros(d)
    for _ in range(100):
        t = A @ x
        s = 1.0 / (1.0 + np.exp(t))  # sigmoid(-t)
        g = -(A.T @ s) / N + lam * x
        if np.linalg.norm(g) <= tol:
            break
        H = (A * (s * (1.0 - s))[:, None]).T @ A / N + lam * np.eye(d)
        step = np.linalg.solve(H, g)
        a, f0 = 1.0, f(x)
        while f(x - a * step) > f0 - 0.25 * a * g @ step and a > 1e-8:
            a *= 0.5
        x = x - a * step
    return x


def smoothness_max(Z, lam: float) -> float:
    """max over clients of lambda_max(Z_m' Z_m / (4 n)) + lam."""
    Z = np.asarray(Z, np.float64)
    n = Z.shape[1]
    return float(max(np.linalg.eigvalsh(z.T @ z / (4.0 * n))[-1] for z in Z) + lam)


def _sigmoid(t):
    return 0.5 * (jnp.tanh(0.5 * t) + 1.0)


@functools.partial(jax.jit, static_argnames=("p", "rounds", "prox_steps", "precision"))
def svrp(Z, y, lam, x_star, L, etas, seeds, *, p: float, rounds: int,
         prox_steps: int, precision: str = "highest"):
    """SVRP from x0 = 0 for each trial (etas[i], seeds[i]).

    Returns `(dist_sq, comm)`, each `(trials, rounds)`: ||x_k - x_*||^2 after
    round k, and the vectors communicated so far (3M to set up the anchor,
    2 per round, 3M per anchor refresh)."""
    mm = matmul(precision)
    A = y[..., None] * Z  # label-signed rows
    M, n, d = A.shape

    def client_sum(Am, x):  # sum over the client's rows of the loss gradient
        return -mm(_sigmoid(-mm(Am, x)), Am)

    def grad(m, x):
        return client_sum(A[m], x) / n + lam * x

    def full_grad(x):  # the mean of the clients' gradients
        return jnp.sum(jax.vmap(client_sum, (0, None))(A, x), axis=0) / (M * n) + lam * x

    def trial(eta, key):
        beta = 1.0 / (L + 1.0 / eta)
        inv_eta = 1.0 / eta

        def prox(m, z):  # Algorithm 7 from y0 = z
            return jax.lax.fori_loop(
                0, prox_steps, lambda _, v: v - beta * (grad(m, v) + (v - z) * inv_eta), z)

        def round_(carry, k):
            x, w, gbar, comm = carry
            k_m, k_c = jax.random.split(k)
            m = jax.random.randint(k_m, (), 0, M)
            x_next = prox(m, x - eta * (gbar - grad(m, w)))
            c = jax.random.bernoulli(k_c, jnp.float32(p))
            w_next = jnp.where(c, x_next, w)
            gbar = jax.lax.cond(c, lambda: full_grad(w_next), lambda: gbar)
            comm = comm + 2 + 3 * M * c.astype(jnp.int32)
            return (x_next, w_next, gbar, comm), (jnp.sum((x_next - x_star) ** 2), comm)

        x0 = jnp.zeros(d, jnp.float32)
        init = (x0, x0, full_grad(x0), jnp.int32(3 * M))
        _, out = jax.lax.scan(round_, init, jax.random.split(key, rounds))
        return out

    keys = jax.vmap(jax.random.key)(jnp.asarray(seeds, jnp.uint32))
    return jax.vmap(trial)(jnp.asarray(etas, jnp.float32), keys)


def floor_gap(got, want) -> float:
    """Largest |log10 got - log10 want| over trials, each side read as the
    median of its last quarter of rounds: where a trial has reached
    float32's floor, the floor's level (which the precision of the
    products sets), and where it has not, the trajectory's."""
    k = max(1, np.shape(got)[-1] // 4)
    g = np.median(np.asarray(got, np.float64)[..., -k:], axis=-1)
    w = np.median(np.asarray(want, np.float64)[..., -k:], axis=-1)
    return float(np.max(np.abs(np.log10(np.maximum(g, 1e-37)) - np.log10(np.maximum(w, 1e-37)))))
