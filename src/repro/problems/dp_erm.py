"""Differentially private ERM: the paper's headline application of second-order
similarity (abstract: "...including distributed statistical learning and
differentially private empirical risk minimization").

Mechanism
---------
Each client holds n samples and privatizes its contribution with the
OBJECTIVE-PERTURBATION form of DP-ERM (Chaudhuri et al. style): the released
per-client objective is

    f_m^DP(x) = f_m(x) + s_m^T x,      s_m = nu * xi_m,   xi_m ~ N(0, I_d),

with nu = sigma * Delta the Gaussian-mechanism scale at per-client gradient
sensitivity Delta = 2 * clip / n (replace-one adjacency after clipping every
per-sample gradient/feature row to norm <= clip) and noise multiplier sigma.
The noise table xi = (M, d) is drawn ONCE from a PRNG key at construction and
carried as problem data, so every execution substrate (sequential / batched /
fused Pallas) consumes bit-identical noise — the substrate-equivalence suite
(tests/test_substrates.py) gates the DP problems including the noise draws.

Because the perturbation is LINEAR in x, three structural facts follow, each
load-bearing for the rest of the repo:

* Hessians are untouched and gradient-deviation DIFFERENCES cancel the
  constant shift, so the second-order similarity constant delta of the base
  problem is EXACTLY preserved (Assumption 1 survives privatization; this is
  why the paper can promise delta ~ O(1/sqrt(n)) for DP-ERM).
* prox_{eta f^DP}(z) = prox_{eta f}(z - eta s_m): the fused Pallas path reuses
  the existing batched prox kernels with a shifted target and the original
  start point (`rounds.prox_gd_fused`; `kernels.logistic_prox` grew a `y0`
  operand for exactly this fold).
* For quadratics the shift is absorbed into b, so every registered solver
  (exact / spectral / gd / newton / newton-cg) works unchanged.

Accounting
----------
`privacy_spent(steps, p, sigma)` is the zCDP accountant for the per-round
gradient-release schedule this noise scale corresponds to: each of the
`steps` rounds releases one Gaussian-mechanism output at noise multiplier
sigma (rho = 1/(2 sigma^2) per release), and a given client's data is touched
in a p-fraction of rounds (uniform single-client sampling at rate p), so the
linearly-composed budget is rho_total = steps * p / (2 sigma^2), converted to
(eps, delta_dp) with the standard zCDP bound eps = rho + 2 sqrt(rho ln(1/delta)).
This is the UNAMPLIFIED composition — privacy amplification by subsampling
(RDP accounting) is a recorded ROADMAP follow-up, as are per-client clipping
schedules.

NOISE-REUSE CAVEAT (read before quoting an eps): the accountant prices the
mechanism that draws FRESH noise at every release, but the simulation above
reuses each client's single draw s_m across all of its participations — a
deliberate utility-side simplification that keeps the three substrates
bit-identical without threading a noise-key lane through the round layer
(the recorded "per-round fresh DP noise" ROADMAP item).  Reused noise does
NOT satisfy the composed guarantee (two releases from the same client at
different iterates cancel s_m exactly), so the (eps, delta) this module
reports is the budget of the CORRESPONDING fresh-noise schedule — the thing
the paper's DP-ERM regime assumes — not a certificate for the replayed
trajectory.  The utility numbers (noise-perturbed optima, convergence under
perturbation, the preserved delta) are what this workload is for.

`similarity_bound()` composes the clipping radius into the paper's
O(1/sqrt(n)) delta estimate via matrix concentration: n i.i.d. per-sample
Hessians, each bounded in operator norm by B_H (clip^2/4 for logistic GLM
rows clipped to norm <= clip; 2 clip^2 for the ridge convention), concentrate
their mean around the population mean at rate B_H sqrt(8 log(2d) / n); client
deviations from the pool average obey twice that.  Cross-validated against
the measured `core.similarity.empirical_delta` in tests/test_dp_erm.py.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core.precision import mm
from repro.problems.logistic import LogisticProblem
from repro.problems.quadratic import QuadraticProblem


# ------------------------------------------------------------- zCDP accountant
def zcdp_to_eps(rho: float, target_delta: float) -> float:
    """The standard zCDP -> approximate-DP conversion (Bun & Steinke):
    rho-zCDP implies (rho + 2 sqrt(rho ln(1/delta)), delta)-DP."""
    if rho == math.inf:
        return math.inf
    return rho + 2.0 * math.sqrt(rho * math.log(1.0 / target_delta))


def privacy_spent(
    steps: int, p: float, sigma: float, *, target_delta: float = 1e-5
) -> tuple[float, float]:
    """(eps, delta_dp) after `steps` rounds at client-sampling rate p and noise
    multiplier sigma, by linear zCDP composition (no subsampling amplification):

        rho = steps * p / (2 sigma^2),   eps = rho + 2 sqrt(rho ln(1/delta)).

    Prices the fresh-noise-per-release schedule; see the module docstring's
    noise-reuse caveat for what the simulation actually replays.
    """
    if steps < 0 or not (0.0 <= p <= 1.0):
        raise ValueError(f"need steps >= 0 and 0 <= p <= 1, got {steps=}, {p=}")
    if sigma < 0:
        raise ValueError(f"noise multiplier must be >= 0, got {sigma=}")
    rho = math.inf if sigma == 0.0 else steps * p / (2.0 * sigma**2)
    return zcdp_to_eps(rho, target_delta), target_delta


def _hessian_concentration_bound(hess_bound: float, n: int, d: int) -> float:
    """delta <= 2 B_H sqrt(8 log(2d) / n): matrix-Hoeffding concentration of a
    mean of n i.i.d. per-sample Hessians (op-norm <= B_H) around the
    population mean, doubled for client-vs-pool-average deviations."""
    return 2.0 * hess_bound * math.sqrt(8.0 * math.log(2.0 * d) / n)


# ------------------------------------------------------------------ quadratic
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DPQuadraticProblem(QuadraticProblem):
    """A QuadraticProblem whose b already carries the DP objective
    perturbation (b_dp = b_base - s_m), plus the DP metadata.

    Every oracle, solver hook, and exact constant is inherited — the linear
    noise is quadratic-native — and `similarity()` is bitwise the base
    problem's (A is untouched).
    """

    dp_shift: jax.Array = None  # (M, d) s_m = nu * xi_m, already folded into b
    dp_sigma: float = dataclasses.field(default=1.0, metadata=dict(static=True))
    dp_clip: float = dataclasses.field(default=1.0, metadata=dict(static=True))
    dp_n: int = dataclasses.field(default=1, metadata=dict(static=True))

    def base_problem(self) -> QuadraticProblem:
        """The non-private comparator (same A, unnoised b) — utility in the
        privacy-utility frontier is measured against ITS minimizer."""
        return QuadraticProblem(A=self.A, b=self.b + self.dp_shift)

    def dp_linear_term(self, m: jax.Array) -> jax.Array:
        """s_m rows for the fused-path noise fold (informational here: the
        quadratic fused oracle reads the noise through `grad` via b)."""
        return jnp.take(self.dp_shift, m, axis=0)

    def privacy_spent(
        self, steps: int, p: float, *, target_delta: float = 1e-5
    ) -> tuple[float, float]:
        return privacy_spent(steps, p, self.dp_sigma, target_delta=target_delta)

    def similarity_bound(self) -> float:
        """Clip-composed O(1/sqrt(n)) delta estimate (ridge convention: the
        per-sample Hessian 2 z z' has op-norm <= 2 clip^2)."""
        return _hessian_concentration_bound(2.0 * self.dp_clip**2, self.dp_n, self.dim)


def make_dp_quadratic(
    base: QuadraticProblem,
    key: jax.Array,
    *,
    sigma: float,
    clip: float,
    n_per_client: int,
) -> DPQuadraticProblem:
    """Wrap a quadratic with the per-client objective perturbation.

    Noise scale nu = sigma * 2 clip / n (Gaussian mechanism at replace-one
    sensitivity 2 clip / n); grad f_m^DP = A_m x - b_m + s_m, i.e. b <- b - s.
    """
    nu = sigma * 2.0 * clip / n_per_client
    xi = jax.random.normal(key, base.b.shape, dtype=base.b.dtype)
    shift = nu * xi
    return DPQuadraticProblem(
        A=base.A, b=base.b - shift, dp_shift=shift,
        dp_sigma=sigma, dp_clip=clip, dp_n=n_per_client,
    )


# ------------------------------------------------------------------- logistic
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DPLogisticProblem(LogisticProblem):
    """LogisticProblem with feature rows clipped to norm <= dp_clip and the
    per-client linear perturbation s_m added to every gradient oracle.

    Hessians (and therefore the measured similarity constants) are untouched;
    `prox`/`minimizer` inherit the guarded Newton through the overridden
    `local_oracle`/`full_grad`, so the noise rides every solver for free.  The
    fused Pallas path reads `dp_linear_term(m)` and folds it into a shifted
    prox target (see `rounds.prox_gd_fused`).
    """

    dp_shift: jax.Array = None  # (M, d) s_m = nu * xi_m
    dp_sigma: float = dataclasses.field(default=1.0, metadata=dict(static=True))
    dp_clip: float = dataclasses.field(default=1.0, metadata=dict(static=True))

    @property
    def dp_n(self) -> int:
        return self.Z.shape[1]

    def base_problem(self) -> LogisticProblem:
        """The non-private comparator: same CLIPPED data, no noise (clipping
        is a preprocessing choice, not part of the privacy noise)."""
        return LogisticProblem(Z=self.Z, y=self.y, lam=self.lam)

    def dp_linear_term(self, m: jax.Array) -> jax.Array:
        return jnp.take(self.dp_shift, m, axis=0)

    # --- noised oracles (linear term has zero Hessian) -----------------------
    def loss(self, m, x):
        return super().loss(m, x) + mm(jnp.take(self.dp_shift, m, axis=0), x)

    def full_loss(self, x):
        return super().full_loss(x) + mm(jnp.mean(self.dp_shift, axis=0), x)

    def grad(self, m, x):
        return super().grad(m, x) + jnp.take(self.dp_shift, m, axis=0)

    def full_grad(self, x):
        return super().full_grad(x) + jnp.mean(self.dp_shift, axis=0)

    def local_oracle(self, m):
        grad0, hess0 = super().local_oracle(m)
        s_m = jnp.take(self.dp_shift, m, axis=0)
        return (lambda x: grad0(x) + s_m), hess0

    # --- DP metadata ---------------------------------------------------------
    def privacy_spent(
        self, steps: int, p: float, *, target_delta: float = 1e-5
    ) -> tuple[float, float]:
        return privacy_spent(steps, p, self.dp_sigma, target_delta=target_delta)

    def similarity_bound(self) -> float:
        """Clip-composed O(1/sqrt(n)) delta estimate: logistic per-sample
        Hessians sigma'(t) z z' have op-norm <= clip^2 / 4 after row clipping."""
        return _hessian_concentration_bound(self.dp_clip**2 / 4.0, self.dp_n, self.dim)


def clip_rows(Z: jax.Array, clip: float) -> jax.Array:
    """Per-sample feature clipping: rows with ||z_i|| > clip are rescaled onto
    the clip sphere (rows already inside are bit-untouched)."""
    norms = jnp.linalg.norm(Z, axis=-1, keepdims=True)
    scale = jnp.minimum(1.0, clip / jnp.maximum(norms, 1e-30))
    return Z * scale


def make_dp_logistic(
    base: LogisticProblem,
    key: jax.Array,
    *,
    sigma: float,
    clip: float,
) -> DPLogisticProblem:
    """Clip the base problem's feature rows to norm <= clip (bounding every
    per-sample gradient by clip, since |l'(t)| <= 1) and add the per-client
    Gaussian objective perturbation at nu = sigma * 2 clip / n."""
    n = base.Z.shape[1]
    nu = sigma * 2.0 * clip / n
    xi = jax.random.normal(key, (base.num_clients, base.dim), dtype=base.Z.dtype)
    return DPLogisticProblem(
        Z=clip_rows(base.Z, clip), y=base.y, lam=base.lam,
        dp_shift=nu * xi, dp_sigma=sigma, dp_clip=clip,
    )


def make_dp_a9a_problem(
    num_clients: int,
    *,
    sigma: float = 1.0,
    clip: float = 1.0,
    n_per_client: int = 2000,
    lam: float = 0.1,
    n_pool: int = 32561,
    dim: int = 123,
    seed: int = 0,
    noise_seed: int = 1,
    **kwargs,
) -> DPLogisticProblem:
    """The DP-ERM validation instance: the a9a-statistics-matched logistic
    pool (statistical similarity from i.i.d. per-client subsampling, Section
    9) privatized by row clipping + objective perturbation."""
    from repro.problems.logistic import make_a9a_like_problem

    base = make_a9a_like_problem(
        num_clients, n_per_client=n_per_client, lam=lam, n_pool=n_pool,
        dim=dim, seed=seed, **kwargs,
    )
    return make_dp_logistic(
        base, jax.random.key(noise_seed), sigma=sigma, clip=clip
    )
