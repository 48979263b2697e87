"""Pluggable approximate proximal-point solvers (the paper's Algorithm 7 and friends).

A b-approximation of prox_{eta h}(z) is any y with ||y - prox_{eta h}(z)||^2 <= b.
The paper evaluates these locally on the sampled client; here they are pure JAX
functions over a client's oracles so the same code runs inside lax.scan
(paper-faithful layer), under vmap (the batched experiment engine), and inside
the pod runtime's local steps (DeepSVRP).

Solver registry
---------------
Every `*_scan` driver that evaluates a client prox dispatches through
`get_prox_solver(name, problem)`, which validates the (solver, problem) pair at
TRACE time and returns a `ProxSolver` with a two-phase contract:

* ``prepare(problem) -> hoisted``  — run ONCE, outside the scan/vmap.  Anything
  expensive and iteration-independent lives here (e.g. the spectral solver's
  per-client eigendecomposition, an O(M d^3) factorization that turns every
  in-scan prox into two matvecs).  Solvers with nothing to hoist return None.
* ``solve(problem, hoisted, m, z, eta, *, smoothness, steps, tol) -> y`` — the
  traced per-step evaluation.  `m`, `z`, `eta` (and `smoothness`) may be traced
  values; `steps`/`tol` are static config, so the whole sweep stays one jit.

Registered solvers:

==========  =======================  ==========================================
name        problem requirement      method
==========  =======================  ==========================================
exact       ``.prox``                problem's own closed-form / high-precision
                                     prox (LU solve for quadratics, guarded
                                     Newton for logistic)
spectral    ``.prox_spectral``       hoisted eigendecomposition; QUADRATIC-ONLY
gd          ``.grad`` + smoothness   Algorithm 7: `steps` gradient steps at the
                                     theory stepsize 1/(L + 1/eta)
newton      ``.hessian``             damped Newton with backtracking line
                                     search + gradient-norm early exit
newton-cg   ``.grad`` (jvp-able)     inexact Newton: CG on Hessian-vector
                                     products (no materialized Hessian — the
                                     batch-friendly path: pure matvecs under
                                     vmap, no serialized LAPACK calls)
==========  =======================  ==========================================

The iterative solvers exit early through `lax.while_loop` once the subproblem
gradient norm drops below `tol`; under vmap the loop runs until every lane
converges while finished lanes' carries are masked, so batched trajectories
stay bitwise-identical to the sequential ones.

Layering: this registry is the LOCAL-SOLVE half of the round-substrate layer
(`repro.core.rounds`).  Each algorithm's round body is defined once there;
the sequential `*_scan` wrappers bind `solver.solve` per sampled client, the
engine's batched substrate (`rounds.registry_batched_scan`) vmaps the same
`solve` per trial inside a batch-level round (which is what makes the anchor
refresh batch-aware), and the fused substrate replaces it with the batched
Pallas Algorithm-7 kernels.  For batched non-quadratic sweeps prefer
"newton-cg": a vmapped `newton` serializes on its per-lane LAPACK solve,
while hvp-CG is pure matvecs (see the measured caveat-track ratios in
ROADMAP.md).
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.precision import mm


def prox_gd(
    grad_fn: Callable[[jax.Array], jax.Array],
    z: jax.Array,
    eta: float,
    L: float,
    num_steps: int,
    y0: jax.Array | None = None,
) -> jax.Array:
    """Algorithm 7: gradient descent on  phi(y) = h(y) + ||y - z||^2 / (2 eta).

    phi is (L + 1/eta)-smooth, so the theory stepsize is beta = 1/(L + 1/eta).
    The paper's stopping rule (||grad phi|| small) is replaced by a static step
    count so the solve is jit/scan-compatible; callers pick `num_steps` from the
    linear rate  (1 - (mu + 1/eta)/(L + 1/eta))^t.
    """
    beta = 1.0 / (L + 1.0 / eta)
    y_init = z if y0 is None else y0

    def body(_, y):
        return y - beta * (grad_fn(y) + (y - z) / eta)

    return jax.lax.fori_loop(0, num_steps, body, y_init)


def prox_gd_batched(
    grad_fn: Callable[[jax.Array], jax.Array],
    z: jax.Array,
    eta: jax.Array,
    L: jax.Array,
    num_steps: int,
    y0: jax.Array | None = None,
    *,
    use_kernel: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """Algorithm 7 across a whole sweep batch at once.

    `z`: `(B, d)` prox targets; `eta`, `L`: per-trial `(B,)` scalars (or
    broadcastable); `grad_fn` maps `(B, d) -> (B, d)` (trial b's client
    gradient applied to row b).  With `use_kernel=True` the inner update runs
    through the fused batched Pallas kernel (`kernels.prox_update_batched`) so
    the bandwidth-bound `y - beta (g + (y - z)/eta)` stays one launch per GD
    step for the entire sweep; otherwise it is the identical jnp expression.
    `interpret=None` compiles the kernel on TPU and interprets it elsewhere
    (`kernels.backend.resolve_interpret`).
    """
    B = z.shape[0]
    eta = jnp.broadcast_to(jnp.asarray(eta, z.dtype), (B,))
    L = jnp.broadcast_to(jnp.asarray(L, z.dtype), (B,))
    beta = 1.0 / (L + 1.0 / eta)  # (B,)
    inv_eta = 1.0 / eta
    y_init = z if y0 is None else y0

    if use_kernel:
        from repro.kernels.prox_update import prox_update_batched

        def body(_, y):
            return prox_update_batched(y, grad_fn(y), z, beta, inv_eta, interpret=interpret)

    else:

        def body(_, y):
            return y - beta[:, None] * (grad_fn(y) + (y - z) * inv_eta[:, None])

    return jax.lax.fori_loop(0, num_steps, body, y_init)


def prox_agd(
    grad_fn: Callable[[jax.Array], jax.Array],
    z: jax.Array,
    eta: float,
    L: float,
    mu: float,
    num_steps: int,
    y0: jax.Array | None = None,
) -> jax.Array:
    """Nesterov AGD on phi — the accelerated local solver the paper invokes for
    its computational-complexity bounds (O(sqrt(kappa) log 1/b) accesses)."""
    Lp = L + 1.0 / eta
    mup = mu + 1.0 / eta
    beta_step = 1.0 / Lp
    kappa = Lp / mup
    momentum = (jnp.sqrt(kappa) - 1.0) / (jnp.sqrt(kappa) + 1.0)
    y_init = z if y0 is None else y0

    def body(_, carry):
        y, v = carry
        g = grad_fn(v) + (v - z) / eta
        y_next = v - beta_step * g
        v_next = y_next + momentum * (y_next - y)
        return (y_next, v_next)

    y_fin, _ = jax.lax.fori_loop(0, num_steps, body, (y_init, y_init))
    return y_fin


# --------------------------------------------------------------- guarded Newton
def _backtrack(phi_grad, y, g, gnorm, direction, max_backtracks: int):
    """Backtracking line search on the gradient-norm merit.

    For the strongly convex prox subproblem, d = -H^{-1} g is a descent
    direction of (1/2)||grad phi||^2, so requiring

        ||grad phi(y + t d)|| <= (1 - c t) ||grad phi(y)||

    (c = 0.1) both damps the raw Newton step far from the solution and admits
    the full step (t = 1) in the quadratic-convergence region.  The condition
    is written as `~(accept)` so a NaN trial gradient (overflow at an
    overshooting step) keeps halving instead of being accepted.
    """
    c = jnp.asarray(0.1, y.dtype)
    one = jnp.asarray(1.0, y.dtype)

    def trial(t):
        y_t = y + t * direction
        g_t = phi_grad(y_t)
        return y_t, g_t, jnp.linalg.norm(g_t)

    def cond(carry):
        t, k, _, _, gn_t = carry
        return ~(gn_t <= (one - c * t) * gnorm) & (k < max_backtracks)

    def body(carry):
        t, k, _, _, _ = carry
        t = 0.5 * t
        y_t, g_t, gn_t = trial(t)
        return (t, k + 1, y_t, g_t, gn_t)

    y_1, g_1, gn_1 = trial(one)
    _, _, y_t, g_t, gn_t = jax.lax.while_loop(
        cond, body, (one, jnp.asarray(0), y_1, g_1, gn_1)
    )
    # Monotonicity guard: if even the smallest step did not decrease the
    # gradient norm (NaN included — the comparison is False), stay at y.
    accept = gn_t < gnorm
    return (
        jnp.where(accept, y_t, y),
        jnp.where(accept, g_t, g),
        jnp.where(accept, gn_t, gnorm),
    )


def prox_newton(
    grad_fn: Callable[[jax.Array], jax.Array],
    hess_fn: Callable[[jax.Array], jax.Array],
    z: jax.Array,
    eta: jax.Array,
    max_steps: int = 50,
    tol: float = 1e-10,
    y0: jax.Array | None = None,
    max_backtracks: int = 30,
) -> jax.Array:
    """Damped Newton on  phi(y) = h(y) + ||y - z||^2/(2 eta), with backtracking.

    Raw Newton steps on a non-quadratic h (logistic) overshoot when the
    Hessian is near its lam + 1/eta floor (saturated sigmoids) while the
    gradient is O(1) — at large eta the un-damped iteration oscillates or
    diverges.  Here every step passes the `_backtrack` guard, and the loop
    exits as soon as ||grad phi|| <= tol (quadratic local convergence makes
    that typically < 10 iterations at f64).
    """
    y_init = z if y0 is None else y0
    inv_eta = 1.0 / jnp.asarray(eta, z.dtype)
    eye = jnp.eye(z.shape[-1], dtype=z.dtype)

    def phi_grad(y):
        return grad_fn(y) + (y - z) * inv_eta

    def cond(carry):
        _, _, gnorm, it = carry
        return (gnorm > tol) & (it < max_steps)

    def body(carry):
        y, g, gnorm, it = carry
        H = hess_fn(y) + inv_eta * eye
        direction = -jnp.linalg.solve(H, g)
        y, g, gnorm = _backtrack(phi_grad, y, g, gnorm, direction, max_backtracks)
        return (y, g, gnorm, it + 1)

    g0 = phi_grad(y_init)
    y_fin, _, _, _ = jax.lax.while_loop(
        cond, body, (y_init, g0, jnp.linalg.norm(g0), jnp.asarray(0))
    )
    return y_fin


def prox_newton_cg(
    grad_fn: Callable[[jax.Array], jax.Array],
    z: jax.Array,
    eta: jax.Array,
    max_steps: int = 50,
    tol: float = 1e-10,
    y0: jax.Array | None = None,
    cg_steps: int = 25,
    max_backtracks: int = 30,
) -> jax.Array:
    """Inexact Newton on phi via CG over Hessian-VECTOR products.

    The Newton system (H_h + I/eta) d = -g is solved by conjugate gradients
    with hvps from `jax.jvp(grad_fn)` — no materialized Hessian and no LAPACK
    call, so the whole solver is matvecs/einsums that batch cleanly under the
    experiment engine's vmap (a batched `linalg.solve` serializes on CPU; this
    path does not).  CG runs to the Eisenstat–Walker forcing tolerance
    min(0.5, sqrt(||g||)) ||g|| (superlinear outer convergence), each outer
    step passes the same backtracking guard as `prox_newton`, and the outer
    loop exits early at ||grad phi|| <= tol.
    """
    y_init = z if y0 is None else y0
    inv_eta = 1.0 / jnp.asarray(eta, z.dtype)

    def phi_grad(y):
        return grad_fn(y) + (y - z) * inv_eta

    def cg_solve(y, g, gnorm):
        # Solve H d = -g to the forcing tolerance (residual norm target).
        # The linearization point is HOISTED: jax.linearize evaluates the
        # (transcendental-heavy) primal trace of grad_fn once per outer step,
        # so each CG iteration is two matvecs, not a full re-linearized jvp.
        _, jvp_fn = jax.linearize(grad_fn, y)

        def hvp(v):
            return jvp_fn(v) + v * inv_eta

        target = jnp.minimum(jnp.asarray(0.5, z.dtype), jnp.sqrt(gnorm)) * gnorm

        def cond(carry):
            _, _, _, rs, k = carry
            return (jnp.sqrt(rs) > target) & (k < cg_steps)

        def body(carry):
            d, r, p, rs, k = carry
            Hp = hvp(p)
            alpha = rs / mm(p, Hp)
            d = d + alpha * p
            r = r - alpha * Hp
            rs_new = mm(r, r)
            p = r + (rs_new / rs) * p
            return (d, r, p, rs_new, k + 1)

        r0 = -g
        d, _, _, _, _ = jax.lax.while_loop(
            cond, body, (jnp.zeros_like(g), r0, r0, mm(r0, r0), jnp.asarray(0))
        )
        return d

    def cond(carry):
        _, _, gnorm, it = carry
        return (gnorm > tol) & (it < max_steps)

    def body(carry):
        y, g, gnorm, it = carry
        direction = cg_solve(y, g, gnorm)
        y, g, gnorm = _backtrack(phi_grad, y, g, gnorm, direction, max_backtracks)
        return (y, g, gnorm, it + 1)

    g0 = phi_grad(y_init)
    y_fin, _, _, _ = jax.lax.while_loop(
        cond, body, (y_init, g0, jnp.linalg.norm(g0), jnp.asarray(0))
    )
    return y_fin


# -------------------------------------------------------------- solver registry
class ProxSolver(NamedTuple):
    """One registered local prox solver (see the module docstring's contract)."""

    name: str
    requires: tuple[str, ...]  # problem attributes the solver dispatches on
    quadratic_only: bool  # True -> reject problems without the closed quadratic form
    prepare: Callable  # (problem) -> hoisted aux (run once, outside the scan)
    solve: Callable  # (problem, hoisted, m, z, eta, *, smoothness, steps, tol) -> y


def _no_prepare(problem):
    return None


def _local_oracles(problem, m):
    """Client-m (grad_fn, hess_fn) with the data gather hoisted when the
    problem offers a `local_oracle` hook — inside an iterative solver the
    per-call gather of `problem.grad(m, .)` sits in the loop body, and under
    the experiment engine's vmap it becomes a (B, n, d) copy per iteration."""
    if hasattr(problem, "local_oracle"):
        return problem.local_oracle(m)
    return (
        lambda y: problem.grad(m, y),
        lambda y: problem.hessian(m, y) if hasattr(problem, "hessian") else None,
    )


def _solve_exact(problem, hoisted, m, z, eta, *, smoothness, steps, tol):
    del hoisted, smoothness, steps, tol
    return problem.prox(m, z, eta)


def _prepare_spectral(problem):
    return problem.prox_factors()


def _solve_spectral(problem, hoisted, m, z, eta, *, smoothness, steps, tol):
    del smoothness, steps, tol
    return problem.prox_spectral(m, z, eta, hoisted)


def _solve_gd(problem, hoisted, m, z, eta, *, smoothness, steps, tol):
    del hoisted, tol
    grad_fn, _ = _local_oracles(problem, m)
    return prox_gd(grad_fn, z, eta, smoothness, steps)


def _solve_newton(problem, hoisted, m, z, eta, *, smoothness, steps, tol):
    del hoisted, smoothness
    grad_fn, hess_fn = _local_oracles(problem, m)
    return prox_newton(grad_fn, hess_fn, z, eta, max_steps=steps, tol=tol)


def _solve_newton_cg(problem, hoisted, m, z, eta, *, smoothness, steps, tol):
    del hoisted, smoothness
    grad_fn, _ = _local_oracles(problem, m)
    return prox_newton_cg(grad_fn, z, eta, max_steps=steps, tol=tol)


PROX_SOLVERS: dict[str, ProxSolver] = {
    "exact": ProxSolver("exact", ("prox",), False, _no_prepare, _solve_exact),
    "spectral": ProxSolver(
        "spectral", ("prox_spectral", "prox_factors"), True,
        _prepare_spectral, _solve_spectral,
    ),
    "gd": ProxSolver("gd", ("grad",), False, _no_prepare, _solve_gd),
    "newton": ProxSolver("newton", ("grad", "hessian"), False, _no_prepare, _solve_newton),
    "newton-cg": ProxSolver(
        "newton-cg", ("grad",), False, _no_prepare, _solve_newton_cg
    ),
}
# Underscore alias so grids/configs built from identifiers also resolve.
PROX_SOLVERS["newton_cg"] = PROX_SOLVERS["newton-cg"]


def get_prox_solver(name: str, problem=None) -> ProxSolver:
    """Resolve a solver by name, validating the (solver, problem) pair.

    Raises at TRACE time — with the failing requirement spelled out — instead
    of letting an unsupported combination die later as an opaque attribute or
    shape error inside the scan.
    """
    if name not in PROX_SOLVERS:
        raise ValueError(
            f"unknown prox_solver {name!r}; available: "
            f"{sorted(set(s.name for s in PROX_SOLVERS.values()))}"
        )
    solver = PROX_SOLVERS[name]
    if problem is not None:
        missing = [a for a in solver.requires if not hasattr(problem, a)]
        if missing:
            kind = type(problem).__name__
            if solver.quadratic_only:
                raise ValueError(
                    f"prox_solver={solver.name!r} is a quadratic-only solver "
                    f"({kind} has no {'/'.join(missing)}); use 'newton', "
                    "'newton-cg', 'gd', or 'exact' for non-quadratic problems"
                )
            raise ValueError(
                f"prox_solver={solver.name!r} requires problem attributes "
                f"{missing}, which {kind} does not provide"
            )
    return solver


def gd_steps_for_accuracy(eta: float, L: float, mu: float, b: float, r0_sq: float) -> int:
    """Static step count so that prox_gd returns a b-approximation, from the
    linear convergence of GD on the (mu+1/eta)-strongly-convex subproblem."""
    import math

    kappa = (L + 1.0 / eta) / (mu + 1.0 / eta)
    rate = 1.0 - 1.0 / kappa
    if b >= r0_sq:
        return 1
    return max(1, math.ceil(math.log(b / r0_sq) / math.log(rate)))
