"""Batched logistic prox-GD — Pallas kernel for the non-quadratic sweep track.

`kernels.prox_update_batched` fuses the ELEMENTWISE Algorithm-7 update
`y - beta (g + (y - z)/eta)` but still reads the gradient from HBM each GD
step.  For the logistic oracle the gradient itself is two skinny matmuls, so
this kernel goes one level deeper: the WHOLE Algorithm-7 loop for every trial
of a sweep runs inside one pallas_call — client data stays resident in VMEM
across all GD steps instead of being re-streamed per step.

Sign-folded operand: with A := y[:, None] * Z (label-signed features, one
(n, d) block per trial) the logistic prox objective needs only A —

    t = A x            (margins y_i z_i'x)
    g = -A' sigmoid(-t)/n + lam x           (client gradient)
    x <- x - beta (g + (x - z) / eta)       (Algorithm 7 step)

so padded rows (A = 0) contribute exactly nothing (sigmoid(0) scales a zero
row) and padded columns stay 0 from the x0 = z start — no masks needed.

Grid is `(B,)`: program b owns trial b's (n_pad, d_pad) block and runs the
full `num_steps` fori_loop in VMEM; per-trial scalars (beta_b, 1/eta_b, lam,
1/n) ride in a `(4, B)` SMEM table, and the per-trial rows (target, start,
result) are laid out `(B, 1, d_pad)` so every VMEM block's last two dims are
the array's (Mosaic's block-tiling rule, for any B).  VMEM budget is the A
block: n_pad * d_pad * itemsize (a9a at f32: 1632 * 128 * 4 = 0.8 MiB,
double-buffered).  Validated in interpret mode against
`ref.logistic_prox_gd_batched`; compiled for a v5e by
tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import HIGHEST
from repro.kernels.backend import resolve_interpret

_LANES = 128
_SUBLANES = 8


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _logistic_prox_kernel(s_ref, a_ref, z_ref, x0_ref, o_ref, *, num_steps: int):
    b = pl.program_id(0)
    A = a_ref[0]  # (n_pad, d_pad) — this trial's label-signed features
    z = z_ref[0]  # (1, d_pad) prox target
    x0 = x0_ref[0]  # (1, d_pad) GD start (== z except for the DP noise fold)
    beta = s_ref[0, b]  # this trial's scalars: column b of the SMEM (4, B) table
    inv_eta = s_ref[1, b]
    lam = s_ref[2, b]
    inv_n = s_ref[3, b]

    def gd_step(_, x):  # x: (1, d_pad)
        # t = x A' : (1, n_pad) margins; sigmoid(-t) = 0.5 (tanh(-t/2) + 1)
        t = jax.lax.dot_general(x, A, (((1,), (1,)), ((), ())), precision=HIGHEST)
        u = 0.5 * (jnp.tanh(-0.5 * t) + 1.0)
        g = -inv_n * jnp.dot(u, A, precision=HIGHEST) + lam * x
        return x - beta * (g + (x - z) * inv_eta)

    o_ref[0] = jax.lax.fori_loop(0, num_steps, gd_step, x0)


@functools.partial(jax.jit, static_argnames=("num_steps", "interpret"))
def logistic_prox_gd_batched(
    A: jax.Array,  # (B, n, d) label-signed client rows (y[:, None] * Z), per trial
    z: jax.Array,  # (B, d) prox targets
    beta: jax.Array,  # (B,) Algorithm-7 stepsize 1/(L + 1/eta)
    inv_eta: jax.Array,  # (B,)
    lam: float,
    num_steps: int,
    *,
    y0: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """`num_steps` of Algorithm 7 on the `(B, n, d)` logistic oracle, one launch.

    Returns the `(B, d)` approximate prox points (started from `y0`, which
    defaults to `z` exactly like `core.prox.prox_gd`).  A separate start point
    is what lets the DP-ERM fused path reuse this kernel unchanged: the linear
    noise term folds into a SHIFTED target z' = z - eta s while the iteration
    still starts at the unshifted z (`rounds.prox_gd_fused`).  `lam` is the
    problem's shared l2 coefficient; the 1/n gradient normalization uses the
    TRUE row count `n` (row padding to the sublane multiple is free by the
    sign-folding above).
    """
    B, n, d = A.shape
    dtype = A.dtype
    d_pad = _round_up(d, _LANES)
    n_pad = _round_up(n, _SUBLANES)

    def rows(v):  # (B, d) -> (B, 1, d_pad): each block's last two dims are full
        return jnp.pad(v.astype(dtype), ((0, 0), (0, d_pad - d)))[:, None, :]

    A_p = jnp.pad(A, ((0, 0), (0, n_pad - n), (0, d_pad - d)))
    z_p = rows(z)
    x0_p = z_p if y0 is None else rows(y0)
    scalars = jnp.stack(
        [
            jnp.broadcast_to(jnp.asarray(beta, dtype), (B,)),
            jnp.broadcast_to(jnp.asarray(inv_eta, dtype), (B,)),
            jnp.full((B,), lam, dtype),
            jnp.full((B,), 1.0 / n, dtype),
        ]
    )  # (4, B): program b reads column b

    row = pl.BlockSpec((1, 1, d_pad), lambda b: (b, 0, 0))
    out = pl.pallas_call(
        functools.partial(_logistic_prox_kernel, num_steps=num_steps),
        grid=(B,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, n_pad, d_pad), lambda b: (b, 0, 0)),
            row,
            row,
        ],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((B, 1, d_pad), dtype),
        interpret=resolve_interpret(interpret),
        name="logistic_prox_gd_batched",
    )(scalars, A_p, z_p, x0_p)
    return out[:, 0, :d]
