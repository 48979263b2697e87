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

Layout.  Grid is `(B,)`: program b owns trial b's (n_pad, d_pad) block, both
padded to the 128-lane width, and runs the full `num_steps` fori_loop in
VMEM.  Per-trial scalars (beta_b, 1/eta_b, lam, 1/n) ride in a `(4, B)` SMEM
table; the per-trial rows (target, start, result) are laid out
`(B, 1, d_pad)` so every VMEM block's last two dims are the array's
(Mosaic's block-tiling rule, for any B).

Products on the VPU.  Before the loop the program writes its block into a
VMEM scratch as A' (d_pad, n_pad), one 128 x 128 transpose per tile, and
turns the target and start rows into (d_pad, 1) columns; x is carried as a
column.  Each step walks the n_pad/128 lane tiles of A': the margins of a
tile are `sum_j(A'_c * x)`, a sublane reduce over d; its sigmoids scale the
same tile into a (d_pad, 128) accumulator, which one lane reduce turns into
the gradient's column.  Both products are float32 multiplies and adds
(`core.precision`'s full-float32 contract, with no bfloat16 split), and the
MXU is never used: at one row per block a float32 matmul re-latches a whole
128 x 128 tile of A as weights in each of its six passes to multiply a
single vector, so the MXU would be fed weights, not work.

VMEM budget: three (n_pad, d_pad) blocks, the double-buffered input and the
A' scratch (a9a at f32: 3 x 1664 x 128 x 4 B = 2.6 MB).  Validated in
interpret mode against `ref.logistic_prox_gd_batched`; compiled for a v5e by
tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

_LANES = 128


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _logistic_prox_kernel(s_ref, a_ref, z_ref, x0_ref, o_ref, at_ref, *, num_steps: int):
    b = pl.program_id(0)
    d_pad, n_pad = at_ref.shape
    beta = s_ref[0, b]  # this trial's scalars: column b of the SMEM (4, B) table
    inv_eta = s_ref[1, b]
    lam = s_ref[2, b]
    inv_n = s_ref[3, b]

    # A' into VMEM scratch once, one (LANES, d_pad) row tile at a time: the
    # GD steps then read lane tiles of A' as plain (d_pad, LANES) loads.
    for c in range(n_pad // _LANES):
        cols = pl.ds(c * _LANES, _LANES)
        at_ref[:, cols] = a_ref[0, cols, :].T

    # Rows <-> columns by a masked reduce (an identity mask): x is carried as
    # a (d_pad, 1) column, so both products below broadcast along lanes.
    eye = (jax.lax.broadcasted_iota(jnp.int32, (d_pad, d_pad), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (d_pad, d_pad), 1))

    def to_col(v):  # (1, d_pad) -> (d_pad, 1)
        return jnp.sum(jnp.where(eye, v, 0), axis=1, keepdims=True)

    z = to_col(z_ref[0])  # prox target
    x0 = to_col(x0_ref[0])  # GD start (== z except for the DP noise fold)

    def gd_step(_, x):  # x: (d_pad, 1)
        acc = jnp.zeros((d_pad, _LANES), x.dtype)
        for c in range(n_pad // _LANES):
            at = at_ref[:, pl.ds(c * _LANES, _LANES)]  # (d_pad, LANES) of A'
            t = jnp.sum(at * x, axis=0, keepdims=True)  # (1, LANES) margins
            u = 0.5 * (jnp.tanh(-0.5 * t) + 1.0)  # sigmoid(-t)
            acc = acc + at * u
        g = -inv_n * jnp.sum(acc, axis=1, keepdims=True) + lam * x
        return x - beta * (g + (x - z) * inv_eta)

    x = jax.lax.fori_loop(0, num_steps, gd_step, x0)
    o_ref[0] = jnp.sum(jnp.where(eye, x, 0), axis=0, keepdims=True)  # back to a row


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
    TRUE row count `n` (row padding to the lane multiple is free by the
    sign-folding above).
    """
    B, n, d = A.shape
    dtype = A.dtype
    d_pad = _round_up(d, _LANES)
    n_pad = _round_up(n, _LANES)

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
        scratch_shapes=[pltpu.VMEM((d_pad, n_pad), dtype)],
        interpret=resolve_interpret(interpret),
        name="logistic_prox_gd_batched",
    )(scalars, A_p, z_p, x0_p)
    return out[:, 0, :d]
