"""Mamba-2 selective scan — Pallas TPU kernel, chunked (SSD) form.

Grid: (B, H, T/block_t).  The (P x N) per-head state is VMEM scratch carried
across sequential time-block grid steps.  Unlike the RWKV kernel, Mamba-2's
decay is a SCALAR per (head, step), so the chunked factorization
exp(cum[t]-cum[s]) is a rank-1 (time x time) matrix — all intra-block work is
MXU matmuls:

    L[t,s]   = exp(cum[t]-cum[s]) * 1[s<=t]
    y_intra  = (L  *  (C_blk @ B_blk^T)) @ (dt * x)
    y_inter  = exp(cum) * (C_blk @ h^T)
    h_next   = exp(cum[-1]) h + (exp(cum[-1]-cum)*dt*x)^T @ B_blk

Numerics are safe: cum is decreasing (A<0, dt>0) so every exponent above is
<= 0 within the masked region.  Validated in interpret mode vs ref.ssm_scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret


def _ssm_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref, y_ref, hout_ref, h_scr, *, block_t):
    it = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(it == 0)
    def _init():
        h_scr[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)  # (bt, P)
    dt = dt_ref[0, 0].astype(jnp.float32)  # (bt, 1)
    A = a_ref[0].astype(jnp.float32)  # (1,) scalar for this head
    Bm = b_ref[0].astype(jnp.float32)  # (bt, N)
    Cm = c_ref[0].astype(jnp.float32)  # (bt, N)
    D = d_ref[0].astype(jnp.float32)  # (1,)

    a = A[0] * dt[:, 0]  # (bt,) negative steps
    cum = jnp.cumsum(a)  # (bt,) inclusive, decreasing
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (block_t, block_t), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (block_t, block_t), 1)
    tri = t_idx >= s_idx
    diff = cum[:, None] - cum[None, :]
    diff = jnp.where(tri, diff, 0.0)  # exponent <= 0 inside mask
    L = jnp.where(tri, jnp.exp(diff), 0.0)  # (bt, bt)

    h = h_scr[...]  # (P, N)
    dx = dt * x  # (bt, P)
    CB = Cm @ Bm.T  # (bt_t, bt_s)
    y_intra = (L * CB) @ dx  # (bt, P)
    y_inter = jnp.exp(cum)[:, None] * (Cm @ h.T)  # (bt, P)
    y = y_intra + y_inter + D[0] * x
    y_ref[0, 0] = y.astype(y_ref.dtype)

    w_out = jnp.exp(cum[-1] - cum)[:, None] * dx  # (bt, P)
    h_scr[...] = jnp.exp(cum[-1]) * h + w_out.T @ Bm  # (P, N)

    @pl.when(it == nt - 1)
    def _final():
        hout_ref[0, 0] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def ssm_scan(x, dt, A, B_mat, C_mat, D, state0=None, *, block_t: int = 128, interpret: bool | None = None):
    """Same contract as ref.ssm_scan: x (B,T,H,P); dt (B,T,H); A,D (H,);
    B_mat, C_mat (B,T,N).  Returns (y (B,T,H,P), final state (B,H,P,N))."""
    Bb, T, H, P = x.shape
    N = B_mat.shape[-1]
    bt = min(block_t, T)
    pad = (-T) % bt
    xt = jnp.moveaxis(x, 2, 1)  # (B,H,T,P)
    dtt = jnp.moveaxis(dt, 2, 1)[..., None]  # (B,H,T,1)
    if pad:
        xt = jnp.pad(xt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        dtt = jnp.pad(dtt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        B_mat = jnp.pad(B_mat, ((0, 0), (0, pad), (0, 0)))
        C_mat = jnp.pad(C_mat, ((0, 0), (0, pad), (0, 0)))
    nt = (T + pad) // bt
    if state0 is None:
        state0 = jnp.zeros((Bb, H, P, N), jnp.float32)
    A2 = A.reshape(H, 1)
    D2 = D.reshape(H, 1)

    y, h_out = pl.pallas_call(
        functools.partial(_ssm_kernel, block_t=bt),
        grid=(Bb, H, nt),
        in_specs=[
            pl.BlockSpec((1, 1, bt, P), lambda b, h, it: (b, h, it, 0)),
            pl.BlockSpec((1, 1, bt, 1), lambda b, h, it: (b, h, it, 0)),
            pl.BlockSpec((1, 1), lambda b, h, it: (h, 0)),
            pl.BlockSpec((1, bt, N), lambda b, h, it: (b, it, 0)),
            pl.BlockSpec((1, bt, N), lambda b, h, it: (b, it, 0)),
            pl.BlockSpec((1, 1), lambda b, h, it: (h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, it: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bt, P), lambda b, h, it: (b, h, it, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, it: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, H, T + pad, P), x.dtype),
            jax.ShapeDtypeStruct((Bb, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(xt, dtt, A2, B_mat, C_mat, D2, state0)
    y = jnp.moveaxis(y[:, :, :T], 1, 2)
    return y, h_out
