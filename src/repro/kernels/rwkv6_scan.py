"""RWKV-6 WKV recurrence — Pallas TPU kernel.

Grid: (B, H, T/block_t).  The (K x V) per-head state lives in VMEM scratch and
is carried across sequential time-block grid steps (TPU grids are sequential
in the minor dimension — the TPU-native substitute for a persistent-CTA
carry).  Within a block the recurrence is stepped with a fori_loop over time:
the data-dependent per-CHANNEL decay w_t makes the chunked matmul
factorization exp(cw[t]-cw[s]) numerically explosive for strong decays, so
the in-block loop is the robust choice (VPU-bound; noted in EXPERIMENTS.md
§Perf — the MXU form with per-block renormalization is the known upgrade).

Validated in interpret mode against `ref.rwkv6_scan`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref, S_scr, *, block_t):
    it = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(it == 0)
    def _init():
        S_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)  # (bt, K)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)  # (bt, V)
    w = w_ref[0, 0].astype(jnp.float32)  # (bt, K) decay factors in (0,1)
    u = u_ref[0].astype(jnp.float32)  # (K,)

    def step(t, y_acc):
        S = S_scr[...]  # (K, V)
        rt = jax.lax.dynamic_slice_in_dim(r, t, 1, 0)  # (1, K)
        kt = jax.lax.dynamic_slice_in_dim(k, t, 1, 0)
        vt = jax.lax.dynamic_slice_in_dim(v, t, 1, 0)  # (1, V)
        wt = jax.lax.dynamic_slice_in_dim(w, t, 1, 0)
        kv = kt.T @ vt  # (K, V)
        y_t = rt @ (S + u[:, None] * kv)  # (1, V)
        S_scr[...] = wt.T * S + kv
        return jax.lax.dynamic_update_slice_in_dim(y_acc, y_t, t, 0)

    y = jax.lax.fori_loop(0, block_t, step, jnp.zeros_like(v))
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(it == nt - 1)
    def _final():
        sout_ref[0, 0] = S_scr[...]


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def rwkv6_scan(r, k, v, w, u, state0=None, *, block_t: int = 64, interpret: bool | None = None):
    """Same contract as ref.rwkv6_scan: r,k,w (B,T,H,K); v (B,T,H,V); u (H,K).
    Returns (y (B,T,H,V), final state (B,H,K,V))."""
    B, T, H, K = r.shape
    V = v.shape[-1]
    bt = min(block_t, T)
    pad = (-T) % bt
    tr = lambda a: jnp.moveaxis(a, 2, 1)  # (B,H,T,*)
    rt, kt2, vt, wt = tr(r), tr(k), tr(v), tr(w)
    if pad:
        zpad = ((0, 0), (0, 0), (0, pad), (0, 0))
        rt = jnp.pad(rt, zpad)
        kt2 = jnp.pad(kt2, zpad)
        vt = jnp.pad(vt, zpad)
        # pad decay with ones so the state is unchanged on padded steps
        wt = jnp.pad(wt, zpad, constant_values=1.0)
    nt = (T + pad) // bt
    if state0 is None:
        state0 = jnp.zeros((B, H, K, V), jnp.float32)

    y, s_out = pl.pallas_call(
        functools.partial(_wkv_kernel, block_t=bt),
        grid=(B, H, nt),
        in_specs=[
            pl.BlockSpec((1, 1, bt, K), lambda b, h, it: (b, h, it, 0)),
            pl.BlockSpec((1, 1, bt, K), lambda b, h, it: (b, h, it, 0)),
            pl.BlockSpec((1, 1, bt, V), lambda b, h, it: (b, h, it, 0)),
            pl.BlockSpec((1, 1, bt, K), lambda b, h, it: (b, h, it, 0)),
            pl.BlockSpec((1, K), lambda b, h, it: (h, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, it: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bt, V), lambda b, h, it: (b, h, it, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, it: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T + pad, V), r.dtype),
            jax.ShapeDtypeStruct((B, H, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(rt, kt2, vt, wt, u, state0)
    y = jnp.moveaxis(y[:, :, :T], 1, 2)  # (B,T,H,V)
    return y, s_out
