"""Fused SVRP local prox-GD step — Pallas TPU kernel.

The inner loop of the paper's Algorithm 7 as executed on every cohort each
round:  y <- y - lr * (g + (y - z) * inv_eta).

Unfused this is 3 HBM reads + 2 intermediate writes + 1 output write per
element; fused it is 3 reads + 1 write — a pure memory-bandwidth op whose
roofline is exactly (4 * bytes)/(HBM bw).  Blocks are (8, 128)-aligned VPU
tiles streamed from HBM through VMEM.

Two entry points:

* `prox_update`          — single trial, any shape/dtype.
* `prox_update_batched`  — a `(B, n)` sweep variant for the batched experiment
  engine: one pallas_call whose grid spans batch x row-blocks, with PER-TRIAL
  scalars `(lr_b, inv_eta_b)` carried in a `(2, B)` SMEM table (one scalar column
  per trial, indexed by the batch grid coordinate), so a whole stepsize x seed
  sweep's Algorithm-7 inner loop stays fused in a single kernel launch.

Validated in interpret mode against ref.prox_update / ref.prox_update_batched;
compiled for a v5e by tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

_LANES = 128
_ROWS = 256  # (256, 128) f32 blocks = 128 KiB per operand in VMEM


def _prox_kernel(y_ref, g_ref, z_ref, s_ref, o_ref):
    y = y_ref[...]
    g = g_ref[...]
    z = z_ref[...]
    lr = s_ref[0, 0]
    inv_eta = s_ref[0, 1]
    o_ref[...] = y - lr * (g + (y - z) * inv_eta)


@functools.partial(jax.jit, static_argnames=("interpret",))
def prox_update(y, g, z, local_lr, inv_eta, *, interpret: bool | None = None):
    """Leaf-wise fused update; any shape/dtype (flattened to (rows, 128))."""
    shape, dtype = y.shape, y.dtype
    n = y.size
    cols = _LANES
    rows_total = -(-n // cols)
    pad = rows_total * cols - n
    block_rows = min(_ROWS, rows_total)
    rpad = (-rows_total) % block_rows

    def prep(a):
        a = a.reshape(-1)
        if pad:
            a = jnp.pad(a, (0, pad))
        a = a.reshape(rows_total, cols)
        if rpad:
            a = jnp.pad(a, ((0, rpad), (0, 0)))
        return a

    yp, gp, zp = prep(y), prep(g), prep(z)
    scalars = jnp.stack(
        [jnp.asarray(local_lr, dtype), jnp.asarray(inv_eta, dtype)]
    ).reshape(1, 2)
    grid = ((rows_total + rpad) // block_rows,)
    out = pl.pallas_call(
        _prox_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, cols), lambda i: (i, 0))] * 3
        + [pl.BlockSpec((1, 2), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(yp.shape, dtype),
        interpret=resolve_interpret(interpret),
    )(yp, gp, zp, scalars)
    return out[:rows_total].reshape(-1)[:n].reshape(shape)


def _prox_kernel_batched(s_ref, y_ref, g_ref, z_ref, o_ref):
    b = pl.program_id(0)
    y = y_ref[...]
    g = g_ref[...]
    z = z_ref[...]
    lr = s_ref[0, b]  # this trial's scalars: column b of the SMEM (2, B) table
    inv_eta = s_ref[1, b]
    o_ref[...] = y - lr * (g + (y - z) * inv_eta)


@functools.partial(jax.jit, static_argnames=("interpret",))
def prox_update_batched(y, g, z, local_lr, inv_eta, *, interpret: bool | None = None):
    """Per-trial fused update for a `(B, ...)` sweep batch.

    `y`, `g`, `z`: `(B, *trail)` — trial b's update uses `local_lr[b]` /
    `inv_eta[b]` (scalars broadcast to all trials).  Each trial's trailing
    dims are flattened to `(rows, 128)` lanes; the pallas grid is
    `(B, row_blocks)` and the per-trial scalar pair rides in a `(2, B)` SMEM
    table indexed by the batch grid coordinate — so the whole sweep is ONE
    kernel launch instead of B.
    """
    shape, dtype = y.shape, y.dtype
    B = shape[0]
    n = int(np.prod(shape[1:])) if len(shape) > 1 else 1
    cols = _LANES
    rows_total = -(-n // cols)
    pad = rows_total * cols - n
    block_rows = min(_ROWS, rows_total)
    rpad = (-rows_total) % block_rows

    def prep(a):
        a = a.reshape(B, -1)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)))
        a = a.reshape(B, rows_total, cols)
        if rpad:
            a = jnp.pad(a, ((0, 0), (0, rpad), (0, 0)))
        return a

    yp, gp, zp = prep(y), prep(g), prep(z)
    scalars = jnp.stack(
        [
            jnp.broadcast_to(jnp.asarray(local_lr, dtype), (B,)),
            jnp.broadcast_to(jnp.asarray(inv_eta, dtype), (B,)),
        ]
    )  # (2, B)
    grid = (B, (rows_total + rpad) // block_rows)
    out = pl.pallas_call(
        _prox_kernel_batched,
        grid=grid,
        # The whole scalar table sits in SMEM: a (1, 2) VMEM block of a
        # (B, 2) table breaks Mosaic's (8, 128) block-tiling rule for any
        # B > 1.  Program (b, i) reads its two scalars at column b.
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [pl.BlockSpec((1, block_rows, cols), lambda b, i: (b, i, 0))] * 3,
        out_specs=pl.BlockSpec((1, block_rows, cols), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(yp.shape, dtype),
        interpret=resolve_interpret(interpret),
        name="prox_update_batched",
    )(scalars, yp, gp, zp)
    return out[:, :rows_total].reshape(B, -1)[:, :n].reshape(shape)
