"""Flash attention — Pallas TPU kernel (pl.pallas_call + explicit BlockSpec).

TPU adaptation notes (vs the CUDA flash-attention algorithm):
  * the KV loop is the *minor grid dimension* — TPU grids execute sequentially
    per core, so the online-softmax running state (m, l, acc) lives in VMEM
    scratch carried across KV grid steps (no shared-memory tiles / warp sync);
  * block shapes are MXU/VPU aligned: (block_q x Dh) and (block_k x Dh) tiles,
    Dh and blocks multiples of 128 preferred (we fall back for small dims);
  * GQA is handled by indexing the KV head as h // group in the BlockSpec
    index_map — KV tiles are never replicated to Q heads in HBM.

VMEM budget per program @ defaults (bq=bk=128, Dh=128, f32 accum):
  q/k/v tiles 3*128*128*4 = 192 KiB, acc 64 KiB, s/p 64 KiB -> ~<0.5 MiB of
  the ~16 MiB/core VMEM, leaving headroom for double buffering.

Causal/sliding-window masking is by absolute position, so the same kernel
serves full causal, window, and non-causal (cross-attention) variants.
Validated in interpret mode against `ref.naive_attention` (see tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    causal: bool,
    sliding_window: int | None,
    q_offset: int,
    block_q: int,
    block_k: int,
    seq_kv: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Skip fully-masked blocks (strictly above the causal diagonal).
    first_q = iq * block_q + q_offset
    block_needed = True
    if causal:
        block_needed = (ik * block_k) <= (first_q + block_q - 1)

    @pl.when(block_needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (Bq, Dh)
        k = k_ref[0, 0].astype(jnp.float32)  # (Bk, Dh)
        v = v_ref[0, 0].astype(jnp.float32)

        s = q @ k.T  # (Bq, Bk)
        q_pos = (
            iq * block_q
            + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            + q_offset
        )
        k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos < seq_kv
        if causal:
            mask &= q_pos >= k_pos
        if sliding_window is not None:
            mask &= (q_pos - k_pos) < sliding_window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_blk = jnp.max(s, axis=-1, keepdims=True)  # (Bq, 1)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        acc_scr[...] = corr * acc_scr[...] + p @ v
        m_scr[...] = m_new
        l_scr[...] = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sliding_window", "q_offset", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q,  # (B, Sq, H, Dh)
    k,  # (B, Skv, KVH, Dh)
    v,
    *,
    causal: bool = True,
    sliding_window: int | None = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
):
    B, Sq, H, Dh = q.shape
    _, Skv, KVH, _ = k.shape
    G = H // KVH

    qt = jnp.moveaxis(q, 2, 1)  # (B, H, Sq, Dh)
    kt = jnp.moveaxis(k, 2, 1)  # (B, KVH, Skv, Dh)
    vt = jnp.moveaxis(v, 2, 1)

    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    pad_q = (-Sq) % bq
    pad_k = (-Skv) % bk
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = (Sq + pad_q) // bq
    nk = (Skv + pad_k) // bk

    kernel = functools.partial(
        _flash_kernel,
        scale=Dh**-0.5,
        causal=causal,
        sliding_window=sliding_window,
        q_offset=q_offset,
        block_q=bq,
        block_k=bk,
        seq_kv=Skv,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, Dh), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, Dh), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dh), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + pad_q, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dh), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qt, kt, vt)
    out = out[:, :, :Sq]
    return jnp.moveaxis(out, 1, 2)  # (B, Sq, H, Dh)
