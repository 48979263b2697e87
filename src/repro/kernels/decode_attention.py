"""Single-token decode attention — Pallas TPU kernel (flash-decode style).

The decode_32k / long_500k serve steps are memory-bound on streaming the KV
cache (§Roofline); this kernel streams the cache through VMEM in (block_s x
Dh) tiles with the online-softmax state in scratch, one pass, no (S)-sized
HBM intermediates.  Grid: (B, H, S/block_s) — the cache-position loop is the
sequential minor grid dimension carrying (m, l, acc), exactly like the
training flash kernel but with a single query row.

Validity masking takes a precomputed bool vector (ring-buffer/sliding-window
semantics are computed by the caller — see layers.attn_decode_apply), so the
same kernel serves full-cache and windowed decode.

Validated in interpret mode vs ref.naive_decode_attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_scr, l_scr, acc_scr, *, scale):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (1, Dh)
    k = k_ref[0, 0].astype(jnp.float32)  # (bs, Dh)
    v = v_ref[0, 0].astype(jnp.float32)
    valid = valid_ref[0] > 0  # (bs,)

    s = (k @ q.T)[:, 0]  # (bs,)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[0, 0]
    m_blk = jnp.max(s)
    m_new = jnp.maximum(m_prev, m_blk)
    p = jnp.exp(s - m_new)
    p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    acc_scr[...] = corr * acc_scr[...] + (p[None, :] @ v)
    l_scr[0, 0] = corr * l_scr[0, 0] + jnp.sum(p)
    m_scr[0, 0] = m_new

    @pl.when(ik == nk - 1)
    def _final():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[0, 0], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(
    q,  # (B, 1, H, Dh)
    k_cache,  # (B, S, KVH, Dh)
    v_cache,
    valid,  # (S,) bool
    *,
    block_s: int = 512,
    interpret: bool | None = None,
):
    B, _, H, Dh = q.shape
    _, S, KVH, _ = k_cache.shape
    G = H // KVH

    qt = q.reshape(B, H, 1, Dh)
    kt = jnp.moveaxis(k_cache, 2, 1)  # (B, KVH, S, Dh)
    vt = jnp.moveaxis(v_cache, 2, 1)
    bs = min(block_s, S)
    pad = (-S) % bs
    if pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vmask = jnp.pad(valid, (0, pad)).astype(jnp.int32).reshape(1, S + pad)
    ns = (S + pad) // bs

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=Dh**-0.5),
        grid=(B, H, ns),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Dh), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, Dh), lambda b, h, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bs, Dh), lambda b, h, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, bs), lambda b, h, ik: (0, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, Dh), lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, Dh), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qt, kt, vt, vmask)
    return out.reshape(B, 1, H, Dh)
