"""Jitted kernel entry points used by the model zoo.

Each op has (a) a pure-jnp *chunked* fast path that is compile-safe at
production shapes (never materializes O(S^2)), used on CPU and as the default;
and (b) a Pallas TPU kernel (see sibling modules), enabled via `use_pallas()`
or the REPRO_USE_PALLAS env var.  `ref.py` holds the naive oracles.
"""
from __future__ import annotations

import functools
import os
from functools import partial

import jax
import jax.numpy as jnp

_USE_PALLAS = os.environ.get("REPRO_USE_PALLAS", "0") == "1"


def use_pallas(enable: bool = True) -> None:
    """Route the ops below through their Pallas kernels, which compile on a
    TPU and run in the interpreter elsewhere
    (`kernels.backend.resolve_interpret`)."""
    global _USE_PALLAS
    _USE_PALLAS = enable


def pallas_enabled() -> bool:
    return _USE_PALLAS


# ----------------------------------------------------------------- attention
def _mask_block(q_pos, k_pos, Skv, causal, sliding_window):
    mask = k_pos[None, :] < Skv
    if causal:
        mask = mask & (q_pos[:, None] >= k_pos[None, :])
    if sliding_window is not None:
        mask = mask & (q_pos[:, None] - k_pos[None, :] < sliding_window)
    return mask


def _chunked_attention_fwd_impl(q, k, v, *, causal, sliding_window, q_offset, chunk):
    """Returns (out, lse) — lse: (B, KVH, G, Sq) f32 logsumexp of scores."""
    B, Sq, H, Dh = q.shape
    _, Skv, KVH, _ = k.shape
    G = H // KVH
    C = min(chunk, Skv)
    pad = (-Skv) % C
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = (Skv + pad) // C

    qg = q.reshape(B, Sq, KVH, G, Dh).astype(jnp.float32) / jnp.sqrt(Dh)
    kc = k.reshape(B, n_chunks, C, KVH, Dh).astype(jnp.float32)
    vc = v.reshape(B, n_chunks, C, KVH, Dh).astype(jnp.float32)
    q_pos = jnp.arange(Sq) + q_offset

    def body(carry, inp):
        m, l, acc = carry
        kb, vb, c_idx = inp
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kb)
        k_pos = c_idx * C + jnp.arange(C)
        mask = _mask_block(q_pos, k_pos, Skv, causal, sliding_window)
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        corr = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - m_safe))
        l_new = corr * l + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bqhgd", p, vb)
        acc_new = corr.transpose(0, 3, 1, 2)[..., None] * acc + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KVH, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KVH, G, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Sq, KVH, G, Dh), jnp.float32)
    kb = jnp.moveaxis(kc, 1, 0)
    vb = jnp.moveaxis(vc, 1, 0)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kb, vb, jnp.arange(n_chunks)))
    l_t = l.transpose(0, 3, 1, 2)[..., None]
    out = (acc / jnp.maximum(l_t, 1e-37)).reshape(B, Sq, H, Dh).astype(q.dtype)
    m_safe = jnp.where(jnp.isinf(m), 0.0, m)
    lse = m_safe + jnp.log(jnp.maximum(l, 1e-37))
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _chunked_attention(q, k, v, causal, sliding_window, q_offset, chunk):
    out, _ = _chunked_attention_fwd_impl(
        q, k, v, causal=causal, sliding_window=sliding_window, q_offset=q_offset, chunk=chunk
    )
    return out


def _ca_fwd(q, k, v, causal, sliding_window, q_offset, chunk):
    out, lse = _chunked_attention_fwd_impl(
        q, k, v, causal=causal, sliding_window=sliding_window, q_offset=q_offset, chunk=chunk
    )
    return out, (q, k, v, out, lse)


def _ca_bwd(causal, sliding_window, q_offset, chunk, res, do):
    """Flash backward: recompute score blocks chunkwise — O(S*Dh) residency,
    never an (S,S) tensor.  Saves only (q,k,v,out,lse) from the forward."""
    q, k, v, out, lse = res
    B, Sq, H, Dh = q.shape
    _, Skv, KVH, _ = k.shape
    G = H // KVH
    C = min(chunk, Skv)
    pad = (-Skv) % C
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else k
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else v
    n_chunks = (Skv + pad) // C

    scale = 1.0 / jnp.sqrt(Dh)
    qg = q.reshape(B, Sq, KVH, G, Dh).astype(jnp.float32) * scale
    dog = do.reshape(B, Sq, KVH, G, Dh).astype(jnp.float32)
    og = out.reshape(B, Sq, KVH, G, Dh).astype(jnp.float32)
    delta = jnp.sum(dog * og, axis=-1)  # (B,Sq,KVH,G)
    delta = delta.transpose(0, 2, 3, 1)  # (B,KVH,G,Sq)
    kc = kp.reshape(B, n_chunks, C, KVH, Dh).astype(jnp.float32)
    vc = vp.reshape(B, n_chunks, C, KVH, Dh).astype(jnp.float32)
    q_pos = jnp.arange(Sq) + q_offset

    def body(dq_acc, inp):
        kb, vb, c_idx = inp  # (B,C,KVH,Dh) x2
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kb)
        k_pos = c_idx * C + jnp.arange(C)
        mask = _mask_block(q_pos, k_pos, Skv, causal, sliding_window)
        p = jnp.exp(s - lse[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)  # (B,KVH,G,Sq,C)
        dv_b = jnp.einsum("bhgqk,bqhgd->bkhd", p, dog)
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", dog, vb)
        ds = p * (dp - delta[..., None])  # (B,KVH,G,Sq,C)
        dq_b = jnp.einsum("bhgqk,bkhd->bqhgd", ds, kb) * scale
        dk_b = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qg)  # qg already scaled
        return dq_acc + dq_b, (dk_b, dv_b)

    dq0 = jnp.zeros((B, Sq, KVH, G, Dh), jnp.float32)
    kb = jnp.moveaxis(kc, 1, 0)
    vb = jnp.moveaxis(vc, 1, 0)
    dq, (dks, dvs) = jax.lax.scan(body, dq0, (kb, vb, jnp.arange(n_chunks)))
    dk = jnp.moveaxis(dks, 0, 1).reshape(B, Skv + pad, KVH, Dh)[:, :Skv]
    dv = jnp.moveaxis(dvs, 0, 1).reshape(B, Skv + pad, KVH, Dh)[:, :Skv]
    return (
        dq.reshape(B, Sq, H, Dh).astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
    )


_chunked_attention.defvjp(_ca_fwd, _ca_bwd)


def _chunked_attention_legacy(q, k, v, *, causal, sliding_window, q_offset=0, chunk=512):
    """Flash-style online-softmax attention, scanning over KV chunks.

    q: (B, Sq, H, Dh); k, v: (B, Skv, KVH, Dh).  GQA via head grouping —
    KV is never repeated to H heads.  All accumulation in f32.
    """
    B, Sq, H, Dh = q.shape
    _, Skv, KVH, _ = k.shape
    G = H // KVH
    C = min(chunk, Skv)
    # pad Skv to a multiple of C
    pad = (-Skv) % C
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = (Skv + pad) // C

    qg = q.reshape(B, Sq, KVH, G, Dh).astype(jnp.float32) / jnp.sqrt(Dh)
    kc = k.reshape(B, n_chunks, C, KVH, Dh).astype(jnp.float32)
    vc = v.reshape(B, n_chunks, C, KVH, Dh).astype(jnp.float32)

    q_pos = jnp.arange(Sq) + q_offset

    def body(carry, inp):
        m, l, acc = carry  # (B,KVH,G,Sq), (B,KVH,G,Sq), (B,Sq,KVH,G,Dh)
        kb, vb, c_idx = inp  # (B,C,KVH,Dh) x2, ()
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kb)  # (B,KVH,G,Sq,C)
        k_pos = c_idx * C + jnp.arange(C)
        mask = k_pos[None, :] < Skv  # padding
        if causal:
            mask = mask & (q_pos[:, None] >= k_pos[None, :])
        if sliding_window is not None:
            mask = mask & (q_pos[:, None] - k_pos[None, :] < sliding_window)
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        # guard -inf - -inf
        m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        corr = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - m_safe))
        l_new = corr * l + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bqhgd", p, vb)
        acc_new = corr.transpose(0, 3, 1, 2)[..., None] * acc + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KVH, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KVH, G, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Sq, KVH, G, Dh), jnp.float32)
    kb = jnp.moveaxis(kc, 1, 0)
    vb = jnp.moveaxis(vc, 1, 0)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kb, vb, jnp.arange(n_chunks)))
    l_t = l.transpose(0, 3, 1, 2)[..., None]  # (B,Sq,KVH,G,1)
    out = acc / jnp.maximum(l_t, 1e-37)
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


@partial(jax.jit, static_argnames=("causal", "sliding_window", "q_offset", "chunk"))
def attention(q, k, v, *, causal=True, sliding_window=None, q_offset=0, chunk=512):
    if _USE_PALLAS:
        from repro.kernels import flash_attention as fa

        return fa.flash_attention(
            q,
            k,
            v,
            causal=causal,
            sliding_window=sliding_window,
            q_offset=q_offset,
        )
    return _chunked_attention(q, k, v, causal, sliding_window, q_offset, chunk)


@jax.jit
def decode_attention(q, k_cache, v_cache, valid):
    """Single-token decode attention. q: (B,1,H,Dh); caches (B,S,KVH,Dh)."""
    if _USE_PALLAS:
        from repro.kernels import decode_attention as dk

        return dk.decode_attention(q, k_cache, v_cache, valid)
    B, _, H, Dh = q.shape
    _, S, KVH, _ = k_cache.shape
    G = H // KVH
    qg = q.reshape(B, KVH, G, Dh).astype(jnp.float32) / jnp.sqrt(Dh)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache.astype(jnp.float32))
    s = jnp.where(valid[None, None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, Dh).astype(q.dtype)


# ----------------------------------------------------------------- rwkv6
def rwkv6_scan(r, k, v, w, u, state0=None):
    if _USE_PALLAS:
        from repro.kernels import rwkv6_scan as rk

        return rk.rwkv6_scan(r, k, v, w, u, state0=state0)
    from repro.kernels import ref

    return ref.rwkv6_scan(r, k, v, w, u, state0=state0)


# ----------------------------------------------------------------- mamba2
def ssm_scan(x, dt, A, B_mat, C_mat, D, state0=None):
    if _USE_PALLAS:
        from repro.kernels import ssm_scan as sk

        return sk.ssm_scan(x, dt, A, B_mat, C_mat, D, state0=state0)
    from repro.kernels import _ssm_chunked

    return _ssm_chunked.ssm_scan_chunked(x, dt, A, B_mat, C_mat, D, state0=state0)


# ------------------------------------------------------------- prox update
def prox_update(y, g, z, local_lr, inv_eta):
    """Fused SVRP local step, applied leaf-wise to parameter pytrees."""
    if _USE_PALLAS:
        from repro.kernels import prox_update as pk

        return pk.prox_update(y, g, z, local_lr, inv_eta)
    from repro.kernels import ref

    return ref.prox_update(y, g, z, local_lr, inv_eta)


def prox_update_tree(y_tree, g_tree, z_tree, local_lr, inv_eta):
    """Fused SVRP local step over a whole parameter pytree.

    This is the default `update_fn` of the shared DeepSVRP local solver
    (`core.rounds.local_prox_gd_tree`), which the pod step (launch/steps.py)
    and the pytree round (`core.deep.deep_svrp_round`) both scan.

    `g` leaves are cast to the matching `y` leaf dtype (gradients arrive in
    f32 against bf16 params on the pod).  On the Pallas path the leaves are
    flattened and concatenated per dtype group so each local prox-GD step is
    ONE batched kernel launch per dtype instead of one launch per leaf.  On
    the jnp path XLA already fuses the leaf-wise elementwise update, so the
    concat copies would be pure overhead and are skipped.
    """
    leaves_y, treedef = jax.tree.flatten(y_tree)
    leaves_g = treedef.flatten_up_to(g_tree)
    leaves_z = treedef.flatten_up_to(z_tree)
    if not _USE_PALLAS:
        from repro.kernels import ref

        out = [
            ref.prox_update(y, g.astype(y.dtype), z, local_lr, inv_eta)
            for y, g, z in zip(leaves_y, leaves_g, leaves_z)
        ]
        return jax.tree.unflatten(treedef, out)

    from repro.kernels import prox_update as pk

    by_dtype: dict = {}
    for i, y in enumerate(leaves_y):
        by_dtype.setdefault(jnp.dtype(y.dtype), []).append(i)
    out = [None] * len(leaves_y)
    for dt, idxs in by_dtype.items():
        sizes = [leaves_y[i].size for i in idxs]
        yc = jnp.concatenate([leaves_y[i].reshape(-1) for i in idxs])
        gc = jnp.concatenate([leaves_g[i].reshape(-1).astype(dt) for i in idxs])
        zc = jnp.concatenate([leaves_z[i].reshape(-1) for i in idxs])
        upd = pk.prox_update(yc, gc, zc, local_lr, inv_eta)
        off = 0
        for i, n in zip(idxs, sizes):
            out[i] = upd[off:off + n].reshape(leaves_y[i].shape)
            off += n
    return jax.tree.unflatten(treedef, out)
