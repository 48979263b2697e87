"""Kernel microbenchmarks (CPU wall-time for the jnp fast paths; the Pallas
TPU kernels are validated in interpret mode — wall-time on CPU interpret is
not meaningful, so we report the fast-path timings plus naive-vs-chunked
speedup, which is the structural claim)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


def _time(f, *args, iters=5):
    out = f(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def run(quick: bool = False):
    rows = []
    key = jax.random.key(0)
    ks = jax.random.split(key, 6)

    # attention: chunked (flash-style) vs naive at a train-ish shape
    B, S, H, KVH, Dh = (1, 512, 4, 2, 64) if quick else (2, 1024, 8, 2, 64)
    q = jax.random.normal(ks[0], (B, S, H, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KVH, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KVH, Dh), jnp.float32)
    t_naive = _time(jax.jit(lambda q, k, v: ref.naive_attention(q, k, v)), q, k, v)
    t_chunk = _time(jax.jit(lambda q, k, v: ops.attention(q, k, v)), q, k, v)
    rows.append(("attention_naive", t_naive, f"S={S}"))
    rows.append(("attention_chunked", t_chunk, f"speedup_vs_naive={t_naive / t_chunk:.2f}x"))

    # ssm: sequential ref vs chunked
    Bb, T, Hh, P, N = (1, 512, 4, 16, 16) if quick else (2, 2048, 8, 32, 64)
    x = jax.random.normal(ks[3], (Bb, T, Hh, P))
    dt = jax.nn.softplus(jax.random.normal(ks[4], (Bb, T, Hh)))
    A = -jnp.abs(jax.random.normal(ks[5], (Hh,)))
    Bm = jax.random.normal(ks[0], (Bb, T, N))
    Cm = jax.random.normal(ks[1], (Bb, T, N))
    D = jnp.ones((Hh,))
    t_seq = _time(jax.jit(lambda *a: ref.ssm_scan(*a)[0]), x, dt, A, Bm, Cm, D)
    t_chk = _time(jax.jit(lambda *a: ops.ssm_scan(*a)[0]), x, dt, A, Bm, Cm, D)
    rows.append(("ssm_scan_sequential", t_seq, f"T={T}"))
    rows.append(("ssm_scan_chunked", t_chk, f"speedup={t_seq / t_chk:.2f}x"))

    # rwkv ref scan
    r = jax.random.normal(ks[2], (Bb, T, Hh, P))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (Bb, T, Hh, P)))
    u = jax.random.normal(ks[4], (Hh, P))
    t_rwkv = _time(jax.jit(lambda *a: ops.rwkv6_scan(*a)[0]), r, x, x, w, u)
    rows.append(("rwkv6_scan", t_rwkv, f"T={T}"))

    # prox_update fused vs unfused
    n = 1_000_000 if not quick else 100_000
    y = jax.random.normal(ks[5], (n,))
    g = jax.random.normal(ks[0], (n,))
    z = jax.random.normal(ks[1], (n,))
    t_fused = _time(jax.jit(lambda y, g, z: ops.prox_update(y, g, z, 0.1, 2.0)), y, g, z)
    unfused = jax.jit(lambda y, g, z: y - 0.1 * (g + (y - z) * 2.0))
    t_unf = _time(unfused, y, g, z)
    rows.append(("prox_update", t_fused, f"n={n},unfused_us={t_unf:.1f}"))
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache(__file__)
    for r in run(quick=True):
        print(f"{r[0]},{r[1]:.1f},{r[2]}")
