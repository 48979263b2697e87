"""Flash-decode Pallas kernel vs the naive decode oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention

SHAPES = [
    # (B, S, H, KVH, Dh, block_s)
    (2, 100, 4, 2, 16, 32),
    (1, 257, 8, 4, 32, 64),
    (3, 64, 6, 3, 8, 16),
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_matches_oracle(shape, dtype):
    B, S, H, KVH, Dh, bs = shape
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, 1, H, Dh), dtype)
    k = jax.random.normal(ks[1], (B, S, KVH, Dh), dtype)
    v = jax.random.normal(ks[2], (B, S, KVH, Dh), dtype)
    tol = dict(atol=3e-2, rtol=3e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)
    for t in [0, S // 2, S - 1]:
        valid = jnp.arange(S) <= t
        o_ref = ref.naive_decode_attention(q, k, v, valid)
        o_pal = decode_attention(q, k, v, valid, block_s=bs, interpret=True)
        np.testing.assert_allclose(
            np.asarray(o_pal, np.float32), np.asarray(o_ref, np.float32), **tol
        )


def test_ring_buffer_mask_pattern():
    """Sliding-window ring-buffer validity (non-contiguous mask) works."""
    B, S, H, KVH, Dh = 1, 48, 2, 1, 8
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (B, 1, H, Dh))
    k = jax.random.normal(ks[1], (B, S, KVH, Dh))
    v = jax.random.normal(ks[2], (B, S, KVH, Dh))
    valid = (jnp.arange(S) % 3 != 0)  # arbitrary scattered validity
    o_ref = ref.naive_decode_attention(q, k, v, valid)
    o_pal = decode_attention(q, k, v, valid, block_s=16, interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref), atol=2e-5, rtol=2e-5)


def test_ops_dispatch_pallas_decode():
    B, S, H, KVH, Dh = 2, 40, 4, 2, 16
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (B, 1, H, Dh))
    k = jax.random.normal(ks[1], (B, S, KVH, Dh))
    v = jax.random.normal(ks[2], (B, S, KVH, Dh))
    valid = jnp.arange(S) <= 20
    ops.use_pallas(True)
    try:
        o_p = ops.decode_attention(q, k, v, valid)
    finally:
        ops.use_pallas(False)
    o_j = ops.decode_attention(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_j), atol=2e-5, rtol=2e-5)
