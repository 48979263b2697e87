"""The benchmark's FLOP and byte counts against XLA's `cost_analysis`.

Each closed form in `bench/counts.py` is compared with the FLOPs XLA's CPU
backend counts for the same arithmetic, compiled from the plain references.
Tolerances, and why:

* a logistic gradient and a prox step: 2%.  XLA counts the products exactly;
  the closed form's ~4 per row for the sigmoid and ~3-5 per coordinate for
  the elementwise tail are estimates of what XLA counts as a few more or
  fewer elementwise operations;
* the batched prox kernel's arithmetic (`steps` unrolled): 2%, as above;
* a dense decoder's forward pass: 3%.  The closed form counts the products
  only; XLA also counts norms, rotary embeddings, the softmax and the
  causal mask, a few percent at these widths;
* the gradient: the closed form says 3 forwards.  XLA's count of the
  backward pass lies within 10% of twice the forward (the gradients of the
  norms and softmax add elementwise work the products do not have).
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import counts  # noqa: E402
from bench.reference import dense_lm  # noqa: E402
from bench.reference.precision import matmul  # noqa: E402


@pytest.fixture(autouse=True)
def x32():
    with jax.enable_x64(False):
        yield


def xla_flops(f, *args) -> float:
    return float(jax.jit(f).lower(*args).compile().cost_analysis()["flops"])


def _logistic(n=512, d=123):
    k1, k2 = jax.random.split(jax.random.key(0))
    A = jnp.sign(jax.random.normal(k1, (n, d)))
    x = jax.random.normal(k2, (d,))
    mm = matmul("highest")

    def grad(x):
        u = 0.5 * (jnp.tanh(-0.5 * mm(A, x)) + 1.0)
        return -mm(u, A) / n + 0.1 * x

    return A, x, grad


def test_logistic_gradient():
    A, x, grad = _logistic()
    n, d = A.shape
    assert xla_flops(grad, x) == pytest.approx(counts.logistic_grad_flops(n, d), rel=0.02)


def test_svrp_round_without_refresh():
    A, x, grad = _logistic()
    n, d = A.shape
    steps = 4

    def round_(x, gbar, xs):  # one SVRP round, the prox loop unrolled
        z = x - 2.0 * (gbar - grad(x))
        y = z
        for _ in range(steps):
            y = y - 0.3 * (grad(y) + (y - z) * 0.5)
        return y, jnp.sum((y - xs) ** 2)

    want = counts.svrp_round_flops(1, n, d, steps)
    assert xla_flops(round_, x, x, x) == pytest.approx(want, rel=0.02)


def test_prox_kernel_arithmetic():
    B, n, d, steps = 3, 256, 123, 5
    A = jnp.sign(jax.random.normal(jax.random.key(1), (B, n, d)))
    z = jax.random.normal(jax.random.key(2), (B, d))
    mm = matmul("highest")

    def kernel_math(A, z):
        def one(A, z):
            x = z
            for _ in range(steps):
                u = 0.5 * (jnp.tanh(-0.5 * mm(A, x)) + 1.0)
                x = x - 0.1 * ((-mm(u, A) / n + 0.1 * x) + (x - z) * 0.5)
            return x
        return jax.vmap(one)(A, z)

    flops, nbytes = counts.logistic_prox_kernel(B, n, d, steps)
    assert xla_flops(kernel_math, A, z) == pytest.approx(flops, rel=0.02)
    assert nbytes == 4 * (A.size + 3 * z.size)


SZ = dict(hidden_size=256, num_attention_heads=4, num_key_value_heads=2, head_dim=64,
          intermediate_size=768, vocab_size=2048, num_hidden_layers=2,
          rms_norm_eps=1e-6, rope_theta=1e6)


def test_dense_forward_and_gradient():
    params = dense_lm.weights(jax.random.key(0), SZ)
    tok = jax.random.randint(jax.random.key(1), (2, 128), 0, SZ["vocab_size"])
    fwd = counts.dense_lm_forward_flops(SZ, 2, 128)
    got_fwd = xla_flops(lambda p, t: dense_lm.logits(p, t, SZ), params, tok)
    # XLA counts the full (S, S) score products; the closed form counts the
    # causal half the algorithm needs.
    full_attn = 2 * 128 * 2 * 2.0 * 4 * 64 * (128 - 129 / 2)
    assert got_fwd == pytest.approx(fwd + full_attn, rel=0.03)
    got_grad = xla_flops(jax.grad(lambda p, t: dense_lm.loss(p, t, t, SZ)), params, tok)
    assert got_grad == pytest.approx(3 * (fwd + full_attn), rel=0.10)
    assert np.isfinite(got_grad)
