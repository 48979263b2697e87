"""The DeepSVRP reference's helpers for the model cell's comparison: leaf
norms, the rule that leaves out leaves that move by round-off alone, the
worst leaf's gap, and the Hessian's top eigenvalue that sets the traffic's
local step size."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.reference import deep_svrp, dense_lm  # noqa: E402
from bench.reference.precision import matmul  # noqa: E402

SZ = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
          intermediate_size=48, vocab_size=64, num_hidden_layers=2,
          rms_norm_eps=1e-6, rope_theta=1e6)


@pytest.fixture(autouse=True)
def x32():
    with jax.enable_x64(False):
        yield


def _grad():
    params = dense_lm.weights(jax.random.key(0), SZ)
    tok = jax.random.randint(jax.random.key(1), (2, 17), 0, SZ["vocab_size"])
    return params, jax.grad(lambda p: dense_lm.loss(p, tok[:, :-1], tok[:, 1:], SZ))(params)


def test_leaf_norms_split_stacked_layers():
    params, _ = _grad()
    names, norms = deep_svrp.leaf_norms(params)
    assert "layers/attn/wq/w[0]" in names and "layers/attn/wq/w[1]" in names
    assert "head/w" in names and len(names) == len(norms)
    i = names.index("layers/ln1/scale[1]")
    assert norms[i] == pytest.approx(np.sqrt(SZ["hidden_size"]))
    _, change = deep_svrp.leaf_change_norms(params, params)
    assert np.all(change == 0)


def test_moved_leaves_leave_out_a_leaf_without_gradient():
    moved = deep_svrp.moved_leaves(np.array([1.0, 2.0, 3.0, 1e-9, 4.0]))
    assert moved.tolist() == [True, True, True, False, True]
    # In Qwen2 the key's bias is rotated with its position before the
    # scores, so unlike a plain key bias it is not lost under softmax: every
    # leaf of the model has a gradient.
    _, grad = _grad()
    assert deep_svrp.moved_leaves(deep_svrp.leaf_norms(grad)[1]).all()


def test_norm_gap_reads_the_worst_moved_leaf():
    want = np.array([1.0, 2.0, 4.0, 1e-6])
    got = np.array([1.1, 2.0, 4.0, 1.0])
    moved = np.array([True, True, True, False])
    gap, i = deep_svrp.norm_gap(got, want, moved)
    assert i == 0 and gap == pytest.approx(0.1 / 2.0)


@pytest.mark.parametrize("precision", ["highest", "fp8"])
def test_top_hessian_eig_of_a_quadratic(precision):
    mm = matmul(precision)
    q = jnp.linalg.qr(jax.random.normal(jax.random.key(2), (6, 6)))[0]
    a = q @ jnp.diag(jnp.array([3.0, -9.0, 1.0, 0.5, 2.0, 4.0])) @ q.T
    f = lambda x: 0.5 * mm(x[None, :], mm(a, x[:, None]))[0, 0]
    lam = deep_svrp.top_hessian_eig(f, jnp.ones(6), jax.random.key(3), iters=80)
    assert lam == pytest.approx(9.0, rel=0.1 if precision == "fp8" else 1e-3)
