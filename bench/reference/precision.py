"""Matrix products at a stated precision, the same on every backend.

The references compute every contraction through one of these.  Each lower
precision is emulated explicitly on float32 operands, so a control run reads
the same on the CPU (where XLA ignores `precision=`) as on a TPU:

* ``highest``: float32 products (the TPU's six-pass ``HIGHEST``);
* ``high``: bfloat16 three-pass (``bf16_3x``, the TPU's ``HIGH``): each
  operand split into a bfloat16 head and tail, the tail x tail term dropped;
* ``fp8``: float8 e4m3 operands with one float32 scale per operand
  (amax / 448, the usual per-tensor fp8 recipe), float32 accumulation.

The backward pass of a product runs at the same precision as its forward
pass (a custom VJP), so a gradient taken through a control is computed at
the control's precision throughout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
_HI = lax.Precision.HIGHEST
_FP8_MAX = 448.0


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI, preferred_element_type=F32)


def _bf16(a):
    return a.astype(jnp.bfloat16).astype(F32)


def _mm_high(a, b):
    a_hi, b_hi = _bf16(a), _bf16(b)
    a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
    return _mm(a_hi, b_hi) + (_mm(a_hi, b_lo) + _mm(a_lo, b_hi))


def _fp8(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / _FP8_MAX
    return (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm_fp8(a, b):
    return _mm(_fp8(a), _fp8(b))


def _with_vjp(prod):
    """`prod` with a backward pass made of `prod` too.  `b` is a matrix
    shared by every leading index of `a`, or has `a`'s leading dims."""

    @jax.custom_vjp
    def f(a, b):
        return prod(a, b)

    def fwd(a, b):
        return prod(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        da = prod(g, jnp.swapaxes(b, -1, -2))
        if b.ndim == 2:
            db = prod(a.reshape(-1, a.shape[-1]).T, g.reshape(-1, g.shape[-1]))
        else:
            db = prod(jnp.swapaxes(a, -1, -2), g)
        return da, db

    f.defvjp(fwd, bwd)
    return f


MATMUL = {name: _with_vjp(p) for name, p in
          (("highest", _mm), ("high", _mm_high), ("fp8", _mm_fp8))}


def matmul(precision: str):
    """The product `a @ b` (float32 result) at `precision`."""
    try:
        return MATMUL[precision]
    except KeyError:
        raise ValueError(f"unknown precision {precision!r}; known: {sorted(MATMUL)}") from None
