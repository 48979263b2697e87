"""Matmul precision of the convex problems: full float32 contraction.

A TPU's default float32 matmul rounds both operands to bfloat16 (an 8-bit
mantissa), on the XLA path and in Mosaic kernels alike.  The convex problems
(`repro.problems.quadratic`, `.logistic`, `.dp_erm`), their prox solvers and
the Algorithm-7 kernel work to ~1e-10 of the optimum, which only the full
float32 contraction resolves, so every contraction there asks for `HIGHEST`.
CPUs contract at full precision whatever is asked.  The model zoo keeps the
default.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def mm(a: jax.Array, b: jax.Array) -> jax.Array:
    """`a @ b` at `HIGHEST` precision."""
    return jnp.matmul(a, b, precision=HIGHEST)
