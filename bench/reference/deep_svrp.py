"""Plain reference for DeepSVRP over any model, and the helpers that
compare a model's leaves.

`deep_svrp` follows the repo's DeepSVRP round (full participation, the
Algorithm-7 local steps at an explicit stepsize, a Bernoulli anchor refresh)
with the blockwise-int8 wire: the server broadcast carries error feedback,
every other link quantizes statelessly.  The model enters only as its
reference loss, `loss(params, tokens, labels, precision) -> scalar`.  It
imports nothing of the program.  Parameters travel as one flat vector in
`jax.flatten_util.ravel_pytree`'s order, which is the order in which the
wire splits them into blocks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

F32 = jnp.float32
QUANT_BLOCK = 256


# ---------------------------------------------------------------- the wire
def int8_roundtrip(v):
    """Blockwise symmetric int8 over a flat vector: blocks of 256, one scale
    max|block| / 127 each, round half to even, clip to +-127."""
    d = v.shape[0]
    nb = -(-d // QUANT_BLOCK)
    blocks = jnp.pad(v, (0, nb * QUANT_BLOCK - d)).reshape(nb, QUANT_BLOCK)
    s = jnp.maximum(jnp.max(jnp.abs(blocks), axis=-1, keepdims=True), 1e-12) / 127.0
    q = jnp.clip(jnp.round(blocks / s), -127, 127)
    return (q * s).reshape(-1)[:d]


# ------------------------------------------------------------ the algorithm
def deep_svrp(loss, params0, tokens, labels, *, eta: float, local_lr: float,
              local_steps: int, anchor_prob: float, rounds: int, seed: int,
              precision: str = "highest"):
    """`rounds` DeepSVRP rounds of the model whose reference loss is `loss`,
    from `params0` over clients' (tokens, labels).

    Returns `(losses (rounds,), comm (rounds,), params_final, grad0)`: the
    mean client loss after each round, the cumulative vectors communicated
    (3M to set up the anchor, 2M a round, 2M more on a refresh), the last
    iterate and the first anchor gradient (the clients' mean gradient at
    `params0`), both as parameter trees.  Each round is one compiled call."""
    x0, unravel = ravel_pytree(params0)
    f = lambda x, tok, lab: loss(unravel(x), tok, lab, precision)
    M = tokens.shape[0]
    keys = jax.random.split(jax.random.key(jnp.uint32(seed)), rounds)
    coins = [bool(jax.random.bernoulli(k, F32(anchor_prob))) for k in keys]
    step = _round_fn(f, eta, local_lr, local_steps)
    full_grad = _full_grad_fn(f)
    grad0 = full_grad(x0, tokens, labels)
    x, w, gbar, resid = x0, x0, grad0, jnp.zeros_like(x0)
    comm, losses, comms = 3 * M, [], []
    for c in coins:
        x, w, gbar, resid, lo = step(x, w, gbar, resid, jnp.asarray(c), tokens, labels)
        comm += 2 * M + 2 * M * int(c)
        losses.append(lo)
        comms.append(comm)
    return np.asarray(jnp.stack(losses)), np.asarray(comms), unravel(x), unravel(grad0)


def _full_grad_fn(f):
    """The clients' mean gradient of the flat-vector loss `f`."""
    g = jax.grad(f)

    @jax.jit
    def full_grad(x, tokens, labels):
        acc, _ = jax.lax.scan(lambda a, tl: (a + g(x, *tl), None),
                              jnp.zeros_like(x), (tokens, labels))
        return acc / tokens.shape[0]

    return full_grad


def _round_fn(f, eta, local_lr, local_steps):
    g = jax.grad(f)
    full_grad = _full_grad_fn(f)
    inv_eta = 1.0 / eta

    @jax.jit
    def step(x, w, gbar, resid, c, tokens, labels):
        sent = int8_roundtrip(x + resid)  # server broadcast, error feedback
        resid = x + resid - sent

        def client(acc, tl):
            z = sent - eta * (gbar - g(w, *tl))
            y = sent
            for _ in range(local_steps):  # Algorithm 7 at stepsize local_lr
                y = y - local_lr * (g(y, *tl) + (y - z) * inv_eta)
            return acc + int8_roundtrip(y), None

        acc, _ = jax.lax.scan(client, jnp.zeros_like(x), (tokens, labels))
        x_next = acc / tokens.shape[0]
        w_next = jnp.where(c, int8_roundtrip(x_next), w)
        gbar = jax.lax.cond(c, lambda: full_grad(w_next, tokens, labels), lambda: gbar)
        lo, _ = jax.lax.scan(lambda a, tl: (a + f(x_next, *tl), None), F32(0), (tokens, labels))
        return x_next, w_next, gbar, resid, lo / tokens.shape[0]

    return step


# ------------------------------------------------------------ the comparison
def leaf_norms(tree) -> tuple[list[str], np.ndarray]:
    """Names and norms of every leaf, in `jax.tree.leaves` order; each layer
    of a stacked leaf (under "layers") counts as a leaf of its own."""
    names, norms = [], []
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        a = a.astype(F32)
        if path[0].key == "layers":
            for i, v in enumerate(jnp.linalg.norm(a.reshape(a.shape[0], -1), axis=1)):
                names.append(f"{name}[{i}]")
                norms.append(v)
        else:
            names.append(name)
            norms.append(jnp.linalg.norm(a.reshape(-1)))
    return names, np.asarray(jnp.stack(norms), np.float64)


def leaf_change_norms(params_a, params_b) -> tuple[list[str], np.ndarray]:
    """`leaf_norms` of `params_a - params_b`."""
    return leaf_norms(jax.tree.map(jnp.subtract, params_a, params_b))


def moved_leaves(grad_norms: np.ndarray, share: float = 1e-3) -> np.ndarray:
    """Leaves whose reference gradient is more than `share` of the median
    leaf's.  The others (a key bias that no rotation follows, under
    softmax) have a gradient that is nought but for rounding, and move by
    round-off alone."""
    return grad_norms > share * np.median(grad_norms)


def norm_gap(got: np.ndarray, want: np.ndarray, moved: np.ndarray) -> tuple[float, int]:
    """Worst moved leaf's |got - want| over max(want, median of want), and
    its index."""
    denom = np.maximum(want, np.median(want[moved]))
    gaps = np.where(moved, np.abs(got - want) / denom, 0.0)
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


def top_hessian_eig(f, x, key, iters: int = 30) -> float:
    """|largest eigenvalue| of the Hessian of the scalar `f` at the flat `x`,
    by power iteration on Hessian-vector products (reverse over reverse, so
    it runs through the references' custom-VJP products)."""
    g = jax.grad(f)
    hvp = jax.jit(lambda v: jax.grad(lambda y: jnp.vdot(g(y), v))(x))
    v = jax.random.normal(key, x.shape, x.dtype)
    lam = 0.0
    for _ in range(iters):
        v = v / jnp.linalg.norm(v)
        hv = hvp(v)
        lam = float(jnp.vdot(v, hv))
        v = hv
    return abs(lam)
