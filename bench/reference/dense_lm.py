"""Plain reference for a dense GQA decoder (Qwen2) under DeepSVRP.

The model follows the Qwen2 technical report (arXiv:2407.10671) and its
public config: pre-RMSNorm blocks, grouped-query causal attention with
biases on the query, key and value projections (none on the output), rotary
embeddings in the rotate-half form, a SwiGLU MLP, a final RMSNorm and a
linear head.  Departures, stated in the configuration file: the head is not
tied to the embedding, and parameters are float32.

`deep_svrp` follows the repo's DeepSVRP round (full participation, the
Algorithm-7 local steps at an explicit stepsize, a Bernoulli anchor refresh)
with the blockwise-int8 wire: the server broadcast carries error feedback,
every other link quantizes statelessly.  It imports nothing of the program.
Parameters travel as one flat vector in `jax.flatten_util.ravel_pytree`'s
order, which is the order in which the wire splits them into blocks.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from bench.reference.precision import matmul

F32 = jnp.float32
QUANT_BLOCK = 256


# ------------------------------------------------------------ weights, data
def weights(key, sz):
    """Seeded weights: matrices N(0, 1/fan_in), embedding N(0, 1/hidden),
    biases N(0, 0.02^2), norm scales 1.  Layer leaves are stacked (L, ...)."""
    D, H, KV, Dh, F, V, L = (sz[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
        "intermediate_size", "vocab_size", "num_hidden_layers"))
    shapes = {
        "embed": {"emb": ((V, D), D)},
        "head": {"w": ((D, V), D)},
        "ln_f": {"scale": ((D,), None)},
        "layers": {
            "ln1": {"scale": ((L, D), None)},
            "ln2": {"scale": ((L, D), None)},
            "attn": {
                "wq": {"w": ((L, D, H * Dh), D), "b": ((L, H * Dh), 0)},
                "wk": {"w": ((L, D, KV * Dh), D), "b": ((L, KV * Dh), 0)},
                "wv": {"w": ((L, D, KV * Dh), D), "b": ((L, KV * Dh), 0)},
                "wo": {"w": ((L, H * Dh, D), H * Dh)},
            },
            "mlp": {
                "gate": {"w": ((L, D, F), D)},
                "up": {"w": ((L, D, F), D)},
                "down": {"w": ((L, F, D), F)},
            },
        },
    }
    is_spec = lambda v: isinstance(v, tuple) and len(v) == 2 and isinstance(v[0], tuple)
    specs, treedef = jax.tree.flatten(shapes, is_leaf=is_spec)
    keys = jax.random.split(key, len(specs))

    def leaf(k, spec):
        shape, fan_in = spec
        if fan_in is None:
            return jnp.ones(shape, F32)
        scale = 0.02 if fan_in == 0 else fan_in ** -0.5
        return scale * jax.random.normal(k, shape, F32)

    return jax.tree.unflatten(treedef, [leaf(k, s) for k, s in zip(keys, specs)])


def client_tokens(key, *, vocab: int, clients: int, sequences: int, length: int,
                  alpha: float, topics: int = 4):
    """(clients, sequences, length + 1) token ids: each client draws from its
    own Dirichlet(alpha) mixture of `topics` unigram distributions."""
    k_t, k_m, k_s = jax.random.split(key, 3)
    topic_p = jax.nn.softmax(2.0 * jax.random.normal(k_t, (topics, vocab)), axis=-1)
    mix = jax.random.dirichlet(k_m, jnp.full((topics,), alpha), (clients,))
    logits = jnp.log(mix @ topic_p + 1e-30)
    return jax.random.categorical(k_s, logits[:, None, None, :], axis=-1,
                                  shape=(clients, sequences, length + 1))


# -------------------------------------------------------------------- model
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):  # x (B, S, heads, Dh), rotate-half form
    S, Dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, Dh, 2, dtype=F32) / Dh)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def logits(params, tokens, sz, precision="highest"):
    mm = matmul(precision)
    H, KV, Dh, eps, theta = (sz[k] for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta"))
    x = params["embed"]["emb"][tokens]
    B, S, _ = x.shape
    causal = jnp.tril(jnp.ones((S, S), bool))
    for i in range(sz["num_hidden_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        at = lp["attn"]
        h = _rms(x, lp["ln1"]["scale"], eps)
        q = _rope((mm(h, at["wq"]["w"]) + at["wq"]["b"]).reshape(B, S, H, Dh), theta)
        k = _rope((mm(h, at["wk"]["w"]) + at["wk"]["b"]).reshape(B, S, KV, Dh), theta)
        v = (mm(h, at["wv"]["w"]) + at["wv"]["b"]).reshape(B, S, KV, Dh)
        k = jnp.repeat(k, H // KV, axis=2).transpose(0, 2, 1, 3)  # (B, H, S, Dh)
        v = jnp.repeat(v, H // KV, axis=2).transpose(0, 2, 1, 3)
        s = mm(q.transpose(0, 2, 1, 3), jnp.swapaxes(k, -1, -2)) / jnp.sqrt(F32(Dh))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = mm(p, v).transpose(0, 2, 1, 3).reshape(B, S, H * Dh)
        x = x + mm(o, at["wo"]["w"])
        h = _rms(x, lp["ln2"]["scale"], eps)
        ml = lp["mlp"]
        x = x + mm(jax.nn.silu(mm(h, ml["gate"]["w"])) * mm(h, ml["up"]["w"]), ml["down"]["w"])
    return mm(_rms(x, params["ln_f"]["scale"], eps), params["head"]["w"])


def loss(params, tokens, labels, sz, precision="highest"):
    lg = logits(params, tokens, sz, precision)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - gold)


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
def deep_svrp(params0, tokens, labels, sz, *, eta: float, local_lr: float,
              local_steps: int, anchor_prob: float, rounds: int, seed: int,
              precision: str = "highest"):
    """`rounds` DeepSVRP rounds from `params0` over clients' (tokens, labels).

    Returns `(losses (rounds,), comm (rounds,), params_final, grad0)`: the
    mean client loss after each round, the cumulative vectors communicated
    (3M to set up the anchor, 2M a round, 2M more on a refresh), the last
    iterate and the first anchor gradient (the clients' mean gradient at
    `params0`), both as parameter trees.  Each round is one compiled call."""
    x0, unravel = ravel_pytree(params0)
    M = tokens.shape[0]
    keys = jax.random.split(jax.random.key(jnp.uint32(seed)), rounds)
    coins = [bool(jax.random.bernoulli(k, F32(anchor_prob))) for k in keys]
    step = _round_fn(unravel, sz, eta, local_lr, local_steps, precision)
    full_grad = _full_grad_fn(unravel, sz, precision)
    grad0 = full_grad(x0, tokens, labels)
    x, w, gbar, resid = x0, x0, grad0, jnp.zeros_like(x0)
    comm, losses, comms = 3 * M, [], []
    for c in coins:
        x, w, gbar, resid, lo = step(x, w, gbar, resid, jnp.asarray(c), tokens, labels)
        comm += 2 * M + 2 * M * int(c)
        losses.append(lo)
        comms.append(comm)
    return np.asarray(jnp.stack(losses)), np.asarray(comms), unravel(x), unravel(grad0)


def _full_grad_fn(unravel, sz, precision):
    g = jax.grad(lambda x, tok, lab: loss(unravel(x), tok, lab, sz, precision))

    @jax.jit
    def full_grad(x, tokens, labels):
        acc, _ = jax.lax.scan(lambda a, tl: (a + g(x, *tl), None),
                              jnp.zeros_like(x), (tokens, labels))
        return acc / tokens.shape[0]

    return full_grad


def _round_fn(unravel, sz, eta, local_lr, local_steps, precision):
    f = lambda x, tok, lab: loss(unravel(x), tok, lab, sz, precision)
    g = jax.grad(f)
    full_grad = _full_grad_fn(unravel, sz, precision)
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
