"""Plain reference for a dense GQA decoder (Qwen2).

The model follows the Qwen2 technical report (arXiv:2407.10671) and its
public config: pre-RMSNorm blocks, grouped-query causal attention with
biases on the query, key and value projections (none on the output), rotary
embeddings in the rotate-half form, a SwiGLU MLP, a final RMSNorm and a
linear head.  Departures, stated in the configuration file: the head is not
tied to the embedding, and parameters are float32.  It imports nothing of
the program; `bench/reference/deep_svrp.py` runs the algorithm over its
`loss`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.precision import matmul

F32 = jnp.float32


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
