"""A dense GQA decoder fine-tuned federatedly: weights and client token
batches from the seed, on the device, and the program's `FedLMProblem`.

The program's model is configured from the file's sizes (every width is
checked against the program's registry entry, so a width can never drift),
with the precision the file states: float32 parameters and activations.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import numpy as np
from jax.flatten_util import ravel_pytree

from bench.reference import dense_lm as ref

# The configuration file's keys -> the program's ModelConfig fields.
_FIELDS = {
    "hidden_size": "d_model", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "num_hidden_layers": "num_layers",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
}
_KEPT = ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff", "rope_theta")


@dataclasses.dataclass
class DenseLM:
    sizes: dict
    tokens: jax.Array  # (clients, sequences, length) int32
    labels: jax.Array
    unravel: object
    problem: object  # the program's FedLMProblem
    x0: jax.Array | None  # the flat start; the driver drops it after warm-up

    def weights(self, seed: int):
        """The seeded weights again, as a parameter tree."""
        return _weights_fn(tuple(sorted(self.sizes.items())))(_key(seed, 0))


def _key(seed: int, stream: int):
    return jax.random.fold_in(jax.random.key(np.uint32(seed % 2**32)), stream)


@functools.lru_cache(maxsize=None)
def _weights_fn(sz_items):
    return jax.jit(lambda k: ref.weights(k, dict(sz_items)))


def program_config(config: dict):
    """The program's ModelConfig for the file.  Where the file names the
    program's registry entry, every published width must match it."""
    from repro.configs.base import ModelConfig

    fields = {_FIELDS[k]: config[k] for k in _FIELDS}
    if "registry_name" in config:
        from repro.configs import REGISTRY

        base = REGISTRY[config["registry_name"]]
        for f in _KEPT:
            if getattr(base, f) != fields[f]:
                raise ValueError(f"{f}: the file says {fields[f]}, the program's "
                                 f"{base.name} has {getattr(base, f)}")
    return ModelConfig(name=config["name"], family="dense", source=config["source"],
                       qkv_bias=config["qkv_bias"], param_dtype=config["param_dtype"],
                       compute_dtype=config["param_dtype"], **fields)


def build(config: dict, traffic: dict, seed: int) -> DenseLM:
    from repro.problems.fed_lm import FedLMProblem

    sizes = {k: config[k] for k in _FIELDS}
    cfg = program_config(config)
    params = _weights_fn(tuple(sorted(sizes.items())))(_key(seed, 0))
    x0, unravel = ravel_pytree(params)
    del params
    toks = jax.jit(functools.partial(
        ref.client_tokens, vocab=sizes["vocab_size"], clients=traffic["clients"],
        sequences=traffic["sequences_per_client"], length=traffic["sequence_length"],
        alpha=traffic["dirichlet_alpha"]))(_key(seed, 1))
    tokens, labels = toks[..., :-1], toks[..., 1:]
    problem = FedLMProblem(tokens=tokens, labels=labels, cfg=cfg, unravel=unravel,
                           num_params=int(x0.size))
    return DenseLM(sizes, tokens, labels, unravel, problem, x0)
