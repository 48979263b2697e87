"""Federated language-model fine-tuning as a flat-vector problem.

The convex engine (`repro.experiments` / `repro.core.rounds`) speaks one
oracle dialect: a problem with ``grad(m, x)`` / ``full_grad(x)`` over a flat
``(d,)`` iterate.  `FedLMProblem` adapts the model zoo (`repro.models`) to
that dialect so the REAL-model DeepSVRP path runs through the exact same
`run_batch` substrates — and therefore the same comm channels and bytes
ledger — as the synthetic quadratics:

* parameters travel as one ravelled ``(d,)`` vector (``jax.flatten_util.
  ravel_pytree``); the unravel closure is static metadata of the pytree;
* each client m holds a fixed heterogeneous token batch (Dirichlet topic
  mixtures via `repro.data.SyntheticLMDataset`), stored client-major so
  ``jnp.take`` works under a traced client index;
* there is no computable minimizer, so the problem exposes ``metric(x)`` —
  the across-client mean LM loss — which `RoundOps.dist_sq` reports in place
  of the squared distance to the optimum (the engine's ``dist_sq`` column
  becomes a loss trajectory).

This is deliberately an example-scale training signal: each client's loss is
over its one resident batch (full-batch local objectives), matching the
deterministic-oracle convention of the convex problems.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np


def _one_row_at_a_time(f: Callable) -> Callable:
    """`f`, except that under `vmap` it runs as a `lax.map` over the batch.

    The engine vmaps client oracles over clients and trials, and every row
    carries its own parameter vector.  Batching the model over per-row
    weights makes XLA's TPU compile grow with the model: for the 100m preset
    (124.7M parameters) over 4 clients, compiling the vmapped loss for a v5e
    took 154 s and 7.4 GB of host memory, the vmapped gradient 138 s and
    8.8 GB, and a whole deep_svrp round more than the 40 GiB host of a v5e
    machine; the mapped gradient compiles in 10 s and 1.5 GB.  Evaluating the
    rows one at a time also keeps device memory at one model's activations.
    Unbatched arguments are shared by every row, not broadcast.
    """
    f = jax.custom_batching.custom_vmap(f)

    @f.def_vmap
    def _rule(axis_size, in_batched, *args):
        def row(batched):
            it = iter(batched)
            return f(*(next(it) if b else a for a, b in zip(args, in_batched)))

        rows = tuple(a for a, b in zip(args, in_batched) if b)
        return jax.lax.map(row, rows), True

    return f


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["tokens", "labels"],
    meta_fields=["cfg", "unravel", "num_params"],
)
@dataclasses.dataclass(frozen=True)
class FedLMProblem:
    """Federated LM fine-tune over M fixed heterogeneous client batches."""

    tokens: jax.Array  # (M, batch, seq) int32, client-major
    labels: jax.Array  # (M, batch, seq) int32
    cfg: Any  # ModelConfig (static)
    unravel: Callable[[jax.Array], Any]  # flat (d,) -> params pytree (static)
    num_params: int

    @property
    def num_clients(self) -> int:
        return self.tokens.shape[0]

    @property
    def dim(self) -> int:
        return self.num_params

    # --- oracles (flat-vector dialect) -----------------------------------
    def _client_loss(self, x: jax.Array, tokens: jax.Array, labels: jax.Array):
        from repro.models import model as M

        params = self.unravel(x)
        return M.loss_fn(params, self.cfg, {"tokens": tokens, "labels": labels})

    def _loss_fn(self) -> Callable:
        return _one_row_at_a_time(self._client_loss)

    def _grad_fn(self) -> Callable:
        return _one_row_at_a_time(jax.grad(self._client_loss))

    def loss(self, m: jax.Array, x: jax.Array) -> jax.Array:
        return self._loss_fn()(
            x, jnp.take(self.tokens, m, axis=0), jnp.take(self.labels, m, axis=0)
        )

    def grad(self, m: jax.Array, x: jax.Array) -> jax.Array:
        return self._grad_fn()(
            x, jnp.take(self.tokens, m, axis=0), jnp.take(self.labels, m, axis=0)
        )

    def full_grad(self, x: jax.Array) -> jax.Array:
        """Across-client mean gradient — a sequential scan over clients so
        peak memory stays one model-gradient regardless of M."""
        grad = self._grad_fn()

        def body(acc, mb):
            tok, lab = mb
            return acc + grad(x, tok, lab), None

        acc, _ = jax.lax.scan(
            body, jnp.zeros_like(x), (self.tokens, self.labels)
        )
        return acc / self.num_clients

    def metric(self, x: jax.Array) -> jax.Array:
        """Across-client mean LM loss — the engine's dist_sq column for
        problems with no computable x_star (`RoundOps.dist_sq` hook)."""

        loss = self._loss_fn()

        def body(acc, mb):
            tok, lab = mb
            return acc + loss(x, tok, lab), None

        acc, _ = jax.lax.scan(
            body, jnp.zeros((), x.dtype), (self.tokens, self.labels)
        )
        return acc / self.num_clients

    def minimizer(self) -> jax.Array:
        raise ValueError(
            "FedLMProblem has no computable minimizer; pass x0=ravelled init "
            "params and x_star=x0 explicitly (x_star is unused — the problem "
            "reports metric(x), the across-client mean LM loss, as dist_sq)"
        )


def make_fed_lm_problem(
    cfg,
    *,
    num_clients: int,
    per_client_batch: int,
    seq_len: int,
    alpha: float = 0.3,
    seed: int = 0,
) -> tuple[FedLMProblem, jax.Array]:
    """Build the problem AND its ravelled init vector.

    Returns ``(problem, x0)`` where ``x0`` is `models.model.init_params(cfg)`
    flattened by the same ravel whose unravel the problem carries — the pair
    every entry point needs (``run_batch(..., x0=x0, x_star=x0)``).
    """
    from jax.flatten_util import ravel_pytree

    from repro.data import SyntheticLMDataset
    from repro.models import model as M

    ds = SyntheticLMDataset(
        vocab_size=cfg.vocab_size, num_clients=num_clients,
        alpha=alpha, seed=seed,
    )
    toks = np.stack(
        [ds.sample(m, per_client_batch, seq_len) for m in range(num_clients)]
    )
    params = M.init_params(cfg, jax.random.key(seed))
    x0, unravel = ravel_pytree(params)
    problem = FedLMProblem(
        tokens=jnp.asarray(toks[:, :, :-1], jnp.int32),
        labels=jnp.asarray(toks[:, :, 1:], jnp.int32),
        cfg=cfg,
        unravel=unravel,
        num_params=int(x0.size),
    )
    return problem, x0
