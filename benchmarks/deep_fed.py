"""Beyond-paper: DeepSVRP vs FedAvg vs deep-SCAFFOLD on a heterogeneous-client
language model — the systems-scale analogue of Figure 1."""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import REGISTRY
from repro.core import (
    DeepSVRPConfig,
    FedAvgState,
    deep_scaffold_init,
    deep_scaffold_round,
    deep_svrp_init,
    deep_svrp_round,
    fedavg_round,
)
from repro.data import ShardedBatcher, SyntheticLMDataset
from repro.models import model as M


def run(quick: bool = False, rounds: int | None = None, alpha: float = 0.2):
    rounds = rounds or (20 if quick else 100)
    cfg = dataclasses.replace(
        REGISTRY["qwen2-1.5b"].reduced(),
        vocab_size=128, d_model=128, num_heads=4, num_kv_heads=2, head_dim=32,
        d_ff=256, param_dtype="float32", compute_dtype="float32",
    )
    ds = SyntheticLMDataset(vocab_size=cfg.vocab_size, num_clients=4, alpha=alpha, seed=0)
    batcher = ShardedBatcher(ds, num_cohorts=4, per_cohort_batch=4, seq_len=32)
    params = M.init_params(cfg, jax.random.key(0))
    loss_fn = lambda p, b: M.loss_fn(p, cfg, b)
    eval_batch = {k: jnp.asarray(v) for k, v in batcher.next_batch().items()}

    rows = []

    # --- DeepSVRP (the paper's technique)
    svrp = DeepSVRPConfig(eta=2.0, local_lr=0.3, local_steps=4, anchor_prob=0.25)
    state = deep_svrp_init(params, jax.grad(loss_fn)(params, eval_batch), jax.random.key(1))
    rj = jax.jit(lambda s, b: deep_svrp_round(loss_fn, s, b, svrp))
    t0 = time.perf_counter()
    for _ in range(rounds):
        b = {k: jnp.asarray(v) for k, v in batcher.next_batch().items()}
        state, _ = rj(state, b)
    dt = (time.perf_counter() - t0) / rounds * 1e6
    rows.append(("deep_svrp", dt, f"final_loss={float(loss_fn(state.params, eval_batch)):.4f}"))

    # --- FedAvg
    st = FedAvgState(params=params, step=jnp.zeros((), jnp.int32))
    rj = jax.jit(lambda s, b: fedavg_round(loss_fn, s, b, local_lr=0.3, local_steps=4))
    t0 = time.perf_counter()
    for _ in range(rounds):
        b = {k: jnp.asarray(v) for k, v in batcher.next_batch().items()}
        st, _ = rj(st, b)
    dt = (time.perf_counter() - t0) / rounds * 1e6
    rows.append(("fedavg", dt, f"final_loss={float(loss_fn(st.params, eval_batch)):.4f}"))

    # --- deep SCAFFOLD
    sst = deep_scaffold_init(params)
    rj = jax.jit(lambda s, b: deep_scaffold_round(loss_fn, s, b, local_lr=0.3, local_steps=4))
    t0 = time.perf_counter()
    for _ in range(rounds):
        b = {k: jnp.asarray(v) for k, v in batcher.next_batch().items()}
        sst, _ = rj(sst, b)
    dt = (time.perf_counter() - t0) / rounds * 1e6
    rows.append(("deep_scaffold", dt, f"final_loss={float(loss_fn(sst.params, eval_batch)):.4f}"))
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache(__file__)
    for r in run(quick=True):
        print(f"{r[0]},{r[1]:.0f},{r[2]}")
