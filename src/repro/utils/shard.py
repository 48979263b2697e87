"""Activation sharding constraints that degrade to no-ops off-mesh.

`constrain(x, *spec)` pins an intermediate's layout when tracing under an
active mesh (jax.set_mesh / the launch layer's MeshStep wrapper) and does
nothing in plain single-device jit — so model code can be written once and
run in tests, examples and the production mesh unchanged.

Why this exists: without pinning, GSPMD propagation inside scan-over-layers
sometimes settles on a d_model-sharded residual stream, which turns every
matmul into partial sums + a full-activation all-reduce per layer (measured:
281s collective term on internvl2-76b train_4k before pinning — see
EXPERIMENTS.md §Perf iteration 0).
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def shard_map_compat(f, *, mesh, in_specs, out_specs, manual_axes):
    """`jax.shard_map` manual over `manual_axes` (the rest stay auto), with
    the replication check off.  Shared by the launch layer's mesh steps and
    the experiment engine's `shard="data"` / `shard="clients"` modes."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=set(manual_axes), check_vma=False,
    )


def _active_axes() -> tuple:
    return tuple(jax.sharding.get_abstract_mesh().axis_names)


def constrain(x, *spec):
    """with_sharding_constraint(x, P(*spec)) iff a mesh with the referenced
    axes is active; otherwise identity."""
    axes = _active_axes()
    if "model" not in axes:
        return x
    # drop axis names the active mesh doesn't have (e.g. 'data' inside a
    # shard_map manual region where only auto axes remain visible)
    clean = []
    for s in spec:
        names = s if isinstance(s, tuple) else (s,)
        kept = tuple(n for n in names if n is None or n in axes)
        kept = tuple(n for n in kept if n is not None)
        clean.append(kept if len(kept) > 1 else (kept[0] if kept else None))
    return jax.lax.with_sharding_constraint(x, P(*clean))


# Residual-stream layout mode:
#   "replicated" — Megatron convention: activations TP-replicated, one
#                  all-reduce per block (2x wire bytes per byte).
#   "seq"        — sequence parallelism: the residual stream is sharded over
#                  'model' along the sequence dim between blocks; GSPMD turns
#                  each block-boundary all-reduce into a reduce-scatter +
#                  all-gather pair (~1x wire bytes each, ~47% less traffic).
#                  §Perf iteration 3.
_ACTIVATION_MODE = "replicated"


def set_activation_mode(mode: str) -> None:
    global _ACTIVATION_MODE
    assert mode in ("replicated", "seq"), mode
    _ACTIVATION_MODE = mode


def activation_mode() -> str:
    return _ACTIVATION_MODE


def replicated(x):
    """Pin the residual-stream layout between blocks (see _ACTIVATION_MODE)."""
    axes = _active_axes()
    if "model" not in axes:
        return x
    if _ACTIVATION_MODE == "seq" and x.ndim == 3:
        try:
            msize = jax.sharding.get_abstract_mesh().shape["model"]
        except Exception:
            msize = 0
        if msize and x.shape[1] % msize == 0 and x.shape[1] >= msize:
            return jax.lax.with_sharding_constraint(x, P(None, "model", None))
    return jax.lax.with_sharding_constraint(x, P(*([None] * x.ndim)))


def constrain_spec(x, spec):
    """constrain() but taking a PartitionSpec directly."""
    return constrain(x, *tuple(spec))


def constrain_tree(tree, specs):
    """Apply per-leaf PartitionSpec constraints (no-op off-mesh)."""
    import jax as _jax

    return _jax.tree.map(
        lambda x, sp: constrain_spec(x, sp), tree, specs,
    )
