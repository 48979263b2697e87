"""Step functions lowered onto the production mesh.

* `make_svrp_train_step`  — the paper's technique as the first-class training
  step: shard_map over the client axes ('pod','data') with GSPMD-auto tensor
  parallelism on 'model'.  Server state (params x, anchor w, anchor gradient
  gbar) is ZeRO-sharded over the client axes and explicitly all-gathered at
  round start / reduce-scattered at round end, so the lowered HLO contains
  EXACTLY the paper's communication schedule:

      per round:  all-gather(x,w,gbar)  +  reduce-scatter(y)      [cheap]
      anchor ref: reduce-scatter(grad at new anchor), Bernoulli-gated [rare]

  and ZERO collectives over the client axes inside the K local prox steps
  (verified by the dry-run's HLO scan).

* `make_adamw_train_step` — standard data-parallel + TP baseline (the
  "ordinary distributed SGD" family the paper compares against).
* `make_prefill_step` / `make_serve_step` — inference paths for the
  prefill_32k / decode_32k / long_500k shapes.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.deep import DeepSVRPConfig
from repro.core.rounds import local_prox_gd_tree
from repro.kernels import ops as kops
from repro.launch import sharding as shd
from repro.launch.mesh import data_axis_names, num_cohorts
from repro.models import model as M
from repro.optim import adamw_init, adamw_update, clip_by_global_norm

# Version-compat shard_map lives in utils.shard (shared with the experiment
# engine's sharded sweep mode).
from repro.utils.shard import shard_map_compat as _shard_map_compat
from repro.utils.tree import tree_where

PyTree = Any


class SVRPServerState(NamedTuple):
    """ZeRO-sharded over the client axes; bf16 x/w, f32 gbar."""

    params: PyTree
    anchor: PyTree
    anchor_grad: PyTree
    step: jax.Array
    rng: jax.Array


# ------------------------------------------------------------ gather/scatter
def _gather_leaf(x, spec: P, axes: tuple[str, ...]):
    """Undo ZeRO sharding: all-gather over any client axis in the spec."""
    for dim, ax in enumerate(spec):
        names = ax if isinstance(ax, tuple) else (ax,)
        for name in names:
            if name in axes:
                x = jax.lax.all_gather(x, name, axis=dim, tiled=True)
    return x


def _scatter_leaf_mean(x, spec: P, axes: tuple[str, ...], n_cohorts: int):
    """Cohort-mean + re-apply ZeRO sharding (reduce-scatter when sharded).

    Reductions run in f32: bf16 cross-replica reduction both loses precision
    and CHECK-crashes the CPU XLA backend (hlo_instruction.cc: 'Invalid
    binary instruction opcode copy') used for the dry-run."""
    dt = x.dtype
    xr = x.astype(jnp.float32) if dt == jnp.bfloat16 else x
    scattered = False
    for dim, ax in enumerate(spec):
        names = ax if isinstance(ax, tuple) else (ax,)
        for name in names:
            if name in axes:
                xr = jax.lax.psum_scatter(xr, name, scatter_dimension=dim, tiled=True)
                scattered = True
    if not scattered:
        xr = jax.lax.pmean(xr, axes)
        return xr.astype(dt)
    return (xr / n_cohorts).astype(dt)


def _tree_gather(tree, specs, axes):
    return jax.tree.map(lambda x, s: _gather_leaf(x, s, axes), tree, specs)


def _manual_only(spec: P, axes: tuple[str, ...]) -> P:
    """Strip non-manual mesh axes from a spec (shard_map in_specs may only
    mention the manual axes; 'model' placement flows through GSPMD)."""
    out = []
    for ax in spec:
        names = ax if isinstance(ax, tuple) else (ax,)
        kept = tuple(n for n in names if n in axes)
        out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
    return P(*out)


def _manual_tree(specs, axes):
    return jax.tree.map(
        lambda s: _manual_only(s, axes), specs, is_leaf=lambda x: isinstance(x, P)
    )


def _tree_scatter_mean(tree, specs, axes, n):
    return jax.tree.map(lambda x, s: _scatter_leaf_mean(x, s, axes, n), tree, specs)



class MeshStep:
    """Wraps a jitted step so `.lower()` traces under `jax.set_mesh(mesh)` —
    required for the activation sharding constraints (utils.shard) to be
    active.  Direct calls skip the context: the constraints are layout hints,
    not semantics, and eager small-scale tests pass uncommitted arrays."""

    def __init__(self, jitted, mesh):
        self._fn = jitted
        self.mesh = mesh

    def lower(self, *args, **kwargs):
        with jax.set_mesh(self.mesh):
            return self._fn.lower(*args, **kwargs)

    def __call__(self, *args, **kwargs):
        return self._fn(*args, **kwargs)


# --------------------------------------------------------------- SVRP step
def make_svrp_train_step(cfg: ModelConfig, mesh, svrp: DeepSVRPConfig):
    """Returns (jitted step, helpers dict).

    step(state: SVRPServerState, batch) -> (state, metrics)
    State leaves are ZeRO-sharded per `zero_pspecs`; the batch's leading dim
    is sharded over the client axes.
    """
    daxes = data_axis_names(mesh)
    n_cohorts = num_cohorts(mesh)

    def loss(params, batch):
        return M.loss_fn(params, cfg, batch)

    # spec trees (computed on abstract shapes; no allocation)
    pshape = jax.eval_shape(lambda k: M.init_params(cfg, k), jax.random.key(0))
    zspecs = shd.zero_pspecs(pshape, mesh, axes=daxes, cfg=cfg)
    # TP ('model'-only) layout of the gathered state inside the manual region —
    # without the pin, GSPMD may replicate big (expert) tensors after the
    # ZeRO all-gather (measured: 3x ~300 GB one-time gathers on qwen3-moe).
    mspecs = shd.param_pspecs(pshape, mesh, cfg)

    from repro.utils import shard as ushard

    def round_fn(x, w, gbar, step_ctr, rng, batch):
        """Cohort-local SVRP round over FULL (model-sharded) state.

        ZeRO gather/scatter happens OUTSIDE this manual region: manual
        collectives force their operands to replicate along the auto 'model'
        axes (measured: full-expert 150 GB all-gathers on qwen3-moe — §Perf
        iteration 7), so the in/out trees here are full parameters and the
        only client-axis collectives are the final pmeans.
        """
        grad_fn = jax.grad(loss)

        # (1) control variate  g_k = gbar - grad f_m(w)
        loss_at_w, g_anchor = jax.value_and_grad(loss)(w, batch)
        g_k = jax.tree.map(lambda a, b: a - b.astype(a.dtype), gbar, g_anchor)

        # (2) prox target z = x - eta g_k
        z = jax.tree.map(lambda xx, g: xx - (svrp.eta * g).astype(xx.dtype), x, g_k)

        # (3) K local prox-GD steps (Algorithm 7) — the SAME shared DeepSVRP
        #     local solver the convex round definition uses
        #     (core.rounds.local_prox_gd_tree); kops.prox_update_tree fuses
        #     the whole-tree elementwise update into one batched kernel
        #     launch per dtype on the Pallas path (leaf-wise jnp otherwise).
        y, g_local_last = local_prox_gd_tree(
            lambda p: grad_fn(p, batch), z, x,
            svrp.local_lr, 1.0 / svrp.eta, svrp.local_steps,
            update_fn=kops.prox_update_tree, g0=g_anchor,
        )

        # (4) server aggregation: ONE pmean over the client axes (f32-safe;
        #     GSPMD's reduce-scatter combiner fuses this with the ZeRO
        #     re-sharding applied outside).
        def pmean_f32(t):
            dt = t.dtype
            tr = t.astype(jnp.float32) if dt == jnp.bfloat16 else t
            return jax.lax.pmean(tr, daxes).astype(dt)

        x_next = jax.tree.map(pmean_f32, y)

        if svrp.refresh_grad_mode == "exact":
            # paper-faithful: gradient at the aggregated new iterate x'
            g_new = grad_fn(x_next, batch)
        else:  # "reuse_local" — beyond-paper (see DeepSVRPConfig docstring)
            g_new = g_local_last
        g_new_mean = jax.tree.map(
            lambda g: pmean_f32(g.astype(jnp.float32)), g_new
        )

        loss_val = jax.lax.pmean(loss_at_w, daxes)
        return x_next, g_new_mean, {"loss": loss_val}

    # --- wire shard_map + jit ------------------------------------------------
    state_specs_full = SVRPServerState(
        params=zspecs, anchor=zspecs, anchor_grad=zspecs, step=P(), rng=P()
    )
    # inside the manual region the full state is replicated over client axes
    full_manual = jax.tree.map(lambda s: _manual_only(P(), daxes), mspecs,
                               is_leaf=lambda xx: isinstance(xx, P))

    def batch_specs(batch_like):
        return shd.batch_pspec(batch_like, mesh)

    def make_step(batch_like):
        bspecs = batch_specs(batch_like)
        smapped = _shard_map_compat(
            round_fn,
            mesh=mesh,
            in_specs=(full_manual, full_manual, full_manual, P(), P(), bspecs),
            out_specs=(full_manual, full_manual, {"loss": P()}),
            manual_axes=set(daxes),
        )

        def step(state: SVRPServerState, batch):
            # ZeRO -> TP-full resharding via GSPMD (auto over ALL axes here)
            x = ushard.constrain_tree(state.params, mspecs)
            w = ushard.constrain_tree(state.anchor, mspecs)
            gbar = ushard.constrain_tree(state.anchor_grad, mspecs)
            x_next_full, g_new_full, metrics = smapped(
                x, w, gbar, state.step, state.rng, batch
            )
            # back to ZeRO shards (reduce-scatter-combined with the pmean)
            x_next = ushard.constrain_tree(x_next_full, zspecs)
            g_new = ushard.constrain_tree(g_new_full, zspecs)
            # Bernoulli anchor refresh on the ZeRO shards
            rng_key = jax.random.wrap_key_data(state.rng)
            coin = jax.random.bernoulli(
                jax.random.fold_in(rng_key, state.step), svrp.anchor_prob
            )
            anchor_next = tree_where(coin, x_next, state.anchor)
            anchor_grad_next = tree_where(coin, g_new, state.anchor_grad)
            new_state = SVRPServerState(
                params=x_next,
                anchor=anchor_next,
                anchor_grad=anchor_grad_next,
                step=state.step + 1,
                rng=state.rng,
            )
            return new_state, metrics

        ns = lambda tree: jax.tree.map(
            lambda sp: NamedSharding(mesh, sp), tree, is_leaf=lambda xx: isinstance(xx, P)
        )
        in_shardings = (ns(state_specs_full), ns(bspecs))
        out_shardings = (in_shardings[0], {"loss": NamedSharding(mesh, P())})
        return MeshStep(
            jax.jit(step, in_shardings=in_shardings, out_shardings=out_shardings), mesh
        )

    def init_state(key) -> SVRPServerState:
        """Host-side init (small models / tests). Big-model dry-runs use
        eval_shape on this function instead."""
        params = M.init_params(cfg, key)
        gbar = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return SVRPServerState(
            params=params,
            anchor=params,
            anchor_grad=gbar,
            step=jnp.zeros((), jnp.int32),
            rng=jax.random.key_data(jax.random.key(0)),
        )

    return make_step, {
        "init_state": init_state,
        "zero_specs": zspecs,
        "state_specs": state_specs_full,
        "batch_specs": batch_specs,
        "param_shapes": pshape,
    }


# --------------------------------------------------------------- AdamW step
class AdamWTrainState(NamedTuple):
    params: PyTree
    opt: Any


def make_adamw_train_step(cfg: ModelConfig, mesh, *, lr: float = 3e-4, clip: float = 1.0):
    daxes = data_axis_names(mesh)

    pshape = jax.eval_shape(lambda k: M.init_params(cfg, k), jax.random.key(0))
    pspecs = shd.param_pspecs(pshape, mesh, cfg)
    mspecs = shd.zero_pspecs(pshape, mesh, axes=daxes, cfg=cfg)  # ZeRO-1 moments
    from repro.optim import OptState

    ospecs = OptState(step=P(), mu=mspecs, nu=mspecs)

    def step(state: AdamWTrainState, batch):
        def mean_loss(p):
            return M.loss_fn(p, cfg, batch)

        loss_val, grads = jax.value_and_grad(mean_loss)(state.params)
        grads, gnorm = clip_by_global_norm(grads, clip)
        new_params, new_opt = adamw_update(grads, state.opt, state.params, lr=lr)
        return AdamWTrainState(new_params, new_opt), {"loss": loss_val, "grad_norm": gnorm}

    def make_step(batch_like):
        bspecs = shd.batch_pspec(batch_like, mesh)
        ns = lambda tree: jax.tree.map(
            lambda s: NamedSharding(mesh, s), tree, is_leaf=lambda x: isinstance(x, P)
        )
        in_shardings = (AdamWTrainState(ns(pspecs), ns(ospecs)), ns(bspecs))
        out_shardings = (
            in_shardings[0],
            {"loss": NamedSharding(mesh, P()), "grad_norm": NamedSharding(mesh, P())},
        )
        return MeshStep(jax.jit(step, in_shardings=in_shardings, out_shardings=out_shardings), mesh)

    def init_state(key):
        params = M.init_params(cfg, key)
        return AdamWTrainState(params, adamw_init(params))

    return make_step, {
        "init_state": init_state,
        "param_specs": pspecs,
        "opt_specs": ospecs,
        "param_shapes": pshape,
    }


# ------------------------------------------------------------ inference steps
def make_prefill_step(cfg: ModelConfig, mesh):
    """Full-sequence forward; returns last-position logits (B, V)."""

    def step(params, batch):
        logits, _ = M.forward(params, cfg, batch, remat=False)
        return logits[:, -1]

    pshape = jax.eval_shape(lambda k: M.init_params(cfg, k), jax.random.key(0))
    pspecs = shd.param_pspecs(pshape, mesh, cfg)

    def make_step(batch_like):
        bspecs = shd.batch_pspec(batch_like, mesh)
        ns = lambda tree: jax.tree.map(
            lambda s: NamedSharding(mesh, s), tree, is_leaf=lambda x: isinstance(x, P)
        )
        daxes = data_axis_names(mesh)
        dax = daxes if len(daxes) > 1 else daxes[0]
        nd = num_cohorts(mesh)
        b = batch_like["tokens"].shape[0] if "tokens" in batch_like else None
        vocab_ok = cfg.vocab_size % mesh.shape["model"] == 0
        out_sh = NamedSharding(
            mesh,
            P(
                dax if (b is None or (b % nd == 0 and b >= nd)) else None,
                "model" if vocab_ok else None,
            ),
        )
        return MeshStep(
            jax.jit(step, in_shardings=(ns(pspecs), ns(bspecs)), out_shardings=out_sh), mesh
        )

    return make_step, {"param_specs": pspecs, "param_shapes": pshape}


def make_serve_step(cfg: ModelConfig, mesh, *, params_like=None):
    """One-token decode: (params, cache, token, pos) -> (logits, cache).

    `params_like` overrides the parameter pytree structure — pass
    `jax.eval_shape(quantize_params, pshape)` to lower the int8 serving path
    (repro.quant)."""

    def step(params, cache, token, pos):
        return M.decode_step(params, cfg, token, cache, pos)

    pshape = params_like if params_like is not None else jax.eval_shape(
        lambda k: M.init_params(cfg, k), jax.random.key(0)
    )
    pspecs = shd.param_pspecs(pshape, mesh, cfg)

    def make_step(cache_like, token_like):
        cspecs = shd.cache_pspec(cache_like, mesh, cfg)
        ns = lambda tree: jax.tree.map(
            lambda s: NamedSharding(mesh, s), tree, is_leaf=lambda x: isinstance(x, P)
        )
        tspec = shd.batch_pspec(token_like, mesh)
        daxes = data_axis_names(mesh)
        dax = daxes if len(daxes) > 1 else daxes[0]
        # logits (B, V): batch over client axes when divisible, vocab on model
        b = token_like.shape[0]
        nd = num_cohorts(mesh)
        vocab_ok = cfg.vocab_size % mesh.shape["model"] == 0
        out_logits = NamedSharding(
            mesh,
            P(dax if b % nd == 0 and b >= nd else None, "model" if vocab_ok else None),
        )
        return MeshStep(
            jax.jit(
                step,
                in_shardings=(ns(pspecs), ns(cspecs), NamedSharding(mesh, tspec), None),
                out_shardings=(out_logits, ns(cspecs)),
            ),
            mesh,
        )

    return make_step, {"param_specs": pspecs, "param_shapes": pshape}
