"""End-to-end driver: federated transformer fine-tuning with DeepSVRP,
through the REAL experiment engine — `run_batch` over a `FedLMProblem` —
with a comm channel on the wire.

    PYTHONPATH=src python examples/fed_transformer.py --quick       # CI smoke
    PYTHONPATH=src python examples/fed_transformer.py               # 20m preset
    PYTHONPATH=src python examples/fed_transformer.py --channel quant8 --rounds 8

Unlike the historical version of this example (which drove the pytree
`deep_svrp_round` in a hand-rolled loop), this goes through the SAME
`RunSpec`/`run_batch` path as every synthetic sweep: the model's parameters
travel as one ravelled vector, the round body is the shared
`rounds.ROUND_DEFS["deep_svrp"]` definition, the engine's dist_sq column is
the across-client mean LM loss (`FedLMProblem.metric`), and the returned
`BatchResult.comm_bytes` is the integer bytes-on-the-wire ledger under the
selected channel.  `--compare` runs float32 and quant8 back to back and
prints the bytes ratio (the benchmark gate holds it at <= 0.27x).

The `--dry-run-qwen` flag prices a production shape without allocating it:
`jax.eval_shape` over qwen2-1.5b's init gives the parameter pytree's shapes,
and `channel.payload_nbytes` prices one server<->client transfer of it per
channel — the wire plan for a real deployment, computed in milliseconds.
"""
import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import REGISTRY
from repro.core.channel import CHANNELS, payload_nbytes
from repro.experiments import RunSpec, run_batch
from repro.problems import make_fed_lm_problem

PRESETS = {
    # (d_model, layers, heads, kv, d_ff, vocab, batch/client, seq)
    "cpu-small": (64, 2, 4, 2, 128, 128, 2, 32),
    "20m": (384, 6, 6, 2, 1024, 8192, 2, 128),
    "100m": (768, 12, 12, 4, 2048, 32000, 4, 256),
}


def build_cfg(preset: str):
    d, L, h, kv, ff, vocab, bsz, seq = PRESETS[preset]
    cfg = dataclasses.replace(
        REGISTRY["llama3.2-3b"].reduced(),
        num_layers=L, d_model=d, num_heads=h, num_kv_heads=kv,
        head_dim=d // h, d_ff=ff, vocab_size=vocab,
        param_dtype="float32", compute_dtype="float32",
    )
    return cfg, bsz, seq


def dry_run_qwen():
    """Price one parameter transfer of qwen2-1.5b per channel WITHOUT
    allocating the model: eval_shape gives the pytree's ShapeDtypeStructs and
    the channel layer prices them from shapes alone."""
    from repro.models import model as M

    cfg = REGISTRY["qwen2-1.5b"]
    shapes = jax.eval_shape(lambda k: M.init_params(cfg, k), jax.random.key(0))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    print(f"qwen2-1.5b dry run: {n/1e9:.2f}B params (eval_shape, nothing allocated)")
    base = payload_nbytes(None, shapes)
    for name in [None, *sorted(CHANNELS)]:
        b = payload_nbytes(name, shapes)
        print(f"  channel={name or 'None(native)':16s} "
              f"{b/1e9:8.3f} GB/transfer  ({b/base:.4f}x)")


def run(preset, rounds, clients, channel, eta, local_lr, anchor_prob,
        local_steps, alpha, seed):
    cfg, bsz, seq = build_cfg(preset)
    problem, x0 = make_fed_lm_problem(
        cfg, num_clients=clients, per_client_batch=bsz, seq_len=seq,
        alpha=alpha, seed=seed,
    )
    print(f"model: {problem.dim/1e6:.1f}M params ({preset}); "
          f"{clients} clients, alpha={alpha}, channel={channel}")
    spec = RunSpec(
        "deep_svrp",
        grid={"eta": eta, "local_lr": local_lr, "anchor_prob": anchor_prob},
        seeds=[seed],
        x0=x0, x_star=x0,  # unused: FedLMProblem reports metric(x) = mean loss
        static={"num_steps": rounds, "local_steps": local_steps,
                "channel": channel},
    )
    t0 = time.time()
    res = run_batch(spec, problem)
    dt = time.time() - t0
    loss = np.asarray(res.dist_sq)[0]
    by = np.asarray(res.comm_bytes)[0]
    for r in range(rounds):
        print(f"round {r + 1:3d}  loss {loss[r]:.4f}  "
              f"wire {by[r]/1e6:10.2f} MB")
    print(f"{dt/rounds:.2f}s/round; final loss {loss[-1]:.4f}; "
          f"total wire {by[-1]/1e9:.3f} GB")
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=PRESETS, default="20m")
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--alpha", type=float, default=0.3,
                    help="client heterogeneity (lower = more)")
    ap.add_argument("--eta", type=float, default=1.0)
    ap.add_argument("--local-lr", type=float, default=0.2)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--anchor-prob", type=float, default=0.25)
    ap.add_argument("--channel", default="quant8",
                    choices=["none", *sorted(CHANNELS)])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compare", action="store_true",
                    help="run float32 and quant8 back to back, print bytes ratio")
    ap.add_argument("--dry-run-qwen", action="store_true",
                    help="price a qwen2-1.5b transfer per channel (eval_shape)")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: cpu-small preset, few rounds, with compare "
                         "+ the qwen dry run")
    args = ap.parse_args()

    if args.quick:
        args.preset, args.rounds, args.compare = "cpu-small", 4, True
        args.dry_run_qwen = True

    if args.dry_run_qwen:
        dry_run_qwen()

    channel = None if args.channel == "none" else args.channel
    res = run(args.preset, args.rounds, args.clients, channel, args.eta,
              args.local_lr, args.anchor_prob, args.local_steps, args.alpha,
              args.seed)

    if args.compare and channel is not None:
        base = run(args.preset, args.rounds, args.clients, None, args.eta,
                   args.local_lr, args.anchor_prob, args.local_steps,
                   args.alpha, args.seed)
        ratio = float(res.comm_bytes[0, -1]) / float(base.comm_bytes[0, -1])
        l0 = float(np.asarray(res.dist_sq)[0, 0])
        lk = float(np.asarray(res.dist_sq)[0, -1])
        print(f"bytes[{channel}] / bytes[float32] = {ratio:.4f}")
        assert lk < l0, f"loss did not decrease under {channel}: {l0} -> {lk}"
        print(f"loss decreased under {channel}: {l0:.4f} -> {lk:.4f}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache(__file__)
    main()
