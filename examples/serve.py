"""Both servers, side by side: model-decode batching AND federated rounds.

The repo has two serving layers that are easy to confuse:

* `repro.launch.serve` — the model DECODE batch server: prefill a batch of
  prompts, then greedy-decode with the per-family KV-cache machinery
  (demoed first, below).
* `repro.serve` — the federated ROUND server: continuous SVRP rounds over a
  churning client stream (demoed second; full version in
  examples/serve_fed.py).

    PYTHONPATH=src python examples/serve.py --arch rwkv6-1.6b --tokens 32
    PYTHONPATH=src python examples/serve.py --arch qwen2-1.5b

Uses the reduced configs (CPU); the same decode_step is what the production
serve path lowers for decode_32k / long_500k (repro/launch/steps.py).
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, REGISTRY
from repro.models import model as M


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2-1.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    args = ap.parse_args()

    cfg = dataclasses.replace(
        REGISTRY[args.arch].reduced(), param_dtype="float32", compute_dtype="float32"
    )
    key = jax.random.key(0)
    params = M.init_params(cfg, key)
    B = args.batch
    cache_len = args.prompt_len + args.tokens

    kw = {}
    if cfg.family == "audio":
        kw = dict(params=params,
                  batch={"frames": jax.random.normal(key, (B, 16, cfg.d_model))})
    cache = M.init_decode_cache(cfg, B, cache_len, dtype=jnp.float32, **kw)

    # prefill the prompt token-by-token through the decode path (exercises the
    # same cache update the batched production prefill would produce)
    prompt = jax.random.randint(key, (B, args.prompt_len), 0, cfg.vocab_size)
    step = jax.jit(lambda p, t, c, pos: M.decode_step(p, cfg, t, c, pos))
    for t in range(args.prompt_len):
        logits, cache = step(params, prompt[:, t], cache, jnp.asarray(t))

    # greedy decode
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [tok]
    t0 = time.perf_counter()
    for t in range(args.prompt_len, args.prompt_len + args.tokens - 1):
        logits, cache = step(params, tok, cache, jnp.asarray(t))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(tok)
    dt = time.perf_counter() - t0
    toks = jnp.stack(out, axis=1)
    print(f"arch={args.arch} family={cfg.family}")
    print(f"decoded {args.tokens} tokens x {B} seqs in {dt:.2f}s "
          f"({B * args.tokens / max(dt, 1e-9):.1f} tok/s on CPU, reduced model)")
    print("sample:", toks[0, :16].tolist())

    # --- and the OTHER server: continuous federated rounds ----------------
    from repro.core import theorem2_stepsize
    from repro.problems import make_synthetic_quadratic
    from repro.serve import FedRoundServer

    prob = make_synthetic_quadratic(num_clients=10, dim=6, mu=1.0, L=80.0,
                                    delta=4.0, seed=1)
    eta = theorem2_stepsize(1.0, float(prob.similarity()))
    srv = FedRoundServer("svrp", prob, hparams={"eta": eta, "p": 0.2})
    stats = srv.run(80)
    print("federated round server (svrp, 10 churning clients):")
    print(" ", stats.report())


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache(__file__)
    main()
