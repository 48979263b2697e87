"""§Perf hillclimb harness: re-lower a (arch x shape) pair, extract the three
roofline terms and the top collective contributors, and append the record to
experiments/perf/<tag>.json — one record per hypothesis->change->measure
cycle.

    PYTHONPATH=src python -m benchmarks.perf_iter --arch qwen2-1.5b \
        --shape train_4k --tag iter2_reuse_local_grad
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)

import argparse
import json

from repro.launch import roofline as rl  # noqa: E402
from repro.launch.dryrun import lower_combo  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.shapes import resolve_config  # noqa: E402


def measure(arch: str, shape: str, train_mode: str = "svrp", svrp=None):
    from repro.launch.dryrun import DEFAULT_SVRP

    svrp = svrp or DEFAULT_SVRP
    lowered, compiled, meta = lower_combo(arch, shape, train_mode=train_mode, svrp=svrp)
    cfg = resolve_config(get_config(arch), shape)
    roof = rl.analyze(compiled, meta["chips"], cfg=cfg, shape_name=shape,
                      kind=meta["kind"], train_mode=train_mode,
                      local_steps=svrp.local_steps,
                      refresh_exact=svrp.refresh_grad_mode == "exact")
    txt = compiled.as_text()
    blocks, _ = rl.parse_computations(txt)
    mults = rl.computation_multipliers(txt)
    tops = []
    for name, lines in blocks.items():
        m = mults.get(name, 0.0)
        if not m:
            continue
        for line in lines:
            cm = rl._COLL_LINE.search(line)
            if cm:
                b = rl._shape_bytes_of(cm.group(1))
                w = rl._OP_TRAFFIC_WEIGHT[cm.group(2)]
                tops.append((m * b * w, m, b, cm.group(2), name[:40]))
    tops.sort(reverse=True)
    mem = compiled.memory_analysis()
    return {
        "meta": meta,
        "roofline": roof.as_dict(),
        "top_collectives": [
            {"wire_GB": t[0] / 1e9, "mult": t[1], "each_MB": t[2] / 1e6, "op": t[3],
             "comp": t[4]}
            for t in tops[:8]
        ],
        "memory": {
            "argument_GiB": mem.argument_size_in_bytes / 2**30,
            "temp_GiB": mem.temp_size_in_bytes / 2**30,
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--tag", required=True)
    ap.add_argument("--train-mode", default="svrp")
    ap.add_argument("--reuse-grad", action="store_true",
                    help="refresh_grad_mode=reuse_local (beyond-paper)")
    ap.add_argument("--seq-parallel", action="store_true",
                    help="sequence-sharded residual stream (beyond-paper)")
    args = ap.parse_args()

    if args.seq_parallel:
        from repro.utils import shard as _shard

        _shard.set_activation_mode("seq")

    from repro.launch.dryrun import DEFAULT_SVRP
    import dataclasses as _dc

    svrp = _dc.replace(
        DEFAULT_SVRP,
        refresh_grad_mode="reuse_local" if args.reuse_grad else "exact",
    )
    rec = measure(args.arch, args.shape, args.train_mode, svrp=svrp)
    os.makedirs("experiments/perf", exist_ok=True)
    path = f"experiments/perf/{args.arch}_{args.shape}_{args.tag}.json"
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)
    r = rec["roofline"]
    print(f"{args.tag}: compute {r['compute_s']*1e3:.1f}ms  mem {r['memory_s']*1e3:.1f}ms  "
          f"coll {r['collective_s']*1e3:.1f}ms  -> {r['dominant']}")
    for t in rec["top_collectives"][:5]:
        print(f"  {t['wire_GB']:9.2f}GB x{t['mult']:6.0f} {t['op']:16s} {t['comp']}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache(__file__)
    main()
