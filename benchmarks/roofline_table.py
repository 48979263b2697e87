"""Roofline tables from whichever perf records this checkout actually has.

Two sources, rendered independently:

* ``experiments/dryrun/*.json`` (written by `repro.launch.dryrun`) — the
  transformer dry-run §Roofline table (compute/memory/collective ms per
  (arch, shape), markdown + CSV).
* ``BENCH_sweep.json`` (written by ``benchmarks.sweep_bench --json``) — the
  federated engine's measured perf block: analytic FLOPs/round, achieved
  GFLOP/s and MFU per timed section (docs/PERFORMANCE.md).

Historically this script rendered ONLY the dry-run table and silently
printed an empty table when ``experiments/dryrun/`` was absent — which is
the common case in this repo (the dry-run launcher is a real-TPU item).  It
now renders every source it finds and FAILS LOUDLY, with a pointer to how
each source is produced, when there is none.

    python -m benchmarks.roofline_table [--dryrun-dir DIR] [--bench PATH]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

ARCH_ORDER = [
    "internvl2-76b", "qwen2-1.5b", "granite-3-2b", "llama3.2-3b", "zamba2-2.7b",
    "qwen3-moe-235b-a22b", "seamless-m4t-large-v2", "rwkv6-1.6b", "qwen3-4b",
    "deepseek-moe-16b",
]
SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def load(dirname="experiments/dryrun", mesh="16x16"):
    recs = {}
    for fn in glob.glob(os.path.join(dirname, "*.json")):
        r = json.load(open(fn))
        if r.get("mesh") != mesh and r.get("status") != "skipped":
            continue
        recs[(r["arch"], r["shape"])] = r
    return recs


def run(quick: bool = False, mesh="16x16", dirname="experiments/dryrun"):
    recs = load(dirname=dirname, mesh=mesh)
    rows = []
    for arch in ARCH_ORDER:
        for shape in SHAPE_ORDER:
            r = recs.get((arch, shape))
            if r is None:
                continue
            if r["status"] == "skipped":
                rows.append((arch, shape, "SKIP", r["reason"][:40], "", "", "", ""))
                continue
            if r["status"] != "ok":
                rows.append((arch, shape, "FAIL", r.get("error", "")[:40], "", "", "", ""))
                continue
            rf = r["roofline"]
            rows.append(
                (
                    arch,
                    shape,
                    f"{rf['compute_s'] * 1e3:.2f}",
                    f"{rf['memory_s'] * 1e3:.2f}",
                    f"{rf['collective_s'] * 1e3:.2f}",
                    rf["dominant"],
                    f"{100 * (r.get('useful_flops_ratio') or 0):.0f}%",
                    f"{(r['memory']['argument_bytes'] or 0) / 2**30:.2f}",
                )
            )
    return rows


def markdown(mesh="16x16", dirname="experiments/dryrun") -> str:
    rows = run(mesh=mesh, dirname=dirname)
    out = [
        "| arch | shape | compute ms | memory ms | collective ms | dominant | useful | args GiB/dev |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        out.append("| " + " | ".join(str(x) for x in r) + " |")
    return "\n".join(out)


def engine_markdown(bench_path="BENCH_sweep.json") -> str:
    """The federated engine's MFU table, from a sweep_bench JSON's ``perf``
    block (same rendering as the CI step summary — check_bench.mfu_table)."""
    from benchmarks.check_bench import mfu_table

    with open(bench_path) as f:
        measured = json.load(f)
    md = mfu_table(measured)
    if not md:
        raise SystemExit(
            f"{bench_path} has no 'perf' block — re-record it with\n"
            "    python -m benchmarks.sweep_bench --json BENCH_sweep.json\n"
            "(JSONs written before the perf-accounting layer lack it; "
            "see docs/PERFORMANCE.md)"
        )
    return md


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun-dir", default="experiments/dryrun",
                    help="directory of repro.launch.dryrun JSON records")
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--bench", default="BENCH_sweep.json",
                    help="sweep_bench JSON with a perf block")
    args = ap.parse_args()

    printed = False
    if glob.glob(os.path.join(args.dryrun_dir, "*.json")):
        print("## Dry-run roofline (transformer shapes)\n")
        print(markdown(mesh=args.mesh, dirname=args.dryrun_dir))
        printed = True
    if os.path.exists(args.bench):
        if printed:
            print()
        print(engine_markdown(args.bench))
        printed = True
    if not printed:
        print(
            "roofline_table: no perf records found.\n"
            f"  - {args.dryrun_dir}/*.json: produced by the dry-run launcher "
            "(python -m repro.launch.dryrun ...; real-TPU item)\n"
            f"  - {args.bench}: produced by "
            "python -m benchmarks.sweep_bench --json BENCH_sweep.json\n"
            "See docs/PERFORMANCE.md.",
            file=sys.stderr,
        )
        sys.exit(1)


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache(__file__)
    main()
