#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip:

    python3 bench/control.py --workload <name> --seeds 1 2 3 ... [--seconds 3]
    python3 bench/control.py --workload <name> --seeds 1 2 3 --control <precision>
    python3 bench/control.py --workload <name> --seeds 1 2 3 --fault <fault>

Without `--control`, each seed runs the cell as a benchmark run does (set-up,
a window of `--seconds`, the comparison) and prints the numbers compared:
the lower readings.  With `--control`, the plain reference computed at
`precision` (`bench/reference/precision.py`) takes the program's place, and
its numbers are the upper readings: a limit has to lie between the two.
With `--fault`, the program runs with that fault (`bench/faults.py`)
planted underneath the timed path.  One JSON line per seed; the benchmark's
own runs never run this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def readings(cell, control: str | None) -> dict:
    from bench import harness

    rec = harness.Recorder(traced=False)
    driver = cell.driver
    t0 = time.perf_counter()
    state = driver.setup(cell, rec, control=control)
    window = driver.window(state, cell, rec)
    driver.release(state)
    checks = driver.check(state, cell)
    return {"seed": cell.seed, "control": control, "attempted": window.attempted,
            "seconds": time.perf_counter() - t0,
            "checks": {c.name: c.value for c in checks}, "ok": all(c.ok for c in checks)}


def main() -> int:
    from bench import faults, harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--fault", default=None, choices=faults.FAULTS)
    args = ap.parse_args()
    cells = [harness.cell_from_files(args.workload, s, args.seconds, False) for s in args.seeds]
    try:
        harness.require_chip(cells[0].chips)
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        return 2
    harness.use_compile_cache()
    sys.path.insert(0, str(harness.ROOT / "src"))
    if args.fault:
        faults.plant(args.fault, cells[0].traffic.get("algo", "deep_svrp"))
    for cell in cells:
        print(json.dumps(dict(readings(cell, args.control), fault=args.fault)), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
