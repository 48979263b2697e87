#!/usr/bin/env python3
"""Run one benchmark cell once:

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, when JAX finds no TPU.  See
`bench/harness.py` for what a run does and `PERF.md` for the cells.
"""
import time

T0 = time.perf_counter()  # set-up is timed from the start of the process

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

if __name__ == "__main__":
    from bench import harness

    sys.exit(harness.main(t0=T0))
