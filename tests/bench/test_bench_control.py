"""Each cell's control, at a size a test run can hold: the plain reference,
computed one precision below what the configuration states, put in the
program's place, must come out not correct against the cell's own limits.

The precisions are emulated explicitly (`bench/reference/precision.py`), so
the CPU reads what the chip reads.  Each cell's control precision and tiny
size are the `control` block of `tests/bench/tiny/<cell>.json`; the
readings at the cells' own sizes, on the chip, are in PERF.md.
"""
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import cells  # noqa: E402
from bench import harness  # noqa: E402

SEED = 2**31 + 11


@pytest.mark.parametrize("workload", [c for c in cells.CELLS if "control" in cells.tiny(c)])
def test_control_is_not_correct(workload):
    cell = cells.cell(workload, SEED, control=True)
    rec = harness.Recorder(traced=False)
    with jax.enable_x64(False):
        st = cell.driver.setup(cell, rec, control=cells.tiny(workload)["control"]["precision"])
        cell.driver.window(st, cell, rec)
        cell.driver.release(st)
        checks = cell.driver.check(st, cell)
    assert not all(c.ok for c in checks), [(c.name, c.value, c.limit) for c in checks]
