"""Each traffic driver at a tiny size on the CPU, called directly.

Every test skips the harness's look for a chip and drives the rest of a run
(`harness.run_cell`: set-up, window, release, comparison with the plain
reference).  A sound run must come out correct; a run with the timed path
broken underneath must not, once for each fault the cell can have:

* a step that returns its state unchanged;
* half of the batch left out (half the trials, or half the clients with the
  mean taken over the rest);
* an answer altered where it is produced.

The faults are `bench/faults.py`'s, which also reads them on the chip.

The cells and their tiny sizes come from data (`tests/bench/cells.py`).
The fault-free and faulty runs use the cell's own limits
(`bench/limits/<cell>.json`).  One chip per cell, so there is no exchange
between chips to leave out.
"""
import sys
import time
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import cells  # noqa: E402
from bench import harness  # noqa: E402

SEED = 2**31 + 7


def run(cell):
    with jax.enable_x64(False):
        jax.clear_caches()
        return harness.run_cell(cell, t0=time.perf_counter(), devices=jax.devices())


def _clear_program_caches():
    from repro.experiments import runner
    from repro.serve import pool

    for f in (runner._fused_runner, runner._fused_body, runner._registry_runner,
              runner._registry_body, pool._pool_chunk_fn):
        f.cache_clear()


@pytest.fixture
def faults(monkeypatch):
    """Plant a fault in the program; undone after the test."""
    from bench import faults as planted

    _clear_program_caches()
    yield lambda fault, algo: planted.plant(fault, algo, monkeypatch)
    monkeypatch.undo()
    _clear_program_caches()


@pytest.mark.parametrize("workload", cells.CELLS)
def test_sound_run_is_correct(workload):
    line = run(cells.cell(workload, SEED))
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) >= {"setup_s"}
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("workload,algo", [(c, cells.algo(c)) for c in cells.CELLS])
@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"],
                         ids=["_unchanged", "_half_trials", "_altered"])
def test_broken_run_is_not_correct(faults, workload, algo, fault):
    if fault == "half":  # half of the batch left out, as the cell's batch allows
        fault = cells.tiny(workload)["half_batch_fault"]
    faults(fault, algo)
    line = run(cells.cell(workload, SEED))
    assert not line["correct"], line["checks"]
