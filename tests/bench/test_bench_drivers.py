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

from bench import harness  # noqa: E402
from cells import cell as make  # noqa: E402


def tiny(workload: str, seconds: float = 1.0):
    if workload == "a9a_pool":
        # Its check reads the tenants evicted in the window: a few seconds of
        # ticks, so that a loaded CPU still evicts some from every lane.
        seconds = max(seconds, 4.0)
    cell = make(workload, seed=2**31 + 7, seconds=seconds)
    if cell.config["family"] == "logistic":
        cell.config.update(num_clients=4, n_per_client=40, n_pool=400)
    if workload == "a9a_sweep":
        cell.traffic.update(rounds=120, seeds_per_call=2)
    if workload == "a9a_pool":
        cell.traffic.update(horizons=[40, 60, 80], stop_decades=3)
    if workload == "qwen2_fedlm":
        cell.config.pop("registry_name")
        cell.config.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                           head_dim=16, intermediate_size=96, vocab_size=128)
        cell.traffic.update(sequences_per_client=2, sequence_length=16, rounds_per_call=3)
    return cell


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


@pytest.mark.parametrize("workload", ["a9a_sweep", "a9a_pool", "qwen2_fedlm"])
def test_sound_run_is_correct(workload):
    line = run(tiny(workload))
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) >= {"setup_s"}
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("workload,algo", [("a9a_sweep", "svrp"), ("a9a_pool", "svrp"),
                                           ("qwen2_fedlm", "deep_svrp")])
@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"],
                         ids=["_unchanged", "_half_trials", "_altered"])
def test_broken_run_is_not_correct(faults, workload, algo, fault):
    if fault == "half":  # a cell of one trial leaves half of its clients out instead
        fault = "half_clients" if workload == "qwen2_fedlm" else "half_trials"
    faults(fault, algo)
    line = run(tiny(workload))
    assert not line["correct"], line["checks"]
