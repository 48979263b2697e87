"""Each cell's control, at a size a test run can hold: the plain reference,
computed one precision below what the configuration states, put in the
program's place, must come out not correct against the cell's own limits.

The precisions are emulated explicitly (`bench/reference/precision.py`), so
the CPU reads what the chip reads.  The cells' sizes here are tiny; the
readings at the cells' own sizes, on the chip, are in PERF.md.
"""
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from cells import cell as make  # noqa: E402

CONTROLS = {"a9a_sweep": "high", "a9a_pool": "high", "qwen2_fedlm": "fp8"}


def tiny(workload: str):
    cell = make(workload, seed=2**31 + 11, seconds=0.5)
    if cell.config["family"] == "logistic":
        cell.config.update(num_clients=4, n_per_client=40, n_pool=400)
    if workload == "a9a_sweep":
        cell.traffic.update(seeds_per_call=2)
    if workload == "a9a_pool":
        # Enough prox steps and rounds that trials reach float32's floor at
        # this size, where the products' precision shows.
        cell.traffic.update(prox_steps=40, horizons=[120])
    if workload == "qwen2_fedlm":
        cell.config.pop("registry_name")
        cell.config.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
                           head_dim=32, intermediate_size=256, vocab_size=256)
        cell.traffic.update(sequences_per_client=2, sequence_length=32, rounds_per_call=3)
    return cell


@pytest.mark.parametrize("workload", sorted(CONTROLS))
def test_control_is_not_correct(workload):
    cell = tiny(workload)
    rec = harness.Recorder(traced=False)
    with jax.enable_x64(False):
        st = cell.driver.setup(cell, rec, control=CONTROLS[workload])
        cell.driver.window(st, cell, rec)
        cell.driver.release(st)
        checks = cell.driver.check(st, cell)
    assert not all(c.ok for c in checks), [(c.name, c.value, c.limit) for c in checks]
