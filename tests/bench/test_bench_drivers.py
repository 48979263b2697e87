"""Each traffic driver at a tiny size on the CPU, called directly.

Every test skips the harness's look for a chip and drives the rest of a run
(`harness.run_cell`: set-up, window, release, comparison with the plain
reference).  A sound run must come out correct; a run with the timed path
broken underneath must not, once for each fault the cell can have:

* a step that returns its state unchanged;
* half of the batch left out (half the trials, or half the clients with the
  mean taken over the rest);
* an answer altered where it is produced.

The fault-free and faulty runs use the cell's own limits
(`bench/limits/<cell>.json`).  One chip per cell, so there is no exchange
between chips to leave out.
"""
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from cells import cell as make  # noqa: E402


def tiny(workload: str, seconds: float = 1.0):
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
    """Patch the program's round definitions; undone after the test."""
    from repro.core import rounds

    _clear_program_caches()
    yield rounds, monkeypatch
    monkeypatch.undo()
    _clear_program_caches()


def _unchanged(rdef):
    """The round, except that it hands its input state back."""
    def round_(ops, s, key):
        _, out = rdef.round(ops, s, key)
        return s, (ops.dist_sq(s[0]), out[1])
    return rdef._replace(round=round_)


def _half_trials(rdef):
    """Only the first half of the trials computed; the rest copy them."""
    def round_(ops, s, key):
        s2, (d2, comm) = rdef.round(ops, s, key)
        if not ops.batched:
            return s2, (d2, comm)
        h = ops.B // 2
        dup = lambda a: jnp.concatenate([a[:h], a[:ops.B - h]]) if a.ndim and a.shape[0] == ops.B else a
        return jax.tree.map(dup, s2), (dup(d2), dup(comm))
    return rdef._replace(round=round_)


def _altered(rdef):
    """The last trial's new iterate nudged where the round produces it."""
    def round_(ops, s, key):
        s2, _ = rdef.round(ops, s, key)
        x = s2[0]
        x = x.at[..., -1, :].multiply(1.01) if ops.batched else x * 1.01
        s2 = (x,) + tuple(s2[1:])
        return s2, (ops.dist_sq(x), _[1])
    return rdef._replace(round=round_)


@pytest.mark.parametrize("workload", ["a9a_sweep", "a9a_pool", "qwen2_fedlm"])
def test_sound_run_is_correct(workload):
    line = run(tiny(workload))
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) >= {"setup_s"}
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("workload,algo", [("a9a_sweep", "svrp"), ("a9a_pool", "svrp"),
                                           ("qwen2_fedlm", "deep_svrp")])
@pytest.mark.parametrize("fault", [_unchanged, _half_trials, _altered])
def test_broken_run_is_not_correct(faults, workload, algo, fault):
    rounds, mp = faults
    if fault is _half_trials and workload == "qwen2_fedlm":
        # One trial: leave half of the clients out of the mean instead.
        def half_mean(self, y):
            return jnp.mean(y[..., : y.shape[-2] // 2, :], axis=-2)
        mp.setattr(rounds.RoundOps, "client_mean", half_mean)
    else:
        mp.setitem(rounds.ROUND_DEFS, algo, fault(rounds.ROUND_DEFS[algo]))
    line = run(tiny(workload))
    assert not line["correct"], line["checks"]
