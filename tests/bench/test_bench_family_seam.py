"""The federated fine-tuning driver takes its model from the configuration's
family alone: its reference loss, which the comparison replays DeepSVRP
over, and its forward FLOPs, which the window's count multiplies.

At the tiny `qwen2_fedlm` size (`tests/bench/tiny/qwen2_fedlm.json`), the
cell run through `harness.run_cell` is correct with the family as built,
and not correct once the built object's `loss` is the Qwen2 reference with
one planted departure: so the driver compares with that loss and no other.
"""
import ast
import sys
import time
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import cells  # noqa: E402
from bench import counts, harness  # noqa: E402
from bench.families import dense_lm as family  # noqa: E402
from bench.reference import dense_lm as ref  # noqa: E402

SEED = 2**31 + 7


def _rope_theta_1e4(lm):
    sizes = dict(lm.sizes, rope_theta=1e4)  # Qwen2's is 1e6
    return lambda params, tokens, labels, precision: ref.loss(
        params, tokens, labels, sizes, precision)


def _gate_up_exchanged(lm):
    def swap(params):
        mlp = dict(params["layers"]["mlp"], gate=params["layers"]["mlp"]["up"],
                   up=params["layers"]["mlp"]["gate"])
        return dict(params, layers=dict(params["layers"], mlp=mlp))

    return lambda params, tokens, labels, precision: ref.loss(
        swap(params), tokens, labels, lm.sizes, precision)


@pytest.mark.parametrize("departure,correct", [(None, True), (_rope_theta_1e4, False),
                                                (_gate_up_exchanged, False)],
                         ids=["as_built", "rope_theta_1e4", "gate_up_exchanged"])
def test_the_driver_compares_with_the_family_reference(monkeypatch, departure, correct):
    build = family.build

    def built_with_departure(config, traffic, seed):
        lm = build(config, traffic, seed)
        if departure is not None:
            lm.loss = departure(lm)
        return lm

    monkeypatch.setattr(family, "build", built_with_departure)
    cell = cells.cell("qwen2_fedlm", SEED)
    assert cell.family is family
    with jax.enable_x64(False):
        jax.clear_caches()
        line = harness.run_cell(cell, t0=time.perf_counter(), devices=jax.devices())
    assert line["correct"] is correct, line["checks"]
    assert line["checks"]["comm_mismatches"]["value"] == 0


def test_deep_svrp_counts_at_the_qwen2_fedlm_sizes():
    """The window's FLOPs a round and a refresh at the cell's own sizes, as
    the dense count gave them before the family supplied the forward pass."""
    w = cells.WORKLOADS["qwen2_fedlm"]
    config = harness.load_json(cells.config_file(w["config"]))
    t = harness.load_json(harness.BENCH / "traffic" / f"{w['traffic']}.json")
    lm = family.DenseLM({k: config[k] for k in family._FIELDS}, None, None, None, None, None)
    fwd = lm.forward_flops(t["sequences_per_client"], t["sequence_length"])
    assert counts.deep_svrp_round_flops(fwd, t["clients"], t["local_steps"]) == 10_120_939_438_080
    assert counts.deep_svrp_refresh_flops(fwd, t["clients"]) == 3_036_281_831_424


def test_the_driver_imports_no_model():
    tree = ast.parse((harness.BENCH / "drivers" / "fedlm.py").read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            imported |= {f"{node.module}.{a.name}" for a in node.names}
        elif isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
    bench = {m for m in imported if m.startswith("bench")}
    assert bench == {"bench.counts", "bench.harness.Check", "bench.harness.Window",
                     "bench.reference.deep_svrp"}, bench
    called = {n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)
              and isinstance(n.value, ast.Name) and n.value.id == "counts"}
    assert called == {"deep_svrp_round_flops", "deep_svrp_refresh_flops", "deep_svrp_refreshes"}
