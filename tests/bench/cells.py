"""The cells the tests drive, found from data: every workload of
`BENCHMARK.json`, and each prepared cell that it does not list yet.

Each cell has a tiny size for the CPU in `tests/bench/tiny/<cell>.json`:

* `config`, `traffic`: overrides of the cell's configuration and traffic
  files; a key set to `null` is removed;
* `seconds`: the window of a driver test;
* `half_batch_fault`: the fault of `bench/faults.py` that leaves half of
  the cell's batch out (`half_trials`, or `half_clients` for a cell of one
  trial);
* `control`: `precision`, the one below the configuration's that the control
  runs at, and `seconds`, `config`, `traffic` of its own (applied to the
  cell's files, not on top of the driver tests' overrides);
* `workload`, for a prepared cell only: its `config`, `traffic` and `chips`,
  as a `BENCHMARK.json` workload would give them;
* `why`: notes, read by no code.

A new cell is one new file here and no edit to a test.
"""
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
TINY = Path(__file__).resolve().parent / "tiny"
sys.path.insert(0, str(ROOT))

from bench import faults, harness  # noqa: E402

BENCH = harness.benchmark()
_LISTED = {w["name"]: w for w in BENCH["workloads"]}


def tiny(name: str) -> dict:
    return harness.load_json(TINY / f"{name}.json")


def _prepared() -> dict:
    out = {}
    for path in sorted(TINY.glob("*.json")):
        w = harness.load_json(path).get("workload")
        if w and path.stem not in _LISTED:
            out[path.stem] = dict(w, name=path.stem)
    return out


WORKLOADS = {**_LISTED, **_prepared()}
CELLS = sorted(WORKLOADS)


def config_file(config: str) -> Path:
    return ROOT / {c["name"]: c["file"] for c in BENCH["configs"]}[config]


def _override(d: dict, changes: dict) -> None:
    for k, v in changes.items():
        if v is None:
            d.pop(k, None)
        else:
            d[k] = v


def algo(name: str) -> str:
    """The program's round that the cell's runs go through."""
    w = WORKLOADS[name]
    return faults.round_algo(harness.load_json(harness.BENCH / "traffic" / f"{w['traffic']}.json"))


def cell(name: str, seed: int, control: bool = False) -> harness.Cell:
    """The cell `name` at its tiny size: the driver tests', or with
    `control` the control test's."""
    w, size = WORKLOADS[name], tiny(name)
    if control:
        size = size["control"]
    c = harness.make_cell(name, config_file(w["config"]), w["traffic"], w["chips"],
                          seed, size["seconds"], False)
    _override(c.config, size.get("config", {}))
    _override(c.traffic, size.get("traffic", {}))
    return c
