"""The cells the tests drive, by their files, whether or not `BENCHMARK.json`
lists them yet."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

# cell -> (configuration file, traffic file)
CELLS = {
    "a9a_sweep": ("a9a_logistic", "svrp_sweep"),
    "qwen2_fedlm": ("qwen2-1.5b-fed", "deep_svrp_rounds"),
    "a9a_pool": ("a9a_logistic", "pool_churn"),
}


def cell(name: str, seed: int, seconds: float) -> harness.Cell:
    config, traffic = CELLS[name]
    return harness.make_cell(name, harness.BENCH / "configs" / f"{config}.json", traffic,
                             1, seed, seconds, False)
