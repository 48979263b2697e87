"""BENCHMARK.json against the benchmark's contract, and the no-chip exit."""
import json
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def _reports(metric, cell):
    sys.path.insert(0, str(ROOT))
    from bench.harness import reports

    return reports(metric, cell, BENCH["end_to_end"])


def test_names_and_units():
    names = [m["name"] for m in METRICS]
    names += [c["name"] for c in BENCH["configs"]] + [w["name"] for w in BENCH["workloads"]]
    names += [w["config"] for w in BENCH["workloads"]] + [w["traffic"] for w in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    bad = [n for n in names if not NAME.match(n)]
    assert not bad, bad
    assert all(UNIT.match(m["unit"]) for m in METRICS), [m["unit"] for m in METRICS]
    for group in (METRICS, BENCH["configs"], BENCH["workloads"]):
        assert len({x["name"] for x in group}) == len(group)
    assert all(m["better"] in ("lower", "higher") for m in METRICS)


def test_every_cell_reports_what_its_layer_metrics_move():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        moved = next(x for x in BENCH["end_to_end"] if x["name"] == m["moves"])
        for cell in cells:
            if _reports(m, cell):
                assert _reports(moved, cell), (m["name"], cell)
    for cell in cells:
        reported = [m["name"] for m in METRICS if _reports(m, cell)]
        assert "setup_s" in reported
        assert len([n for n in reported if n in e2e]) >= 2, cell
        assert any(m["name"] in reported for m in BENCH["per_layer"]), cell


def test_every_name_has_its_files():
    assert BENCH["command"][1] == "bench/run.py"
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/")
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    for w in BENCH["workloads"]:
        assert w["chips"] == 1
        traffic = json.loads((ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        assert (ROOT / "bench" / "drivers" / f"{traffic['driver']}.py").is_file()
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").is_file()
    for m in BENCH["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


def test_bounds_and_run_length():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")
    # A full check of the largest benchmark (24 cells) fits the driver's 12 h.
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_run_without_a_tpu_exits_nonzero_and_prints_no_result(workload, tmp_path):
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", workload,
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": str(tmp_path)})
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
