"""chip_smoke.py on the CPU: every phase at tiny sizes, and the no-TPU exit.

The script's phases are plain functions of their sizes; here they run with
x64 off, as on the chip, and the Pallas kernels in interpret mode (the CPU
side of `kernels.backend.resolve_interpret`).  The four-chip phase runs on
four virtual CPU devices in a subprocess, so the device-count flag never
reaches the rest of the suite.
"""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.kernels.backend import resolve_interpret
from repro.utils.compile_cache import CACHE_ENV, enable_compile_cache
from repro.utils.roofline import PEAKS, get_peak

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def x32():
    with jax.enable_x64(False):
        yield


@pytest.mark.parametrize("which", ["a9a_logistic", "quadratic"])
def test_sweep_phase_tiny(cs, x32, which):
    if which == "a9a_logistic":
        problem, etas = cs.a9a_problem(num_clients=6, n_per_client=40, n_pool=400), cs.A9A_ETAS
    else:
        problem, etas = cs.quadratic_problem(num_clients=6, dim=16), cs.QUAD_ETAS
    out = cs.sweep_phase(which, problem, etas=etas, seeds=2, num_steps=200,
                         prox_steps=40, p=0.2)
    assert out["min_decades"] >= cs.MIN_DECADES


def test_serve_phase_tiny(cs, x32):
    problem = cs.a9a_problem(num_clients=6, n_per_client=40, n_pool=400)
    out = cs.serve_phase(problem, eta=4.0, seeds=2, num_steps=12, chunk=3,
                         chunks=2, tenants=3, prox_steps=10)
    assert out["session_s"] > 0 and out["pool_s"] > 0


def test_model_phase_tiny(cs, x32):
    sys.path.insert(0, str(ROOT))
    from examples.fed_transformer import build_cfg

    cfg, bsz, seq = build_cfg("cpu-small")
    out = cs.model_phase(cfg, clients=2, per_client_batch=bsz, seq_len=seq, rounds=3)
    assert out["seconds_per_round"] > 0


def test_main_exits_without_tpu(cs, capsys):
    with pytest.raises(SystemExit) as exc:
        cs.main([])
    assert "no TPU found" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_script_exits_nonzero_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    last = (r.stdout.strip().splitlines() or [""])[-1]
    assert not last.startswith("{"), last


def test_interpret_rule_on_cpu(cs):
    assert jax.default_backend() == "cpu"
    assert resolve_interpret() is True
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False
    assert cs.kernels_compiled() is False


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v6 lite", "tpu", "NVIDIA H100 80GB HBM3"])
def test_peaks_raise_on_unknown_device_kind(kind):
    with pytest.raises(ValueError, match="PEAKS"):
        get_peak(kind)


def test_peaks_v5e_entry_is_the_datasheet():
    peak = get_peak("TPU v5 lite")
    assert peak is PEAKS["TPU v5 lite"]
    assert (peak.flops, peak.hbm_bw) == (197e12, 819e9)
    assert "TPU v5e" in peak.source and "datasheet" in peak.source
    assert "calibrated" in get_peak().source  # this CPU device: measured


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_the_checkout(cache_dir_config, monkeypatch):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    path = enable_compile_cache(ROOT / "chip_smoke.py")
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()
    # An entry point below the root finds the same directory.
    assert enable_compile_cache(ROOT / "benchmarks" / "run.py") == path


def test_compile_cache_env_wins(cache_dir_config, monkeypatch, tmp_path):
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache(ROOT / "chip_smoke.py") == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set in code


_FOUR_CPU = r"""
import json, sys
sys.path.insert(0, ".")
import jax
import chip_smoke as cs
assert len(jax.devices()) == 4, jax.devices()
out = cs.four_chip_phase(cs.a9a_problem(num_clients=6, n_per_client=40, n_pool=400),
                         etas=[4.0, 2.0], seeds=2, num_steps=60, p=0.2, prox_steps=20)
print(json.dumps(out))
"""


def test_four_chip_phase_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", _FOUR_CPU], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=500)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    assert "clients_per_device=2" in r.stdout
    assert set(json.loads(r.stdout.strip().splitlines()[-1])) == {"one_chip_s", "four_chips_s"}
