"""The reduction from a profiler trace to busy time, idle share, kernel time
and labelled gaps (`bench/trace.py`), on hand-made events shaped as a TPU
trace records them: instruction text as the name, a `while` spanning its
body, operations overlapping and crossing the window's edges."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace as tr  # noqa: E402


def test_op_names():
    assert tr.op_name("%logistic_prox_gd_batched.8 = f32[32,1,128]{2,1,0} custom-call(f32[4,32])") \
        == "logistic_prox_gd_batched.8"
    assert tr.op_kind("logistic_prox_gd_batched.8") == "logistic_prox_gd_batched"
    assert tr.op_kind("fusion.128") == "fusion" and tr.op_kind("copy") == "copy"
    assert tr.op_kind(tr.op_name("%while.5 = (s32[]) while(...)")) == "while"


def _trace():
    P = "/device:TPU:0"
    ops = [
        ("while.1", 100, 800, P),        # control flow: spans its body, not busy
        ("fusion.1", 100, 100, P),
        ("fusion.2", 150, 100, P),       # overlaps fusion.1: union 100..250
        ("kern.3", 400, 200, P),
        ("kern.3", 950, 100, P),         # runs past the window's end (1000)
        ("fusion.4", 10, 20, P),         # before the window
    ]
    spans = [("window", 100, 900), ("run_batch", 90, 320), ("readback", 600, 300)]
    return tr.Trace(ops, spans)


def test_busy_idle_kernels_and_gaps():
    s = tr.reduce(_trace())
    assert s.window_s == pytest.approx(900e-9)
    busy = (250 - 100) + (600 - 400) + (1000 - 950)
    assert s.busy_s == pytest.approx(busy * 1e-9)
    assert s.idle_share == pytest.approx(1 - busy / 900)
    assert s.kernel_s["kern"] == (pytest.approx(250e-9), 2)
    assert s.kernel_s["fusion"] == (pytest.approx(200e-9), 2)
    assert "while" not in s.kernel_s
    assert s.device_ops[0] == ["kern.3", pytest.approx(250e-9)]
    # gaps: 250..400 (middle 325: run_batch ends at 410), 600..950 (readback)
    assert s.idle_gaps == [["readback", pytest.approx(350e-9)], ["run_batch", pytest.approx(150e-9)]]


def test_window_span_is_required():
    t = _trace()
    t.spans = [s for s in t.spans if s[0] != "window"]
    with pytest.raises(ValueError):
        tr.reduce(t)
