"""The federated fine-tuning cell's scope readers (`bench/metrics/
<scope>_ms_per_call.fedlm.py`) on hand-made events: device milliseconds per
call under their scope, and None off the `fedlm` driver, without a trace, or
where no operation carries the scope."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench import spans as sp  # noqa: E402

P = "/device:TPU:0"
READERS = ["prox_ms_per_call.fedlm", "channel_ms_per_call.fedlm", "refresh_ms_per_call.fedlm"]

# (name, start, dur, ids); the window is [100, 10_000), two calls start in it.
SPANS = [
    ("run_batch", 50, 60, {"call": 1}),  # starts before the window: not counted
    ("run_batch", 200, 4_000, {"call": 2}),
    ("run_batch.wait", 300, 3_800, {"call": 2}),
    ("run_batch", 5_000, 4_000, {"call": 3}),
]

# (name, start, dur, plane, scope): per scope, the union inside the window.
OPS = [
    ("while.4", 250, 8_000, P, None),  # the rounds' loop: control flow
    ("fusion.1", 300, 1_000, P, "prox"),
    ("fusion.2", 800, 1_000, P, "prox"),  # overlaps fusion.1: union 300..1800
    ("convolution.3", 5_100, 600, P, "prox"),
    ("fusion.9", 1_900, 40, P, "channel"),
    ("reduce.9", 5_800, 60, P, "channel"),
    ("conditional.2", 2_000, 900, P, "refresh"),  # control flow: spans its body
    ("fusion.20", 2_100, 300, P, "refresh"),
    ("fusion.21", 9_900, 200, P, "refresh"),  # runs past the window's end: 9_900..10_000
    ("fusion.30", 60, 30, P, "prox"),  # before the window
    ("add.7", 3_000, 10, P, "accounting"),
]

# reader -> device ns under its scope in the window, summed over both calls
WANT_NS = {
    "prox_ms_per_call.fedlm": 1_500 + 600,
    "channel_ms_per_call.fedlm": 40 + 60,
    "refresh_ms_per_call.fedlm": 300 + 100,
}


def _ctx(driver="fedlm", traced=True):
    return SimpleNamespace(cell=SimpleNamespace(traffic={"driver": driver}),
                           trace=object() if traced else None)


@pytest.fixture
def program(monkeypatch):
    """Hand `spans.program()` the events given; undone after the test."""
    def use(ops, spans=SPANS):
        monkeypatch.setattr(sp, "program", lambda: sp.Program(spans, ops, (100, 10_000)))
    return use


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_device_ms_per_call_under_its_scope(program, name):
    program(OPS)
    got = harness.metric_reader(name).read(_ctx())
    assert got == pytest.approx(WANT_NS[name] * 1e-6 / 2)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_off_the_fedlm_driver_or_untraced(program, name):
    program(OPS)
    reader = harness.metric_reader(name)
    assert reader.read(_ctx(driver="sweep")) is None
    assert reader.read(_ctx(driver="pool")) is None
    assert reader.read(_ctx(traced=False)) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_where_its_scope_is_absent(program, name):
    scope = name.split("_ms_per_call")[0]
    program([op for op in OPS if op[4] != scope])
    assert harness.metric_reader(name).read(_ctx()) is None
    program([op[:4] + (None,) for op in OPS])  # a program without scopes
    assert harness.metric_reader(name).read(_ctx()) is None
    program(OPS, spans=[s for s in SPANS if s[0] != "run_batch"])  # no call in the window
    assert harness.metric_reader(name).read(_ctx()) is None
