"""The program's spans and scopes from a profiler trace (`bench/spans.py`):
the reductions on hand-made events, and the reading of a trace file built
by hand in the protobuf form a TPU trace has (the `op_name` path as the
`tf_op` stat of each operation's event metadata)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spans as sp  # noqa: E402

P = "/device:TPU:0"


def test_scope_of_takes_the_innermost_scope_and_never_the_primitive():
    assert sp.scope_of("jit(run)/while/body/closed_call/prox/jit(_take)/gather:") == "prox"
    assert sp.scope_of("jit(run)/refresh/cond/branch_1_fun/psum/psum") == "psum"
    assert sp.scope_of("jit(run)/shard_map/psum") is None  # an unscoped psum primitive
    assert sp.scope_of("jit(run)/while/body/add") is None
    assert sp.scope_of(None) is None and sp.scope_of("") is None


def _spans():
    return [
        # (name, start, dur, ids); the window is [100, 1000)
        ("run_batch", 50, 100, {"call": 1}),  # starts before the window: not counted
        ("run_batch.wait", 60, 80, {"call": 1}),
        ("run_batch", 200, 300, {"call": 2}),
        ("run_batch.prepare", 200, 20, {"call": 2}),
        ("run_batch.wait", 240, 250, {"call": 2}),  # own time 300 - 250 = 50
        ("run_batch", 600, 200, {"call": 3}),
        ("run_batch.wait", 620, 100, {"call": 3}),
        ("run_batch.wait", 700, 150, {"call": 3}),  # overlaps the first and runs past the parent
        ("run_batch.wait", 610, 20, {"call": 2}),  # another call's child: not this one's
    ]


def test_host_self_time_is_the_span_less_its_wait():
    got = sp.self_ms_per_call(_spans(), 100, 1000)
    # call 2: 300 - 250 = 50; call 3: 200 - (620..800 covered = 180) = 20
    assert got == pytest.approx(0.5 * (50 + 20) * 1e-6)
    assert sp.self_ms_per_call(_spans(), 100, 550) == pytest.approx(50e-6)


def _ops():
    return [
        # (name, start, dur, plane, scope)
        ("conditional.3", 150, 500, P, "refresh"),  # control flow: spans its body
        ("fusion.8", 90, 40, P, "refresh"),  # crosses the window's start: 100..130
        ("fusion.10", 300, 100, P, "refresh"),
        ("fusion.11", 350, 100, P, "refresh"),  # overlaps fusion.10: union 300..450
        ("fusion.12", 980, 50, P, "refresh"),  # crosses the end: 980..1000
        ("fusion.128", 500, 30, P, "prox"),
        ("pad.65", 530, 10, P, "prox"),
        ("logistic_prox_gd_batched.8", 540, 300, P, "prox"),
        ("multiply_reduce_fusion.8", 850, 40, P, "grad"),
        ("add.1", 900, 5, P, None),
    ]


def test_scope_time_is_a_union_clipped_to_the_window():
    assert sp.scope_ns(_ops(), "refresh", 100, 1000) == 30 + 150 + 20
    assert sp.scope_ns(_ops(), "prox", 100, 1000) == 30 + 10 + 300
    feed = sp.scope_ns(_ops(), "prox", 100, 1000, leave_out=("logistic_prox_gd_batched",))
    assert feed == 40


def test_scope_time_averages_over_device_planes():
    ops = _ops() + [("fusion.10", 300, 400, "/device:TPU:1", "refresh")]
    assert sp.scope_ns(ops, "refresh", 100, 1000) == pytest.approx((200 + 400) / 2)


def test_scope_per_call_and_none_when_absent():
    prog = sp.Program(_spans(), _ops(), (100, 1000))
    assert sp.scope_ms_per_call(prog, "refresh") == pytest.approx(200e-6 / 2)
    assert sp.scope_ms_per_call(prog, "prox", ("logistic_prox_gd_batched",)) \
        == pytest.approx(40e-6 / 2)
    assert sp.scope_ms_per_call(prog, "psum") is None  # no operation carries it
    unscoped = sp.Program(_spans(), [op[:4] + (None,) for op in _ops()], (100, 1000))
    assert sp.scope_ms_per_call(unscoped, "refresh") is None  # a program without scopes
    no_calls = sp.Program([s for s in _spans() if s[0] != "run_batch"], _ops(), (100, 1000))
    assert sp.scope_ms_per_call(no_calls, "refresh") is None
    assert sp.self_ms_per_call(no_calls.spans, 100, 1000) is None


# ----------------------------------------------- a trace file made by hand
def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(num: int, value) -> bytes:
    """One protobuf field: a varint for an int, length-delimited otherwise."""
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _stat(meta_id, field, value):
    return _f(1, meta_id) + _f(field, value)  # XStat: metadata_id, then its value


def _plane(name, stat_names, metas, lines):
    """An XPlane: stat metadata {id: name}, event metadata {id: (name,
    [stat])}, lines [(name, timestamp_ns, [(meta_id, offset_ps, dur_ps,
    [stat])])]."""
    out = _f(2, name)
    for sid, sname in stat_names.items():
        out += _f(5, _f(1, sid) + _f(2, _f(1, sid) + _f(2, sname)))
    for mid, (mname, stats) in metas.items():
        body = _f(1, mid) + _f(2, mname) + b"".join(_f(5, s) for s in stats)
        out += _f(4, _f(1, mid) + _f(2, body))
    for lid, (lname, ts, events) in enumerate(lines):
        evs = b"".join(_f(4, _f(1, m) + _f(2, off) + _f(3, dur) + b"".join(_f(4, s) for s in st))
                       for m, off, dur, st in events)
        out += _f(3, _f(1, lid + 1) + _f(2, lname) + _f(3, ts) + evs)
    return out


GATHER = "%fusion.128 = f32[32,1628,123]{2,1,0} fusion(f32[20,1628,123]{2,1,0} %z), kind=kLoop"
KERNEL = "%logistic_prox_gd_batched.8 = f32[32,1,128]{2,1,0} custom-call(f32[32,1632,128] %a)"
REFRESH = "%fusion.8 = f32[32,20,1628]{2,1,0} fusion(f32[20,1628,123]{2,1,0} %z), kind=kOutput"
PLAIN = "%add.1 = f32[32]{0} add(f32[32]{0} %a, f32[32]{0} %b)"
COPY = "%copy.1 = u32[32]{0} copy(u32[32]{0} %seed.1)"


def _xspace() -> bytes:
    device = _plane(
        P, {1: "tf_op", 2: "program_id", 3: "jit(run)/while/body/refresh/dot_general:"},
        {1: (GATHER, [_stat(1, 5, "jit(run)/while/body/closed_call/prox/jit(_take)/gather:"),
                      _stat(2, 3, 77)]),
         2: (KERNEL, [_stat(1, 5, "jit(run)/while/body/closed_call/prox/"
                                  "jit(logistic_prox_gd_batched)/pallas_call:")]),
         3: (REFRESH, [_stat(1, 7, 3)]),  # the path as a reference into the stat names
         4: (PLAIN, [_stat(2, 3, 77)]),  # no path
         5: (COPY, [_stat(1, 5, "jit(run)/grad/copy:")]),  # one text, two paths
         6: (COPY, [_stat(1, 5, "jit(other)/prox/copy:")])},
        [("XLA Modules", 0, []),
         ("XLA Ops", 1000, [(1, 200_000, 30_000, []), (2, 230_000, 300_000, []),
                            (3, 600_000, 50_000, []), (4, 700_000, 5_000, [])])])
    host = _plane(
        "/host:CPU", {1: "call", 2: "trials"},
        {1: ("bench:window", []), 2: ("repro:run_batch", []), 3: ("repro:run_batch.wait", []),
         4: (GATHER, [_stat(1, 5, "not a device plane")])},
        [("python", 1000, [(1, 0, 900_000, []),
                           (2, 50_000, 800_000, [_stat(1, 4, 5), _stat(2, 4, 32)]),
                           (3, 100_000, 700_000, [_stat(1, 4, 5)])])])
    return _f(1, device) + _f(1, host)


def test_op_paths_read_the_metadata_stats():
    paths = sp.op_paths(_xspace())
    assert paths == {
        (P, GATHER): "jit(run)/while/body/closed_call/prox/jit(_take)/gather:",
        (P, KERNEL): "jit(run)/while/body/closed_call/prox/jit(logistic_prox_gd_batched)/pallas_call:",
        (P, REFRESH): "jit(run)/while/body/refresh/dot_general:",
        (P, COPY): None,
    }


def test_read_joins_events_to_their_scopes(tmp_path):
    (tmp_path / "t.xplane.pb").write_bytes(_xspace())
    prog = sp.read(tmp_path)
    assert prog.window == (1000, 1900)  # offsets and durations are in ps
    assert [(n, sc) for n, *_, sc in prog.ops] == [
        ("fusion.128", "prox"), ("logistic_prox_gd_batched.8", "prox"),
        ("fusion.8", "refresh"), ("add.1", None)]
    assert prog.ops[0][1:4] == (1200, 30, P)
    assert sorted(s[0] for s in prog.spans) == ["run_batch", "run_batch.wait"]
    (call,) = sp.calls_in(prog.spans, *prog.window)
    assert call[3] == {"call": 5, "trials": 32}
    assert sp.self_ms_per_call(prog.spans, *prog.window) == pytest.approx(100e-6)
    assert sp.scope_ms_per_call(prog, "prox", ("logistic_prox_gd_batched",)) == pytest.approx(30e-6)
    assert sp.scope_ms_per_call(prog, "refresh") == pytest.approx(50e-6)
