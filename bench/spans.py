"""The program's own spans and scopes, read from the traced window.

The engine marks its host phases as `repro:<name>` profiler annotations and
its round hooks as named scopes (`src/repro/utils/spans.py`).  `program`
reads the newest `.xplane.pb` under the harness's trace directory, once per
process, into two plain lists:

* program spans: every `repro:` event on a host plane, as
  `(name, start_ns, dur_ns, ids)`, `ids` its arguments (`call`, `trials`,
  `rounds`, `tick`, ...);
* scoped device operations: every event on the "XLA Ops" line of each
  `/device:TPU:<n>` plane, as `(name, start_ns, dur_ns, plane, scope)`,
  `scope` the innermost program scope on the operation's `op_name` path, or
  None.  A fusion carries its root instruction's path.

and the measured window, from the harness's own spans (`bench/trace.py`).

Where the path comes from: a TPU trace keeps each operation's `op_name`
path as the `tf_op` stat of the event's metadata (with the instruction's
text as the metadata's name), which `jax.profiler.ProfileData` does not
expose, so `op_paths` reads that table straight from the file's protobuf
encoding; the events themselves come from `ProfileData`, joined by plane and
name.  The program is never lowered again here.

Everything past the reading works on plain lists, so the test suite checks
it on events made by hand.  Where the program has no spans or scopes (a
commit before they were added) the readers find nothing and return None.
"""
from __future__ import annotations

import dataclasses
import functools
import statistics
from pathlib import Path

from bench import trace as tr

PREFIX = "repro:"
DEVICE = "/device:TPU:"
SCOPES = ("sample", "grad", "prox", "channel", "refresh", "client_mean", "psum", "accounting")
CALL = "run_batch"
WAIT = "run_batch.wait"
PATH_STAT = "tf_op"


def scope_of(path: str | None) -> str | None:
    """The innermost program scope on an `op_name` path:
    `jit(run)/while/body/closed_call/prox/jit(_take)/gather:` -> `prox`.
    The last component names the primitive, never a scope."""
    for part in reversed((path or "").split("/")[:-1]):
        if part in SCOPES:
            return part
    return None


# ------------------------------------------------ the trace's protobuf form
def _varint(buf, i: int):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    the raw bytes (a memoryview) for everything else."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_paths(raw: bytes) -> dict:
    """{(device plane, event name): `op_name` path} from an `XSpace`'s bytes:
    each device plane's event metadata (XPlane field 4, XEventMetadata name
    2, stats 5) with its `tf_op` stat (XStat metadata_id 1, str_value 5 or
    ref_value 7 into the plane's stat metadata, field 5).  A name that two
    metadata entries give different paths maps to None."""
    out: dict = {}
    for num, plane in _fields(memoryview(raw)):
        if num != 1:  # XSpace.planes
            continue
        name, stat_names, metas = "", {}, []
        for f, v in _fields(plane):
            if f == 2:
                name = _text(v)
            elif f == 4:
                metas.append(dict(_fields(v)).get(2, b""))
            elif f == 5:
                m = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[m.get(1, 0)] = _text(m.get(2, b""))
        if not name.startswith(DEVICE):
            continue
        for meta in metas:
            event, path = None, None
            for f, v in _fields(meta):
                if f == 2:
                    event = _text(v)
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == PATH_STAT:
                        path = _text(stat[5]) if 5 in stat else stat_names.get(stat.get(7))
            if event is not None and path is not None:
                key = (name, event)
                out[key] = path if out.get(key, path) == path else None
    return out


# --------------------------------------------------------------- reading
@dataclasses.dataclass
class Program:
    spans: list  # [(name, start_ns, dur_ns, ids)]
    ops: list  # [(name, start_ns, dur_ns, plane, scope)]
    window: tuple  # (start_ns, end_ns) of the harness's window


def read(trace_dir: Path) -> Program:
    from jax.profiler import ProfileData

    window = tr.window_bounds(tr.load(trace_dir))
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    raw = files[-1].read_bytes()
    paths = op_paths(raw)
    spans, ops = [], []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith(DEVICE):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((tr.op_name(e.name), e.start_ns, e.duration_ns, plane.name,
                                scope_of(paths.get((plane.name, e.name))))
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name[len(PREFIX):], e.start_ns, e.duration_ns,
                              {k: v for k, v in e.stats})
                             for e in line.events if e.name.startswith(PREFIX))
    return Program(spans, ops, window)


@functools.lru_cache(maxsize=None)
def _read_once(trace_dir: str) -> Program:
    return read(Path(trace_dir))


def program() -> Program:
    """The run's trace (`bench.harness.TRACE_DIR`), read once per process."""
    from bench.harness import TRACE_DIR

    return _read_once(str(TRACE_DIR))


# ------------------------------------------------------------- reductions
def calls_in(spans, lo, hi) -> list:
    """The `run_batch` spans that start inside [lo, hi)."""
    return [s for s in spans if s[0] == CALL and lo <= s[1] < hi]


def self_ms_per_call(spans, lo, hi, child: str = WAIT):
    """Median, over the `run_batch` spans that start in the window, of the
    span's duration less the part its `child` spans of the same call cover,
    in ms; None without such a span."""
    calls = calls_in(spans, lo, hi)
    if not calls:
        return None
    kids: dict = {}
    for name, s, d, ids in spans:
        if name == child:
            kids.setdefault(ids.get("call"), []).append((s, s + d))
    own = []
    for _, s, d, ids in calls:
        clipped = [tr._clip(a, b, s, s + d) for a, b in kids.get(ids.get("call"), [])]
        covered = sum(b - a for a, b in tr._merge(iv for iv in clipped if iv[1] > iv[0]))
        own.append((d - covered) * 1e-6)
    return statistics.median(own)


def scope_ns(ops, scope: str, lo, hi, leave_out=()) -> float:
    """Device time under `scope` in [lo, hi): on each device plane the union
    of its operations' intervals clipped to the window (control flow, and
    the operation kinds in `leave_out`, left out), averaged over planes."""
    planes = sorted({op[3] for op in ops})
    total = 0
    for plane in planes:
        ivs = []
        for name, s, d, p, sc in ops:
            kind = tr.op_kind(name)
            if p != plane or sc != scope or kind in tr.CONTROL_FLOW or kind in leave_out:
                continue
            s, e = tr._clip(s, s + d, lo, hi)
            if e > s:
                ivs.append((s, e))
        total += sum(e - s for s, e in tr._merge(ivs))
    return total / len(planes) if planes else 0.0


def scope_ms_per_call(prog: Program, scope: str, leave_out=()):
    """`scope_ns` in ms over the window's `run_batch` calls; None where no
    operation carries the scope or no call started in the window."""
    lo, hi = prog.window
    calls = calls_in(prog.spans, lo, hi)
    if not calls or not any(op[4] == scope for op in prog.ops):
        return None
    return scope_ns(prog.ops, scope, lo, hi, leave_out) * 1e-6 / len(calls)
