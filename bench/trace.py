"""From a profiler trace to busy time, idle share, kernel time and gaps.

A run with `--trace 1` records the measured window with the JAX profiler.
`load` reads the `.xplane.pb` it writes into two plain lists:

* device operations: every event on the "XLA Ops" line of each
  `/device:TPU:<n>` plane, as `(name, start_ns, duration_ns, plane)`.  An
  event's name is the HLO instruction's text (`%logistic_prox_gd_batched.8
  = f32[...] custom-call(...)`); `op_name` keeps the instruction's name;
* host spans: the harness's own `TraceAnnotation`s, named `bench:<what>`,
  on the host planes, as `(what, start_ns, duration_ns)`.

Both lie on the trace's one clock.  Everything else here works on those lists,
so the test suite checks it on events made by hand.

Control-flow instructions (`while`, `conditional`, `call`) span the
operations they run, so they are left out of busy time and of the top
operations: a device is busy while an operation runs on it.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

CONTROL_FLOW = ("while", "conditional", "call")
HOST_PREFIX = "bench:"
WINDOW_SPAN = "window"
_NAME = re.compile(r"^%?([^\s=]+)")
_NUMBERED = re.compile(r"^(.*?)(\.\d+)?$")


def op_name(event_name: str) -> str:
    """`%fusion.128 = f32[...] fusion(...)` -> `fusion.128`."""
    m = _NAME.match(event_name.strip())
    return m.group(1) if m else event_name


def op_kind(name: str) -> str:
    """`fusion.128` -> `fusion`; `logistic_prox_gd_batched.8` ->
    `logistic_prox_gd_batched`."""
    return _NUMBERED.match(name).group(1)


@dataclasses.dataclass
class Trace:
    ops: list  # [(name, start_ns, dur_ns, plane)]
    spans: list  # [(what, start_ns, dur_ns)]


def load(trace_dir: Path) -> Trace:
    """Read the newest `.xplane.pb` under `trace_dir`."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    ops, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((op_name(e.name), e.start_ns, e.duration_ns, plane.name)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name[len(HOST_PREFIX):], e.start_ns, e.duration_ns)
                             for e in line.events if e.name.startswith(HOST_PREFIX))
    return Trace(ops, spans)


def _merge(intervals):
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # averaged over the device planes
    kernel_s: dict  # op kind -> (seconds, launches), control flow left out
    device_ops: list  # [[name, seconds]] the ten longest-running ops
    idle_gaps: list  # [[host span, seconds]] the ten longest gaps

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def window_bounds(trace: Trace) -> tuple[float, float]:
    """The measured window: the harness's `window` span."""
    w = [(s, s + d) for name, s, d in trace.spans if name == WINDOW_SPAN]
    if len(w) != 1:
        raise ValueError(f"expected one '{WINDOW_SPAN}' span, found {len(w)}")
    return w[0]


def reduce(trace: Trace, top: int = 10) -> Summary:
    lo, hi = window_bounds(trace)
    planes = sorted({p for *_, p in trace.ops})
    busy = 0.0
    kernel: dict = {}
    per_op: dict = {}
    merged_by_plane = {}
    for plane in planes:
        ivs = []
        for name, s, d, p in trace.ops:
            if p != plane or op_kind(name) in CONTROL_FLOW:
                continue
            s, e = _clip(s, s + d, lo, hi)
            if e <= s:
                continue
            ivs.append((s, e))
            kind = op_kind(name)
            t, n = kernel.get(kind, (0.0, 0))
            kernel[kind] = (t + (e - s) * 1e-9, n + 1)
            per_op[name] = per_op.get(name, 0.0) + (e - s) * 1e-9
        merged = _merge(ivs)
        merged_by_plane[plane] = merged
        busy += sum(e - s for s, e in merged) * 1e-9
    busy /= max(len(planes), 1)
    device_ops = sorted(([n, t] for n, t in per_op.items()), key=lambda x: -x[1])[:top]
    gaps = []
    for merged in merged_by_plane.values():
        edges = [lo] + [v for iv in merged for v in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[label_gap(trace.spans, s, e), (e - s) * 1e-9] for s, e in gaps[:top]]
    return Summary((hi - lo) * 1e-9, busy, kernel, device_ops, idle)


def label_gap(spans, s, e) -> str:
    """The innermost harness span that covers the gap's middle."""
    mid = 0.5 * (s + e)
    best = None
    for name, st, d in spans:
        if name != WINDOW_SPAN and st <= mid <= st + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "outside any span"
