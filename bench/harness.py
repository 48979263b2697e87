"""Run one cell of `BENCHMARK.json` once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (`bench/configs/<config>.json`, whose `family`
names `bench/families/<family>.py`) and a traffic mix
(`bench/traffic/<traffic>.json`, whose `driver` names
`bench/drivers/<driver>.py`); its correctness limits are in
`bench/limits/<cell>.json`, and each per-layer metric is read by
`bench/metrics/<metric>.py`.  Everything is found by name, so a later cell,
configuration or metric is new files and new entries only.

A run: find the chip (there is no CPU fallback), build the cell and warm up
every shape it uses (`setup_s`), measure for `--seconds` with tracing off or,
with `--trace 1`, under the profiler, read the peak device memory, free the
program's state, compare what the window produced with the plain reference,
and print one JSON line last on stdout.  The numbers compared go last on
stderr too, each beside its limit.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / "bench_out" / "trace"


class NoChip(SystemExit):
    pass


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit (pass: <=)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Window:
    """What a driver's measured window did."""

    start: float  # host clock, seconds
    end: float
    attempted: int
    failed: int
    end_to_end: dict  # metric name -> value
    counters: dict = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool

    @property
    def family(self):
        return importlib.import_module(f"bench.families.{self.config['family']}")

    @property
    def driver(self):
        return importlib.import_module(f"bench.drivers.{self.traffic['driver']}")


class Recorder:
    """Host spans and counters of one run.  A span is timed on the host
    clock and, in a traced run, written into the trace as `bench:<name>`."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.in_window = False
        self.span_s: dict = {}  # name -> seconds, window only
        self.compiles = 0  # executables built inside the window

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        ann = jax.profiler.TraceAnnotation(f"bench:{name}") if self.traced else contextlib.nullcontext()
        t0 = time.perf_counter()
        with ann:
            yield
        if self.in_window:
            self.span_s[name] = self.span_s.get(name, 0.0) + time.perf_counter() - t0

    def _on_duration(self, event: str, *_args, **_kw) -> None:
        if self.in_window and event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        if self.in_window and event == "/jax/compilation_cache/cache_hits":
            self.compiles += 1

    @contextlib.contextmanager
    def listening(self):
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        try:
            yield
        finally:
            monitoring.unregister_event_duration_listener(self._on_duration)
            monitoring.unregister_event_listener(self._on_event)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_from_files(name: str, seed: int, seconds: float, trace: bool, bench: dict | None = None) -> Cell:
    """The cell `name` of `BENCHMARK.json`."""
    bench = bench or benchmark()
    w = {c["name"]: c for c in bench["workloads"]}.get(name)
    if w is None:
        raise SystemExit(f"unknown workload {name!r}; known: {[c['name'] for c in bench['workloads']]}")
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return make_cell(name, ROOT / conf["file"], w["traffic"], w["chips"], seed, seconds, trace)


def make_cell(name: str, config_file: Path, traffic: str, chips: int, seed: int,
              seconds: float, trace: bool) -> Cell:
    return Cell(name=name, chips=chips, config=load_json(config_file),
                traffic=load_json(BENCH / "traffic" / f"{traffic}.json"),
                limits=load_json(BENCH / "limits" / f"{name}.json"),
                seed=seed, seconds=seconds, trace=trace)


def reports(metric: dict, cell: str, end_to_end: list) -> bool:
    """Whether `cell` reports `metric`: a listed cell, or for a per-layer
    metric without a list, a cell that reports what it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        moved = {m["name"]: m for m in end_to_end}[metric["moves"]]
        return reports(moved, cell, end_to_end)
    return True


def require_chip(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: no TPU found (JAX's first device is {devs[0].platform!r}); "
                     "this benchmark has no CPU fallback")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips]


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: `JAX_COMPILATION_CACHE_DIR` where
    set, else `.jax_cache` at the checkout's root, a fixed path.  Every
    program is kept, however short its compile, so a second run compiles
    nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def run_cell(cell: Cell, *, t0: float, devices, bench: dict | None = None) -> dict:
    """Set up, measure, check; returns the result line as a dict."""
    import jax

    bench = bench or benchmark()
    rec = Recorder(traced=cell.trace)
    driver = cell.driver
    state = driver.setup(cell, rec)
    setup_s = time.perf_counter() - t0

    if cell.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    rec.in_window = True
    try:
        with rec.listening(), rec.span("window"):
            window = driver.window(state, cell, rec)
    finally:
        rec.in_window = False
        if cell.trace:
            jax.profiler.stop_trace()
    memory_peak = device_memory_peak(devices)

    e2e = bench["end_to_end"]
    metrics, breakdown = {}, None
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    if not cell.trace:
        values = dict(window.end_to_end, setup_s=setup_s)
        for m in e2e:
            if reports(m, cell.name, e2e):
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        from bench import trace as tr

        summary = tr.reduce(tr.load(TRACE_DIR))
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = {"device_ops": summary.device_ops, "idle_gaps": summary.idle_gaps}
        ctx = SimpleNamespace(cell=cell, state=state, window=window, trace=summary,
                              peaks=peaks(devices[0].device_kind))
        for m in bench["per_layer"]:
            if reports(m, cell.name, e2e):
                v = metric_reader(m["name"]).read(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    driver.release(state)
    checks = driver.check(state, cell)
    line = {"correct": bool(checks) and all(c.ok for c in checks),
            "attempted": window.attempted, "failed": window.failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    return line


def metric_reader(name: str):
    """`bench/metrics/<name>.py`, loaded by path (a name may hold dots)."""
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def main(argv=None, *, t0: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    bench = benchmark()
    cell = cell_from_files(args.workload, args.seed, args.seconds, bool(args.trace), bench)
    try:
        devices = require_chip(cell.chips)
    except NoChip as e:
        print(e, file=sys.stderr)
        return 2
    use_compile_cache()
    sys.path.insert(0, str(ROOT / "src"))
    line = run_cell(cell, t0=t0, devices=devices, bench=bench)
    for name, c in line["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0
