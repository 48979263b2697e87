"""Generic roofline machinery: HLO parsing, loop-aware collective stats,
per-backend peaks, and the `Roofline` record.

This module is the substrate-agnostic half of the perf-accounting layer
(docs/PERFORMANCE.md).  It is consumed by BOTH users of the roofline model:

* the transformer dry-run path (`repro.launch.roofline`, which keeps the
  model-specific analytic cost formulas and re-exports everything here for
  backward compatibility), and
* the federated engine's analytic FLOPs model (`repro.core.flops`) plus the
  bench harness (`benchmarks.sweep_bench` emits achieved GFLOP/s and MFU for
  every timed section against `get_peak()`).

The HLO half exists because of one measured caveat (documented where it was
found, in the launch/roofline docstring): `compiled.cost_analysis()` counts
while-loop *bodies once*, ignoring trip count.  `parse_computations` /
`computation_multipliers` / `collective_stats` reconstruct loop-aware totals
by parsing the optimized HLO text — building the call graph
(while/cond/body/calls/to_apply/branch_computations), inferring each while's
trip count from the s32 constant in its condition computation, and weighting
by products of enclosing trip counts.  `tests/test_flops.py` unit-tests the
parser on handwritten HLO snippets; `tests/test_roofline.py` holds it against
real jitted programs.

The peak half is the gpu-recipes `MAX_TFLOPS` idiom grown one step: datasheet
peaks per accelerator `device_kind`, and a MEASURED peak for CPU (`calibrated_cpu_peak`:
time a dense matmul on this host, cache the result) — so the CPU MFU numbers
the bench gate holds are fractions of what this machine demonstrably does,
not of a made-up constant (docs/PERFORMANCE.md#per-backend-peaks).
"""
from __future__ import annotations

import dataclasses
import re
import time
from collections import defaultdict

# TPU v5e, per chip (the dry-run brief's constants — kept as module-level
# names because the launch-path `Roofline` terms are defined against them).
PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # B/s / chip
ICI_BW = 50e9  # B/s / link / chip

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->")
_REF_RE = re.compile(r"(body|condition|to_apply|calls)=%?([\w\.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_CONST_RE = re.compile(r"[su](?:32|64)\[\]\s+constant\((\d+)\)")
_COLL_LINE = re.compile(
    r"=\s*(\(?[^=]*?)\s+(" + "|".join(_COLL_OPS) + r")\("
)


def _shape_bytes_of(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims.strip():
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_computations(txt: str):
    """-> (blocks: name -> [lines], entry_name)."""
    blocks: dict[str, list[str]] = {}
    entry = None
    current = None
    for raw in txt.splitlines():
        line = raw.rstrip()
        stripped = line.strip()
        if current is None:
            m = _COMP_HDR.match(stripped)
            if m and stripped.endswith("{"):
                current = m.group(2)
                blocks[current] = []
                if m.group(1):
                    entry = current
            continue
        if stripped == "}":
            current = None
            continue
        blocks[current].append(stripped)
    return blocks, entry


def _while_trip(cond_lines: list[str]) -> int:
    """Trip count of a while whose condition is `i < N`: the N appears as an
    s32 constant inside the condition computation.  Heuristic: max constant."""
    consts = [int(m.group(1)) for line in cond_lines for m in _CONST_RE.finditer(line)]
    return max(consts) if consts else 1


def computation_multipliers(txt: str) -> dict[str, float]:
    """How many times each computation executes per program invocation."""
    blocks, entry = parse_computations(txt)
    mult: dict[str, float] = defaultdict(float)

    def visit(name: str, m: float, depth=0):
        if name not in blocks or depth > 50:
            return
        mult[name] += m
        for line in blocks[name]:
            # whiles: body/cond scaled by the trip count
            if " while(" in line:
                refs = dict((k, v) for k, v in _REF_RE.findall(line))
                cond = refs.get("condition")
                body = refs.get("body")
                trip = _while_trip(blocks.get(cond, [])) if cond else 1
                if body:
                    visit(body, m * trip, depth + 1)
                if cond:
                    visit(cond, m * (trip + 1), depth + 1)
                continue
            for kind, ref in _REF_RE.findall(line):
                if kind in ("calls", "to_apply"):
                    visit(ref, m, depth + 1)
            bm = _BRANCH_RE.search(line)
            if bm:
                for b in bm.group(1).split(","):
                    visit(b.strip().lstrip("%"), m, depth + 1)

    if entry is None:
        return {}
    visit(entry, 1.0)
    return dict(mult)


# Per-device wire-traffic weight per output byte, ring algorithms:
#   all-reduce = reduce-scatter + all-gather over the full buffer ~ 2x
#   all-gather / reduce-scatter / all-to-all / permute ~ 1x
_OP_TRAFFIC_WEIGHT = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def collective_stats(txt: str):
    """(wire bytes_per_device by op kind, counts by op kind), loop-weighted."""
    blocks, entry = parse_computations(txt)
    mults = computation_multipliers(txt)
    bytes_by: dict[str, float] = defaultdict(float)
    counts: dict[str, float] = defaultdict(float)
    for name, lines in blocks.items():
        m = mults.get(name, 0.0)
        if m == 0.0:
            continue
        for line in lines:
            cm = _COLL_LINE.search(line)
            if not cm:
                continue
            out_shapes, op = cm.groups()
            bytes_by[op] += m * _shape_bytes_of(out_shapes) * _OP_TRAFFIC_WEIGHT[op]
            counts[op] += m
    return dict(bytes_by), dict(counts)


# --------------------------------------------------------------- peak table
@dataclasses.dataclass(frozen=True)
class BackendPeak:
    """One backend's roofline ceiling: peak FLOP/s (and bandwidths when the
    datasheet gives them — None means 'not modeled for this backend')."""

    flops: float  # peak FLOP/s per chip
    hbm_bw: float | None  # B/s per chip
    ici_bw: float | None  # B/s per link per chip
    source: str  # "datasheet" or the calibration recipe used


# Datasheet peaks, keyed by `jax.Device.device_kind` — a generation, not a
# platform, so a chip missing from the table raises instead of borrowing
# another generation's numbers.  CPU has NO row on purpose: `get_peak` on a
# CPU device measures this host instead (`calibrated_cpu_peak`).
PEAKS: dict[str, BackendPeak] = {
    "TPU v5 lite": BackendPeak(
        PEAK_FLOPS, HBM_BW, ICI_BW,
        'Google Cloud "TPU v5e" datasheet: 197 TFLOP/s bf16, 819 GB/s HBM',
    ),
}

_CPU_PEAK_CACHE: dict[str, BackendPeak] = {}


def calibrated_cpu_peak(dtype: str = "float32", n: int = 512, reps: int = 5) -> BackendPeak:
    """Measured CPU peak FLOP/s: best-of-`reps` dense (n, n) matmul.

    There is no honest datasheet number for 'the CI runner': thread count,
    SIMD width and turbo state all vary.  So the CPU peak is CALIBRATED — a
    jitted n x n @ n x n matmul (2 n^3 flops) timed on THIS host, cached per
    dtype.  An MFU gated against it is a same-host fraction: the host's
    absolute speed appears in numerator and denominator and largely cancels,
    which is what makes the bench gate's absolute roofline floor portable
    across runner generations (docs/PERFORMANCE.md#per-backend-peaks).
    `min` over reps, per the bench methodology (docs/BENCHMARKS.md).
    """
    key = f"{dtype}:{n}"
    if key not in _CPU_PEAK_CACHE:
        import jax
        import jax.numpy as jnp

        a = jnp.ones((n, n), dtype=jnp.dtype(dtype))
        f = jax.jit(lambda x: x @ x)
        jax.block_until_ready(f(a))  # compile outside the timed region
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f(a))
            best = min(best, time.perf_counter() - t0)
        _CPU_PEAK_CACHE[key] = BackendPeak(
            2.0 * n**3 / best, None, None,
            f"calibrated ({n}x{n} {dtype} matmul, best of {reps})",
        )
    return _CPU_PEAK_CACHE[key]


def get_peak(device_kind: str | None = None, dtype: str = "float32") -> BackendPeak:
    """The roofline ceiling for `device_kind` (default: `jax.devices()[0]`'s).

    Accelerators come from the datasheet table `PEAKS` and an unknown kind
    raises; a CPU device (kind ``"cpu"``) is measured on first use
    (`calibrated_cpu_peak`) and cached for the process.
    """
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    if device_kind in PEAKS:
        return PEAKS[device_kind]
    if device_kind == "cpu":
        return calibrated_cpu_peak(dtype=dtype)
    raise ValueError(
        f"no peak entry for device kind {device_kind!r}: add it, with its "
        "source, to repro.utils.roofline.PEAKS "
        "(docs/PERFORMANCE.md#per-backend-peaks)"
    )


def mfu(achieved_flops_per_s: float, device_kind: str | None = None,
        dtype: str = "float32") -> float:
    """Model FLOPs utilization: achieved FLOP/s over the device's peak."""
    return achieved_flops_per_s / get_peak(device_kind, dtype=dtype).flops


# --------------------------------------------------------------------------
@dataclasses.dataclass
class Roofline:
    flops: float  # analytic, all devices
    hbm_bytes: float
    coll_bytes_per_device: float
    chips: int
    coll_breakdown: dict
    coll_counts: dict
    xla_flops_flat: float  # raw cost_analysis (loop-unaware), per device
    xla_bytes_flat: float
    detail: dict

    @property
    def compute_s(self) -> float:
        return self.flops / (self.chips * PEAK_FLOPS)

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / (self.chips * HBM_BW)

    @property
    def collective_s(self) -> float:
        return self.coll_bytes_per_device / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    def as_dict(self) -> dict:
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "coll_bytes_per_device": self.coll_bytes_per_device,
            "chips": self.chips,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "coll_breakdown": self.coll_breakdown,
            "coll_counts": self.coll_counts,
            "xla_flops_flat": self.xla_flops_flat,
            "xla_bytes_flat": self.xla_bytes_flat,
            "detail": {k: float(v) for k, v in self.detail.items() if isinstance(v, (int, float))},
        }


def xla_flops(fn, *args) -> float:
    """Raw (loop-UNAWARE) `cost_analysis` flops of `jit(fn)(*args)`.

    While-loop bodies are counted once regardless of trip count — the caveat
    the parser half of this module exists to correct.  `tests/test_flops.py`
    uses this to validate the engine's analytic per-round model: compile a
    single loop-free round body, and for looped solvers reconstruct the
    loop-aware total from two compilations at different static trip counts.
    """
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    return float(compiled.cost_analysis().get("flops", 0.0))
