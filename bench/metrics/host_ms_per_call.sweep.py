"""Host milliseconds per sweep call inside the program: the median, over
the window's `repro:run_batch` spans (`experiments/runner.py::run_batch`),
of the span's duration less the part its `run_batch.wait` child covers
(preparing inputs, dispatching, the rest of the call's host work; the wait
for the device left out).  Read from the profiler trace (`bench/spans.py`);
None where the program has no such span."""
from bench import spans
from bench.metrics.common import driven_by


def read(ctx):
    if not driven_by(ctx, "sweep") or ctx.trace is None:
        return None
    prog = spans.program()
    return spans.self_ms_per_call(prog.spans, *prog.window)
