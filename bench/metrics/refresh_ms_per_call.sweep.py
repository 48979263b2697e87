"""Device milliseconds per sweep call of the anchor refresh
(`core/rounds.py::RoundOps.refresh_grad`): the union of the device
operations under the program's `refresh` scope, clipped to the window,
over the window's `repro:run_batch` calls (`bench/spans.py`).  None where
the program has no such scope."""
from bench import spans
from bench.metrics.common import driven_by


def read(ctx):
    if not driven_by(ctx, "sweep") or ctx.trace is None:
        return None
    return spans.scope_ms_per_call(spans.program(), "refresh")
