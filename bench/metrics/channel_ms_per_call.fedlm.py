"""Device milliseconds per federated fine-tuning call of the wire
(`core/rounds.py::RoundOps.chan_down`, `chan_up`, `chan_bcast`: the quant8
codec over whole parameter vectors, memory-bound): the union of the device
operations under the program's `channel` scope, clipped to the window, over
the window's `repro:run_batch` calls (`bench/spans.py`).  None where the
program has no such scope."""
from bench import spans
from bench.metrics.common import driven_by


def read(ctx):
    if not driven_by(ctx, "fedlm") or ctx.trace is None:
        return None
    return spans.scope_ms_per_call(spans.program(), "channel")
