"""Device milliseconds per federated fine-tuning call of the anchor's full
gradient over the clients (`core/rounds.py::RoundOps.init_full_grad` at the
call's start and `refresh_grad` on a refresh round): the union of the device
operations under the program's `refresh` scope, clipped to the window, over
the window's `repro:run_batch` calls (`bench/spans.py`).  None where the
program has no such scope."""
from bench import spans
from bench.metrics.common import driven_by


def read(ctx):
    if not driven_by(ctx, "fedlm") or ctx.trace is None:
        return None
    return spans.scope_ms_per_call(spans.program(), "refresh")
