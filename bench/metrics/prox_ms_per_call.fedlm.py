"""Device milliseconds per federated fine-tuning call of the clients' local
steps (`core/rounds.py::RoundOps.local_prox_gd`, the Algorithm-7 loop of
`_deep_svrp_round`: each step a forward and backward pass of every client):
the union of the device operations under the program's `prox` scope,
clipped to the window, over the window's `repro:run_batch` calls
(`bench/spans.py`).  None where the program has no such scope."""
from bench import spans
from bench.metrics.common import driven_by


def read(ctx):
    if not driven_by(ctx, "fedlm") or ctx.trace is None:
        return None
    return spans.scope_ms_per_call(spans.program(), "prox")
