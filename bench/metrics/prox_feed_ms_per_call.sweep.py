"""Device milliseconds per sweep call spent feeding the local prox kernel:
the operations under the program's `prox` scope (`RoundOps.prox`, the
`kernels/logistic_prox.py` wrapper) other than the
`logistic_prox_gd_batched` kernel's own events, that is the client-block
gather and the pad, clipped to the window, over the window's
`repro:run_batch` calls (`bench/spans.py`).  None where the program has no
such scope."""
from bench import spans
from bench.metrics.common import driven_by

KERNEL = "logistic_prox_gd_batched"


def read(ctx):
    if not driven_by(ctx, "sweep") or ctx.trace is None:
        return None
    return spans.scope_ms_per_call(spans.program(), "prox", leave_out=(KERNEL,))
