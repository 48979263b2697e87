"""Share (%) of the chip's bf16 peak that the sweep's counted work reaches:
the SVRP rounds' FLOPs (`bench/counts.py`, refreshes counted from each
trial's comm ledger) over the window's wall time.  The convex path runs its
float32 products at HIGHEST precision, several bf16 passes each, so this is
a true but low share of the stated peak."""
from bench.metrics.common import peak_share_percent


def read(ctx):
    return peak_share_percent(ctx, "sweep")
