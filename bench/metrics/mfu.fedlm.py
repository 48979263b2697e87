"""Share (%) of the chip's bf16 peak that the federated rounds' model work
reaches: forward and backward FLOPs that the DeepSVRP round definition
requires (`bench/counts.py`, recomputation not counted, refreshes counted
from each call's comm ledger) over the window's wall time."""
from bench.metrics.common import peak_share_percent


def read(ctx):
    return peak_share_percent(ctx, "fedlm")
