"""Share (%) of the federated fine-tuning cell's measured window in which no
operation ran on the device: 1 - (union of device-op intervals) / window,
from the trace."""
from bench.metrics.common import idle_percent


def read(ctx):
    return idle_percent(ctx, "fedlm")
