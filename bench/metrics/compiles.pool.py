"""Executables built inside the pool cell's window (JAX's compile and
compilation-cache-hit events).  The warm-up admits every horizon, the
longest first, so it should read 0."""
from bench.metrics.common import driven_by


def read(ctx):
    if not driven_by(ctx, "pool"):
        return None
    return float(ctx.window.counters["compiles"])
