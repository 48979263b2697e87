"""Host milliseconds per pool tick: the harness's host-clock spans around
the pool's own calls (`evict`, `admit`, `step`; the tick's stats readback
left out) summed over the window and divided by its ticks."""
from bench.metrics.common import driven_by


def read(ctx):
    c = ctx.window.counters
    if not driven_by(ctx, "pool") or not c.get("ticks"):
        return None
    return 1e3 * c["host_s"] / c["ticks"]
