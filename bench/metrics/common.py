"""What several metric readers share."""


def driven_by(ctx, driver: str) -> bool:
    return ctx.cell.traffic["driver"] == driver


def idle_percent(ctx, driver: str):
    if not driven_by(ctx, driver) or ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * ctx.trace.idle_share


def peak_share_percent(ctx, driver: str):
    """The window's counted FLOPs over its wall time, as a share (%) of the
    chip's bf16 peak (bench/peaks.json)."""
    flops = ctx.window.counters.get("flops") if driven_by(ctx, driver) else None
    if not flops:
        return None
    return 100.0 * flops / ctx.window.seconds / ctx.peaks["bf16_flops_per_s"]
