"""Roofline share (%) of the batched logistic prox kernel
(`kernels/logistic_prox.py::logistic_prox_gd_batched`): for each launch in
the trace the least time the chip could take, max(FLOPs / bf16 peak, bytes
/ HBM bandwidth), summed and divided by the kernel's summed event time.
FLOPs: steps x 4 n d per trial; bytes: each trial's (n, d) block read once.
At the a9a shapes the bytes bound it."""
from bench import counts
from bench.metrics.common import driven_by

KERNEL = "logistic_prox_gd_batched"


def read(ctx):
    if not driven_by(ctx, "sweep") or ctx.trace is None or KERNEL not in ctx.trace.kernel_s:
        return None
    seconds, launches = ctx.trace.kernel_s[KERNEL]
    M, n, d = ctx.state.data.Z.shape
    flops, nbytes = counts.logistic_prox_kernel(ctx.window.counters["trials"], n, d,
                                                ctx.cell.traffic["prox_steps"])
    least = max(flops / ctx.peaks["bf16_flops_per_s"], nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * launches * least / seconds
