"""Kernel layer (``kernels/fused_tick.py``), serve cells: the least time of the
traced ticks' work (``bench/work.py``, at the chip's peaks) over the
megakernel's device time, in percent. Nothing when the kernel is absent."""
from bench.kernel_names import FUSED_TICK


def read(ctx):
    if ctx.kind != "serve" or ctx.trace is None:
        return None
    kernel = ctx.trace.time_of(FUSED_TICK)
    if kernel <= 0:
        return None
    return 100.0 * ctx.work.least_s / kernel
