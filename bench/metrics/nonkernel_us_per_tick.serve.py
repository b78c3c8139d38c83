"""Engine layer (``core/engine.py`` scan, generator draw, telemetry carry),
serve cells: device-busy microseconds per tick outside the megakernel's events,
from the traced chunks. Nothing when the megakernel is not in the trace."""
from bench.kernel_names import FUSED_TICK


def read(ctx):
    if ctx.kind != "serve" or ctx.trace is None:
        return None
    kernel = ctx.trace.time_of(FUSED_TICK)
    if kernel <= 0:
        return None
    return (ctx.trace.busy_s - kernel) * 1e6 / ctx.traced.ticks
