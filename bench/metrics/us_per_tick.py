"""End to end, simulation cells: wall microseconds per simulated 1 ms tick
over the whole window, every chunk ending in a blocking flush. Real time is
1000 us/tick; 1000 over this is the real-time factor."""


def read(ctx):
    if ctx.kind != "sim":
        return None
    return ctx.window_s * 1e6 / ctx.ticks
