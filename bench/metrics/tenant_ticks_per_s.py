"""End to end, serving cells: ticks of occupied lanes completed per wall
second over the whole window (each chunk's flushes included)."""


def read(ctx):
    if ctx.kind != "serve":
        return None
    return ctx.lane_ticks / ctx.window_s
