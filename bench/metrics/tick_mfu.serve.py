"""Whole tick, serve cells: the least time of the traced ticks' work
(``bench/work.py``, at the chip's peaks) over the traced window's wall
time, in percent — the whole step's share of the chip."""


def read(ctx):
    if ctx.kind != "serve" or ctx.trace is None:
        return None
    return 100.0 * ctx.work.least_s / ctx.trace.window_s
