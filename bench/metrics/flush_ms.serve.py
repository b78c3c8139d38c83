"""Serve layer (``serve/pool.py``, ``serve/scheduler.py`` flush): host
milliseconds of one chunk's flushes of every tenant, median over the
window's chunks that ran with the profiler off. Moves tenant_ticks_per_s."""
import statistics


def read(ctx):
    if ctx.kind != "serve" or ctx.traced is None:
        return None
    rest = ctx.flush_s[ctx.traced.chunks:] or ctx.flush_s
    return 1e3 * statistics.median(rest)
