"""Serve layer, serve cells: wall milliseconds of the slowest chunk (one
``pool.step`` to the end of its last flush) among the window's chunks that
ran with the profiler off: the worst wait a tenant saw. A tenant keeps real
time while this stays within the chunk's model time (1 ms per tick)."""


def read(ctx):
    if ctx.kind != "serve" or ctx.traced is None:
        return None
    rest = ctx.chunk_s[ctx.traced.chunks:] or ctx.chunk_s
    return 1e3 * max(rest)
