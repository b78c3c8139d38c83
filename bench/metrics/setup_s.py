"""End to end, every cell: seconds from process start to the first measured
chunk (import and TPU bring-up, network build, admission, compile or
persistent-cache load, one warm chunk)."""


def read(ctx):
    return ctx.setup_s
