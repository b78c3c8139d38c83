"""Whole tick, plastic sim cells: the least time of the traced ticks' work
(``bench/work.py``'s plus the plasticity floor of ``bench/work_plastic.py``,
at the chip's peaks) over the traced window's wall time, in percent. The
sizes are those of ``configs/izhikevich2006_x10.json``, the configuration
of the one cell that lists this metric."""
from bench import work, work_plastic

CONFIG = "izhikevich2006_x10"


def read(ctx):
    if ctx.kind != "sim" or ctx.trace is None:
        return None
    import jax

    peak = work.peaks(jax.devices()[0].device_kind)
    total = work_plastic.with_plasticity(
        ctx.work, work_plastic.network_of(CONFIG), ctx.traced.ticks, peak)
    return 100.0 * total.least_s / ctx.trace.window_s
