"""The program's own spans on the device trace's clock.

``repro.obs`` opens a ``jax.profiler.TraceAnnotation`` named
``repro.<name>`` with every span, so a profiler trace holds the program's
``repro.step``, ``repro.dispatch``, ``repro.flush``, ``repro.read`` … on its
host plane, beside the program launches (``PJRT_LoadedExecutable_Execute``,
one per device program the host starts). From the planes
:func:`bench.trace_reduce.load` returns, inside the window the harness
names (``traced``), :func:`reduce_spans` keeps:

* the intervals of every host event whose name starts with the prefix;
* the launches' start times;
* the device's idle intervals (the window less the union of the ``XLA Ops``
  events, as :func:`bench.trace_reduce.reduce_planes` computes busy time).

:meth:`Spans.idle_s` is the device idle time inside the union of one span
name's intervals: the idle that the host work of that span leaves, which a
span overlapped by device work does not cause. :meth:`Spans.launches_per`
is the launches that start inside a span, per span: the programs one call
costs.
"""
from __future__ import annotations

import bisect
import dataclasses
from collections import defaultdict

from bench.trace_reduce import DEVICE_PLANE, OPS_LINE, _union

PREFIX = "repro."
LAUNCH = "PJRT_LoadedExecutable_Execute"


@dataclasses.dataclass
class Spans:
    window_s: float
    idle: list  # per device: idle intervals (ns) inside the window
    spans: dict  # span name -> [(start_ns, end_ns)] clipped to the window
    launches: list  # start (ns) of every program launch inside the window

    def idle_s(self, name: str) -> float:
        """Device idle seconds inside the union of ``name``'s intervals,
        mean over devices; 0 when the span is absent."""
        covered = _union(self.spans.get(name, ()))
        total = sum(_overlap(gaps, covered) for gaps in self.idle)
        return total * 1e-9 / max(len(self.idle), 1)

    def idle_share(self, name: str) -> float:
        """:meth:`idle_s` over the window's length."""
        return self.idle_s(name) / self.window_s

    def launches_per(self, name: str) -> float | None:
        """Program launches that start inside a ``name`` span, per span;
        None when the window holds no such span."""
        spans = self.spans.get(name)
        if not spans:
            return None
        covered = _union(spans)
        starts = [a for a, _ in covered]
        n = 0
        for t in self.launches:
            i = bisect.bisect_right(starts, t) - 1
            n += i >= 0 and t < covered[i][1]
        return n / len(spans)


def _overlap(xs, ys) -> int:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total = i = j = 0
    while i < len(xs) and j < len(ys):
        a, b = xs[i]
        c, d = ys[j]
        total += max(0, min(b, d) - max(a, c))
        if b < d:
            i += 1
        else:
            j += 1
    return total


def reduce_spans(planes, window: str = "traced",
                 prefix: str = PREFIX) -> Spans:
    """``planes`` as for :func:`bench.trace_reduce.reduce_planes`."""
    host = defaultdict(list)
    launches = []
    devices = []
    for pname, lines in planes:
        if DEVICE_PLANE.match(pname):
            devices.append([(a, b) for lname, evs in lines
                            if lname == OPS_LINE for _, a, b in evs])
        elif pname.startswith("/host:"):
            for _, evs in lines:
                for name, a, b in evs:
                    if name == window or name.startswith(prefix):
                        host[name].append((a, b))
                    elif name == LAUNCH:
                        launches.append(a)
    if not host.get(window):
        raise ValueError(f"no host span {window!r} in the trace")
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    w0 = min(a for a, _ in host[window])
    w1 = max(b for _, b in host[window])
    idle = []
    for evs in devices:
        busy = _union([(max(a, w0), min(b, w1)) for a, b in evs
                       if b > w0 and a < w1])
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        idle.append([(a, b) for a, b in zip(edges[::2], edges[1::2])
                     if b > a])
    spans = {name: [(max(a, w0), min(b, w1)) for a, b in ivs
                    if w0 <= a < w1]
             for name, ivs in host.items() if name != window}
    return Spans(window_s=(w1 - w0) * 1e-9, idle=idle,
                 spans={k: v for k, v in spans.items() if v},
                 launches=[t for t in launches if w0 <= t < w1])
