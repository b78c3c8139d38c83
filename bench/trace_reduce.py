"""Reduce a JAX profiler trace to what the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes.
The window is the host span the harness names (``traced``). On every TPU
device plane the ``XLA Ops`` line holds one event per device operation,
named by its HLO text; a loop (``while``) is an event of its own around the
events of its body. The union of their intervals inside the window is the
device's busy time. Time per op counts the ops outside loops' own events, so
nothing is counted twice. Idle gaps are labelled with the harness's own
host span (``dispatch``, ``wait``, ``flush``, ``admit``) that covers most
of the gap.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_SPANS = ("dispatch", "wait", "flush", "admit")
# HLO control flow: its event spans the events of the ops it runs.
CONTAINER = re.compile(r"[)}\]] (while|conditional|call)\(")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # mean over devices
    devices: int
    op_s: dict  # op name -> device seconds inside the window, over devices
    gaps: list  # (label, seconds), longest first

    def time_of(self, pattern: str) -> float:
        """Device seconds of the ops whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(s for name, s in self.op_s.items() if rx.search(name))

    def top_ops(self, n: int) -> list:
        """The ``n`` ops with the most device time, by short name."""
        short = defaultdict(float)
        for name, s in self.op_s.items():
            short[_short(name)] += s
        return [[k, v] for k, v in sorted(short.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int) -> list:
        return [[k, v] for k, v in self.gaps[:n]]


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns


def reduce_planes(planes, window: str = "traced") -> Summary:
    """``planes``: iterable of ``(plane name, [(line name, events)])`` where
    events are ``(name, start_ns, end_ns)`` — the shape of ``ProfileData``."""
    host = defaultdict(list)
    devices = []
    for pname, lines in planes:
        if DEVICE_PLANE.match(pname):
            devices.append([ev for lname, evs in lines if lname == OPS_LINE
                            for ev in evs])
        elif pname.startswith("/host:"):
            for _, evs in lines:
                for name, a, b in evs:
                    if name == window or name in HOST_SPANS:
                        host[name].append((a, b))
    if not host.get(window):
        raise ValueError(f"no host span {window!r} in the trace")
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    w0 = min(a for a, _ in host[window])
    w1 = max(b for _, b in host[window])
    op_s = defaultdict(float)
    busy = 0.0
    gaps = []
    for evs in devices:
        clipped = [(n, max(a, w0), min(b, w1)) for n, a, b in evs
                   if b > w0 and a < w1]
        for n, a, b in clipped:
            if not CONTAINER.search(n):
                op_s[n] += (b - a) * 1e-9
        merged = _union([(a, b) for _, a, b in clipped])
        busy += sum(b - a for a, b in merged) * 1e-9
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_label(host, a, b), (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(w1 - w0) * 1e-9, busy_s=busy / len(devices),
                   devices=len(devices), op_s=dict(op_s), gaps=gaps)


def _short(name: str) -> str:
    """``%fusion.45`` of an HLO text; a Mosaic kernel, alone or wrapped in
    a custom fusion, says so."""
    head = name.split(" = ", 1)[0]
    if 'custom_call_target="tpu_custom_call"' in name:
        return head + " (tpu_custom_call)"
    if "kind=kCustom" in name:
        return head + " (kCustom fusion)"
    return head


def _label(host, a: int, b: int) -> str:
    best, cover = "host", 0
    for name in HOST_SPANS:
        c = sum(max(0, min(b, y) - max(a, x)) for x, y in host.get(name, ()))
        if c > cover:
            best, cover = name, c
    return best


def load(path: str):
    """Planes of one ``.xplane.pb`` file in :func:`reduce_planes`' shape."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [(p.name, [(ln.name, list(_events(ln))) for ln in p.lines])
            for p in pd.planes]


def summarize(trace_dir: str, window: str = "traced") -> Summary:
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(files)}")
    return reduce_planes(load(files[0]), window)
