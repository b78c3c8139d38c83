"""The program's spans against device idle time, on a made-up trace and on
the one recorded on the chip; and the reduction the per-layer metrics read
today, pinned on that recording."""
from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import spans, trace_reduce, work
from bench.harness import load_module
from bench.references import synfire as reference

BENCH = Path(__file__).resolve().parents[1]
FIXTURE = BENCH / "tests" / "fixtures" / "synfire4_sim"
MS = 1_000_000  # ns


def _planes():
    launch = spans.LAUNCH
    host = [("python", [("traced", 10 * MS, 30 * MS),
                        ("repro.step", 10 * MS, 12 * MS),
                        ("repro.dispatch", 11 * MS, 12 * MS),
                        ("flush", 20 * MS, 30 * MS),
                        ("repro.flush", 20 * MS, 25 * MS),
                        ("repro.flush", 25 * MS, 30 * MS),
                        ("repro.read", 24 * MS, 25 * MS),
                        ("repro.early", 5 * MS, 15 * MS)]),
            ("main", [(launch, 11 * MS, 11 * MS + 10),
                      (launch, 21 * MS, 21 * MS + 10),
                      (launch, 22 * MS, 22 * MS + 10),
                      (launch, 26 * MS, 26 * MS + 10),
                      (launch, 35 * MS, 35 * MS + 10)])]
    dev = [("XLA Ops", [("%while.2 = (s32[]) while((s32[]) %t)", 5 * MS, 21 * MS),
                        ("%copy.4 = f32[8] copy(f32[8] %c)", 22 * MS, 23 * MS),
                        ("late", 35 * MS, 36 * MS)]),
           ("XLA Modules", [("jit_run", 0, 40 * MS)])]
    return [("/host:CPU", host), ("/device:TPU:0", dev)]


def test_spans_on_a_made_up_trace():
    s = spans.reduce_spans(_planes())
    assert s.window_s == pytest.approx(0.020)
    # Busy [10, 21] and [22, 23]: idle [21, 22] and [23, 30].
    assert s.idle_s("repro.flush") == pytest.approx(0.008)
    assert s.idle_share("repro.flush") == pytest.approx(0.4)
    assert s.idle_s("repro.step") == 0.0
    assert s.idle_s("repro.read") == pytest.approx(0.001)
    assert s.idle_s("repro.absent") == 0.0
    # The window's launches at 21, 22 and 26 ms fall in the two flushes.
    assert s.launches_per("repro.flush") == pytest.approx(1.5)
    assert s.launches_per("repro.step") == 1.0
    assert s.launches_per("repro.read") == 0.0
    assert s.launches_per("repro.absent") is None
    # A span that starts before the window, and the harness's own "flush",
    # are not the program's spans in the window.
    assert set(s.spans) == {"repro.step", "repro.dispatch", "repro.flush",
                            "repro.read"}


def test_idle_in_disjoint_spans_never_passes_device_idle():
    s = spans.reduce_spans(_planes())
    device_idle = s.window_s - trace_reduce.reduce_planes(_planes()).busy_s
    total = s.idle_s("repro.flush") + s.idle_s("repro.step")
    assert total <= device_idle + 1e-12


def test_reduce_spans_needs_the_window_and_a_device():
    with pytest.raises(ValueError, match="no host span"):
        spans.reduce_spans(_planes(), window="missing")
    with pytest.raises(ValueError, match="no TPU"):
        spans.reduce_spans(_planes()[:1])


@pytest.fixture(scope="module")
def chip_planes():
    return trace_reduce.load(str(FIXTURE.with_suffix(".xplane.pb")))


def test_launches_match_the_device_modules_on_the_chip_fixture(chip_planes):
    """The fixture predates the program's spans, so the harness's own
    ``flush`` spans stand in: each holds the 58 eager programs of one
    ``flush_carry``, launched on the host and run as ``XLA Modules``."""
    s = spans.reduce_spans(chip_planes, prefix="flush")
    assert s.launches_per("flush") == 58.0
    modules = [a for p, lines in chip_planes if p == "/device:TPU:0"
               for line, evs in lines if line == "XLA Modules"
               for _, a, _ in evs]
    flushes = s.spans["flush"]
    assert all(sum(a <= t < b for t in modules) == 58 for a, b in flushes)
    summary = trace_reduce.reduce_planes(chip_planes)
    assert 0 < s.idle_s("flush") <= summary.window_s - summary.busy_s
    assert spans.reduce_spans(chip_planes).spans == {}


def test_existing_reduction_is_unchanged_on_the_chip_fixture(chip_planes):
    """What the per-layer metrics read from the recorded trace, to the
    last digit, as the benchmark first read it."""
    s = trace_reduce.reduce_planes(chip_planes)
    assert s.window_s == pytest.approx(0.052776288000000005, rel=1e-12)
    assert s.busy_s == pytest.approx(0.0023951740000000003, rel=1e-12)
    assert len(s.op_s) == 169
    assert sum(s.op_s.values()) == pytest.approx(0.002234106, rel=1e-9)
    assert s.top_ops(3) == [
        ["%closed_call.8 (tpu_custom_call)",
         pytest.approx(0.0017721360000000016, rel=1e-12)],
        ["%fusion.45", pytest.approx(0.00015595500000000026, rel=1e-12)],
        ["%reduce_sum.7", pytest.approx(1.9759000000000002e-05, rel=1e-12)]]
    assert len(s.gaps) == 367
    assert s.top_gaps(3) == [
        ["flush", pytest.approx(0.002969699, rel=1e-12)],
        ["flush", pytest.approx(0.0028783230000000003, rel=1e-12)],
        ["flush", pytest.approx(0.002875905, rel=1e-12)]]

    meta = json.loads(FIXTURE.with_suffix(".json").read_text())
    cfg = json.loads((BENCH / "configs" / f"{meta['config']}.json")
                     .read_text())
    ticks = meta["chunk_ticks"] * meta["chunks"]
    ctx = SimpleNamespace(
        kind="sim", trace=s,
        traced=SimpleNamespace(chunks=meta["chunks"], ticks=ticks),
        work=work.chunk_work(reference.build(cfg["network"], meta["seed"]),
                             meta["spikes"], ticks * meta["lanes"],
                             meta["chunks"], meta["lanes"],
                             work.peaks(meta["device_kind"])))
    expected = {
        "device_idle.sim": 95.46164747319857,
        "fused_tick_roofline.sim": 0.044917821431587825,
        "nonkernel_us_per_tick.sim": 1.557594999999997,
        "tick_mfu.sim": 0.001508262354496936,
        "chunk_max_ms.serve": None, "device_idle.serve": None,
        "flush_ms.serve": None, "fused_tick_roofline.serve": None,
        "nonkernel_us_per_tick.serve": None, "tick_mfu.serve": None,
    }
    read = {name: load_module(BENCH / "metrics" / f"{name}.py").read(ctx)
            for name in expected}
    assert read == {k: v if v is None else pytest.approx(v, rel=1e-12)
                    for k, v in expected.items()}
