"""The trace reduction, on a made-up trace and on one recorded on the chip."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import trace_reduce, work
from bench.kernel_names import FUSED_TICK
from bench.references import synfire as reference

FIXTURES = Path(__file__).with_name("fixtures")
MS = 1_000_000  # ns


def _planes():
    host = [("python", [("traced", 10 * MS, 30 * MS),
                        ("dispatch", 10 * MS, 11 * MS),
                        ("flush", 20 * MS, 30 * MS),
                        ("outside", 0, 40 * MS)])]
    kernel = '%closed_call.3 = f32[8] custom-call(f32[8] %a), custom_call_target="tpu_custom_call"'
    dev = [("XLA Ops", [("%while.2 = (s32[]) while((s32[]) %t)", 5 * MS, 21 * MS),
                        (kernel, 5 * MS, 12 * MS),
                        ("%fusion.1 = f32[8] fusion(f32[8] %b)", 11 * MS, 15 * MS),
                        (kernel, 16 * MS, 20 * MS),
                        ("%copy.4 = f32[8] copy(f32[8] %c)", 22 * MS, 23 * MS),
                        ("late", 35 * MS, 36 * MS)]),
           ("XLA Modules", [("jit_run", 0, 40 * MS)])]
    return [("/host:CPU", host), ("/device:TPU:0", dev),
            ("/device:TPU:0 SparseCore", [("XLA Ops", [("x", 0, 40 * MS)])])]


def test_reduce_made_up_trace():
    s = trace_reduce.reduce_planes(_planes())
    assert s.window_s == pytest.approx(0.020)
    # Busy inside [10, 30] ms: the loop's [10, 21] and the copy's [22, 23].
    assert s.busy_s == pytest.approx(0.012)
    assert s.time_of(FUSED_TICK) == pytest.approx(0.006)
    # The loop's own event counts toward busy time, not toward any op.
    assert sum(s.op_s.values()) == pytest.approx(0.006 + 0.004 + 0.001)
    assert "late" not in s.op_s and "jit_run" not in s.op_s
    assert s.top_ops(2) == [["%closed_call.3 (tpu_custom_call)",
                             pytest.approx(0.006)],
                            ["%fusion.1", pytest.approx(0.004)]]
    # Gaps: [21, 22] and [23, 30] fall under "flush".
    assert s.gaps == [("flush", pytest.approx(0.007)),
                      ("flush", pytest.approx(0.001))]
    assert sum(g for _, g in s.gaps) == pytest.approx(s.window_s - s.busy_s)


def test_vmapped_kernel_is_found_as_a_custom_fusion():
    """Under vmap the megakernel's op is a kCustom fusion (a serving trace)."""
    host = [("python", [("traced", 0, 10 * MS)])]
    ops = [("%closed_call.12 = (f32[64,11,1792]) fusion(f32[64,11,1792] %a), "
            "kind=kCustom, calls=%fused_computation.1", 1 * MS, 4 * MS),
           ("%dynamic-slice_bitcast_fusion.14 = f32[8,384,384] fusion("
            "f32[64,8,384,384] %w), kind=kLoop", 4 * MS, 6 * MS)]
    s = trace_reduce.reduce_planes([("/host:CPU", host),
                                    ("/device:TPU:0", [("XLA Ops", ops)])])
    assert s.time_of(FUSED_TICK) == pytest.approx(0.003)
    assert s.top_ops(1) == [["%closed_call.12 (kCustom fusion)",
                             pytest.approx(0.003)]]


def test_reduce_needs_the_window_and_a_device():
    with pytest.raises(ValueError, match="no host span"):
        trace_reduce.reduce_planes(_planes(), window="missing")
    with pytest.raises(ValueError, match="no TPU"):
        trace_reduce.reduce_planes(_planes()[:1])


def test_chip_fixture_reduces_and_rooflines_stay_under_100():
    """A trace of two 200-tick chunks of the synfire4.sim driver, recorded on
    a TPU v5 lite, with the spikes those chunks reported."""
    meta = json.loads((FIXTURES / "synfire4_sim.json").read_text())
    s = trace_reduce.reduce_planes(
        trace_reduce.load(str(FIXTURES / "synfire4_sim.xplane.pb")))
    assert 0 < s.busy_s <= s.window_s
    kernel = s.time_of(FUSED_TICK)
    assert 0 < kernel <= s.busy_s
    cfg = json.loads((Path(__file__).parents[1] / "configs" /
                      f"{meta['config']}.json").read_text())
    net = reference.build(cfg["network"], meta["seed"])
    ticks = meta["chunk_ticks"] * meta["chunks"]
    w = work.chunk_work(net, meta["spikes"], ticks * meta["lanes"],
                        meta["chunks"], meta["lanes"],
                        work.peaks(meta["device_kind"]))
    roofline = 100 * w.least_s / kernel
    mfu = 100 * w.least_s / s.window_s
    assert 0 < mfu <= roofline <= 100
