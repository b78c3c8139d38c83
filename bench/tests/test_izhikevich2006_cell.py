"""The polychronization cell on the CPU: the plasticity floor counted by
hand, the cell found by name, and a small copy of it run end to end — the
check holding at the configured precision and failing under the control's.
"""
from __future__ import annotations

import copy
import json
import time

import pytest

from bench import control, harness, work, work_plastic

from conftest import ROOT, cell_entry, write_bench

SEED = 2**31 + 7
# The published structure at a size the CPU runs in seconds: 500 neurons,
# fan-out 50 over delays 1..10 (5 per delay), one kick per ms.
SMALL = {"n_exc": 400, "n_inh": 100, "fanout": 50, "max_delay": 10,
         "w_exc": 6.0, "w_inh": -5.0, "kicks_per_tick": 1, "kick_amp": 20.0,
         "storage_dtype": "float16"}
# 500-tick chunks, so a 4-chunk trial holds two weight updates.
SIM = {"driver": "session_loop", "chunk_ticks": 500, "trial_chunks": 4,
       "check_streams": 3, "trace_chunks": 1}


def test_plastic_floor_by_hand():
    """The published 1,000-neuron network: per tick 800 LTP and 1,000 LTD
    traces at 2 operations and 8 bytes each, and 80,000 plastic synapses
    updated once in 1,000 ticks at 5 operations and 2+2+2+2 bytes."""
    network = {"n_exc": 800, "n_inh": 200, "fanout": 100,
               "storage_dtype": "float16"}
    ops, nbytes = work_plastic.per_tick(network)
    assert ops == 2 * 1800 + 5 * 80_000 / 1000 == 4000
    assert nbytes == 8 * 1800 + 8 * 80_000 / 1000 == 15_040
    base = work.Work(ops=1e6, bytes=2e6, least_s=0.0, bound="memory")
    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    total = work_plastic.with_plasticity(base, network, 10, peak)
    assert total.ops == 1e6 + 40_000 and total.bytes == 2e6 + 150_400
    assert total.least_s == pytest.approx(total.bytes / 1e9)


def test_cell_found_by_name():
    cell = harness.resolve("izhikevich2006_x10.sim")
    assert cell.config["name"] == "izhikevich2006_x10" and cell.chips == 1
    bench = ROOT / "bench"
    assert (bench / "systems" / f"{cell.config['system']}.py").is_file()
    assert (bench / "references" / f"{cell.config['reference']}.py").is_file()
    assert {m["name"] for m in cell.end_to_end} == {"us_per_tick", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {"device_idle.sim",
                                                   "plastic_tick_mfu.sim"}
    for m in cell.end_to_end + cell.per_layer:
        assert (bench / "metrics" / f"{m['name']}.py").is_file()
    assert cell.config["network"] == {**cell.config["network"],
                                      **work_plastic.network_of(
                                          "izhikevich2006_x10")}


@pytest.fixture
def small_bench(tmp_path):
    config = json.loads((ROOT / "bench" / "configs" /
                         "izhikevich2006_x10.json").read_text())
    config["network"] = dict(SMALL)
    return write_bench(tmp_path, {"small_izh": config}, {"sim": SIM},
                       [cell_entry("small_izh", "sim")],
                       per_layer=[m for m in json.loads(
                           (ROOT / "BENCHMARK.json").read_text())["per_layer"]
                           if m["name"] in ("device_idle.sim",
                                            "plastic_tick_mfu.sim")])


def test_small_cell_runs_correct(small_bench, monkeypatch):
    from bench import trace_reduce

    fake = trace_reduce.Summary(window_s=0.5, busy_s=0.4, devices=1,
                                op_s={"%gather.1 = f32[8] gather()": 0.4},
                                gaps=[("flush", 0.1)])
    monkeypatch.setattr(trace_reduce, "summarize", lambda *a, **k: fake)
    monkeypatch.setattr(harness.work, "peaks", lambda kind: {
        "flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    cell = harness.resolve("small_izh.sim", bench=small_bench)
    r = harness.run_cell(cell, SEED, 0.3, True, time.perf_counter(),
                         bench=small_bench)
    assert r["correct"] is True, r["checks"]
    assert r["checks"]["count_mismatches"] == {"value": 0, "limit": 0}
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert m["device_idle.sim"] == pytest.approx(20.0)
    assert 0 < m["plastic_tick_mfu.sim"] < 100


def test_control_reads_mismatches(small_bench):
    """Computed at the configured fp16 the program agrees with the
    reference; under the control's bf16 it does not."""
    cell = harness.resolve("small_izh.sim", bench=small_bench)
    sound, = control.readings(cell, [SEED], 4, bench=small_bench)
    low, = control.readings(cell, [SEED], 4, control=True, bench=small_bench)
    assert sound["count_mismatches"] == 0 and sound["reference_spikes"] > 0
    assert low["count_mismatches"] > 0 and low["policy"] == "bf16"
