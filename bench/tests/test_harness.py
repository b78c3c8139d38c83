"""The harness end to end on the CPU: cells found by name, results, and the
check coming out false under each fault a cell can have."""
from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from bench import harness

from conftest import ROOT, SIM, cell_entry, small_config, write_bench

SEED = 2**31 + 5  # above 32 signed bits, as the driver's seeds are


def run(bench, name, seconds=0.3, trace=False):
    cell = harness.resolve(name, bench=bench)
    return harness.run_cell(cell, SEED, seconds, trace, time.perf_counter(),
                            bench=bench)


@pytest.mark.parametrize("name, metric", [("small.sim", "us_per_tick"),
                                          ("small.serve", "tenant_ticks_per_s")])
def test_cell_runs_correct(small_bench, name, metric):
    r = run(small_bench, name)
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["metrics"][metric]["value"] > 0
    assert r["metrics"]["setup_s"]["value"] > 0
    assert list(r)[-1] == "checks"
    assert r["checks"]["count_mismatches"] == {"value": 0, "limit": 0}
    assert r["device"]["platform"] == "cpu"


@pytest.mark.parametrize("name", ["small.sim", "small.serve"])
def test_traced_run_reports_per_layer_metrics(small_bench, monkeypatch, name):
    """The trace itself is only readable on a TPU; here the reduction is
    stood in for by a fixed summary, and everything after it runs."""
    from bench import trace_reduce

    fake = trace_reduce.Summary(
        window_s=0.5, busy_s=0.4, devices=1,
        op_s={'%closed_call.1 = f32[8] custom-call(f32[8] %a), '
              'custom_call_target="tpu_custom_call"': 0.3,
              "%fusion.2 = f32[8] fusion(f32[8] %b)": 0.1},
        gaps=[("flush", 0.08), ("dispatch", 0.02)])
    monkeypatch.setattr(trace_reduce, "summarize", lambda *a, **k: fake)
    monkeypatch.setattr(harness.work, "peaks", lambda kind: {
        "flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    r = run(small_bench, name, trace=True)
    assert r["correct"] is True
    assert r["device"]["busy_s"] == 0.4 and r["device"]["window_s"] == 0.5
    assert r["breakdown"]["device_ops"][0] == [
        "%closed_call.1 (tpu_custom_call)", 0.3]
    assert r["breakdown"]["idle_gaps"][0] == ["flush", 0.08]
    kind = name.split(".")[1]
    m = {k.split(".")[0]: v["value"] for k, v in r["metrics"].items()}
    assert m["device_idle"] == pytest.approx(20.0)
    assert 0 < m["tick_mfu"] < m["fused_tick_roofline"] <= 100
    assert m["nonkernel_us_per_tick"] > 0
    assert ("flush_ms" in m) == (kind == "serve")
    assert ("chunk_max_ms" in m) == (kind == "serve")
    assert "us_per_tick" not in m and "setup_s" not in m


def _digests(d):
    return {p.relative_to(d): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(d.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_config_mix_and_metric_found_by_name(tmp_path):
    """A configuration, a traffic mix and a metric that only exist as new
    files run without an edit to any file the benchmark already has."""
    bench = write_bench(tmp_path, {}, {}, [])
    before = _digests(bench)
    cfg = small_config()
    cfg["name"] = "throwaway"
    (bench / "configs" / "throwaway.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "quick.json").write_text(
        json.dumps({**SIM, "chunk_ticks": 50, "trial_chunks": 2}))
    (bench / "metrics" / "chunks_run.py").write_text(
        "def read(ctx):\n    return len(ctx.chunk_s)\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "throwaway", "source": "test",
                        "file": "bench/configs/throwaway.json",
                        "reduced": [], "why": "test"}]
    spec["workloads"] = [cell_entry("throwaway", "quick")]
    spec["end_to_end"].append({"name": "chunks_run", "unit": "chunks",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    r = run(bench, "throwaway.quick")
    assert r["correct"] is True
    assert r["metrics"]["chunks_run"]["value"] == r["attempted"]
    after = _digests(bench)
    assert {k: v for k, v in after.items() if k in before} == before


def test_committed_cells_resolve():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.resolve(w["name"])
        assert cell.per_layer and cell.end_to_end
        for m in cell.end_to_end + cell.per_layer:
            assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()


# -- faults: each must turn `correct` false --------------------------------

def _state_unchanged(monkeypatch):
    import repro.core.engine as engine
    import repro.serve.scheduler as scheduler

    orig = engine._run_impl

    def broken(static, params, state, n_steps, **kw):
        _, out = orig(static, params, state, n_steps, **kw)
        return state, out

    monkeypatch.setattr(engine, "_run_impl", broken)
    monkeypatch.setattr(scheduler, "_run_impl", broken)


def _half_batch(monkeypatch):
    from repro.serve.scheduler import LaneScheduler

    orig = LaneScheduler._step_impl

    def broken(self, n_ticks):
        old = (self.states, self._tel)
        orig(self, n_ticks)
        half = self.capacity // 2
        keep = lambda new, prev: new.at[half:].set(prev[half:])  # noqa: E731
        self.states = jax.tree.map(keep, self.states, old[0])
        self._tel = jax.tree.map(keep, self._tel, old[1])

    monkeypatch.setattr(LaneScheduler, "_step_impl", broken)


def _answer_altered(monkeypatch):
    from repro.telemetry import monitors

    orig = monitors.flush_carry
    calls = []

    def broken(static, carry):
        values, new = orig(static, carry)
        calls.append(None)
        if len(calls) == 3:  # one answer, once
            values["spike_count"] = np.asarray(values["spike_count"]).copy()
            values["spike_count"][1] += 1
        return values, new

    monkeypatch.setattr(monitors, "flush_carry", broken)


@pytest.mark.parametrize("name, fault", [
    ("small.sim", _state_unchanged),
    ("small.serve", _state_unchanged),
    ("small.serve", _half_batch),
    ("small.sim", _answer_altered),
    ("small.serve", _answer_altered),
], ids=lambda x: getattr(x, "__name__", x))
def test_fault_turns_check_false(small_bench, monkeypatch, name, fault):
    jax.clear_caches()
    fault(monkeypatch)
    try:
        r = run(small_bench, name)
    finally:
        jax.clear_caches()
    assert r["correct"] is False
    assert r["checks"]["count_mismatches"]["value"] > 0


def test_run_without_a_tpu_exits_nonzero_and_prints_nothing():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "synfire4.sim",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout == ""
    assert "TPU" in p.stderr
