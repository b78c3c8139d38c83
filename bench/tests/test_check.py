"""The comparison, the control, and the work count on the CPU."""
from __future__ import annotations

import ast
import json

import numpy as np
import pytest

from bench import check, control, harness, work

from conftest import ROOT, small_config

SEEDS = (3, 2**31 + 17, 901)


def test_reference_imports_nothing_of_the_program():
    for path in (harness.BENCH / "references").glob("*.py"):
        tree = ast.parse(path.read_text())
        mods = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                for a in n.names}
        mods |= {n.module for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom) and n.module}
        assert not any(m == "repro" or m.startswith("repro.") for m in mods)


@pytest.mark.parametrize("name", ["small.sim", "small.serve"])
def test_program_matches_reference_and_control_does_not(small_bench, name):
    cell = harness.resolve(name, bench=small_bench)
    sound = control.readings(cell, SEEDS, 4, bench=small_bench)
    assert all(r["correct"] and r["count_mismatches"] == 0 for r in sound)
    lower = control.readings(cell, SEEDS, 4, control=True, bench=small_bench)
    assert all(not r["correct"] and r["count_mismatches"] > 0 for r in lower)


def test_sample_keeps_the_longest_and_follows_the_seed():
    streams = [(i, np.zeros((1 + (i == 7) * 5, 3), int)) for i in range(20)]
    a = check.sample(streams, 4, 11)
    assert len(a) == 4 and any(s == 7 for s, _ in a)
    assert [s for s, _ in a] == [s for s, _ in check.sample(streams, 4, 11)]
    assert len(check.sample(streams[:3], 4, 11)) == 3


def test_compare_counts_every_differing_entry():
    got = [(1, np.array([[5, 6], [7, 8]]))]
    want = np.array([[[5, 6], [7, 9], [0, 0]]])
    v = check.compare(got, want)
    assert v["numbers"]["count_mismatches"]["value"] == 1
    assert v["correct"] is False
    assert v["diagnostics"]["count_gap"] == pytest.approx(1 / 27)


def test_work_count_is_the_same_for_packed_and_sparse_builds():
    """Both layouts run the same network to the same spikes, and the count
    takes nothing else from the program."""
    from bench.systems import synfire as system
    from bench.references import synfire as reference
    from repro.core import Engine
    from repro.serve import Session

    cfg = small_config()
    ref_net = reference.build(cfg["network"], SEEDS[1])
    peak = work.peaks("TPU v5 lite")
    counts = {}
    for prop in ("packed", "sparse"):
        net = system.build(small_config(propagation=prop), SEEDS[1])
        assert net.n_synapses == ref_net.n_synapses()
        s = Session.create(Engine(net), seed=5)
        s.run(200)
        counts[prop] = np.asarray(s.flush()["spike_count"])
    assert np.array_equal(counts["packed"], counts["sparse"])
    w = {p: work.chunk_work(ref_net, c, 200, 1, 1, peak)
         for p, c in counts.items()}
    assert w["packed"] == w["sparse"]
    assert w["packed"].ops > 0 and w["packed"].bytes > 0


def test_peak_table_is_keyed_by_device_kind():
    assert work.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("cpu")
    table = json.loads((harness.BENCH / "peaks.json").read_text())
    assert "source" in table


def test_benchmark_spec_names_files_that_exist():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in spec["workloads"]:
        assert (harness.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
