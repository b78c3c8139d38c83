"""CPU fixtures: a copy of ``bench/`` with a small network and short traffic,
found by name the way the benchmark finds its cells.

Run from the repository root: ``python -m pytest bench/tests``."""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# Synfire4's structure at a size the CPU runs in seconds: the fan-ins are
# cut with the groups, the weights raised so that the wave still travels.
SMALL_NETWORK = {
    "n_segments": 4, "n_exc": 40, "n_inh": 10, "n_stim": 40,
    "fanin_exc": 12, "fanin_inh": 5,
    "w_exc": 5.0, "w_inh_drive": 17.5, "w_inh": -10.0,
    "delay_ff": 10, "delay_inh": 8,
    "stim_pulse_hz": 300.0, "stim_pulse_ms": 15.0, "stim_rate_hz": 8.0,
    "connect_mode": "prob", "storage_dtype": "float16",
}
SIM = {"driver": "session_loop", "chunk_ticks": 100, "trial_chunks": 3,
       "check_streams": 4, "trace_chunks": 1}
SERVE = {"driver": "pool_loop", "tenants": 8, "rungs": [8], "chunk_ticks": 50,
         "check_streams": 8, "trace_chunks": 1}


def small_config(**build) -> dict:
    return {"name": "small", "source": "test", "system": "synfire",
            "reference": "synfire", "network": dict(SMALL_NETWORK),
            "build": {"policy": "fp16", "backend": "fused",
                      "propagation": "packed", **build},
            "reduced": [], "assumed": {}}


def write_bench(tmp: Path, configs: dict, traffic: dict, workloads: list,
                per_layer: list | None = None) -> Path:
    """A checkout holding a copy of ``bench/`` plus the given configuration
    and traffic files and a BENCHMARK.json naming ``workloads``; returns
    the copy's ``bench`` directory."""
    bench = tmp / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name, cfg in configs.items():
        (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    for name, mix in traffic.items():
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": n, "source": "test",
                        "file": f"bench/configs/{n}.json", "reduced": [],
                        "why": "test"} for n in configs]
    spec["workloads"] = workloads
    if per_layer is not None:
        spec["per_layer"] = per_layer
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return bench


def cell_entry(config: str, traffic: str) -> dict:
    return {"name": f"{config}.{traffic}", "config": config,
            "traffic": traffic, "chips": 1, "why": "test"}


@pytest.fixture
def small_bench(tmp_path):
    """A bench copy with ``small.sim`` and ``small.serve``."""
    return write_bench(tmp_path, {"small": small_config()},
                       {"sim": SIM, "serve": SERVE},
                       [cell_entry("small", "sim"),
                        cell_entry("small", "serve")])
