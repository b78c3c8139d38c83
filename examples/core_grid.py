"""Core grid: one network cut across a fleet of MCU-sized cores.

The paper runs 186 neurons on ONE Cortex-M33 inside 8.477 MB. The
compile-time partitioner turns that per-device ceiling into a scaling
axis: ``compile(partition=PartitionSpec(...))`` cuts the neuron index
space into contiguous cores, each with its own CSR slice, delay ring and
verified memory ledger, stitched together by a spike-exchange plan. Both
lowerings are bitwise identical to the unpartitioned engine.

This demo scales Synfire4 ×100 — 120,000 neurons / ~9M synapses, ~35×
too big for one MCU budget — and:

1. partitions it under the paper's 8.477 MB per-core ceiling
   (sequential lowering: one device program loops the cores),
2. runs it and reads the exchange-volume counters the run published,
3. prints the per-core ``obs.health`` verdicts,
4. re-runs a 4-core cut of the base Synfire4 on a 4-virtual-device mesh
   (``shard_map`` + ``all_gather``) and checks it against the
   single-program run, bit for bit.

  PYTHONPATH=src python examples/core_grid.py
"""
import os
# Four virtual devices for the mesh demo on a CPU host. The flag touches
# only the host CPU platform: on an accelerator host jax.devices() lists
# the real chips and the demo runs on those.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import time

import jax
import numpy as np

from repro import obs
from repro.configs.synfire4 import SYNFIRE4, build_synfire, scale_synfire
from repro.core.engine import Engine
from repro.core.partition import PartitionSpec
from repro.memory.ledger import MCU_BUDGET_BYTES
from repro.obs.health import health_snapshot

T = 200


def fleet_demo() -> None:
    """Synfire4 ×100 under per-core MCU budgets, sequential lowering."""
    cfg = scale_synfire(SYNFIRE4, 100)
    print(f"== Synfire4 x100: partitioning under "
          f"{MCU_BUDGET_BYTES / 2**20:.3f} MB/core ==")
    t0 = time.time()
    net = build_synfire(cfg, policy="fp16", propagation="sparse",
                        monitors=None, monitor_ms_hint=0,
                        partition=PartitionSpec())  # default: MCU budget
    plan = net.partition
    print(f"built+partitioned in {time.time() - t0:.1f}s: "
          f"{net.n_neurons} neurons / {net.n_synapses} synapses "
          f"-> {plan.n_cores} cores")
    for c in plan.cores:
        print(f"  core{c.index}: neurons [{c.lo:6d}, {c.hi:6d})  "
              f"{c.bytes_total / 2**20:5.2f} MB "
              f"({c.bytes_total / MCU_BUDGET_BYTES * 100:4.1f}% of budget)  "
              f"imports {c.n_ext - (c.hi - c.lo)} spike flags/tick")
    ex = plan.exchange
    print(f"exchange plan: {len(ex.edges)} core->core edges, "
          f"{ex.bytes_per_tick} bytes/tick")

    t0 = time.time()
    state, out = Engine(net).run(T)
    spikes = np.asarray(out["spikes"])
    print(f"run({T}) in {time.time() - t0:.1f}s wall: "
          f"{int(spikes.sum())} spikes, "
          f"mean rate {spikes.sum() / net.n_neurons / (T / 1000):.1f} Hz")

    # the run published its exchange volume — the trace agrees w/ the plan
    snap = obs.registry().snapshot()
    for name in ("repro_partition_ticks_total",
                 "repro_partition_exchange_bytes_total"):
        for series in snap.get(name, {}).get("series", []):
            print(f"  {name}{series.get('labels', {})} = "
                  f"{series['value']:.0f}")

    h = health_snapshot(net)
    cores = [c for c in h["checks"] if c["name"].startswith("core_bytes")]
    print(f"obs.health: {len(cores)} per-core verdicts")
    for c in cores:
        print(f"  {c['name']:>18}: {c['status']:4}  {c['detail']}")
    assert all(c["status"] == "pass" for c in cores)


def mesh_demo() -> None:
    """The same cut on a device mesh: shard_map + one all_gather/tick."""
    n = min(4, len(jax.devices()))
    if n < 2:
        print(f"\n(mesh demo skipped: one {jax.devices()[0].platform} "
              "device; the mesh lowering needs one device per core)")
        return
    print(f"\n== Synfire4 on a {n}-device core mesh (shard_map lowering) ==")
    seq = build_synfire(SYNFIRE4, policy="fp32", propagation="sparse",
                        partition=PartitionSpec(n_cores=n))
    _, o_seq = Engine(seq).run(T)
    mesh = build_synfire(SYNFIRE4, policy="fp32", propagation="sparse",
                         partition=PartitionSpec(n_cores=n,
                                                 lowering="mesh"))
    _, o_mesh = Engine(mesh).run(T)
    same = np.array_equal(np.asarray(o_seq["spikes"]),
                          np.asarray(o_mesh["spikes"]))
    print(f"cores: {[(c.lo, c.hi) for c in mesh.partition.cores]}")
    print(f"mesh raster == sequential raster: {same}")
    assert same


if __name__ == "__main__":
    fleet_demo()
    mesh_demo()
