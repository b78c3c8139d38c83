#!/usr/bin/env python3
"""Bring-up check: the simulator's main path on a TPU, through the entry
points a user calls, with its results checked on the same chip.

    python chip_smoke.py             # one chip: engine + serving phases
    python chip_smoke.py --chips 4   # four chips: the multi-chip paths only

One chip:

* engine — Synfire4 (1,200 neurons, the paper's size) in fp16 and fp32,
  1,000 ticks (1 s of model time) through ``Engine.run`` with
  ``backend="fused"``: the Pallas megakernel must engage
  (``NetStatic.fused_kernel``), its raster and final state must equal
  ``backend="xla"`` bit for bit, fp16-vs-fp32 spike-count accuracy must
  reach the paper's 97.5%, and the spike count must sit in the Synfire4
  band. Synfire4×10 (12k neurons) with ``propagation="sparse"`` gets the
  same bitwise check; its line gives the CSR gather's chunk passes per
  tick (``csr_chunks``: each tile's source window against the whole row).
* serve — 64 Synfire4 tenants in one ``LaneScheduler``, five chunks of
  100 ticks with a flush after each; every flush must equal a solo
  ``Session`` of the same seed bit for bit.

Four chips (``--chips 4``):

* Synfire4×100 cut into 4 cores, ``lowering="mesh"`` across the chips,
  against ``lowering="sequential"`` on one chip;
* ``LaneScheduler(512, mesh=lane_mesh(4))`` against the unsharded
  scheduler, per lane.

Each phase prints one JSON line (compile and wall seconds, spike counts,
``fused_kernel``, parity verdicts — set-up facts, not benchmark numbers).
The last line is ``{"ok": true, "device": {...}}``, printed only when every
phase passed. Without a TPU, or without the repo's sources next to this
file, the script exits non-zero and prints no result. Networks are built
from seeds; nothing is read from disk but the sources.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Synfire4, 1 s of model time: a healthy wave fires this many spikes
# (the paper: 27,364 in fp16, 26,694 in fp32).
SPIKE_BAND = (20_000, 33_000)
PAPER_ACCURACY = 0.975  # paper §III-A, fp16 vs fp32 spike counts
TICKS = 1000
CHUNK, N_CHUNKS, N_TENANTS = 100, 5, 64
MESH_TICKS, MESH_LANES, LANE_CHUNKS = 200, 512, 2


def log(phase: str, **kw) -> None:
    print(json.dumps({"phase": phase, **kw}, default=str), flush=True)


def _leaf_diffs(a, b) -> dict[str, int]:
    """``{leaf path: differing elements}`` between two pytrees, compared
    bit for bit (typed PRNG keys by their key data); empty when equal."""
    import jax
    import numpy as np

    la = jax.tree_util.tree_flatten_with_path(a)[0]
    lb = jax.tree.leaves(b)
    if len(la) != len(lb):
        return {"<structure>": abs(len(la) - len(lb))}
    diffs = {}
    for (path, x), y in zip(la, lb):
        if jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key):
            x, y = jax.random.key_data(x), jax.random.key_data(y)
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape:
            diffs[jax.tree_util.keystr(path)] = -1
            continue
        xb = x.reshape(x.size, -1).view(np.uint8)
        yb = y.reshape(y.size, -1).view(np.uint8)
        n = int((xb != yb).any(axis=1).sum())
        if n:
            diffs[jax.tree_util.keystr(path)] = n
    return diffs


def _timed_run(net, ticks: int):
    """Run twice from ``state0``: the first call compiles. Returns the
    second run's (final, raster, wall_s), the compile estimate, and
    whether the two runs agreed bit for bit."""
    import jax
    import numpy as np

    from repro.core import Engine

    eng = Engine(net)
    t0 = time.perf_counter()
    f1, o1 = eng.run(ticks)
    jax.block_until_ready((f1, o1))
    t1 = time.perf_counter()
    f2, o2 = eng.run(ticks)
    jax.block_until_ready((f2, o2))
    t2 = time.perf_counter()
    r1, r2 = np.asarray(o1["spikes"]), np.asarray(o2["spikes"])
    repeat_ok = np.array_equal(r1, r2) and not _leaf_diffs(f1, f2)
    return f2, r2, t2 - t1, max(0.0, (t1 - t0) - (t2 - t1)), repeat_ok


def engine_pair(cfg, policy: str, propagation: str, ticks: int) -> int:
    """fused (megakernel engaged) vs xla on the same chip; returns the
    spike count. Raises on any failed check."""
    from repro import obs
    from repro.configs.synfire4 import build_synfire

    runs = {}
    for backend in ("fused", "xla"):
        net = build_synfire(cfg, policy=policy, backend=backend,
                            propagation=propagation, budget=None,
                            monitor_ms_hint=0)
        final, raster, wall, comp, repeat_ok = _timed_run(net, ticks)
        runs[backend] = (net, final, raster)
        fused = net.static.fused
        # the megakernel payload assembled for this run publishes them
        chunks = obs.registry().get("repro_fused_csr_chunks")
        csr_chunks = ({w: chunks.value(walk=w) for w in ("window", "row")}
                      if net.static.fused_kernel and chunks else None)
        log("engine", net=cfg.name, policy=policy, propagation=propagation,
            backend=backend, ticks=ticks, compile_s=round(comp, 3),
            wall_s=round(wall, 4), spikes=int(raster.sum()),
            fused_kernel=net.static.fused_kernel,
            kernel_reason=fused.kernel_reason if fused else None,
            csr_chunks=csr_chunks, repeat_bitwise=repeat_ok)
        if not repeat_ok:
            raise AssertionError(f"{cfg.name}/{policy}/{backend}: two runs "
                                 "from state0 differ")
    net_f = runs["fused"][0]
    if not net_f.static.fused_kernel:
        raise AssertionError(
            f"{cfg.name}/{policy}: megakernel did not engage "
            f"({net_f.static.fused.kernel_reason or 'not on a TPU'})")
    raster_eq = bool((runs["fused"][2] == runs["xla"][2]).all())
    state_diff = _leaf_diffs(runs["fused"][1], runs["xla"][1])
    log("engine_parity", net=cfg.name, policy=policy,
        propagation=propagation, raster_bitwise=raster_eq,
        state_bitwise=not state_diff, state_diff=state_diff)
    if not raster_eq or state_diff:
        raise AssertionError(f"{cfg.name}/{policy}/{propagation}: fused "
                             "diverges from xla on the chip")
    return int(runs["xla"][2].sum())


def engine_phase(cfg, cfg_x10, ticks: int, band) -> None:
    from repro.telemetry.metrics import spike_count_accuracy

    counts = {pol: engine_pair(cfg, pol, "packed", ticks)
              for pol in ("fp16", "fp32")}
    acc = spike_count_accuracy(counts["fp16"], counts["fp32"])
    in_band = all(band[0] <= c <= band[1] for c in counts.values())
    log("engine_accuracy", net=cfg.name, spikes=counts, accuracy=acc,
        paper_accuracy=PAPER_ACCURACY, band=band, in_band=in_band)
    if acc < PAPER_ACCURACY or not in_band:
        raise AssertionError(f"accuracy {acc} or counts {counts} out of "
                             "bounds")
    engine_pair(cfg_x10, "fp16", "sparse", ticks)


def serve_phase(cfg, n_tenants: int, chunk: int, n_chunks: int) -> None:
    """Tenants in one LaneScheduler vs solo Sessions of the same seeds."""
    import jax
    import numpy as np

    from repro.configs.synfire4 import build_synfire
    from repro.core import Engine
    from repro.serve import LaneScheduler, Session

    net = build_synfire(cfg, policy="fp16", backend="fused", budget=None)
    sched = LaneScheduler(net, n_tenants)
    ids = [f"tenant{i}" for i in range(n_tenants)]
    for i, sid in enumerate(ids):
        sched.admit(sid, seed=i)
    eng = Engine(net)
    solos = [Session.create(eng, seed=i) for i in range(n_tenants)]
    mismatched: set[str] = set()
    total = 0
    for c in range(n_chunks):
        t0 = time.perf_counter()
        sched.step(chunk)
        jax.block_until_ready(sched.states)
        t_sched = time.perf_counter() - t0
        got = sched.flush_all()
        t0 = time.perf_counter()
        for s in solos:
            s.run(chunk)
        jax.block_until_ready([s.state for s in solos])
        t_solo = time.perf_counter() - t0
        for sid, s in zip(ids, solos):
            want = s.flush()
            g = got[sid]
            if g.keys() != want.keys() or any(
                    np.asarray(g[k]).tobytes() != np.asarray(want[k]).tobytes()
                    for k in want):
                mismatched.add(sid)
        spikes = int(sum(np.asarray(v["spike_count"]).sum()
                         for v in got.values()))
        total += spikes
        log("serve_chunk", chunk=c, tenants=n_tenants, ticks=chunk,
            sched_wall_s=round(t_sched, 4), solo_wall_s=round(t_solo, 4),
            spikes=spikes, mismatched=len(mismatched))
    log("serve", net=cfg.name, tenants=n_tenants, chunks=n_chunks,
        fused_kernel=net.static.fused_kernel, total_spikes=total,
        bitwise_vs_solo=not mismatched)
    if mismatched or total == 0:
        raise AssertionError(f"served tenants differ from solo sessions: "
                             f"{sorted(mismatched)[:8]} (total spikes "
                             f"{total})")


def _device_sets(tree) -> list[int]:
    import jax
    return sorted({len(x.sharding.device_set) for x in jax.tree.leaves(tree)
                   if hasattr(x, "sharding")})


def partition_mesh_phase(cfg, n_cores: int, ticks: int) -> None:
    """A cut across a device mesh vs the same cut looped on one chip."""
    import numpy as np

    from repro.configs.synfire4 import build_synfire
    from repro.core.partition import PartitionSpec

    runs = {}
    for lowering in ("sequential", "mesh"):
        net = build_synfire(
            cfg, policy="fp16", propagation="sparse", monitors=None,
            monitor_ms_hint=0,
            partition=PartitionSpec(n_cores=n_cores, core_budget_bytes=None,
                                    lowering=lowering))
        final, raster, wall, comp, repeat_ok = _timed_run(net, ticks)
        runs[lowering] = (final, raster)
        log("partition", net=cfg.name, lowering=lowering,
            cores=net.partition.n_cores, ticks=ticks,
            compile_s=round(comp, 3), wall_s=round(wall, 4),
            spikes=int(raster.sum()), repeat_bitwise=repeat_ok,
            device_set_sizes=_device_sets((final, raster)))
        if not repeat_ok:
            raise AssertionError(f"partition/{lowering}: repeat differs")
    raster_eq = np.array_equal(runs["mesh"][1], runs["sequential"][1])
    state_diff = _leaf_diffs(runs["mesh"][0], runs["sequential"][0])
    log("partition_parity", net=cfg.name, cores=n_cores,
        raster_bitwise=raster_eq, state_bitwise=not state_diff,
        state_diff=state_diff)
    if not raster_eq or state_diff or runs["mesh"][1].sum() == 0:
        raise AssertionError("mesh lowering diverges from sequential")


def lane_mesh_phase(cfg, n_lanes: int, n_devices: int, chunk: int,
                    n_chunks: int) -> None:
    """The lane-sharded scheduler vs the unsharded one, lane by lane."""
    import jax
    import numpy as np

    from repro.configs.synfire4 import build_synfire
    from repro.core.distributed import lane_mesh
    from repro.serve import LaneScheduler

    net = build_synfire(cfg, policy="fp16", backend="fused", budget=None)
    scheds = {"single": LaneScheduler(net, n_lanes),
              "sharded": LaneScheduler(net, n_lanes,
                                       mesh=lane_mesh(n_devices))}
    flushes = {}
    for name, sched in scheds.items():
        for i in range(n_lanes):
            sched.admit(f"lane{i}", seed=i)
        walls = []
        for _ in range(n_chunks):
            t0 = time.perf_counter()
            sched.step(chunk)
            jax.block_until_ready(sched.states)
            walls.append(round(time.perf_counter() - t0, 4))
        flushes[name] = sched.flush_all()
        log("lanes", scheduler=name, lanes=n_lanes, chunks=n_chunks,
            ticks=chunk, chunk_wall_s=walls,
            fused_kernel=net.static.fused_kernel,
            device_set_sizes=_device_sets(sched.states))
    state_diff = _leaf_diffs(scheds["single"].states,
                             scheds["sharded"].states)
    flush_eq = all(
        np.asarray(flushes["single"][s][k]).tobytes()
        == np.asarray(flushes["sharded"][s][k]).tobytes()
        for s in flushes["single"] for k in flushes["single"][s])
    spikes = int(sum(np.asarray(v["spike_count"]).sum()
                     for v in flushes["single"].values()))
    log("lanes_parity", lanes=n_lanes, devices=n_devices,
        state_bitwise=not state_diff, state_diff=state_diff,
        flush_bitwise=flush_eq, spikes=spikes)
    if state_diff or not flush_eq or spikes == 0:
        raise AssertionError("sharded lanes diverge from the unsharded "
                             "scheduler")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: engine + serving phases; 4: only the "
                         "multi-chip paths and their one-chip references")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "this check runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s) visible", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache
    from repro.configs.synfire4 import (SYNFIRE4, SYNFIRE4_X10,
                                        scale_synfire)

    log("start", platform=devices[0].platform, kind=devices[0].device_kind,
        count=len(devices), jax=jax.__version__,
        compile_cache=enable_compile_cache())
    t0 = time.perf_counter()
    if args.chips == 1:
        engine_phase(SYNFIRE4, SYNFIRE4_X10, TICKS, SPIKE_BAND)
        serve_phase(SYNFIRE4, N_TENANTS, CHUNK, N_CHUNKS)
    else:
        partition_mesh_phase(scale_synfire(SYNFIRE4, 100), 4, MESH_TICKS)
        lane_mesh_phase(SYNFIRE4, MESH_LANES, 4, CHUNK, LANE_CHUNKS)
    log("done", wall_s=round(time.perf_counter() - t0, 3))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
