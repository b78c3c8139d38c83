"""Benchmark driver — one function per paper table.

Prints ``name,us_per_call,derived`` CSV rows (the harness contract), then a
human-readable dump of each table. Roofline rows are appended when dry-run
artifacts exist under results/dryrun.

``--smoke`` shrinks the engine sweep (fewer ticks, one rep) so CI can run
the full driver end-to-end in a couple of minutes — it exercises every
code path (all propagation modes, the ×10 sparse build, the JSON merge)
without producing publication-grade timings.

Every invocation also exports the run's observability record under
``results/``: ``obs_trace.jsonl`` + ``obs_trace.chrome.json`` (load the
latter in Perfetto / chrome://tracing), ``obs_metrics.prom`` (Prometheus
text snapshot of the runtime and bench metrics), ``obs_health.json``
(the SLO verdict vs the paper's M33 real-time and 8.477 MB budgets),
``obs_alerts.jsonl`` (the run's watch-trip / quarantine / flight-record /
replay events), and ``flight_manifest.json`` (every quarantine dump's
manifest, aggregated). The alert artifacts are exercised end-to-end by a
deliberate NaN-poisoned two-lane fleet each run — detection, quarantine,
evidence dump, and bit-exact replay all leave a record in CI.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks import paper_tables  # noqa: E402


def _run(name, fn):
    t0 = time.time()
    rows, derived = fn()
    us = (time.time() - t0) * 1e6
    print(f"{name},{us:.0f},{json.dumps(derived, default=str)}")
    return rows, derived


def main(argv: list[str] | None = None) -> None:
    from benchmarks.bench_engine import bench_engine
    from benchmarks.bench_partition import bench_partition
    from benchmarks.bench_serve import (
        bench_obs,
        bench_pool,
        bench_serve,
        bench_watch,
    )
    from benchmarks.report import paper_report
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="quick CI pass: tiny tick counts, one rep")
    args = ap.parse_args(argv)

    if args.smoke:
        def engine_fn():
            # don't merge throwaway smoke timings into BENCH_engine.json;
            # DO enforce the <10% in-scan monitor overhead budget (2-3%
            # true cost + the single-core executable-layout lottery), the
            # sparse-plastic ≤ dense-plastic tick gate, the plastic ×10
            # sparse build fitting the 8.477 MB MCU budget, and the fused
            # backend not regressing the packed b=1 tick
            return bench_engine(n_ticks=60, reps=1, x10_ticks=30,
                                plastic_ticks=20, write_json=False,
                                check_overhead=True, check_plastic=True,
                                check_fused=True)

        def report_fn():
            # full 1 s accuracy window (the headline number), shortened
            # mini horizon; keep smoke numbers out of BENCH_engine.json
            return paper_report(mini_ticks=3000, write_json=False)

        def serve_fn():
            # tiny chunks, one rep — but ALWAYS the seed-determinism gate:
            # a same-seed tenant fleet must reproduce its flushed counts
            # bit-for-bit (the serve cells' merge-key contract)
            return bench_serve(chunk_ticks=40, n_chunks=2, reps=1,
                               write_json=False, check_determinism=True)

        def pool_fn():
            # elastic-pool smoke: rungs capped at 64 lanes, one rep, but
            # ALWAYS both gates — bitwise seed determinism + migration
            # preservation, and ladder throughput no worse than the raw
            # PR 5 single-scheduler fleet at the same N
            return bench_pool(chunk_ticks=40, n_chunks=1, reps=1,
                              write_json=False, check_determinism=True,
                              check_regression=True, max_tenants=64)

        def obs_fn():
            # obs-overhead gate: instrumentation must cost < 2% µs/tick on
            # the 64-lane fleet (same executable both arms — no layout
            # lottery, so the tight budget is safe), retry-after-cool-down
            # like every other timing gate
            return bench_obs(chunk_ticks=50, reps=3, write_json=False,
                             check_gate=True)

        def watch_fn():
            # watchpoint-overhead gate: the in-scan watch reductions must
            # cost < 5% µs/tick on the warm 64-lane fleet (distinct
            # executables per arm — the monitors' budget, not obs's 2%),
            # retry-after-cool-down like every other timing gate
            return bench_watch(chunk_ticks=50, reps=3, write_json=False,
                               check_gate=True)

        def partition_fn():
            # core-grid smoke: Synfire4 in 2 sequential cores must stay
            # within 1.15x of the unpartitioned µs/tick (with bitwise
            # raster parity asserted unconditionally); the ×100 cell is
            # full-run-only — its 30 s CSR build has no place in smoke
            return bench_partition(n_ticks=60, reps=1, write_json=False,
                                   check_gate=True, include_x100=False)
    else:
        engine_fn = bench_engine
        report_fn = paper_report
        serve_fn = bench_serve
        pool_fn = bench_pool
        obs_fn = bench_obs
        watch_fn = bench_watch
        partition_fn = bench_partition

    results = {}
    for name, fn in [
        ("table3_memory_rampup", paper_tables.table3_memory_rampup),
        ("table4_memory_rampup_mini", paper_tables.table4_memory_rampup_mini),
        ("accuracy_fp16_vs_fp32", paper_tables.accuracy_fp16_vs_fp32),
        ("memory_fp16_halving", paper_tables.memory_fp16_halving),
        ("table5_performance", paper_tables.table5_performance),
        ("bench_engine", engine_fn),  # writes/merges BENCH_engine.json
        ("bench_serve", serve_fn),  # serve_* cells, same JSON merge
        ("bench_pool", pool_fn),  # elastic-pool cells (rungs, latencies)
        ("bench_obs", obs_fn),  # obs on/off overhead (<2% gate in smoke)
        ("bench_watch", watch_fn),  # watch on/off overhead (<5% in smoke)
        ("watch_alert_drill", _watch_alert_drill),  # poisoned-lane e2e
        ("bench_partition", partition_fn),  # core-grid cells + 1.15x gate
        ("paper_report", report_fn),  # accuracy / real-time / energy metrics
    ]:
        results[name] = _run(name, fn)

    # roofline: runs only over dry-run artifacts; without them it is skipped
    dryrun_dir = os.path.join("results", "dryrun")
    if glob.glob(os.path.join(dryrun_dir, "*.json")):
        from benchmarks import roofline
        rows = roofline.build_table(dryrun_dir)
        n_ok = sum(1 for r in rows if r.get("dominant") != "SKIPPED")
        print(f"roofline_table,0,{json.dumps({'cells': n_ok})}")
        results["roofline"] = rows
    else:
        skipped = {"skipped": f"no dry-run artifacts in {dryrun_dir}"}
        print(f"roofline_table,0,{json.dumps(skipped)}")

    print("\n=== detail ===")
    for name, payload in results.items():
        print(f"\n--- {name} ---")
        rows = payload[0] if isinstance(payload, tuple) else payload
        for r in rows:
            print(" ", r)

    os.makedirs("results", exist_ok=True)
    with open("results/benchmarks.json", "w") as f:
        json.dump({k: (v[0] if isinstance(v, tuple) else v)
                   for k, v in results.items()}, f, indent=1, default=str)

    _export_obs("results")


def _watch_alert_drill() -> tuple[list[dict], dict]:
    """End-to-end fire drill for the alert pipeline, every driver run:
    poison one lane of a watch-enabled fp16 fleet with a NaN, assert the
    ``nonfinite`` watch trips within one chunk, quarantine the tenant
    with its flight-recorder window, dump the evidence under
    ``results/quarantine`` (count-capped rotation), and replay the
    recorded window bit-exactly. The trip/quarantine/replay events land
    on the tracer, so ``results/obs_alerts.jsonl`` always carries a real
    alert trail and the flight manifest a real dump."""
    import jax
    import numpy as np

    from repro import serve
    from repro.configs.synfire4 import SYNFIRE4_MINI, build_synfire
    from repro.serve.scheduler import _write_lane

    net = build_synfire(SYNFIRE4_MINI, policy="fp16", watches="default")
    sched = serve.LaneScheduler(net, 2, flight_window=2)
    sched.admit("victim", seed=0)
    sched.admit("bystander", seed=1)
    for _ in range(2):
        sched.step(40)
    lane = sched.lane_of("victim")
    st = jax.tree.map(lambda x: x[lane], sched.states)
    # neuron 40 is mid-chain — generator-group state is overwritten by
    # the stimulus every tick, so a NaN there would just vanish
    v = st.neurons.v.at[40].set(st.neurons.v.dtype.type(float("nan")))
    sched.states = _write_lane(
        sched.states, lane, st._replace(neurons=st.neurons._replace(v=v)))
    sched.step(40)
    alerts = sched.check_watches()
    assert "victim" in alerts, "poisoned lane must trip within one chunk"
    q = sched.quarantine("victim", alerts["victim"])
    ddir = serve.dump_quarantine(os.path.join("results", "quarantine"), q,
                                 keep_last=4)
    # Post-mortem: the flight ring holds the last healthy snapshot
    # (captured at the chunk boundary BEFORE the poison landed) and the
    # corrupted one after. Re-inject the same fault into the healthy
    # snapshot and replay the chunk — the corruption must reproduce
    # bit-for-bit, because that is what makes the recording evidence.
    ring = q.recording
    st0 = ring[0].state
    v0 = st0.neurons.v.at[40].set(st0.neurons.v.dtype.type(float("nan")))
    snap0 = ring[0]._replace(
        state=st0._replace(neurons=st0.neurons._replace(v=v0)))
    session, _ = serve.replay(net, snap0,
                              ring[-1].ticks - ring[0].ticks)
    for a, b in zip(jax.tree.leaves(session.state),
                    jax.tree.leaves(ring[-1].state)):
        if jax.dtypes.issubdtype(a.dtype, jax.dtypes.prng_key):
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), \
            "flight-recorder replay must be bit-exact"
    survivors = sched.session_ids
    sched.close()
    row = {
        "tripped": [v.watch for v in q.verdicts],
        "flight_snapshots": len(ring),
        "dump_dir": ddir,
        "survivors": survivors,
        "replay_bit_exact": True,
    }
    return [row], {"watch_alerts": len(q.verdicts),
                   "replay_bit_exact": True}


def _export_obs(out_dir: str) -> None:
    """Dump the driver run's observability record as CI artifacts: the
    trace (JSONL + Perfetto-loadable Chrome JSON), the Prometheus text
    snapshot of every metric the benches and the runtime emitted, the
    health verdict against the paper's budgets, the run's alert trail
    (watch trips, quarantines, flight records, replays), and the
    aggregated manifests of every quarantine evidence dump."""
    import dataclasses

    from repro import obs

    obs.tracer().to_jsonl(os.path.join(out_dir, "obs_trace.jsonl"))
    obs.tracer().to_chrome(os.path.join(out_dir, "obs_trace.chrome.json"))
    with open(os.path.join(out_dir, "obs_metrics.prom"), "w") as f:
        f.write(obs.registry().to_prometheus())
    with open(os.path.join(out_dir, "obs_health.json"), "w") as f:
        json.dump(obs.health.health_snapshot(), f, indent=1)

    alert_kinds = {"watch_trip", "quarantine", "flight_record", "replay"}
    with open(os.path.join(out_dir, "obs_alerts.jsonl"), "w") as f:
        for e in obs.tracer().snapshot():
            if e.name in alert_kinds:
                f.write(json.dumps(dataclasses.asdict(e), default=str)
                        + "\n")

    manifests = []
    qdir = os.path.join(out_dir, "quarantine")
    if os.path.isdir(qdir):
        for name in sorted(os.listdir(qdir)):
            mpath = os.path.join(qdir, name, "manifest.json")
            if os.path.isfile(mpath):
                with open(mpath) as f:
                    manifests.append({"dump": name, **json.load(f)})
    with open(os.path.join(out_dir, "flight_manifest.json"), "w") as f:
        json.dump({"dumps": manifests}, f, indent=1)


if __name__ == "__main__":
    main()
