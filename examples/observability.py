"""Observability: trace, metrics, and health for a serving pool under load.

What the operator of a `repro.serve` deployment actually sees — the
``repro.obs`` plane riding a 3-tenant pool through an up-rung migration:

1. Admit three tenants into a ``ServePool`` with rungs (2, 8). The third
   admission overflows rung 2, so the ladder migrates the whole fleet up
   mid-admission — ``rung_migrate`` span, ``export``/``restore`` per
   lane, rung-bytes gauges re-pointed, all recorded as it happens.
2. Serve chunks, flushing every tenant after each — the serving loop.
   Each ``pool.step`` is a ``step`` span (the host's work) around one
   ``dispatch`` (the jit call, which returns before the device is done);
   the first flush after it closes the ``chunk`` span once the chunk's
   outputs are ready, which feeds the ``repro_serve_chunk_latency_ms`` /
   ``repro_serve_us_per_tick`` histograms. Every ``flush`` is one compiled
   drain and one ``read``, its device-to-host copy
   (``repro_flush_host_reads_total``);
   compilations are filed under the span that ran them.
3. Dump the observability record: a JSONL trace, a Chrome trace you can
   open at https://ui.perfetto.dev, the Prometheus text snapshot, the
   health verdict against the paper's budgets (real-time factor on
   the Cortex-M33 spec, per-rung bytes vs the 8.477 MB MCU ceiling,
   completed-chunk µs/tick vs the 1 ms tick), and one chunk under the
   JAX profiler: the same spans, named ``repro.<span>``, on the profiler's
   host plane beside the program launches and the device's operations.
4. **Incident drill**: one tenant's fp16 membrane state is deliberately
   poisoned with a NaN. The network was compiled with
   ``watches="default"``, so the in-scan ``nonfinite`` watch counts the
   bad values inside the scan (O(1) memory, zero numeric footprint) and
   ``check_watches()`` trips within one chunk; the tenant is
   **quarantined** — evicted with its final snapshot, the tripped
   verdicts, and the flight recorder's last chunk-boundary snapshots —
   its evidence dumped to disk under a count-capped retention policy,
   and the recorded window **replayed bit-exactly** as a solo session
   for the post-mortem. Survivors never notice (asserted bitwise in
   ``tests/test_watch.py``).

Observability is default-on and host-side only — device programs and
results are bitwise identical with it off (``tests/test_obs.py``).

  PYTHONPATH=src python examples/observability.py
"""
import glob
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses

import jax
import numpy as np

from repro import obs, serve
from repro.configs.synfire4 import SYNFIRE4_MINI, build_synfire
from repro.serve import ServePool
from repro.serve.scheduler import _write_lane

# Sustained stimulus keeps the tenants firing: the default `silent`
# watch would (correctly!) trip on the mini config at rest, which is a
# different demo than the NaN incident below.
DRIVEN = dataclasses.replace(SYNFIRE4_MINI, stim_rate_hz=60.0)

CHUNK = 100  # ticks per serving chunk (= 100 ms of model time)
OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "results")


def main() -> None:
    obs.configure(reset=True, enabled=True)  # start a clean flight record

    net = build_synfire(DRIVEN, policy="fp16", watches="default")
    pool = ServePool(rungs=(2, 8), flight_window=4)

    # Two tenants fit rung 2; the third admission forces the up-rung
    # migration (export 2 lanes -> build rung 8 -> restore 2 lanes) before
    # taking its seat. Watch it happen in the trace.
    for i in range(3):
        fp = pool.admit(net, f"tenant{i}", seed=i)
        lad = pool.ladder_of(f"tenant{i}")
        print(f"admit tenant{i}: fingerprint {fp[:8]}, rung {lad.rung}, "
              f"migrations so far {lad.migrations}")

    # Enough chunks that the one-off compile chunk falls outside the p95
    # of the measured-serve health check (merged across all chunks —
    # including the first, compiling one).
    for _ in range(24):
        pool.step(CHUNK)
        flushed = {sid: pool.flush(sid) for sid in pool.session_ids}
    for sid, f in flushed.items():
        print(f"last flush {sid}: {int(f['spike_count'].sum())} spikes "
              f"over {f['n_ticks']} ticks")

    # -- the operator's view ------------------------------------------------
    os.makedirs(OUT_DIR, exist_ok=True)
    trace_jsonl = os.path.join(OUT_DIR, "observability_trace.jsonl")
    trace_chrome = os.path.join(OUT_DIR, "observability_trace.chrome.json")
    prom_path = os.path.join(OUT_DIR, "observability_metrics.prom")

    obs.tracer().to_jsonl(trace_jsonl)
    obs.tracer().to_chrome(trace_chrome)
    with open(prom_path, "w") as f:
        f.write(obs.registry().to_prometheus())

    reg = obs.registry()
    lat = reg.histogram("repro_serve_chunk_latency_ms")
    n_chunks = int(sum(s[2] for s in lat.series().values()))
    n_compiles = int(sum(reg.counter("repro_compiles_total")
                         .series().values()))
    n_up = int(reg.counter("repro_rung_migrations_total")
               .value(direction="up"))
    print(f"\nchunks served: {n_chunks}, p95 latency "
          f"{lat.quantile(0.95):.1f} ms; "
          f"compiles {n_compiles}, migrations {n_up} up")
    print(f"trace: {len(obs.tracer())} events "
          f"(dropped {obs.tracer().dropped}) -> {trace_jsonl}")
    host_ms: dict[str, list[float]] = {}
    for e in obs.tracer().snapshot():
        if e.name in ("step", "dispatch", "chunk", "ready", "flush", "read"):
            host_ms.setdefault(e.name, []).append(e.dur_us / 1e3)
    for name, ms in host_ms.items():
        print(f"  {name:8s} x{len(ms):3d}  median {np.median(ms):8.3f} ms")
    reads = reg.counter("repro_flush_host_reads_total").value()
    print(f"host reads by flushes: {int(reads)}")
    print(f"chrome trace (open in Perfetto): {trace_chrome}")
    print(f"prometheus snapshot: {prom_path}")

    # Health verdict over the *clean* serving phase (the incident drill
    # below deliberately adds compile-laden post-mortem chunks that have
    # no business in the serving-latency p95).
    health = obs.health.health_snapshot(net)
    print(f"\nhealth: {health['status']} on {health['hardware']}")
    for check in health["checks"]:
        print(f"  [{check['status']:4s}] {check['name']}: {check['detail']}")
    with open(os.path.join(OUT_DIR, "observability_health.json"), "w") as f:
        json.dump(health, f, indent=1)

    # -- one timeline: the program's spans and the device's operations ------
    profile_dir = os.path.join(OUT_DIR, "observability_profile")
    with jax.profiler.trace(profile_dir):
        pool.step(CHUNK)
        for sid in pool.session_ids:
            pool.flush(sid)
    (xplane,) = glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True)
    counts: dict[str, int] = {}
    for plane in jax.profiler.ProfileData.from_file(xplane).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    counts[e.name] = counts.get(e.name, 0) + 1
    print(f"\nprofiler trace (TensorBoard/Perfetto): {xplane}")
    print("  program spans on its host plane: "
          + ", ".join(f"{k} x{v}" for k, v in sorted(counts.items())))

    # -- incident drill: NaN tenant -> trip -> quarantine -> replay ---------
    print("\n--- incident drill ---")
    assert pool.check_watches() == {}  # healthy fleet: nothing trips

    # Poison tenant1's membrane state the way a real fp16 overflow would
    # (lane surgery stands in for the numerics going bad on their own).
    # Neuron 40 sits mid-chain: generator-group neurons are overwritten
    # by the stimulus every tick, so a NaN there would just vanish.
    sched = pool.ladder_of("tenant1").scheduler
    lane = sched.lane_of("tenant1")
    st = jax.tree.map(lambda x: x[lane], sched.states)
    v = st.neurons.v.at[40].set(st.neurons.v.dtype.type(float("nan")))
    sched.states = _write_lane(
        sched.states, lane, st._replace(neurons=st.neurons._replace(v=v)))

    pool.step(CHUNK)  # ONE chunk later...
    alerts = pool.check_watches()
    for sid, verdicts in alerts.items():
        for v in verdicts:
            print(f"TRIPPED {sid}: watch={v.watch} value={v.value:g} "
                  f"limit={v.limit:g} ({v.detail})")

    q = pool.quarantine("tenant1", alerts["tenant1"])
    print(f"quarantined tenant1 at tick {q.snapshot.ticks}; flight "
          f"recorder holds {len(q.recording)} chunk-boundary snapshots; "
          f"survivors: {pool.session_ids}")

    dump_dir = serve.dump_quarantine(
        os.path.join(OUT_DIR, "quarantine"), q, keep_last=4)
    print(f"evidence dumped (count-capped retention): {dump_dir}")

    # Post-mortem: the ring's second-to-last snapshot is the last healthy
    # chunk boundary — the one the poison landed on. Re-inject the same
    # fault there and replay the incident chunk solo, with the full
    # raster the serving fleet never materialized; the corrupted state
    # the watch tripped on reproduces bit-for-bit.
    ring = q.recording
    st0 = ring[-2].state
    bad = st0.neurons.v.at[40].set(st0.neurons.v.dtype.type(float("nan")))
    snap0 = ring[-2]._replace(
        state=st0._replace(neurons=st0.neurons._replace(v=bad)))
    session, out = serve.replay(net, snap0, ring[-1].ticks - ring[-2].ticks)
    for a, b in zip(jax.tree.leaves(session.state),
                    jax.tree.leaves(ring[-1].state)):
        if jax.dtypes.issubdtype(a.dtype, jax.dtypes.prng_key):
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    raster = np.asarray(out["spikes"])
    print(f"replayed ticks {ring[-2].ticks}..{ring[-1].ticks}: "
          f"[{raster.shape[0]}x{raster.shape[1]}] raster, "
          f"{int(raster.sum())} spikes — the incident chunk reproduced "
          "bit-exactly under the microscope")

    # The incident is now on the record: the watchpoint health check
    # turns WARN for the rest of this process's life.
    hc = obs.health.watch_check(obs.registry())
    print(f"  [{hc.status:4s}] {hc.name}: {hc.detail}")


if __name__ == "__main__":
    main()
