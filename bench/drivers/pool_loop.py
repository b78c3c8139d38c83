"""Traffic driver: a fleet of tenants on one ``repro.serve.ServePool``.

Set-up admits ``tenants`` sessions of one network, each on its own stimulus
stream, into a pool whose only rung is ``rungs`` (so one program serves the
whole window). Each step is one ``pool.step(chunk_ticks)`` for every tenant
at once, a wait for it, then a flush of every tenant's spike counts: a
closed loop, with no arrival queue.

Parameters (the traffic file): ``tenants``, ``rungs``, ``chunk_ticks``,
``check_streams`` (tenants compared with the reference), ``trace_chunks``.
"""
from __future__ import annotations

import time

import jax
import numpy as np

KIND = "serve"


class Driver:
    def __init__(self, net, traffic: dict, seed: int):
        from repro.serve import ServePool

        self.chunk = int(traffic["chunk_ticks"])
        self.lanes = int(traffic["tenants"])
        self.pool = ServePool(rungs=tuple(int(r) for r in traffic["rungs"]))
        seeds = np.random.default_rng([seed, 2]).integers(
            0, 2**31 - 1, size=self.lanes)
        self.ids = [f"tenant{i}" for i in range(self.lanes)]
        self.seeds = dict(zip(self.ids, (int(s) for s in seeds)))
        with jax.profiler.TraceAnnotation("admit"):
            for sid in self.ids:
                self.pool.admit(net, sid, seed=self.seeds[sid])
        self.counts: dict[str, list[np.ndarray]] = {sid: [] for sid in self.ids}

    def step(self) -> float:
        """One chunk for every tenant; returns the seconds of its flushes."""
        with jax.profiler.TraceAnnotation("dispatch"):
            self.pool.step(self.chunk)
        with jax.profiler.TraceAnnotation("wait"):
            jax.block_until_ready(self.pool.ladder_of(self.ids[0]).scheduler.states)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("flush"):
            for sid in self.ids:
                counts = self.pool.flush(sid)["spike_count"]
                self.counts[sid].append(np.asarray(counts, np.int64))
        return time.perf_counter() - t0

    def streams(self) -> list[tuple[int, np.ndarray]]:
        """``(stimulus seed, spike counts [chunk, group])`` per tenant."""
        return [(self.seeds[sid], np.stack(self.counts[sid])) for sid in self.ids]

    def close(self) -> None:
        self.pool = None
