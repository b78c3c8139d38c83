"""Traffic driver: solo simulations, chunk after chunk in a closed loop.

One ``repro.serve.Session`` on one ``Engine`` at a time. Each step runs one
chunk of ``chunk_ticks`` ticks with streamed telemetry (no raster), waits
for it, and flushes the session's spike counts. A trial is ``trial_chunks``
chunks from rest on a fresh stimulus stream; the next trial starts a new
session on the same engine, so its programs are already compiled.

Parameters (the traffic file): ``chunk_ticks``, ``trial_chunks``,
``check_streams`` (trials compared with the reference), ``trace_chunks``.
"""
from __future__ import annotations

import time

import jax
import numpy as np

KIND = "sim"


class Driver:
    def __init__(self, net, traffic: dict, seed: int):
        from repro.core import Engine

        self.engine = Engine(net)
        self.chunk = int(traffic["chunk_ticks"])
        self.trial_chunks = int(traffic["trial_chunks"])
        self.lanes = 1
        self._seeds = np.random.default_rng([seed, 1])
        self._session = None
        self.trials: list[tuple[int, list[np.ndarray]]] = []

    def step(self) -> float:
        """One chunk; returns the seconds spent in its flush."""
        from repro.serve import Session

        if self._session is None or len(self.trials[-1][1]) == self.trial_chunks:
            s = int(self._seeds.integers(0, 2**31 - 1))
            self._session = Session.create(self.engine, seed=s)
            self.trials.append((s, []))
        with jax.profiler.TraceAnnotation("dispatch"):
            self._session.run(self.chunk)
        with jax.profiler.TraceAnnotation("wait"):
            jax.block_until_ready(self._session.state)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("flush"):
            counts = self._session.flush()["spike_count"]
        self.trials[-1][1].append(np.asarray(counts, np.int64))
        return time.perf_counter() - t0

    def streams(self) -> list[tuple[int, np.ndarray]]:
        """``(stimulus seed, spike counts [chunk, group])`` per trial."""
        return [(s, np.stack(c)) for s, c in self.trials if c]

    def close(self) -> None:
        self._session = None
        self.engine = None
