"""Device-resident serving sessions — unbounded horizons as chunk sequences.

A :class:`Session` owns one simulated network's *live* state (the
``NetState`` pytree — membrane variables, delay ring, plastic weights,
STDP/homeostasis traces) plus the telemetry accumulators, and advances it
by fixed-size chunks: every :meth:`Session.run` call feeds the previous
call's state and monitor carry back into ``engine.run``, so a serving
horizon is ``while True: session.run(chunk)`` with O(chunk) device work
per call and O(1) host traffic (nothing crosses to the host until a
:meth:`SessionMonitors.flush`).

**Chunking guarantee** (the serving contract, asserted by
``tests/test_serve.py`` across every propagation mode × backend, fp32 and
fp16, plastic and not): a session advanced as k chunks of T/k ticks
produces bit-identical spike rasters, weights, and final state to one
uninterrupted ``Engine.run(T)`` over the same stream. The mechanism is the
counter-keyed generator stream (``run(gen_base=...)``): tick t's stimulus
uniforms are ``uniform(fold_in(session_key, t))`` with t the absolute
``state.t``, so the realized stimulus depends only on (key, t) — never on
where the chunk boundaries fall. Networks compiled with a homeostasis
period apply CARLsim's slow-timer scaling at segment boundaries *inside*
``run``, so the boundary schedule is also split-invariant as long as every
chunk is a multiple of the period (the engine enforces this).

Sessions are what the :class:`repro.serve.LaneScheduler` multiplexes onto
vmap lanes, and what ``repro.serve.lifecycle`` checkpoints and restores
bit-exactly.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro import obs
from repro.core.engine import Engine
from repro.core.network import CompiledNetwork, NetState
from repro.obs import watch as wat
from repro.telemetry import monitors as tel

__all__ = ["Session", "SessionMonitors"]


class SessionMonitors:
    """Flushable telemetry accumulators that persist across chunked calls.

    Holds the raw cumulative carry slots (``SpikeCount`` / ``GroupRate``
    per-neuron accumulators) on device between ``run`` calls;
    :meth:`flush` drains them to the host as per-group values — the
    periodic host sync of an unbounded run. Spike counts re-zero on
    device (windowed sums since the last flush); the ``GroupRate``
    filter *level* is reported but kept (see
    ``telemetry.monitors.flush_carry``). Per-chunk monitors
    (``VoltageProbe`` traces, ``WeightNorm`` snapshot rings) are
    re-initialized every chunk and come back in each call's
    ``outputs["telemetry"]``.
    """

    def __init__(self, static):
        self.static = static
        self.carry: tuple | None = None  # None until the first chunk runs
        self.ticks_since_flush = 0
        # The session's last chunk, timed until this flush sees it ready.
        self.chunks = obs.ChunkTimer(scope="session", rung="solo")

    def chunk_carry(self, n_ticks: int) -> tuple:
        """The ``tel_carry`` to feed the next ``run`` call of ``n_ticks``."""
        return tel.chunk_carry(self.static, self.carry, n_ticks)

    def absorb(self, carry: tuple, n_ticks: int) -> None:
        """Take the raw final carry handed back by ``run``. Only the
        cumulative slots are kept (per-chunk probe/snapshot buffers are
        chunk outputs, not session state) — this keeps the persistent
        carry's pytree structure chunk-size independent, which is what
        lets checkpoints restore it against a fixed template."""
        self.carry = tuple(
            c if isinstance(s, tel.CUMULATIVE) else ()
            for s, c in zip(self.static.monitors, carry)
        )
        self.ticks_since_flush += n_ticks

    def flush(self) -> dict:
        """Drain cumulative accumulators to the host.

        Returns ``{monitor_name: per-group numpy array, "n_ticks": ticks
        covered since the previous flush}``. Exact: the flushed spike
        counts over a chunk sequence sum to the uninterrupted run's totals
        bit-for-bit (counts re-zero on device; the rate-filter level
        persists). O(N) work per flush regardless of elapsed ticks.
        """
        if self.carry is None:
            raise RuntimeError("flush() before any chunk has run")
        with obs.span("flush", scope="session"):
            self.chunks.close()
            values, self.carry = tel.flush_carry(self.static, self.carry)
            values["n_ticks"] = self.ticks_since_flush
            self.ticks_since_flush = 0
        obs.inc("repro_serve_flushes_total", rung="solo")
        return values


@dataclasses.dataclass
class Session:
    """One tenant's device-resident simulation, advanced chunk by chunk.

    Build with :meth:`Session.create`; drive with :meth:`run`; drain
    telemetry with ``session.monitors.flush()``; persist with
    ``repro.serve.lifecycle.save_session`` / ``restore_session``.
    """

    engine: Engine
    gen_key: jax.Array  # base of the counter-keyed generator stream
    state: NetState
    monitors: SessionMonitors | None
    ticks: int = 0  # host mirror of state.t (ticks served so far)
    # Raw in-scan watchpoint accumulators (networks compiled with
    # watches=...); threaded through every run() and drained host-side by
    # check_watches(). None until the first chunk runs.
    watch_carry: tuple | None = None

    @classmethod
    def create(
        cls,
        net: CompiledNetwork | Engine,
        *,
        seed: int = 0,
        key: jax.Array | None = None,
        state: NetState | None = None,
        monitors: bool = True,
    ) -> "Session":
        """New session over a compiled network (or an existing ``Engine``
        whose jitted programs it then shares — same-topology sessions reuse
        one compilation). ``seed``/``key`` names the session's stimulus
        stream; ``state`` resumes from an existing ``NetState`` (e.g. a
        lane evicted from the scheduler or a restored checkpoint)."""
        engine = net if isinstance(net, Engine) else Engine(net)
        if key is None:
            key = jax.random.key(seed)
        state = state if state is not None else engine.net.state0
        mon = (SessionMonitors(engine.net.static)
               if monitors and engine.net.static.monitors else None)
        return cls(engine=engine, gen_key=key, state=state, monitors=mon,
                   ticks=int(state.t))

    @classmethod
    def from_snapshot(cls, net: CompiledNetwork | Engine,
                      snap) -> "Session":
        """Continue an exported scheduler lane as a solo session.

        The dual of ``LaneScheduler.restore`` for the pool→solo direction:
        a :class:`~repro.serve.LaneSnapshot` (from ``export`` or
        ``lifecycle.restore_lane``) carries the lane's cumulative telemetry
        and flush counters, which land in ``self.monitors`` — so the next
        flush reports exactly what the still-scheduled tenant's would.
        """
        session = cls.create(net, key=snap.gen_key, state=snap.state)
        session.ticks = snap.ticks
        if session.monitors is not None and snap.tel is not None:
            session.monitors.carry = tuple(snap.tel)
            session.monitors.ticks_since_flush = snap.ticks_since_flush
        return session

    def run(self, n_ticks: int, *, record: str = "monitors", **kw) -> dict:
        """Advance the session ``n_ticks``; returns the chunk's outputs.

        ``record="monitors"`` (default) is the serving mode: no [T, N]
        raster exists, cumulative telemetry persists in
        ``self.monitors`` until flushed. ``record="raster"`` returns the
        chunk's raster (the parity/debug mode); ``"none"`` runs bare.
        """
        want_mon = record in ("monitors", "both")
        if want_mon and self.monitors is None:
            raise ValueError(
                "session created with monitors=False (or a monitor-free "
                "network) cannot record='monitors'")
        if self.monitors is not None:
            self.monitors.chunks.start(n_ticks)
        with obs.span("step", scope="session", n_ticks=n_ticks,
                      record=record):
            if want_mon:
                kw["tel_carry"] = self.monitors.chunk_carry(n_ticks)
                kw["return_tel_carry"] = True
            want_watch = bool(self.engine.net.static.watches)
            if want_watch and self.watch_carry is not None:
                kw["watch_carry"] = self.watch_carry
            self.state, out = self.engine.run(
                n_ticks, state=self.state, record=record,
                gen_base=self.gen_key, **kw)
            if want_mon:
                self.monitors.absorb(out.pop("tel_carry"), n_ticks)
            if want_watch:
                self.watch_carry = out.pop("watch_carry")
            self.ticks += n_ticks
        if self.monitors is not None:
            self.monitors.chunks.dispatched(self.state.t)
        return out

    def check_watches(self) -> list:
        """Drain the session's watch accumulators: returns ALL verdicts
        (tripped or not); tripped ones are published to the obs plane
        (``watch_trip`` events + counters, rung="solo"). The drained
        window restarts. Empty list until a chunk has run."""
        if not self.engine.net.static.watches:
            raise ValueError(
                "network compiled without watches — pass watches=... "
                "(e.g. 'default') to compile()")
        if self.watch_carry is None:
            return []
        verdicts, self.watch_carry = wat.drain(
            self.engine.net.static, self.watch_carry)
        wat.alert(verdicts, rung="solo")
        return verdicts

    def flush(self) -> dict:
        """Shorthand for ``self.monitors.flush()``."""
        if self.monitors is None:
            raise ValueError("session has no monitors")
        return self.monitors.flush()

    def spike_raster(self, n_ticks: int, **kw) -> np.ndarray:
        """Advance ``n_ticks`` returning the chunk's [T, N] bool raster
        (debug/parity helper — serving paths should stay on monitors)."""
        return np.asarray(self.run(n_ticks, record="raster", **kw)["spikes"])
