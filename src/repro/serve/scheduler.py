"""Multi-tenant lane scheduler — same-topology sessions on vmap lanes.

The throughput configuration of the serving runtime: N tenants whose
networks share one compiled topology (same ``NetStatic``/``NetParams``)
are packed into the lanes of ONE vmapped device program — the same
batching machinery as ``Engine.run_batch``, but with *independent
per-lane state*: each lane carries its own ``NetState`` (membrane state,
delay ring, **plastic weights**, STDP/homeostasis traces), its own
counter-keyed generator stream, and its own telemetry accumulators, so 64
sessions advance one chunk in one ``lax.scan`` launch amortizing the
weight-image decode and scheduling overhead across the fleet
(``benchmarks/bench_serve.py``).

Lanes are *slots*: :meth:`LaneScheduler.admit` writes a session into a
free lane, :meth:`LaneScheduler.evict` slices its live state back out
(bit-exactly resumable as a solo :class:`~repro.serve.Session` or on
another scheduler), :meth:`LaneScheduler.step` advances every lane one
chunk. Idle lanes stay in the program but are gated by the per-lane
``active`` flag: their generator draw is suppressed (no stimulus → the
network relaxes to rest and emits no spike events, so every event-driven
term — propagation drive, STDP deltas — is arithmetic on zeros) and
homeostasis holds (otherwise an idle lane's below-target average rate
would quietly inflate its plastic weights). Host memory per chunk is O(1)
in the horizon: ``step`` runs ``record="monitors"`` (or ``"none"``) — no
[T, N] raster is ever materialized; telemetry crosses to the host only on
:meth:`flush`.

**Mesh sharding** (``mesh=``): the lane axis can be placed across a
device mesh — :func:`jax.shard_map` partitions the batched pytrees on
their leading (lane) dimension, so each device runs the vmapped tick scan
over its own ``capacity / n_devices`` lanes. Lanes are embarrassingly
parallel (no cross-lane term anywhere in the tick), so the sharded step
needs **zero collectives** and is bit-identical per lane to the
single-device scheduler — asserted by the 4-virtual-device subprocess
parity test in ``tests/test_serve_pool.py`` (the
``--xla_force_host_platform_device_count`` pattern from
``tests/test_distributed.py``). The shared ``NetParams`` (weights images,
CSR tables, generator schedules) stay replicated; only per-lane state,
keys, flags, and telemetry shard.

**Migration** (:meth:`export` / :meth:`restore`): the no-flush twin of
evict/admit. ``export`` slices a lane out *with* its raw cumulative
telemetry carry and flush counters — nothing is drained to the host, so
the tenant's observable flush accounting is untouched; ``restore`` writes
the snapshot into a free lane of any same-topology scheduler (a different
capacity rung, a mesh-sharded scheduler, another process via
``serve.lifecycle.save_lane``). This is what
:class:`repro.serve.CapacityLadder` rides to move whole fleets between
pre-compiled lane-count rungs bit-exactly.

Lane occupancy and per-session bytes are registered in the network's
:class:`~repro.memory.MemoryLedger` under a dedicated "8. Serve Lanes"
stage, extending the paper's seven-step ramp-up table to the serving
deployment (``MemoryLedger.serve_bytes``; per-rung breakdown via
``ledger_key`` and ``MemoryLedger.serve_rung_bytes``).
"""
from __future__ import annotations

import dataclasses
import zlib
from collections import deque
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs
from repro.core.engine import _run_impl
from repro.obs import watch as wat
from repro.core.network import CompiledNetwork, NetState
from repro.precision.policy import tree_bytes
from repro.telemetry import monitors as tel

__all__ = ["LaneScheduler", "LaneSnapshot", "Evicted", "Quarantined"]


@dataclasses.dataclass(frozen=True)
class _LaneInfo:
    """Host-side bookkeeping for one occupied lane."""

    session_id: str
    ticks: int = 0


class Evicted(NamedTuple):
    """What :meth:`LaneScheduler.evict` hands back — everything needed to
    resume the tenant bit-exactly elsewhere (``Session.create(net,
    key=ev.gen_key, state=ev.state)`` or a re-admit)."""

    state: NetState
    gen_key: jax.Array  # the tenant's stimulus-stream key
    flush: dict | None  # final telemetry drain (None for record="none")


class LaneSnapshot(NamedTuple):
    """A lane sliced out *without* flushing — the migration payload.

    Unlike :class:`Evicted`, the cumulative telemetry carry rides along
    raw (``tel``; non-cumulative slots are stripped to ``()`` exactly as
    ``SessionMonitors.absorb`` does, keeping the structure chunk-size
    independent) together with the ticks-since-flush counter, so a
    :meth:`LaneScheduler.restore` on any same-topology scheduler —
    another capacity rung, a sharded mesh, another process — continues
    the tenant as if never moved: same state, same stimulus stream, and
    the *next flush reports exactly what the unmoved tenant's would*.
    """

    session_id: str
    state: NetState
    gen_key: jax.Array
    tel: tuple | None  # cumulative carry slots; () where per-chunk
    ticks: int
    ticks_since_flush: int


class Quarantined(NamedTuple):
    """What :meth:`LaneScheduler.quarantine` hands back — the evidence
    bundle for a tripped tenant: its no-flush snapshot (bit-exactly
    resumable/replayable), the tripped watch verdicts, and its
    flight-recorder window (the last K chunk-boundary snapshots). Persist
    it with ``serve.lifecycle.dump_quarantine``."""

    session_id: str
    snapshot: LaneSnapshot
    verdicts: tuple  # WatchVerdict records that triggered the quarantine
    recording: tuple  # last-K chunk-boundary LaneSnapshots (oldest first)


def _stack(tree, n: int):
    return jax.tree.map(lambda x: jnp.stack([x] * n), tree)


@jax.jit
def _write_lane(batched, lane, value):
    return jax.tree.map(lambda b, x: b.at[lane].set(x), batched, value)


@jax.jit
def _read_lane(batched, lane):
    return jax.tree.map(lambda b: b[lane], batched)


class LaneScheduler:
    """Admit/evict/step scheduler over ``capacity`` vmap lanes.

    All admitted sessions must share the scheduler's compiled network
    (same topology, params, and precision policy — that is what lets one
    device program serve them all). ``record`` selects the per-chunk mode:
    ``"monitors"`` (default; requires compiled monitors) accumulates
    flushable telemetry per lane, ``"none"`` runs bare.

    ``mesh``/``mesh_axis`` shard the lane axis across a device mesh (the
    axis must divide ``capacity``); ``ledger_key`` namespaces the memory
    ledger registrations (``serve.lanes.<key>``) so a ladder of
    schedulers reports per-rung bytes.
    """

    def __init__(self, net: CompiledNetwork, capacity: int, *,
                 record: str = "monitors", mesh: Mesh | None = None,
                 mesh_axis: str = "lanes", ledger_key: str | None = None,
                 flight_window: int = 0):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if flight_window < 0:
            raise ValueError(
                f"flight_window must be >= 0, got {flight_window}")
        if record not in ("monitors", "none"):
            raise ValueError(
                f"record must be 'monitors' or 'none', got {record!r} — "
                "raster modes would materialize [T, N] per lane")
        if record == "monitors" and not net.static.monitors:
            raise ValueError(
                "record='monitors' needs a network compiled with monitors")
        if mesh is not None:
            if mesh_axis not in mesh.shape:
                raise ValueError(
                    f"mesh has no axis {mesh_axis!r} (axes: "
                    f"{tuple(mesh.shape)})")
            if capacity % mesh.shape[mesh_axis]:
                raise ValueError(
                    f"capacity ({capacity}) must be a multiple of the mesh "
                    f"axis size ({mesh.shape[mesh_axis]}) — lanes shard "
                    "evenly, no ragged device gets a partial lane block")
        self.net = net
        self.capacity = capacity
        self.record = record
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        # Per-lane event gating (lax.cond) lowers to both-branches+select
        # under vmap, exactly as in Engine.run_batch — the batched program
        # relies on silent lanes contributing zero *events*, not on
        # skipping their ops.
        self.static = dataclasses.replace(net.static, event_gated=False)
        self.states: NetState = _stack(net.state0, capacity)
        self.gen_keys = _stack(jax.random.key(0), capacity)
        self.active = jnp.zeros((capacity,), bool)
        self._tel = (_stack(tel.init_carry(net.static, 1), capacity)
                     if record == "monitors" else ())
        # Watchpoint accumulators (compiled via compile(watches=...)): one
        # carry per lane, threaded through every chunk; drained host-side
        # by check_watches() at flush cadence.
        self._watch = (_stack(wat.init_carry(net.static), capacity)
                       if net.static.watches else ())
        # Flight recorder: last-K chunk-boundary snapshots per session
        # (bounded ring, captured after every step when flight_window > 0).
        self.flight_window = int(flight_window)
        self._flight: dict[str, deque] = {}
        self._lanes: list[_LaneInfo | None] = [None] * capacity
        self._ticks_since_flush = [0] * capacity
        # Ledger: the serving deployment's footprint — per-lane replicated
        # state (the dominant term: N× the single-tenant mutable state)
        # plus the per-lane telemetry accumulators. ledger_key namespaces
        # the names so a capacity ladder reports bytes per rung.
        suffix = f".{ledger_key}" if ledger_key else ""
        self._ledger_names = (f"serve.lanes{suffix}",
                              f"serve.telemetry{suffix}",
                              f"serve.watch{suffix}")
        # The label the obs plane files this scheduler's series under:
        # the ledger key when namespaced (a ladder rung), else the bare
        # capacity — stable across the scheduler's lifetime.
        self._obs_rung = ledger_key or f"cap{capacity}"
        # The last step's chunk, timed until a flush sees it ready.
        self._chunks = obs.ChunkTimer(scope="scheduler", rung=self._obs_rung)
        for name in self._ledger_names:
            net.ledger.release(name)
        with net.ledger.stage("8. Serve Lanes"):
            net.ledger.register(self._ledger_names[0], self.states)
            if self._tel:
                net.ledger.register(self._ledger_names[1], self._tel)
            if self._watch:
                net.ledger.register(self._ledger_names[2], self._watch)
        if obs.enabled():
            self._obs_occupancy()

    def close(self) -> None:
        """Drop this scheduler's ledger registrations (a ladder migrating
        off a rung frees its lane bytes; the arrays die with the object)."""
        self._chunks.drop()
        for name in self._ledger_names:
            self.net.ledger.release(name)
        for gauge in ("repro_serve_lane_occupancy",
                      "repro_serve_lane_capacity"):
            obs.remove_gauge(gauge, rung=self._obs_rung)

    def _obs_occupancy(self) -> None:
        obs.gauge("repro_serve_lane_occupancy", float(self.occupancy),
                  rung=self._obs_rung)
        obs.gauge("repro_serve_lane_capacity", float(self.capacity),
                  rung=self._obs_rung)

    # -- occupancy ------------------------------------------------------------
    @property
    def occupancy(self) -> int:
        return sum(1 for s in self._lanes if s is not None)

    @property
    def session_ids(self) -> list[str]:
        return [s.session_id for s in self._lanes if s is not None]

    @property
    def free_lanes(self) -> list[int]:
        return [i for i, s in enumerate(self._lanes) if s is None]

    @property
    def lane_sessions(self) -> list[str | None]:
        """Per-lane occupancy view (session id or None), for admission
        policies that place by lane geometry."""
        return [s.session_id if s is not None else None
                for s in self._lanes]

    @property
    def session_bytes(self) -> int:
        """Device bytes one admitted session costs: its lane's replicated
        NetState slice plus its telemetry and watch accumulators."""
        return (tree_bytes(self.states) + tree_bytes(self._tel)
                + tree_bytes(self._watch)) // self.capacity

    def lane_of(self, session_id: str) -> int:
        for i, s in enumerate(self._lanes):
            if s is not None and s.session_id == session_id:
                return i
        raise KeyError(session_id)

    # -- admit / evict --------------------------------------------------------
    def admit(self, session_id: str, *, seed: int | None = None,
              key: jax.Array | None = None,
              state: NetState | None = None,
              lane: int | None = None) -> int:
        """Place a session into a free lane; returns the lane index.

        ``seed``/``key`` names the tenant's stimulus stream; when neither
        is given the seed is ``crc32(session_id)`` — stable across
        processes and restarts (NOT Python's salted ``hash``), so a
        re-admitted tenant keeps its stream. ``state`` resumes an existing
        session (an evicted lane, a solo ``Session.state``, or a restored
        checkpoint) instead of the network's fresh ``state0``. ``lane``
        pins the placement to a specific free lane (admission policies —
        ``ServePool(policy="best_fit")``); default is first-fit.
        """
        with obs.span("admit", rung=self._obs_rung, session=session_id):
            lane = self._admit_impl(session_id, seed=seed, key=key,
                                    state=state, lane=lane)
        if obs.enabled():
            obs.inc("repro_serve_admits_total", rung=self._obs_rung)
            self._obs_occupancy()
        return lane

    def _admit_impl(self, session_id: str, *, seed, key, state,
                    lane=None) -> int:
        free = self.free_lanes
        if not free:
            raise RuntimeError(
                f"scheduler full ({self.capacity} lanes) — evict before "
                "admitting")
        if any(s is not None and s.session_id == session_id
               for s in self._lanes):
            raise ValueError(f"session id {session_id!r} already admitted")
        if lane is None:
            lane = free[0]
        elif lane not in free:
            raise ValueError(
                f"lane {lane} is not free (free lanes: {free[:8]}...)"
                if len(free) > 8 else
                f"lane {lane} is not free (free lanes: {free})")
        if key is None:
            key = jax.random.key(seed if seed is not None else
                                 zlib.crc32(session_id.encode()))
        state = state if state is not None else self.net.state0
        # Recycled-slot hygiene: the incoming ``state`` replaces EVERY
        # per-lane NetState leaf (membrane state, ring phase, plastic
        # weights, homeostasis averages), and the telemetry carry is
        # zeroed wholesale below. Both matter: evict() flushes but keeps
        # the GroupRate filter *level* in the lane, and export() drains
        # nothing at all — without this zeroing a recycled lane would
        # hand its predecessor's rate level (or whole spike counts) to
        # the next tenant (regression-tested in tests/test_serve_pool.py).
        self.states = _write_lane(self.states, lane, state)
        self.gen_keys = _write_lane(self.gen_keys, lane, key)
        self.active = self.active.at[lane].set(True)
        self._zero_lane_tel(lane)
        self._reset_lane_watch(lane)
        self._lanes[lane] = _LaneInfo(session_id=session_id,
                                      ticks=int(state.t))
        self._ticks_since_flush[lane] = 0
        return lane

    def _zero_lane_tel(self, lane: int) -> None:
        """Fully re-zero one lane's telemetry carry — counts AND filter
        levels (``flush`` deliberately keeps the latter, so an admit into
        a previously-used slot must not rely on it)."""
        if self._tel:
            self._tel = _write_lane(
                self._tel, lane,
                jax.tree.map(jnp.zeros_like, _read_lane(self._tel, lane)))

    def _reset_lane_watch(self, lane: int) -> None:
        """Fresh watch accumulators for one lane — init values, not zeros
        (WeightDrift's norm slot is a *level* seeded from the compile-time
        baseline). Same recycled-slot hygiene rationale as telemetry."""
        if self._watch:
            self._watch = _write_lane(self._watch, lane,
                                      wat.init_carry(self.net.static))

    def evict(self, session_id: str) -> Evicted:
        """Remove a session; returns its live ``NetState``, its stimulus
        key, and the final telemetry flush (:class:`Evicted`).

        State + key together resume bit-exactly anywhere — solo session,
        re-admit, checkpoint; the lane goes idle (generator-gated silent)
        until the next admit. The final flush *drains* the tenant's
        telemetry — for a move that must preserve flush accounting (rung
        migration), use :meth:`export` instead.
        """
        with obs.span("evict", rung=self._obs_rung, session=session_id):
            lane = self.lane_of(session_id)
            state = _read_lane(self.states, lane)
            gen_key = self.gen_keys[lane]
            final = self.flush(session_id) if self._tel else None
            self.active = self.active.at[lane].set(False)
            self._lanes[lane] = None
            self._flight.pop(session_id, None)
        if obs.enabled():
            obs.inc("repro_serve_evicts_total", rung=self._obs_rung)
            self._obs_occupancy()
        return Evicted(state=state, gen_key=gen_key, flush=final)

    # -- migration ------------------------------------------------------------
    def snapshot(self, session_id: str) -> LaneSnapshot:
        """Read a session's :class:`LaneSnapshot` WITHOUT vacating the lane
        — the flight recorder's non-destructive capture. Carries the same
        payload as :meth:`export` (state, stimulus key, raw cumulative
        telemetry, flush counters), so a recorded snapshot replays or
        restores exactly like an exported one."""
        lane = self.lane_of(session_id)
        tel_lane = None
        if self._tel:
            raw = _read_lane(self._tel, lane)
            tel_lane = tuple(
                c if isinstance(s, tel.CUMULATIVE) else ()
                for s, c in zip(self.net.static.monitors, raw)
            )
        return LaneSnapshot(
            session_id=session_id,
            state=_read_lane(self.states, lane),
            gen_key=self.gen_keys[lane],
            tel=tel_lane,
            ticks=self._lanes[lane].ticks,
            ticks_since_flush=self._ticks_since_flush[lane],
        )

    def export(self, session_id: str) -> LaneSnapshot:
        """Slice a session out WITHOUT flushing — the migration payload.

        The raw cumulative telemetry carry and the ticks-since-flush
        counter ride along, so :meth:`restore` on another scheduler (a
        different capacity rung, a mesh-sharded twin, another process via
        ``serve.lifecycle.save_lane``) continues the tenant bit-exactly
        INCLUDING its flush accounting: the next flush reports the same
        counts/levels the unmoved tenant's would. The vacated lane keeps
        stale carry values until the next admit, which zeroes them.
        """
        with obs.span("export", rung=self._obs_rung, session=session_id):
            lane = self.lane_of(session_id)
            snap = self.snapshot(session_id)
            self.active = self.active.at[lane].set(False)
            self._lanes[lane] = None
        if obs.enabled():
            obs.inc("repro_serve_exports_total", rung=self._obs_rung)
            self._obs_occupancy()
        return snap

    def restore(self, snap: LaneSnapshot) -> int:
        """Admit an exported lane, carrying its telemetry accumulators and
        flush counters through — the receiving half of a migration."""
        with obs.span("restore", rung=self._obs_rung,
                      session=snap.session_id):
            lane = self.admit(snap.session_id, key=snap.gen_key,
                              state=snap.state)
            if self._tel and snap.tel is not None:
                cur = _read_lane(self._tel, lane)
                merged = tuple(
                    s_snap if isinstance(spec, tel.CUMULATIVE) else s_cur
                    for spec, s_snap, s_cur in zip(self.net.static.monitors,
                                                   snap.tel, cur)
                )
                self._tel = _write_lane(self._tel, lane, merged)
            self._ticks_since_flush[lane] = snap.ticks_since_flush
        obs.inc("repro_serve_restores_total", rung=self._obs_rung)
        return lane

    def export_all(self) -> list[LaneSnapshot]:
        """Export every occupied lane (the whole-fleet migration payload),
        in lane order — deterministic, so a ladder migration is seed-stable."""
        return [self.export(s.session_id)
                for s in list(self._lanes) if s is not None]

    # -- advance --------------------------------------------------------------
    def step(self, n_ticks: int) -> None:
        """Advance EVERY lane ``n_ticks`` in one vmapped device program.

        O(1) host memory: nothing is fetched; per-lane state and telemetry
        stay resident. Idle lanes ride along silenced (see module doc).
        With a mesh, the lane axis is shard_map-partitioned across devices
        — zero collectives, bit-identical per lane to the unsharded step.
        """
        if self._tel:  # only a flush can close the chunk
            self._chunks.start(n_ticks)
        if not obs.enabled():
            return self._step_impl(n_ticks)
        # Spans wrap host work and jit *dispatch*, not traced computation —
        # the program and its outputs are bitwise identical with obs on or
        # off.
        occ = self.occupancy
        with obs.span("step", rung=self._obs_rung, n_ticks=n_ticks,
                      occupancy=occ):
            self._step_impl(n_ticks)
        self._chunks.dispatched(self.states.t)
        obs.inc("repro_serve_ticks_total", float(n_ticks * occ),
                rung=self._obs_rung)

    def _step_impl(self, n_ticks: int) -> None:
        tel_in = self._chunk_tel(n_ticks) if self._tel else None
        watch_in = self._watch if self._watch else None
        with obs.span("dispatch", n_ticks=n_ticks):
            if self.mesh is None:
                out = _step_lanes(self.static, self.net.params, self.states,
                                  self.gen_keys, self.active, n_ticks,
                                  self.record, tel_carry=tel_in,
                                  watch_carry=watch_in)
            else:
                out = _step_lanes_sharded(
                    self.static, self.net.params, self.states,
                    self.gen_keys, self.active, n_ticks, self.record,
                    self.mesh, self.mesh_axis, tel_carry=tel_in,
                    watch_carry=watch_in)
        self.states, *rest = out
        if self._tel:
            self._tel = rest[0]
        if self._watch:
            self._watch = rest[-1]
        for i, info in enumerate(self._lanes):
            if info is not None:
                self._lanes[i] = dataclasses.replace(
                    info, ticks=info.ticks + n_ticks)
                self._ticks_since_flush[i] += n_ticks
        if self.flight_window:
            self._record_flight()

    def _record_flight(self) -> None:
        """Capture every occupied lane's chunk-boundary snapshot into its
        bounded ring (``deque(maxlen=flight_window)`` — the last K chunk
        boundaries per session, oldest evicted first)."""
        for info in self._lanes:
            if info is None:
                continue
            ring = self._flight.get(info.session_id)
            if ring is None:
                ring = self._flight[info.session_id] = deque(
                    maxlen=self.flight_window)
            ring.append(self.snapshot(info.session_id))
        if obs.enabled() and self.occupancy:
            obs.event("flight_record", rung=self._obs_rung,
                      sessions=self.occupancy, window=self.flight_window)
            obs.inc("repro_flight_records_total", float(self.occupancy),
                    rung=self._obs_rung)

    def flight(self, session_id: str) -> tuple[LaneSnapshot, ...]:
        """The session's recorded flight window, oldest first (empty when
        the recorder is off or no chunk boundary has passed yet)."""
        return tuple(self._flight.get(session_id, ()))

    def _chunk_tel(self, n_ticks: int) -> tuple:
        """Per-step telemetry carry: cumulative slots persist (batched),
        per-chunk slots (probe/snapshot buffers) re-init at this chunk's
        shape."""
        fresh = _stack(tel.init_carry(self.net.static, n_ticks),
                       self.capacity)
        return tuple(
            c if isinstance(s, tel.CUMULATIVE) else f
            for s, c, f in zip(self.net.static.monitors, self._tel, fresh)
        )

    # -- telemetry ------------------------------------------------------------
    def flush(self, session_id: str) -> dict:
        """Drain one session's cumulative telemetry to the host: per-group
        spike counts since its last flush (lane accumulator re-zeroed) and
        the current filtered group rates (filter level kept).

        One compiled program over the whole (donated) fleet carry, the
        lane a traced index, then one host copy
        (``telemetry.monitors.flush_carry`` on a ``LaneCarry``); the other
        lanes' slots come back bitwise untouched."""
        if not self._tel:
            raise ValueError("scheduler built with record='none'")
        lane = self.lane_of(session_id)
        with obs.span("flush", rung=self._obs_rung, session=session_id):
            self._chunks.close()
            values, self._tel = tel.flush_carry(
                self.net.static, tel.LaneCarry(self._tel, lane))
            values["n_ticks"] = self._ticks_since_flush[lane]
            self._ticks_since_flush[lane] = 0
        obs.inc("repro_serve_flushes_total", rung=self._obs_rung)
        return values

    def flush_all(self) -> dict[str, dict]:
        return {s.session_id: self.flush(s.session_id)
                for s in self._lanes if s is not None}

    # -- watchpoints ----------------------------------------------------------
    def check_watches(self) -> dict[str, list]:
        """Drain every occupied lane's watch accumulators and return the
        TRIPPED verdicts by session id (sessions with no trips are
        omitted). Tripped verdicts are published to the obs plane
        (``watch_trip`` events + ``repro_watch_trips_total``). Runs at
        flush cadence — one device→host fetch for the whole fleet, then a
        cheap numpy pass per lane; the drained windows restart on device.
        """
        if not self._watch:
            raise ValueError(
                "network compiled without watches — pass watches=... "
                "(e.g. 'default') to compile()")
        host = jax.tree.map(np.asarray, self._watch)
        alerts: dict[str, list] = {}
        for lane, info in enumerate(self._lanes):
            if info is None:
                continue
            lane_carry = jax.tree.map(lambda b: b[lane], host)
            verdicts, reset = wat.drain(self.net.static, lane_carry)
            self._watch = _write_lane(self._watch, lane, reset)
            tripped = wat.alert(verdicts, rung=self._obs_rung,
                                session=info.session_id)
            if tripped:
                alerts[info.session_id] = tripped
        return alerts

    def quarantine(self, session_id: str, verdicts=()) -> Quarantined:
        """Evict a tripped tenant WITH its evidence: the no-flush
        :class:`LaneSnapshot` (bit-exactly replayable), the verdicts that
        tripped, and its flight-recorder window. The lane is vacated —
        surviving lanes are untouched (their state never left the device).
        Persist the bundle with ``serve.lifecycle.dump_quarantine``."""
        recording = tuple(self._flight.pop(session_id, ()))
        snap = self.export(session_id)
        if obs.enabled():
            obs.event("quarantine", rung=self._obs_rung, session=session_id,
                      watches=",".join(v.watch for v in verdicts),
                      recorded=len(recording))
            obs.inc("repro_quarantines_total", rung=self._obs_rung)
        return Quarantined(session_id=session_id, snapshot=snap,
                           verdicts=tuple(verdicts), recording=recording)


def _lanes_vmap(static, params, states, gen_keys, active, n_ticks, record,
                tel_carry, watch_carry):
    """One chunk for every lane in the given batched pytrees: vmap of the
    engine's ``_run_impl`` over (state, gen stream, active flag, telemetry
    + watch carries). Shared by the single-device jit and the shard_map
    per-device body — per-lane arithmetic is identical either way, which
    is the whole sharded-parity story. Only carries come back — per-chunk
    outputs (telemetry dicts the caller didn't ask for) are dead code the
    jit eliminates. Returns a tuple ``(states[, tel][, watch])`` whose
    arity is decided by ``record`` and ``static.watches``."""
    want_mon = record == "monitors"
    want_watch = bool(static.watches)

    def one(state, key, act, *carries):
        tc = carries[0] if want_mon else None
        wc = carries[-1] if want_watch else None
        final, out = _run_impl(
            static, params, state, n_ticks, record=record,
            gen_base=key, active=act,
            tel_carry=tc, return_tel_carry=want_mon,
            watch_carry=wc)
        res = [final]
        if want_mon:
            res.append(out["tel_carry"])
        if want_watch:
            res.append(out["watch_carry"])
        return tuple(res)

    extras = (() if not want_mon else (tel_carry,)) + (
        () if not want_watch else (watch_carry,))
    return jax.vmap(one)(states, gen_keys, active, *extras)


@partial(jax.jit, static_argnames=("static", "n_ticks", "record"))
def _step_lanes(static, params, states, gen_keys, active, n_ticks, record,
                tel_carry=None, watch_carry=None):
    return _lanes_vmap(static, params, states, gen_keys, active, n_ticks,
                       record, tel_carry, watch_carry)


@partial(jax.jit, static_argnames=("static", "n_ticks", "record", "mesh",
                                   "mesh_axis"))
def _step_lanes_sharded(static, params, states, gen_keys, active, n_ticks,
                        record, mesh, mesh_axis, tel_carry=None,
                        watch_carry=None):
    """The mesh-sharded step: shard_map partitions every per-lane pytree on
    its leading (lane) axis; ``params`` stays replicated. Each device runs
    the same vmapped body over its lane block — no collective appears
    anywhere (lanes never interact), so the only cross-device traffic is
    the initial resharding of freshly-admitted lane state. Typed PRNG key
    arrays shard like any other leaf (PartitionSpec applies to the visible
    shape). The watch carry shards on the lane axis like telemetry."""
    lane = P(mesh_axis)
    want_mon = record == "monitors"
    want_watch = bool(static.watches)
    extras = (() if not want_mon else (tel_carry,)) + (
        () if not want_watch else (watch_carry,))
    n_out = 1 + len(extras)

    def body(p, s, k, a, *ex):
        tc = ex[0] if want_mon else None
        wc = ex[-1] if want_watch else None
        return _lanes_vmap(static, p, s, k, a, n_ticks, record, tc, wc)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(),) + (lane,) * (3 + len(extras)),
        out_specs=(lane,) * n_out,
        check_vma=False,
    )
    return fn(params, states, gen_keys, active, *extras)
