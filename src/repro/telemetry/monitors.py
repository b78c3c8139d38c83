"""In-scan monitors — CARLsim's SpikeMonitor/GroupMonitor, compiled into
the tick scan.

The seed repo could only compute statistics *post hoc* on a fully
materialized ``[T, N]`` raster (``repro.core.monitors``), which caps run
length and network size at O(T·N) host memory. Real neuromorphic telemetry
lives *inside* the tick loop: CARLsim's monitors accumulate as the
simulation advances, and the paper's entire evaluation (spike-count
accuracy, real-time factor, energy per event) is computed from those
streamed quantities.

This module is the compiled equivalent. A monitor is a *declarative spec*
(a small frozen dataclass) attached to the network at compile time
(``NetworkBuilder.compile(monitors=...)`` stores the resolved tuple in
``NetStatic.monitors``). The engine lowers the specs into accumulators that
ride the ``lax.scan`` carry — so ``Engine.run(n, record="monitors")``
needs O(N) device memory for telemetry state regardless of run length,
while ``record="raster"`` keeps the seed behavior bit-identical.

Monitor kinds:

* :class:`SpikeCount` — exact integer spike totals. The carry holds
  per-neuron int32 counts (one vectorized ``[N]`` add per tick — group
  slicing inside the scan would cost a kernel launch per group per tick);
  the per-group reduction happens once, post-scan. The derived group rates
  are **bit-for-bit** equal to the post-hoc
  ``repro.core.monitors.group_rates`` (exact counts through the shared
  :func:`repro.telemetry.metrics.rate_from_count`).
* :class:`GroupRate` — exponentially filtered population rate per group
  (Hz): ``r += (dt/tau)·(inst − r)``, CARLsim's GroupMonitor-style
  smoothed rate, readable at any time without history. Carried per neuron
  (``[N]`` f32, pure elementwise tick update) and averaged per group
  post-scan — the filter is linear, so in exact arithmetic this equals
  filtering the group-mean rate directly.
* :class:`VoltageProbe` — membrane-potential trace of a *selected* handful
  of neurons, emitted as per-tick scan outputs (``[T, k]`` with k ≪ N).
* :class:`WeightNorm` — per-projection L2 weight norms snapshotted every
  ``stride`` ticks into a carry ring (``[⌈T/stride⌉, P]``); the cheap way
  to watch STDP drift without dumping weight matrices.

The carry/ys layout is a tuple aligned with ``static.monitors``; all
functions here are pure jnp so they vmap transparently under
``Engine.run_batch``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

__all__ = [
    "SpikeCount",
    "GroupRate",
    "VoltageProbe",
    "WeightNorm",
    "DEFAULT_MONITORS",
    "CUMULATIVE",
    "resolve",
    "carry_struct",
    "init_carry",
    "chunk_carry",
    "flush_carry",
    "update",
    "collect",
    "summarize",
]


@dataclasses.dataclass(frozen=True)
class SpikeCount:
    """Exact spike totals: per-neuron int32 in the carry, per-group out."""

    name: str = "spike_count"


@dataclasses.dataclass(frozen=True)
class GroupRate:
    """Exponentially filtered population rate (Hz): per-neuron f32 in the
    carry, per-group mean out."""

    tau_ms: float = 100.0
    name: str = "group_rate"


@dataclasses.dataclass(frozen=True)
class VoltageProbe:
    """Membrane-potential trace of ``neurons`` (global ids), ``[T, k]``."""

    neurons: tuple[int, ...] = ()
    name: str = "vprobe"


@dataclasses.dataclass(frozen=True)
class WeightNorm:
    """Per-projection L2 weight norms, snapshotted every ``stride`` ticks."""

    stride: int = 100
    name: str = "weight_norm"


MonitorSpec = SpikeCount | GroupRate | VoltageProbe | WeightNorm

# What compile(monitors="default") attaches: exact counts (feeds the
# paper's accuracy metric + bit-parity group rates) and the filtered rate.
DEFAULT_MONITORS: tuple[MonitorSpec, ...] = (SpikeCount(), GroupRate())


def resolve(specs, *, n: int, n_projections: int,
            dt: float = 1.0) -> tuple[MonitorSpec, ...]:
    """Validate a monitor set at compile time; returns the resolved tuple.

    ``specs`` may be ``"default"`` (→ :data:`DEFAULT_MONITORS`), ``None``
    or ``()`` (no monitors), or an iterable of spec instances. Raises on
    duplicate names, probe ids outside ``[0, n)``, or degenerate
    stride/tau (a filter with ``tau_ms < dt`` has ``|1 − α| > 1`` and
    diverges) — the errors a streamed 10-hour run cannot afford to hit at
    tick 1.
    """
    if isinstance(specs, str):
        if specs != "default":
            raise ValueError(f"unknown monitor preset {specs!r}")
        specs = DEFAULT_MONITORS
    if specs is None:
        specs = ()
    specs = tuple(specs)
    seen: set[str] = set()
    for s in specs:
        if not isinstance(s, (SpikeCount, GroupRate, VoltageProbe, WeightNorm)):
            raise TypeError(f"not a monitor spec: {s!r}")
        if s.name in seen:
            raise ValueError(f"duplicate monitor name {s.name!r}")
        seen.add(s.name)
        if isinstance(s, GroupRate) and not s.tau_ms >= dt:
            raise ValueError(
                f"GroupRate tau_ms must be >= dt ({dt} ms) for a stable "
                f"filter, got {s.tau_ms}")
        if isinstance(s, VoltageProbe):
            if not s.neurons:
                raise ValueError("VoltageProbe needs at least one neuron id")
            bad = [i for i in s.neurons if not 0 <= int(i) < n]
            if bad:
                raise ValueError(f"VoltageProbe ids out of range [0, {n}): {bad}")
        if isinstance(s, WeightNorm):
            if s.stride < 1:
                raise ValueError(f"WeightNorm stride must be >= 1, got {s.stride}")
            if n_projections == 0:
                raise ValueError("WeightNorm on a network with no projections")
    return specs


def n_snapshots(n_steps: int, stride: int) -> int:
    return -(-n_steps // stride)


def carry_struct(
    specs: tuple[MonitorSpec, ...], n: int, n_projections: int, n_steps: int,
) -> tuple:
    """ShapeDtypeStructs of all telemetry storage for an ``n_steps`` run.

    Covers both the scan-carry accumulators and the stacked probe outputs
    — the *peak* monitor-state bytes, which ``network.compile`` registers
    in the memory ledger (stage "7. Auxiliary Data"). Everything is
    O(N + probes·T + snapshots·projections); never O(T·N).
    """
    out = []
    for s in specs:
        if isinstance(s, SpikeCount):
            out.append(jax.ShapeDtypeStruct((n,), jnp.int32))
        elif isinstance(s, GroupRate):
            out.append(jax.ShapeDtypeStruct((n,), jnp.float32))
        elif isinstance(s, VoltageProbe):
            out.append(jax.ShapeDtypeStruct((n_steps, len(s.neurons)),
                                            jnp.float32))
        elif isinstance(s, WeightNorm):
            out.append(jax.ShapeDtypeStruct(
                (n_snapshots(n_steps, s.stride), n_projections), jnp.float32))
    return tuple(out)


def init_carry(static, n_steps: int) -> tuple:
    """Zeroed accumulators that ride the scan carry, aligned with
    ``static.monitors``. VoltageProbe emits per-tick ys instead of carrying
    state, so its slot is the empty pytree ``()``."""
    out = []
    for s in static.monitors:
        if isinstance(s, SpikeCount):
            out.append(jnp.zeros((static.n,), jnp.int32))
        elif isinstance(s, GroupRate):
            out.append(jnp.zeros((static.n,), jnp.float32))
        elif isinstance(s, VoltageProbe):
            out.append(())
        elif isinstance(s, WeightNorm):
            out.append(jnp.zeros(
                (n_snapshots(n_steps, s.stride), len(static.projections)),
                jnp.float32))
    return tuple(out)


# Monitor kinds whose accumulators are meaningful ACROSS runs: their carry
# slots persist over chunked serving calls (``run(tel_carry=...)``) until a
# host flush drains them. VoltageProbe emits per-tick ys and WeightNorm
# keeps a per-run snapshot ring — both are per-chunk outputs, re-initialized
# every call (their buffer shapes depend on the call's n_steps).
CUMULATIVE = (SpikeCount, GroupRate)


def chunk_carry(static, carry: tuple | None, n_steps: int) -> tuple:
    """Telemetry carry for the next chunked call of ``n_steps`` ticks:
    cumulative slots resume from ``carry`` (zeroed when ``None`` — a fresh
    session), per-chunk slots (probe/snapshot buffers) are re-initialized
    at the chunk size. This is what ``repro.serve`` feeds to
    ``run(tel_carry=...)``."""
    fresh = init_carry(static, n_steps)
    if carry is None:
        return fresh
    return tuple(
        c if isinstance(s, CUMULATIVE) else f
        for s, c, f in zip(static.monitors, carry, fresh)
    )


def flush_carry(static, carry: tuple) -> tuple[dict, tuple]:
    """Drain the cumulative accumulators to the host; returns
    ``(host_values, carry')`` (per-chunk slots pass through untouched).

    ``host_values`` maps monitor name → numpy array of per-group values —
    the same per-group reductions :func:`collect` runs post-scan. The two
    cumulative kinds drain differently, by what they *are*:

    * ``SpikeCount`` is a windowed sum: flushed counts are exact per-group
      totals **since the previous flush**, and the slot re-zeros on device
      — summing flushes over a chunk sequence equals the uninterrupted
      run's totals bit-for-bit.
    * ``GroupRate`` is an exponential-filter *level*, not an accumulation:
      the flush reports its current per-group value and the filter state
      is KEPT (zeroing it would restart the EMA from 0 and bias every
      post-flush reading low by ~(1 − e^(−window/τ)) — readings would
      diverge from an uninterrupted run's, breaking the serving
      invariance).

    Cost is O(N) per flush, independent of how many ticks elapsed — the
    periodic host sync of an unbounded serving session. Each monitor's
    per-group values cross to the host in one copy (a ``read`` span,
    counted by ``repro_flush_host_reads_total``).
    """
    out: dict = {}
    new = []
    for s, c in zip(static.monitors, carry):
        if isinstance(s, SpikeCount):
            out[s.name] = _read(jnp.stack([
                c[g.start:g.start + g.size].sum() for g in static.groups
            ]))
            new.append(jnp.zeros_like(c))
        elif isinstance(s, GroupRate):
            out[s.name] = _read(jnp.stack([
                c[g.start:g.start + g.size].mean() for g in static.groups
            ]))
            new.append(c)  # filter level persists — see docstring
        else:
            new.append(c)
    return out, tuple(new)


def _read(x: jax.Array) -> np.ndarray:
    """One device-to-host copy of a flush."""
    with obs.span("read"):
        host = np.asarray(x)
    obs.inc("repro_flush_host_reads_total")
    return host


def update(static, carry: tuple, i: jax.Array, spikes: jax.Array,
           v: jax.Array, weights: tuple) -> tuple[tuple, tuple]:
    """One telemetry tick: fold this tick's spikes/voltages/weights into the
    accumulators. Returns ``(carry', ys)`` with ``ys`` aligned to
    ``static.monitors`` (``None`` for carry-only monitors).

    The per-tick work of the group monitors is deliberately a couple of
    vectorized ``[N]`` elementwise ops — no per-group reductions inside the
    scan (those run once, post-scan, in :func:`collect`). The benchmark
    contract is < 5% overhead vs ``record="none"``
    (``benchmarks/bench_engine.py::monitor_overhead``).

    ``i`` is the *local* step index within the scan (0-based), used for
    snapshot strides; spike/voltage values are read-only so the simulation
    dynamics are untouched (raster-mode runs stay bit-identical).
    """
    new_carry, ys = [], []
    for s, c in zip(static.monitors, carry):
        if isinstance(s, SpikeCount):
            new_carry.append(c + spikes.astype(jnp.int32))
            ys.append(None)
        elif isinstance(s, GroupRate):
            # Per-neuron instantaneous rate: a spike this tick = 1000/dt Hz.
            inst = spikes.astype(jnp.float32) * jnp.float32(1000.0 / static.dt)
            alpha = jnp.float32(static.dt / s.tau_ms)
            new_carry.append(c + alpha * (inst - c))
            ys.append(None)
        elif isinstance(s, VoltageProbe):
            ids = jnp.asarray(s.neurons, jnp.int32)
            new_carry.append(c)
            ys.append(v[ids].astype(jnp.float32))
        elif isinstance(s, WeightNorm):
            def write(buf, s=s):
                norms = jnp.stack([
                    jnp.sqrt(jnp.sum(jnp.square(w.astype(jnp.float32))))
                    for w in weights
                ])
                return jax.lax.dynamic_update_index_in_dim(
                    buf, norms, i // s.stride, axis=0)

            # The norm reduction (O(synapses)) only runs on snapshot ticks.
            new_carry.append(jax.lax.cond(i % s.stride == 0, write,
                                          lambda b: b, c))
            ys.append(None)
    return tuple(new_carry), tuple(ys)


def collect(static, carry: tuple, ys: tuple) -> dict:
    """Assemble the post-scan telemetry output dict ``{name: array}`` from
    the final carry and the stacked per-tick ys. The per-group reductions
    deferred out of the tick loop happen here, once per run."""
    out = {}
    for s, c, y in zip(static.monitors, carry, ys):
        if isinstance(s, SpikeCount):
            out[s.name] = jnp.stack([
                c[g.start:g.start + g.size].sum() for g in static.groups
            ])
        elif isinstance(s, GroupRate):
            out[s.name] = jnp.stack([
                c[g.start:g.start + g.size].mean() for g in static.groups
            ])
        elif isinstance(s, VoltageProbe):
            out[s.name] = y
        else:
            out[s.name] = c
    return out


def summarize(static, telemetry: dict, n_steps: int) -> dict:
    """Host-side summary of a telemetry output dict (the streaming
    counterpart of ``repro.core.monitors.population_summary``).

    Group rates are computed through
    :func:`repro.telemetry.metrics.rate_from_count` — the same expression
    the post-hoc raster path uses, so for a run of equal length the two are
    bit-for-bit identical (asserted across every propagation mode and
    backend by ``tests/test_telemetry.py``).
    """
    from repro.telemetry.metrics import rate_from_count

    out: dict = {
        "n_ticks": int(n_steps),
        "model_time_s": n_steps * static.dt / 1000.0,
    }
    for spec in static.monitors:
        val = np.asarray(telemetry[spec.name])
        if isinstance(spec, SpikeCount):
            out["group_spike_counts"] = {
                g.name: int(c) for g, c in zip(static.groups, val)
            }
            out["total_spikes"] = int(val.sum())
            out["group_rates"] = {
                g.name: rate_from_count(c, g.size, n_steps, static.dt)
                for g, c in zip(static.groups, val)
            }
            out["mean_rate_hz"] = rate_from_count(
                int(val.sum()), static.n, n_steps, static.dt)
        elif isinstance(spec, GroupRate):
            out["group_rate_filtered_hz"] = {
                g.name: float(r) for g, r in zip(static.groups, val)
            }
        else:  # VoltageProbe / WeightNorm: pass the array through
            out[spec.name] = val
    return out
