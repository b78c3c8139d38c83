"""In-scan watchpoints — device-side health sentinels riding the scan carry.

On a deployed MCU there is no debugger: the runtime itself must notice when
a tenant goes wrong (NaN'd fp16 state, runaway or silent spiking, plastic
weight divergence) and say so *without* perturbing the simulation. Watches
follow the telemetry-monitor pattern exactly (``repro.telemetry.monitors``):
a compile-time spec tuple on ``NetStatic.watches`` lowers into the
``lax.scan`` carry as O(1)-memory reductions over each tick's observables —
pure reads of the step output, so results are bitwise identical watch-on vs
watch-off — and verdicts drain host-side at chunk/flush boundaries only.

Specs
-----
- :class:`NonFinite` — NaN/Inf sentinel on the neuron membrane state every
  tick and on plastic weights (and the weight-change accumulators ``sd`` of
  DelaySTDP projections) every ``weight_stride`` ticks. The fp16
  poisoned-lane detector.
- :class:`RateBand` — per-group mean firing rate must sit in
  ``[lo_hz, hi_hz]`` over the drained window (runaway / seizure detection).
- :class:`WeightDrift` — relative L2 drift of each projection's weights vs
  its compile-time baseline (``compile()`` fills the baseline from
  ``state0``); catches runaway plasticity before it detonates the net.
- :class:`Silent` — longest run of consecutive zero-spike ticks; a network
  that has died reports it even though nothing is NaN.

Carry shapes are independent of the chunk length, so the same lane-batched
accumulators ride any chunking (``serve.LaneScheduler`` stacks one carry
per lane). :func:`drain` is host-side numpy — cheap enough to run at every
flush boundary — and returns typed :class:`WatchVerdict` records plus the
reset carry for the next window.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "NonFinite", "RateBand", "WeightDrift", "Silent", "WatchSpec",
    "WatchVerdict", "DEFAULT_WATCHES", "resolve", "carry_struct",
    "init_carry", "update", "drain", "alert",
]

_EPS = 1e-9


@dataclasses.dataclass(frozen=True)
class NonFinite:
    """NaN/Inf sentinel: membrane state every tick, plastic weights every
    ``weight_stride`` ticks (strided like ``telemetry.WeightNorm`` — the
    weight reduction is O(nnz), the state check is O(N))."""
    weight_stride: int = 100
    name: str = "nonfinite"


@dataclasses.dataclass(frozen=True)
class RateBand:
    """Per-group mean rate must sit inside ``[lo_hz, hi_hz]`` over the
    drained window. The default band only catches runaway (seizure-like)
    activity; set ``lo_hz`` > 0 to also require a minimum rate."""
    lo_hz: float = 0.0
    hi_hz: float = 1000.0
    name: str = "rate_band"


@dataclasses.dataclass(frozen=True)
class WeightDrift:
    """Relative L2 drift of each projection's weights vs the compile-time
    baseline: trips when ``|‖w‖ - ‖w₀‖| / ‖w₀‖ > limit`` for any
    projection. ``baseline`` is filled by ``compile()`` from ``state0``
    (same L2 expression as ``telemetry.WeightNorm``)."""
    limit: float = 0.5
    stride: int = 100
    baseline: tuple[float, ...] | None = None
    name: str = "weight_drift"


@dataclasses.dataclass(frozen=True)
class Silent:
    """Trips when the network produced zero spikes for ``window``
    consecutive ticks anywhere in the drained window."""
    window: int = 500
    name: str = "silent"


WatchSpec = NonFinite | RateBand | WeightDrift | Silent

#: The serving-plane default: poisoned-state detection, runaway-rate band,
#: and dead-network detection. ``WeightDrift`` is opt-in (it needs plastic
#: projections to be meaningful).
DEFAULT_WATCHES: tuple[WatchSpec, ...] = (NonFinite(), RateBand(), Silent())


@dataclasses.dataclass(frozen=True)
class WatchVerdict:
    """One drained watch verdict — the typed alert record."""
    watch: str  # spec name (unique per compiled net)
    kind: str  # spec class name
    tripped: bool
    value: float  # measured quantity (count, rate, drift, run length)
    limit: float  # the violated (or guarding) bound
    detail: str

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def resolve(specs, *, n: int, n_projections: int, dt: float = 1.0,
            baseline_norms: tuple[float, ...] | None = None,
            ) -> tuple[WatchSpec, ...]:
    """Validate and normalize a watch request at compile time.

    ``specs`` may be None (no watches), ``"default"`` (:data:`DEFAULT_WATCHES`),
    a single spec, or a tuple of specs. ``baseline_norms`` (one L2 norm per
    projection, from ``state0``) fills any :class:`WeightDrift` whose
    ``baseline`` was left None.
    """
    if specs is None:
        return ()
    if specs == "default":
        specs = DEFAULT_WATCHES
    if isinstance(specs, WatchSpec):
        specs = (specs,)
    specs = tuple(specs)

    seen: set[str] = set()
    out = []
    for s in specs:
        if not isinstance(s, WatchSpec):
            raise ValueError(f"not a watch spec: {s!r}")
        if s.name in seen:
            raise ValueError(f"duplicate watch name {s.name!r}")
        seen.add(s.name)
        if isinstance(s, NonFinite):
            if s.weight_stride < 1:
                raise ValueError(f"{s.name}: weight_stride must be >= 1")
        elif isinstance(s, RateBand):
            if not (0.0 <= s.lo_hz <= s.hi_hz):
                raise ValueError(
                    f"{s.name}: need 0 <= lo_hz <= hi_hz, "
                    f"got [{s.lo_hz}, {s.hi_hz}]")
        elif isinstance(s, WeightDrift):
            if s.stride < 1:
                raise ValueError(f"{s.name}: stride must be >= 1")
            if s.limit <= 0.0:
                raise ValueError(f"{s.name}: limit must be > 0")
            if n_projections == 0:
                raise ValueError(f"{s.name}: network has no projections")
            if s.baseline is None:
                if baseline_norms is None:
                    raise ValueError(
                        f"{s.name}: no baseline and no baseline_norms")
                s = dataclasses.replace(
                    s, baseline=tuple(float(b) for b in baseline_norms))
            if len(s.baseline) != n_projections:
                raise ValueError(
                    f"{s.name}: baseline has {len(s.baseline)} entries "
                    f"for {n_projections} projections")
        elif isinstance(s, Silent):
            if s.window < 1:
                raise ValueError(f"{s.name}: window must be >= 1")
        out.append(s)
    return tuple(out)


def carry_struct(specs, n: int, n_projections: int) -> tuple:
    """ShapeDtypeStructs of the watch carry — for the memory ledger. Shapes
    are chunk-length independent (unlike monitor snapshot ledgers)."""
    i32 = jnp.int32
    structs: list = []
    for s in specs:
        if isinstance(s, NonFinite):
            structs += [jax.ShapeDtypeStruct((), i32)] * 2
        elif isinstance(s, RateBand):
            structs += [jax.ShapeDtypeStruct((n,), i32),
                        jax.ShapeDtypeStruct((), i32)]
        elif isinstance(s, WeightDrift):
            structs += [jax.ShapeDtypeStruct((n_projections,), jnp.float32)]
        elif isinstance(s, Silent):
            structs += [jax.ShapeDtypeStruct((), i32)] * 2
    return tuple(structs)


def init_carry(static) -> tuple:
    """Fresh accumulators for ``static.watches`` — one slot tuple per spec."""
    z = jnp.zeros((), jnp.int32)
    carry: list = []
    for s in static.watches:
        if isinstance(s, NonFinite):
            carry.append((z, z))
        elif isinstance(s, RateBand):
            carry.append((jnp.zeros((static.n,), jnp.int32), z))
        elif isinstance(s, WeightDrift):
            carry.append((jnp.asarray(s.baseline, jnp.float32),))
        elif isinstance(s, Silent):
            carry.append((z, z))
    return tuple(carry)


def _l2(w: jax.Array) -> jax.Array:
    # Same expression as telemetry.WeightNorm — drift baselines and live
    # norms must be computed identically.
    return jnp.sqrt(jnp.sum(jnp.square(w.astype(jnp.float32))))


def update(static, carry: tuple, i: jax.Array, spikes: jax.Array,
           v: jax.Array, weights: tuple, stdp: tuple = ()) -> tuple:
    """One watch tick: fold this tick's observables into the accumulators.

    Pure reads of the step output — never feeds back into the dynamics, so
    the simulation is bitwise identical with watches compiled in or out.
    ``i`` is the local step index (strided checks), ``spikes`` the [N] bool
    spike row, ``v`` the f32 membrane view, ``weights`` the post-update
    weight storages, ``stdp`` the post-update plasticity states.
    """
    new: list = []
    for s, c in zip(static.watches, carry):
        if isinstance(s, NonFinite):
            bad_v, bad_w = c
            bad_v = bad_v + (~jnp.isfinite(v).all()).astype(jnp.int32)
            plastic = [w for w, cfg in zip(weights, static.stdp)
                       if cfg is not None]
            plastic += [stdp[j].sd for j in static.delay_csr if stdp]
            if plastic:
                def check(b, _ws=tuple(plastic)):
                    ok = jnp.bool_(True)
                    for w in _ws:
                        ok = ok & jnp.isfinite(w).all()
                    return b + (~ok).astype(jnp.int32)
                bad_w = jax.lax.cond(i % s.weight_stride == 0,
                                     check, lambda b: b, bad_w)
            new.append((bad_v, bad_w))
        elif isinstance(s, RateBand):
            counts, ticks = c
            new.append((counts + spikes.astype(jnp.int32), ticks + 1))
        elif isinstance(s, WeightDrift):
            (norms,) = c
            norms = jax.lax.cond(
                i % s.stride == 0,
                lambda b: jnp.stack([_l2(w) for w in weights]),
                lambda b: b, norms)
            new.append((norms,))
        elif isinstance(s, Silent):
            run, max_run = c
            run = jnp.where(spikes.any(), 0, run + 1).astype(jnp.int32)
            new.append((run, jnp.maximum(max_run, run)))
    return tuple(new)


def drain(static, carry: tuple) -> tuple[list[WatchVerdict], tuple]:
    """Host-side verdict pass: evaluate each watch over the accumulated
    window and reset the window. Returns ``(verdicts, carry')`` where
    ``carry'`` starts the next window (level quantities — drift norms, the
    current silent run — persist; window counters reset).
    """
    verdicts: list[WatchVerdict] = []
    new: list = []
    for s, c in zip(static.watches, carry):
        if isinstance(s, NonFinite):
            bad_v = int(np.asarray(c[0]))
            bad_w = int(np.asarray(c[1]))
            verdicts.append(WatchVerdict(
                s.name, "NonFinite", bad_v + bad_w > 0,
                float(bad_v + bad_w), 0.0,
                f"{bad_v} tick(s) with non-finite neuron state, "
                f"{bad_w} strided check(s) with non-finite plastic weights"))
            new.append((np.int32(0), np.int32(0)))
        elif isinstance(s, RateBand):
            counts = np.asarray(c[0])
            ticks = int(np.asarray(c[1]))
            offending: list[str] = []
            worst, bound = 0.0, s.hi_hz
            if ticks:
                for g in static.groups:
                    n_sp = float(counts[g.start:g.start + g.size].sum())
                    rate = 1000.0 * n_sp / (g.size * ticks * static.dt)
                    if not (s.lo_hz <= rate <= s.hi_hz):
                        offending.append(f"{g.name}={rate:.1f}Hz")
                        dev = abs(rate - (s.hi_hz if rate > s.hi_hz
                                          else s.lo_hz))
                        if dev >= worst:
                            worst, bound = rate, (
                                s.hi_hz if rate > s.hi_hz else s.lo_hz)
            verdicts.append(WatchVerdict(
                s.name, "RateBand", bool(offending), worst, bound,
                ("groups outside band: " + ", ".join(offending))
                if offending else
                f"all groups in [{s.lo_hz}, {s.hi_hz}] Hz over {ticks} ticks"))
            new.append((np.zeros_like(counts), np.int32(0)))
        elif isinstance(s, WeightDrift):
            norms = np.asarray(c[0], np.float64)
            base = np.asarray(s.baseline, np.float64)
            rel = np.abs(norms - base) / np.maximum(np.abs(base), _EPS)
            j = int(rel.argmax()) if rel.size else 0
            tripped = bool(rel.size and rel[j] > s.limit)
            verdicts.append(WatchVerdict(
                s.name, "WeightDrift", tripped,
                float(rel[j]) if rel.size else 0.0, s.limit,
                f"max relative drift {float(rel[j]):.4f} at projection {j} "
                f"(‖w‖ {float(norms[j]):.4f} vs baseline {float(base[j]):.4f})"
                if rel.size else "no projections"))
            new.append((np.asarray(c[0]),))  # norms are a level — keep
        elif isinstance(s, Silent):
            run = np.int32(np.asarray(c[0]))
            max_run = int(np.asarray(c[1]))
            verdicts.append(WatchVerdict(
                s.name, "Silent", max_run >= s.window, float(max_run),
                float(s.window),
                f"longest zero-spike run {max_run} tick(s) "
                f"(window {s.window})"))
            new.append((run, run))  # current run persists; the max resets
    return verdicts, tuple(new)


def alert(verdicts, **labels) -> list[WatchVerdict]:
    """Publish tripped verdicts to the obs plane (typed tracer events +
    Prometheus counters) and return them. ``labels`` (rung, session, ...)
    tag both the events and the counters."""
    from repro import obs

    tripped = [v for v in verdicts if v.tripped]
    for v in tripped:
        obs.event("watch_trip", watch=v.watch, kind=v.kind, value=v.value,
                  limit=v.limit, detail=v.detail, **labels)
        obs.inc("repro_watch_trips_total", watch=v.watch,
                **{k: v_ for k, v_ in labels.items() if k == "rung"})
    return tripped
