"""repro.obs — the operational observability plane.

``repro.telemetry`` measures the *simulation* (spike counts, rates,
in-scan monitor carries — scientific telemetry that rides the device
program). This package measures the *runtime*: where each chunk's host
time goes, how long a chunk takes to complete, compilations, lane
occupancy, ledger bytes against the paper's budgets. Three submodules:

* :mod:`repro.obs.trace`   — bounded ring-buffer spans/events, JSONL +
  Chrome-trace (Perfetto) exporters; every span is also a profiler
  annotation.
* :mod:`repro.obs.metrics` — counters / gauges / fixed-bucket histograms
  with Prometheus text and JSON snapshot exporters.
* :mod:`repro.obs.health`  — SLO snapshots: live metrics vs the paper's
  budgets (real-time factor on the M33 spec, per-rung bytes vs the
  8.477 MB MCU ceiling, completed-chunk µs/tick vs the 1 ms tick).
  Imported lazily — it pulls in ``repro.memory`` and
  ``repro.core.sizing``, which themselves may import this package.

This module is the facade the instrumented runtime calls: a process-wide
tracer + registry behind module functions (:func:`span`, :func:`event`,
:func:`inc`, :func:`gauge`, :func:`observe`) and a per-scheduler
:class:`ChunkTimer`. Observability is **default-on** (disable with
``obs.configure(enabled=False)`` or ``REPRO_OBS=0``; disabled, each call
site costs one predicate) because it is host-side only: spans wrap jit
*dispatch* and scheduler bookkeeping, never traced computation, so device
programs, rasters, and weights are bitwise identical with obs on or off —
asserted by ``tests/test_obs.py``.

Spans on the chunk path (each also named ``repro.<name>`` on the profiler
trace):

* ``step`` — the host work of one chunk (``Session.run``,
  ``LaneScheduler.step``): carry assembly, the flight recorder,
  bookkeeping, and inside it
* ``dispatch`` — the jit call of the chunk program (``Engine.run`` /
  ``run_batch``, the scheduler's lane program). It returns before the
  device is done.
* ``chunk`` — step entry to the moment the chunk's outputs are ready. The
  next flush closes it, adding no wait of its own: at once when the
  outputs are already ready, else after a ``ready`` span in which the
  flush waits for them. ``repro_serve_chunk_latency_ms`` and
  ``repro_serve_us_per_tick`` read it. A chunk that no flush follows
  before the next step is not timed.
* ``flush`` — a telemetry drain, enclosing one ``read`` per device-to-host
  copy (``repro_flush_host_reads_total``).
* ``compile`` — an instant per executable a dispatch had to obtain,
  compiled or loaded from the persistent compilation cache (JAX's
  ``backend_compile_duration`` event), tagged with the innermost open span
  as ``site``; also ``repro_compiles_total{site=}``.

To see the program's spans and the device's operations on one timeline,
run under the JAX profiler::

    with jax.profiler.trace("/tmp/trace"):
        pool.step(100)
        pool.flush("tenant0")

and open the ``.xplane.pb`` in TensorBoard or Perfetto, or read it with
``jax.profiler.ProfileData``: the ``repro.*`` events sit on the host plane
beside the launches (``PJRT_LoadedExecutable_Execute``) of the programs
they ran.
"""
from __future__ import annotations

import os
from typing import Any

import jax

from repro.obs.metrics import MetricsRegistry, us_per_tick
from repro.obs.trace import Tracer, annotate

__all__ = [
    "ChunkTimer",
    "configure",
    "enabled",
    "event",
    "gauge",
    "inc",
    "observe",
    "registry",
    "remove_gauge",
    "span",
    "tracer",
    "us_per_tick",
]


def _env_enabled() -> bool:
    return os.environ.get("REPRO_OBS", "1").strip().lower() not in (
        "0", "false", "off", "no")


_enabled: bool = _env_enabled()
_tracer = Tracer()
_registry = MetricsRegistry()


def enabled() -> bool:
    """Whether instrumentation currently records anything."""
    return _enabled


def configure(*, enabled: bool | None = None,
              trace_capacity: int | None = None,
              reset: bool = False) -> None:
    """Reconfigure the process-global plane.

    ``enabled`` flips recording (the instrumentation hooks stay in place
    either way — disabled they cost one predicate per call site);
    ``trace_capacity`` rebuilds the tracer ring at a new size;
    ``reset=True`` drops all recorded events and metric series (tests and
    examples start clean this way).
    """
    global _enabled, _tracer, _registry
    if reset:
        _tracer = Tracer(trace_capacity or _tracer.capacity)
        _registry = MetricsRegistry()
    elif trace_capacity is not None and trace_capacity != _tracer.capacity:
        _tracer = Tracer(trace_capacity)
    if enabled is not None:
        _enabled = bool(enabled)


def tracer() -> Tracer:
    return _tracer


def registry() -> MetricsRegistry:
    return _registry


class _NoopSpan:
    """`with obs.span(...) as sp:` yields None when disabled — call sites
    key their metric emission on that, so the disabled path allocates
    nothing beyond the argument dict."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc: Any) -> bool:
        return False


_NOOP_SPAN = _NoopSpan()


def span(name: str, **args: Any):
    """Record a span around the with-body; yields the live span (with
    ``dur_s`` set on exit) or None when disabled."""
    if not _enabled:
        return _NOOP_SPAN
    return _tracer.span(name, **args)


def event(name: str, **args: Any) -> None:
    if _enabled:
        _tracer.event(name, **args)


def inc(_metric: str, value: float = 1.0, **labels: Any) -> None:
    if _enabled:
        _registry.counter(_metric).inc(value, **labels)


def gauge(_metric: str, value: float, **labels: Any) -> None:
    # First param deliberately avoids the name "name": labels may carry a
    # ``name=...`` dimension (the ledger's per-registration gauge does).
    if _enabled:
        _registry.gauge(_metric).set(value, **labels)


def remove_gauge(_metric: str, **labels: Any) -> None:
    """Drop gauge series whose labels include the given subset (close /
    teardown hygiene — runs even when disabled so a close under
    ``enabled=False`` still clears series recorded while enabled)."""
    g = _registry.get(_metric)
    if g is not None and g.kind == "gauge":
        g.clear_where(**labels)


def observe(_metric: str, value: float, **labels: Any) -> None:
    if _enabled:
        _registry.histogram(_metric).observe(value, **labels)


class ChunkTimer:
    """Completed-chunk spans of one scheduler or session.

    :meth:`start` at step entry, :meth:`dispatched` with one array the
    chunk program returned, :meth:`close` at the next flush's entry. The
    chunk ends when that array is ready — all outputs of one program
    execution become ready together — so the span covers host dispatch,
    the device queue and the device's work. ``labels`` go on the ring
    event and on both chunk histograms.
    """

    __slots__ = ("labels", "_t0_us", "_n_ticks", "_ann", "_out")

    def __init__(self, **labels: Any):
        self.labels = labels
        self._n_ticks = 0
        self._t0_us = self._ann = self._out = None

    def start(self, n_ticks: int) -> None:
        self.drop()
        if not _enabled:
            return
        self._n_ticks = n_ticks
        self._out = None
        self._ann = annotate("chunk", self.labels)
        self._t0_us = _tracer.now_us()

    def dispatched(self, out: jax.Array) -> None:
        if self._t0_us is not None:
            self._out = out

    def close(self) -> None:
        """End the open chunk once its outputs are ready, waiting for
        them inside a ``ready`` span only if they are not yet: the
        flush calling this reads them next, so it waits either way."""
        if self._t0_us is None or self._out is None:
            self.drop()
            return
        if not self._out.is_ready():
            with span("ready"):
                self._out.block_until_ready()
        t1_us = _tracer.now_us()
        dur_s = (t1_us - self._t0_us) / 1e6
        _tracer.complete("chunk", self._t0_us, t1_us,
                         n_ticks=self._n_ticks, **self.labels)
        observe("repro_serve_chunk_latency_ms", dur_s * 1e3, **self.labels)
        observe("repro_serve_us_per_tick", us_per_tick(dur_s, self._n_ticks),
                **self.labels)
        self.drop()

    def drop(self) -> None:
        """Forget the open chunk, if any, untimed (no flush closed it
        before the next step; on a profiler trace it ends here)."""
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._t0_us = self._ann = self._out = None


# -- compilations ------------------------------------------------------------
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_compile(event: str, secs: float, **_: Any) -> None:
    """``jax.monitoring`` listener: one ``compile`` event and counter per
    executable compiled or loaded from the persistent cache, filed under
    the innermost span open on the calling thread (jit obtains it
    synchronously inside its dispatch)."""
    if event != _COMPILE_EVENT or not _enabled:
        return
    site = _tracer.innermost() or "none"
    _tracer.event("compile", site=site, secs=secs)
    _registry.counter("repro_compiles_total").inc(site=site)


jax.monitoring.register_event_duration_secs_listener(_on_compile)


def __getattr__(name: str):
    if name == "health":  # lazy: health imports repro.memory/core.sizing
        import repro.obs.health as health
        return health
    if name == "watch":  # lazy: watch imports jax
        import repro.obs.watch as watch
        return watch
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
