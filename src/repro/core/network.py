"""Network builder — CARLsim's createGroup/connect API, compiled to pytrees.

The builder mirrors how the paper's Synfire4 network is declared in CARLsim
(groups + connection groups, Tables I/II), then ``compile()`` lowers it into
three pytrees:

  * static  — hashable topology (slices, delays, receptor types, dt, ...)
  * params  — immutable arrays (neuron parameters, connectivity masks,
              generator rates)
  * state   — mutable arrays (membrane state, **fp16 synaptic weights**,
              delay ring, STP/STDP traces, RNG key)

Weights live in *state*, not params, because STDP mutates them at runtime —
exactly the data CARLsim moved to IEEE fp16. ``compile()`` registers every
allocation against a :class:`~repro.memory.MemoryLedger` under the paper's
seven load-step names, reproducing Tables III/IV.
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import neurons as nrn
from repro.core.conductance import COBAConfig, ConductanceState, init_conductance_state
from repro.core.plasticity import (
    DASTDPState,
    DelaySTDPConfig,
    DelaySTDPState,
    HomeostasisConfig,
    STDPConfig,
    STDPState,
    init_da_stdp_state,
    init_delay_stdp_state,
    init_stdp_state,
)
from repro.core.synapses import (
    CSRFanin,
    ProjectionParams,
    ProjectionSpec,
    STPConfig,
    STPState,
    build_bernoulli,
    build_csr_direct,
    build_fanout,
    build_fixed_fanin,
    csr_layout,
    dense_to_csr,
    init_stp_state,
)
from repro.kernels import ops as kops
from repro.memory import MemoryLedger
from repro.obs import watch as wspec
from repro.precision import PrecisionPolicy, get_policy
from repro.telemetry import monitors as telem

__all__ = ["NetworkBuilder", "CompiledNetwork", "NetStatic", "NetParams",
           "NetState", "BucketSpec", "FusedPlan"]


@dataclasses.dataclass(frozen=True)
class GroupSpec:
    name: str
    start: int
    size: int
    is_generator: bool = False
    rate_hz: float = 0.0  # rate during [0, until_ms) — the stimulus pulse
    until_ms: float = math.inf
    rate_after_hz: float = 0.0  # sustained rate after the pulse


@dataclasses.dataclass(frozen=True)
class BucketSpec:
    """One propagation bucket. ``kind`` selects the execution strategy:

    * ``"dense"`` — a single block-dense ``[P, Q]`` matmul over the sorted
      union of its members' pre/post index ranges. ``members`` places each
      projection's weight block at ``(row, col)`` inside the bucket image.
      Buckets are formed per (delay, ring-channel) pair when the member
      blocks fill the union rectangle densely enough to amortize the fused
      matmul; sparse groups are split into per-projection buckets (zero
      wasted cells) that still share the hoisted f32 decode and the single
      ring scatter-add.
    * ``"sparse"`` — a single-projection CSR fan-in bucket: the member's
      weights live as ``(idx, weight) [Q, fanin]`` rows
      (``NetState.weights`` holds the CSR weight rows, the int indices sit
      in ``NetParams.bucket_csr_idx``) and propagation is an event-gated
      gather + segment-sum (``repro.kernels.syn_gather``) touching
      ``Q × fanin`` cells per tick instead of ``P × Q``.

    ``pre_start >= 0`` marks a contiguous pre union starting there (the
    spike gather lowers to a static slice)."""

    delay_ms: int
    channel: int  # ring channel: 0 = exc/signed, 1 = inh magnitude (COBA)
    p: int
    q: int
    pre_start: int  # -1 => gather via params.bucket_pre_ids
    post_start: int  # -1 => scatter via params.bucket_post_ids
    members: tuple[tuple[int, int, int], ...]  # (proj_idx, row0, col0)
    kind: str = "dense"  # "dense" (matmul) | "sparse" (CSR gather)
    fanin: int = 0  # CSR row width (sparse buckets only)


@dataclasses.dataclass(frozen=True)
class FusedPlan:
    """Compile-time tile plan for ``backend="fused"`` (one program per tick).

    The packed bucket plan is reused as the tile schedule: dense buckets
    with identical ``[P, Q]`` geometry fuse into one batched contraction
    (``dense_classes``), CSR buckets stream their fan-in rows, and the
    distinct ``delays`` drive the single ring-commit epilogue. ``tile_q`` /
    ``tile_r`` size the weight / CSR tiles the Pallas kernel streams
    through VMEM (each double-buffered tile stays under
    ``_VMEM_TILE_BYTES`` so two in-flight buffers plus the resident
    neuron state fit comfortably in a 16 MB VMEM)."""

    delays: tuple[int, ...]  # sorted distinct ring delays committed per tick
    # ((p, q), bucket_ids): dense buckets sharing a [P, Q] shape, batched
    # into one dot_general on the XLA path / one tile run on the kernel.
    dense_classes: tuple[tuple[tuple[int, int], tuple[int, ...]], ...]
    sparse_ids: tuple[int, ...]  # bucket indices executed as CSR gathers
    # True when the whole tick lowers to the single Pallas program
    # (IZH4+generators only, CUBA, euler, no plasticity/STP, contiguous
    # bucket spans); ``kernel_reason`` names the first rule a refused net
    # breaks ("" when eligible).
    kernel_ok: bool
    kernel_reason: str = ""
    tile_q: int = 128  # weight-tile columns streamed per grid step
    tile_r: int = 128  # CSR rows streamed per grid step


# VMEM budget per streamed tile buffer: double-buffering means two of
# these are in flight while the resident state (ring, v/u, traces) holds
# the rest of the ~16 MB VMEM.
_VMEM_TILE_BYTES = 512 * 1024


def _plan_fused(
    buckets: tuple[BucketSpec, ...],
    specs: tuple["ProjectionSpec", ...],
    channels: int,
    izh4_only: bool,
    method: str,
    kicks: bool = False,
) -> FusedPlan:
    delays = sorted({b.delay_ms for b in buckets} | {
        s.delay_ms for s in specs if s.plastic or s.stp is not None
    })
    classes: dict[tuple[int, int], list[int]] = {}
    sparse_ids: list[int] = []
    for bi, b in enumerate(buckets):
        if b.kind == "sparse":
            sparse_ids.append(bi)
        else:
            classes.setdefault((b.p, b.q), []).append(bi)
    refusals = (
        (channels != 1, "COBA ring (2 channels); the kernel commits one "
                        "CUBA channel"),
        (not izh4_only, "neuron models other than IZH4 and generators"),
        (method != "euler", f"method {method!r}; the kernel integrates "
                            "euler"),
        (any(s.plastic or s.stp is not None for s in specs),
         "plastic or STP projections; their weights change every tick"),
        (not all(b.pre_start >= 0 and b.post_start >= 0 for b in buckets),
         "a bucket with a non-contiguous pre or post span"),
        (kicks, "kick input; the kernel draws no kicks"),
    )
    reason = next((why for refused, why in refusals if refused), "")
    # Tile geometry: the widest streamed buffer must fit _VMEM_TILE_BYTES.
    p_pad = max((-(-b.p // 8) * 8 for b in buckets if b.kind == "dense"),
                default=8)
    f_pad = max((max(b.fanin, 1) for b in buckets if b.kind == "sparse"),
                default=1)
    tile_q = max(128, _VMEM_TILE_BYTES // (p_pad * 4) // 128 * 128)
    tile_r = max(8, _VMEM_TILE_BYTES // (f_pad * 8) // 8 * 8)
    return FusedPlan(
        delays=tuple(delays),
        dense_classes=tuple((pq, tuple(ids)) for pq, ids in classes.items()),
        sparse_ids=tuple(sparse_ids),
        kernel_ok=not reason, kernel_reason=reason,
        tile_q=int(tile_q), tile_r=int(tile_r),
    )


@dataclasses.dataclass(frozen=True)
class NetStatic:
    """Hashable network topology; closed over by the jitted step.

    Propagation mode contract (``propagation``):

    * ``"packed"`` (default) — every non-plastic/non-STP projection lowers
      to a dense bucket matmul (compile-time (delay, receptor) packing).
    * ``"sparse"`` — every non-plastic/non-STP projection lowers to a CSR
      fan-in gather bucket; its weights are *stored* CSR (``[post, fanin]``
      rows in ``NetState.weights``) so both the memory ledger and the
      per-tick byte traffic scale with ``n_post × fanin``. **Plastic**
      (non-STP) projections are forced onto CSR storage too
      (``plastic_csr``): their weights, validity mask, and DA eligibility
      all live as fan-in rows, and the engine runs the CSR-native
      gather + elementwise STDP updates (``repro.core.plasticity``).
    * ``"auto"`` — per-projection cost model: a projection (plastic or
      not) goes sparse when the dense path touches ≥
      ``_SPARSE_ADVANTAGE ×`` the CSR bytes per tick (``_csr_wins``); the
      rest pack densely as in "packed".
    * ``"loop"`` — the seed per-projection reference path (dense storage),
      kept verbatim as the semantic oracle and benchmark baseline.

    All four modes integrate identical dynamics; with exactly-representable
    weights (the Synfire tables) their spike rasters are bit-identical —
    asserted by ``tests/test_sparse.py`` / ``tests/test_backends.py``.
    Plastic projections stay bit-identical across packed/sparse/auto even
    as STDP drives their weights off the representable grid: every
    non-loop mode computes their drive and their weight updates on the
    same fan-in rows (``NetParams.proj_csr_idx``), so dense storage and
    CSR storage express the exact same f32 terms in the exact same order
    (``tests/test_plasticity_sparse.py``).
    """

    n: int
    ring_len: int
    ring_channels: int  # 1 = CUBA (signed), 2 = COBA (exc, inh magnitudes)
    dt: float
    substeps: int
    method: str
    policy_name: str
    groups: tuple[GroupSpec, ...]
    projections: tuple[ProjectionSpec, ...]
    stdp: tuple[STDPConfig | DelaySTDPConfig | None, ...]  # per projection
    coba: COBAConfig | None = None
    # -- execution strategy (see repro.core.backend) --------------------------
    backend: str = "xla"  # "xla" | "pallas" | "fused"
    propagation: str = "packed"  # "packed" | "sparse" | "auto" | "loop"
    pallas_interpret: bool = True  # interpret-mode kernels (never on a TPU)
    izh4_only: bool = False  # network is IZH4 + generators only (kernel-able)
    event_gated: bool = True  # skip a bucket's matmul when its pres are silent
    buckets: tuple[BucketSpec, ...] = ()
    # Plastic (non-STP) projections stored as CSR fan-in rows — assigned at
    # compile time (forced by propagation="sparse", cost-model-picked by
    # "auto"). They never join buckets (their weights mutate every tick);
    # the engine's per-projection plasticity/drive paths key off this.
    plastic_csr: tuple[int, ...] = ()
    # STP projections are *always* CSR-stored in non-loop modes: the
    # per-pre u·x scaling is gather-compatible (scale the pre spike row,
    # then gather), so the fan-in-row drive subsumes the old dense matmul
    # fallback and the fused kernel never needs one. Loop mode keeps
    # dense storage (it is the semantic oracle, kept verbatim).
    stp_csr: tuple[int, ...] = ()
    # Compile-time tile plan for backend="fused" (None otherwise).
    fused: FusedPlan | None = None
    # True when the fused tick runs as ONE Pallas program (TPU, or
    # REPRO_PALLAS_INTERPRET=1 forcing interpret mode); False runs the
    # single-dispatch XLA expression of the same plan (``fused.kernel_reason``
    # says why when the net itself is ineligible).
    fused_kernel: bool = False
    # Compiled in-scan monitor specs (repro.telemetry); the engine lowers
    # them into scan-carry accumulators when run(record="monitors"/"both").
    monitors: tuple[telem.MonitorSpec, ...] = ()
    # Chunk-boundary homeostasis (CARLsim's slow-timer synaptic scaling),
    # aligned with projections (None = no homeostasis). The engine applies
    # it every ``homeo_period`` ticks — between inner scan segments, never
    # inside the tick — from spike counts accumulated over the segment.
    # Only plastic non-STP projections may carry a config (their weights
    # are re-read every tick; bucketed weights are hoisted per run and
    # must stay loop-invariant).
    homeo: tuple[HomeostasisConfig | None, ...] = ()
    homeo_period: int = 0  # ticks between applications (0 = never)
    # Compiled in-scan watchpoints (repro.obs.watch); when non-empty the
    # engine folds their O(1) accumulators into the scan carry on EVERY
    # run and returns them as outputs["watch_carry"]. Pure reads of the
    # step output — outputs stay bitwise identical watch-on vs watch-off.
    watches: tuple = ()
    # Projections with a delay per synapse (``ProjectionSpec.synapse_delays``,
    # all plastic under DelaySTDPConfig): stored fan-in-major ``[fanin,
    # post]``, their drive read at delivery from the pre neurons' trace
    # history (``NetParams.proj_hist_idx``), never through the delay ring.
    delay_csr: tuple[int, ...] = ()
    # External "thalamic" input (Izhikevich 2006): every tick,
    # ``kick_per_tick`` neurons drawn uniformly from all N get
    # ``kick_amp`` of input (set semantics: a neuron drawn twice gets it
    # once). 0 = none.
    kick_per_tick: int = 0
    kick_amp: float = 0.0

    @property
    def gen_spans(self) -> tuple[tuple[int, int], ...]:
        """(start, size) of every generator group — the only neurons that
        consume per-tick RNG (the packed path draws uniforms just for
        these spans)."""
        return tuple((g.start, g.size) for g in self.groups if g.is_generator)

    @property
    def n_gen(self) -> int:
        return sum(size for _, size in self.gen_spans)

    @property
    def csr_projs(self) -> frozenset[int]:
        """Projection indices whose weights are stored CSR ``[post, fanin]``
        (members of sparse buckets plus ``plastic_csr`` plus ``stp_csr``)
        rather than dense ``[pre, post]``."""
        return frozenset(
            m[0] for b in self.buckets if b.kind == "sparse" for m in b.members
        ) | frozenset(self.plastic_csr) | frozenset(self.stp_csr)

    def group(self, name: str) -> GroupSpec:
        for g in self.groups:
            if g.name == name:
                return g
        raise KeyError(name)

    def group_slice(self, name: str) -> slice:
        g = self.group(name)
        return slice(g.start, g.start + g.size)


class NetParams(NamedTuple):
    neuron: nrn.NeuronParams
    # Per projection: [pre, post] bool for dense-stored projections;
    # [post, fanin] bool *validity rows* for plastic CSR projections (the
    # STDP mask in fan-in layout); None for non-plastic CSR projections
    # (propagation never needs a mask — padding weights are exact zeros —
    # so the dense bool rectangle is never materialized on device and its
    # ledger bytes are replaced by the CSR index table).
    masks: tuple[jax.Array | None, ...]
    gen_rate: jax.Array  # [N] Hz during the pulse (0 for non-generators)
    gen_until: jax.Array  # [N] ms pulse end
    gen_rate_after: jax.Array  # [N] Hz sustained after the pulse
    # Packed-propagation gather/scatter indices, aligned with static.buckets:
    # pre_ids[b] [P_b] selects the bucket's presynaptic spikes, post_ids[b]
    # [Q_b] are the ring columns its fused matmul scatters into.
    bucket_pre_ids: tuple[jax.Array, ...] = ()
    bucket_post_ids: tuple[jax.Array, ...] = ()
    # CSR fan-in index tables, aligned with static.buckets (None for dense
    # buckets): idx[b] [Q_b, fanin_b] int16/int32 presynaptic sources, local
    # to the bucket's pre slice. The matching weight rows live in
    # NetState.weights[proj] (storage dtype).
    bucket_csr_idx: tuple[jax.Array | None, ...] = ()
    # Per-projection fan-in index tables [post, fanin], aligned with
    # static.projections; set for every CSR-stored projection (aliasing the
    # bucket tables for non-plastic members) AND for dense-stored *plastic*
    # projections in non-loop modes. The latter use a sentinel pad (index
    # n_pre, one past the pre group — propagation appends an exact-zero
    # row/slot) instead of the CSR 0-pad, so padded drive terms are exact
    # +0.0 in both storages and dense↔CSR rasters stay bit-identical.
    proj_csr_idx: tuple[jax.Array | None, ...] = ()
    # Per projection, for ``static.delay_csr`` members: ``[fanin, post]``
    # int32 ``(delay - 1) * pre_size + pre`` — each synapse's entry in its
    # pre group's ``[max_delay, pre]`` trace history (0 on padding).
    proj_hist_idx: tuple[jax.Array | None, ...] = ()


class NetState(NamedTuple):
    t: jax.Array  # int32 tick
    key: jax.Array  # PRNG key
    neurons: nrn.NeuronState
    ring: jax.Array  # [D, N, C] storage dtype
    weights: tuple[jax.Array, ...]  # per projection [pre, post] storage dtype
    stp: tuple[STPState | None, ...]
    stdp: tuple[STDPState | DASTDPState | DelaySTDPState | None, ...]
    cond: ConductanceState | None
    # Per-projection homeostasis running-average firing rate [post_size]
    # f32 (None where static.homeo[j] is None). Lives in NetState so the
    # slow-timer state survives chunked serving calls and checkpoints.
    homeo: tuple[jax.Array | None, ...] = ()


@dataclasses.dataclass
class _PendingConnect:
    pre: str
    post: str
    fanin: int
    weight: float
    delay_ms: int
    plastic: bool
    stdp: STDPConfig | None
    stp: STPConfig | None
    da_modulated: bool
    # "fanin" (exact) | "prob" (CARLsim random connect) | "fanout" (exact
    # per pre neuron, spnet)
    mode: str = "fanin"
    homeostasis: HomeostasisConfig | None = None
    fanout: int = 0
    delays: tuple[int, ...] | None = None  # per outgoing synapse slot


class NetworkBuilder:
    """CARLsim-style declarative network construction."""

    def __init__(self, *, seed: int = 42):
        self._groups: list[tuple[str, nrn.NeuronParams | None, GroupSpec]] = []
        self._connects: list[_PendingConnect] = []
        self._cursor = 0
        self._seed = seed
        self._kicks = (0, 0.0)

    # -- groups ---------------------------------------------------------------
    def add_group(self, name: str, params: nrn.NeuronParams) -> str:
        size = int(params.model.shape[0])
        spec = GroupSpec(name=name, start=self._cursor, size=size)
        self._groups.append((name, params, spec))
        self._cursor += size
        return name

    def add_spike_generator(
        self, name: str, size: int, rate_hz: float, until_ms: float = math.inf,
        rate_after_hz: float = 0.0,
    ) -> str:
        spec = GroupSpec(
            name=name, start=self._cursor, size=size,
            is_generator=True, rate_hz=rate_hz, until_ms=until_ms,
            rate_after_hz=rate_after_hz,
        )
        self._groups.append((name, nrn.generator(size), spec))
        self._cursor += size
        return name

    def add_kicks(self, per_tick: int, amplitude: float) -> None:
        """Random "thalamic" input (Izhikevich 2006): every tick,
        ``per_tick`` neurons drawn uniformly from all N (index draws from
        the stimulus stream, see ``engine.run``) get ``amplitude`` of
        input, once even if drawn twice."""
        if per_tick < 1:
            raise ValueError(f"per_tick must be >= 1, got {per_tick}")
        self._kicks = (int(per_tick), float(amplitude))

    # -- connections ------------------------------------------------------------
    def connect(
        self,
        pre: str,
        post: str | tuple[str, ...],
        *,
        fanin: int = 0,
        weight: float,
        delay_ms: int | tuple[int, ...],
        plastic: bool = False,
        stdp: STDPConfig | DelaySTDPConfig | None = None,
        stp: STPConfig | None = None,
        da_modulated: bool = False,
        mode: str = "fanin",
        homeostasis: HomeostasisConfig | None = None,
        fanout: int = 0,
    ) -> None:
        """Add a connection group. ``post`` may name adjacent groups, whose
        span it then covers. ``mode="fanout"`` gives each pre neuron
        ``fanout`` distinct targets (:func:`~repro.core.synapses.build_fanout`);
        there ``delay_ms`` may instead be a sequence of ``fanout`` delays,
        one per outgoing synapse slot, for a projection under
        :class:`DelaySTDPConfig`."""
        delays = None
        if not isinstance(delay_ms, (int, np.integer)):
            delays = tuple(int(d) for d in delay_ms)
            if mode != "fanout" or len(delays) != fanout:
                raise ValueError("per-synapse delays need mode='fanout' and "
                                 "one delay per outgoing synapse slot")
            if not isinstance(stdp, DelaySTDPConfig):
                raise ValueError("per-synapse delays are implemented for "
                                 "DelaySTDPConfig projections only")
            delay_ms = max(delays)
        if min(delays or (delay_ms,)) < 1:
            raise ValueError("delay must be >= 1 ms (one tick)")
        if mode == "fanout" and fanout < 1:
            raise ValueError("mode='fanout' needs fanout >= 1")
        if mode != "fanout" and fanin < 1:
            raise ValueError(f"mode={mode!r} needs fanin >= 1")
        if isinstance(stdp, DelaySTDPConfig) and (
                delays is None or stp is not None or homeostasis is not None
                or da_modulated or weight < 0):
            raise ValueError("DelaySTDPConfig needs per-synapse delays, an "
                             "excitatory weight, and no STP, homeostasis or "
                             "dopamine")
        if homeostasis is not None and stp is not None:
            raise ValueError("homeostasis on STP projections is unsupported")
        self._connects.append(
            _PendingConnect(pre, post, fanin, weight, delay_ms,
                            plastic or stdp is not None or homeostasis is not None,
                            stdp, stp, da_modulated, mode, homeostasis,
                            fanout, delays)
        )

    def _span(self, names: str | tuple[str, ...]) -> GroupSpec:
        """The group ``names`` names, or the one span of adjacent groups."""
        names = (names,) if isinstance(names, str) else tuple(names)
        specs = [next(s for _, _, s in self._groups if s.name == nm)
                 for nm in names]
        for a, b in zip(specs, specs[1:]):
            if b.start != a.start + a.size:
                raise ValueError(f"groups {names} are not adjacent")
        if len(specs) == 1:
            return specs[0]
        return GroupSpec(name="+".join(names), start=specs[0].start,
                         size=sum(s.size for s in specs))

    # -- compile ------------------------------------------------------------------
    def compile(
        self,
        *,
        policy: str | PrecisionPolicy = "fp32",
        dt: float = 1.0,
        substeps: int = 2,
        method: str = "euler",
        conductances: COBAConfig | None = None,
        ledger: MemoryLedger | None = None,
        monitor_ms_hint: int = 0,
        monitors: str | tuple | None = "default",
        watches: str | tuple | None = None,
        backend: str = "xla",
        propagation: str = "packed",
        pallas_interpret: bool | None = None,
        pack_density: float = 0.5,
        homeostasis_period: int = 0,
        partition=None,
    ) -> "CompiledNetwork":
        if backend not in ("xla", "pallas", "fused"):
            raise ValueError(f"unknown backend {backend!r}")
        if propagation not in ("packed", "sparse", "auto", "loop"):
            raise ValueError(f"unknown propagation {propagation!r}")
        if backend == "fused" and propagation == "loop":
            raise ValueError(
                "backend='fused' fuses the bucketed tick — it has no "
                "per-projection loop expression; use propagation="
                "'packed'/'sparse'/'auto'")
        if any(c.homeostasis is not None for c in self._connects):
            if homeostasis_period < 1:
                raise ValueError(
                    "connections carry homeostasis configs but "
                    f"homeostasis_period is {homeostasis_period} — pass the "
                    "slow-timer period (in ticks) to compile()")
        elif homeostasis_period:
            raise ValueError(
                "homeostasis_period set but no connection has a "
                "HomeostasisConfig")
        delayed = [c for c in self._connects if c.delays is not None]
        if any(c.mode == "fanout" for c in self._connects) and (
                propagation != "sparse"):
            raise ValueError("mode='fanout' builds CSR fan-in rows: compile "
                             "with propagation='sparse'")
        if delayed and (backend == "fused" or conductances is not None
                        or partition is not None):
            raise ValueError(
                "per-synapse delays run on the xla/pallas multi-op path, "
                "CUBA, unpartitioned")
        n_kick = self._kicks[0]
        if n_kick and any(spec.is_generator for _, _, spec in self._groups):
            raise ValueError("kick input and generator groups would share "
                             "the stimulus key: use one or the other")
        if n_kick and partition is not None:
            raise ValueError("kick input runs unpartitioned")
        pallas_interpret = kops.resolve_interpret(pallas_interpret)
        if isinstance(policy, str):
            policy = get_policy(policy)
        ledger = ledger if ledger is not None else MemoryLedger()
        sdt = policy.state_storage
        wdt = policy.param_storage

        groups = tuple(spec for _, _, spec in self._groups)
        n = self._cursor

        # 1. CARLsim Init — builder bookkeeping / static tables.
        with ledger.stage("1. CARLsim Init."):
            ledger.register("static.tables", jnp.zeros((len(groups) * 16,), jnp.int32))

        # 2. Random Gen — RNG state + generator schedules.
        key = jax.random.key(self._seed)
        gen_rate = np.zeros((n,), np.float32)
        gen_until = np.full((n,), np.float32(np.inf))
        gen_rate_after = np.zeros((n,), np.float32)
        for _, _, spec in self._groups:
            if spec.is_generator:
                sl = slice(spec.start, spec.start + spec.size)
                gen_rate[sl] = spec.rate_hz
                gen_until[sl] = spec.until_ms
                gen_rate_after[sl] = spec.rate_after_hz
        gen_rate = jnp.asarray(gen_rate)
        gen_until = jnp.asarray(gen_until)
        gen_rate_after = jnp.asarray(gen_rate_after)
        with ledger.stage("2. Random Gen."):
            ledger.register("rng", (key, gen_rate, gen_until, gen_rate_after))

        # 3. Conn. Info — connectivity (host-side build), realized fan-in
        # metadata, and the propagation plan. The plan is computed *before*
        # the ledger stages so sparse-assigned projections register CSR
        # index tables instead of dense bool masks — the sizing report then
        # reflects what actually lives on device against the 8 MB budget.
        rng = np.random.default_rng(self._seed)
        specs: list[ProjectionSpec] = []
        projs: list[ProjectionParams] = []
        stdp_cfgs: list[STDPConfig | None] = []
        homeo_cfgs: list[HomeostasisConfig | None] = []
        for c in self._connects:
            gpre = self._span(c.pre)
            gpost = self._span(c.post)
            receptor = "inh" if c.weight < 0 else "exc"
            if c.delays is not None and dt != 1.0:
                raise ValueError("per-synapse delays are in ticks of 1 ms")
            spec = ProjectionSpec(
                name=f"{gpre.name}->{gpost.name}",
                pre_start=gpre.start, pre_size=gpre.size,
                post_start=gpost.start, post_size=gpost.size,
                delay_ms=int(round(c.delay_ms / dt)),
                receptor=receptor, plastic=c.plastic, stp=c.stp,
                synapse_delays=c.delays is not None,
            )
            specs.append(spec)
            if c.mode == "fanout":
                projs.append(build_fanout(rng, spec, c.fanout, c.weight,
                                          delays=c.delays,
                                          storage_dtype=wdt))
            elif gpre.size * gpost.size > _DENSE_BUILD_CELLS:
                # Too big to materialize the dense [pre, post] mask on the
                # host (a Synfire4×100 layer is 4e8 cells) — sample the
                # fan-in rows directly. Bitwise-different draws from the
                # dense builders, so the threshold keeps every network the
                # baselines cover on the dense path.
                projs.append(build_csr_direct(
                    rng, spec, c.fanin, c.weight,
                    mode=("fanin" if c.mode == "fanin" else "prob"),
                    storage_dtype=wdt))
            else:
                builder = build_fixed_fanin if c.mode == "fanin" else build_bernoulli
                projs.append(builder(rng, spec, c.fanin, c.weight, storage_dtype=wdt))
            if c.stdp is not None and c.da_modulated and c.stdp.tau_elig is None:
                c = dataclasses.replace(c, stdp=dataclasses.replace(c.stdp, tau_elig=100.0))
            stdp_cfgs.append(c.stdp)
            homeo_cfgs.append(c.homeostasis)
        for j, p in enumerate(projs):
            if isinstance(p, CSRFanin):
                specs[j] = dataclasses.replace(
                    specs[j],
                    fanin=int(p.valid.shape[1]),
                    n_syn=int(p.valid.sum()),
                )
            else:
                m = np.asarray(p.mask)
                specs[j] = dataclasses.replace(
                    specs[j],
                    fanin=int(m.sum(axis=0).max(initial=0)),
                    n_syn=int(m.sum()),
                )
        channels = 2 if conductances is not None else 1
        buckets, pre_ids, post_ids = _plan_buckets(
            tuple(specs), channels, pack_density, propagation
        )
        # Plastic (non-STP) projections never join buckets, but their
        # *storage* flips to CSR fan-in rows when forced ("sparse") or when
        # the plastic cost model wins ("auto") — weights, validity mask,
        # and DA eligibility all shrink to [post, fanin].
        plastic_csr = tuple(sorted(
            j for j, s in enumerate(specs)
            if s.plastic and s.stp is None and not s.synapse_delays
            and (propagation == "sparse"
                 or (propagation == "auto" and _csr_wins(s)))
        ))
        delay_csr = tuple(j for j, s in enumerate(specs) if s.synapse_delays)
        # STP projections go CSR in *every* non-loop mode: their per-pre
        # u·x scale composes with the fan-in gather (scale the pre spike
        # row, then gather), so the drive shares the plastic fan-in-row
        # path and the dense matmul fallback is gone from the hot loop.
        stp_csr = tuple(sorted(
            j for j, s in enumerate(specs)
            if s.stp is not None and propagation != "loop"
        ))
        csr_set = frozenset(
            m[0] for b in buckets if b.kind == "sparse" for m in b.members
        ) | frozenset(plastic_csr) | frozenset(stp_csr) | frozenset(delay_csr)
        for j, p in enumerate(projs):
            if isinstance(p, CSRFanin) and j not in csr_set:
                raise ValueError(
                    f"{specs[j].name}: {specs[j].pre_size}×"
                    f"{specs[j].post_size} is past the dense build "
                    "threshold and was sampled straight into CSR rows, but "
                    f"propagation={propagation!r} assigned it dense "
                    "storage — compile with propagation='sparse' or 'auto'")
        csr: dict[int, CSRFanin] = {
            j: (projs[j] if isinstance(projs[j], CSRFanin)
                else dense_to_csr(projs[j].mask, projs[j].weight,
                                  fanin=specs[j].fanin, storage_dtype=wdt))
            for j in sorted(csr_set)
        }
        bucket_csr_idx = tuple(
            csr[b.members[0][0]].idx if b.kind == "sparse" else None
            for b in buckets
        )
        # Per-projection fan-in tables: CSR-stored projections alias their
        # CSR idx; dense-stored plastic projections (packed mode, or auto
        # deciding dense) get a sentinel-padded table so the engine can run
        # the same fan-in-row drive/update arithmetic on the dense
        # rectangle — that shared row order is what keeps plastic runs
        # bit-identical across propagation modes.
        proj_csr_idx: list[jax.Array | None] = []
        for j, s in enumerate(specs):
            if j in delay_csr:
                proj_csr_idx.append(None)
            elif j in csr_set:
                proj_csr_idx.append(csr[j].idx)
            elif s.plastic and s.stp is None and propagation != "loop":
                # Index geometry only — no quantized weight rows, no device
                # round-trips (the rows stay in the dense rectangle).
                idx, valid = csr_layout(projs[j].mask, fanin=s.fanin)
                sent = np.where(valid, idx, s.pre_size)
                idt = (np.int16 if s.pre_size <= np.iinfo(np.int16).max
                       else np.int32)
                proj_csr_idx.append(jnp.asarray(sent.astype(idt)))
            else:
                proj_csr_idx.append(None)
        # Per-synapse-delay projections go fan-in-major ([fanin, post]):
        # each tick sums a row's entries one slot after another, and a slot
        # across all post neurons is then one contiguous vector.
        proj_hist_idx = tuple(
            jnp.asarray(np.where(
                csr[j].valid,
                (csr[j].delay - 1) * specs[j].pre_size
                + np.asarray(csr[j].idx, np.int32), 0).T.astype(np.int32))
            if j in delay_csr else None
            for j in range(len(specs)))
        # Validity rows go on device only for plastic CSR projections (the
        # STDP mask); non-plastic CSR builds never pay the transfer.
        masks = tuple(
            jnp.asarray(csr[j].valid.T) if j in delay_csr
            else jnp.asarray(csr[j].valid) if j in csr_set and p_spec.plastic
            else (None if j in csr_set else p.mask)
            for j, (p_spec, p) in enumerate(zip(specs, projs))
        )
        weights = tuple(
            csr[j].weight.T if j in delay_csr
            else csr[j].weight if j in csr_set else p.weight
            for j, p in enumerate(projs)
        )
        with ledger.stage("3. Conn. Info"):
            ledger.register("masks", tuple(m for m in masks if m is not None))
            idx_tables = tuple(t for t in (*proj_csr_idx, *proj_hist_idx)
                               if t is not None)
            if idx_tables:
                ledger.register("csr.indices", idx_tables)

        # 4. Syn. State — weights (the fp16 payload; CSR rows for sparse
        # projections), delay ring, STP.
        max_delay = max((s.delay_ms for s in specs if not s.synapse_delays),
                        default=1)
        ring_len = max_delay + 1
        ring = jnp.zeros((ring_len, n, channels), sdt)
        stp_states: list[STPState | None] = [
            init_stp_state(s.stp, s.pre_size, sdt) if s.stp is not None else None
            for s in specs
        ]
        with ledger.stage("4. Syn. State"):
            ledger.register("weights", weights)
            ledger.register("ring", ring)
            ledger.register("stp", tuple(s for s in stp_states if s is not None))

        # 5. Neuron State — v, u, refractory, conductances.
        neuron_params = nrn.concat_params([p for _, p, _ in self._groups])
        nstate = nrn.init_neuron_state(neuron_params, sdt)
        cond = init_conductance_state(n, sdt) if conductances is not None else None
        with ledger.stage("5. Neuron State"):
            ledger.register("neuron.state", nstate)
            if cond is not None:
                ledger.register("conductances", cond)

        # 6. Group State — per-neuron model parameter tables.
        with ledger.stage("6. Group State"):
            ledger.register("neuron.params", neuron_params)

        # 7. Auxiliary Data — plasticity traces + monitor buffers. The
        # telemetry accumulators (scan-carry state + probe traces over a
        # monitor_ms_hint horizon) are registered here so the sizing report
        # accounts the streaming-monitor footprint — O(groups + probes·T),
        # never the O(T·N) raster the `monitor.spikes` hint budgets for.
        stdp_states: list = []
        for j, (spec, cfg) in enumerate(zip(specs, stdp_cfgs)):
            if cfg is None:
                stdp_states.append(None)
            elif isinstance(cfg, DelaySTDPConfig):
                stdp_states.append(init_delay_stdp_state(
                    spec.pre_size, spec.post_size, spec.delay_ms, spec.fanin,
                    wdt))
            elif cfg.tau_elig is not None:
                # CSR-stored projections carry eligibility on the fan-in
                # rows — [post, fanin] instead of the [pre, post] rectangle.
                stdp_states.append(init_da_stdp_state(
                    spec.pre_size, spec.post_size, sdt,
                    fanin=spec.fanin if j in csr_set else None))
            else:
                stdp_states.append(init_stdp_state(spec.pre_size, spec.post_size))
        # Homeostasis slow-timer state: one running-average rate row per
        # homeostatic projection's post group (CARLsim keeps per-neuron
        # averages; the per-projection row is the same thing scoped to the
        # projection so chunked serving can checkpoint/carry it in
        # NetState). Homeostasis needs the per-tick weight re-read of the
        # plastic path — bucketed (hoisted) weights cannot scale mid-run.
        homeo_states: list[jax.Array | None] = []
        for j, hcfg in enumerate(homeo_cfgs):
            if hcfg is None:
                homeo_states.append(None)
                continue
            if specs[j].stp is not None or not specs[j].plastic:
                raise ValueError(
                    f"homeostasis on {specs[j].name}: only plastic non-STP "
                    "projections can scale at chunk boundaries")
            homeo_states.append(jnp.zeros((specs[j].post_size,), jnp.float32))
        mon_specs = telem.resolve(monitors, n=n, n_projections=len(specs),
                                  dt=dt)
        # Watchpoint baselines (WeightDrift) come from the state0 weights,
        # via the exact L2 expression telemetry.WeightNorm reports.
        watch_specs = wspec.resolve(
            watches, n=n, n_projections=len(specs), dt=dt,
            baseline_norms=tuple(
                float(jnp.sqrt(jnp.sum(jnp.square(w.astype(jnp.float32)))))
                for w in weights) if watches is not None else None)
        if partition is not None and watch_specs:
            raise ValueError(
                "watches are not supported on partitioned networks yet — "
                "the per-core lowerings carry no watch accumulators")
        with ledger.stage("7. Auxiliary Data"):
            ledger.register("stdp.traces", tuple(s for s in stdp_states if s is not None))
            if any(h is not None for h in homeo_states):
                ledger.register(
                    "homeo.avg_rate",
                    tuple(h for h in homeo_states if h is not None))
            if monitor_ms_hint:
                ledger.register(
                    "monitor.spikes",
                    jax.ShapeDtypeStruct((monitor_ms_hint, n), jnp.bool_),
                )
            if mon_specs:
                ledger.register(
                    "monitor.telemetry",
                    telem.carry_struct(mon_specs, n, len(specs),
                                       monitor_ms_hint or 1000),
                )
            if watch_specs:
                ledger.register(
                    "monitor.watch",
                    wspec.carry_struct(watch_specs, n, len(specs)),
                )

        model_codes = np.asarray(neuron_params.model)
        izh4_only = bool(np.all(
            (model_codes == int(nrn.NeuronModel.GENERATOR))
            | (model_codes == int(nrn.NeuronModel.IZH4))
        ))

        fused = None
        fused_kernel = False
        if backend == "fused":
            fused = _plan_fused(buckets, tuple(specs), channels,
                                izh4_only, method, kicks=bool(n_kick))
            # The Pallas program engages on TPU (native lowering) or when
            # CI forces interpret execution; other CPU runs take the
            # single-dispatch XLA expression of the same plan.
            fused_kernel = fused.kernel_ok and (
                kops.on_tpu() or bool(kops.env_interpret()))

        static = NetStatic(
            n=n, ring_len=ring_len, ring_channels=channels, dt=dt,
            substeps=substeps, method=method, policy_name=policy.name,
            groups=groups, projections=tuple(specs), stdp=tuple(stdp_cfgs),
            coba=conductances,
            backend=backend, propagation=propagation,
            pallas_interpret=pallas_interpret, izh4_only=izh4_only,
            buckets=buckets, plastic_csr=plastic_csr, stp_csr=stp_csr,
            fused=fused, fused_kernel=fused_kernel, monitors=mon_specs,
            homeo=tuple(homeo_cfgs), homeo_period=int(homeostasis_period),
            watches=watch_specs, delay_csr=delay_csr,
            kick_per_tick=n_kick, kick_amp=self._kicks[1],
        )
        params = NetParams(
            neuron=neuron_params,
            masks=masks,
            gen_rate=gen_rate,
            gen_until=gen_until,
            gen_rate_after=gen_rate_after,
            bucket_pre_ids=pre_ids,
            bucket_post_ids=post_ids,
            bucket_csr_idx=bucket_csr_idx,
            proj_csr_idx=tuple(proj_csr_idx),
            proj_hist_idx=proj_hist_idx,
        )
        state0 = NetState(
            t=jnp.int32(0), key=key, neurons=nstate, ring=ring,
            weights=weights,
            stp=tuple(stp_states), stdp=tuple(stdp_states), cond=cond,
            homeo=tuple(homeo_states),
        )
        net = CompiledNetwork(static=static, params=params, state0=state0,
                              ledger=ledger, policy=policy)
        if partition is not None:
            from repro.core.partition import plan_partition

            net.partition = plan_partition(net, partition)
        return net


# How many × fewer bytes the CSR layout must touch per tick before a
# projection is auto-assigned the sparse-gather path: a dense image streams
# sequentially through the MXU / SIMD units while a CSR row does a random
# gather per cell, so sparse must win on bytes by a healthy margin. Cost
# per tick: dense reads 4·pre·post bytes (the hoisted f32 image); CSR reads
# ≤ 8·post·fanin bytes (4-byte index — int16 tables halve this — plus the
# hoisted 4-byte f32 weight). At paper fan-ins (tens) this flips to sparse
# once pre grows to a few hundred — exactly the fanin ≪ n_pre regime.
_SPARSE_ADVANTAGE = 4.0

# Above this many pre×post cells a projection skips the dense host-side
# mask build and samples CSR fan-in rows directly (`build_csr_direct`).
# 2^25 ≈ 33.5M cells keeps every baseline network (Synfire4×10's biggest
# layer is 4M cells) bit-for-bit on the dense builders while letting
# Synfire4×100-scale layers (4e8 cells ≈ 11+ GB dense scratch) build at
# all.
_DENSE_BUILD_CELLS = 1 << 25


def _csr_wins(spec: ProjectionSpec) -> bool:
    """Cost model: bytes touched per tick, dense vs CSR fan-in layout.

    Non-plastic: dense matmul image read vs CSR index+weight gather.
    Plastic projections add the STDP traffic to both sides — the dense
    update rewrites the whole ``[pre, post]`` rectangle (storage-dtype
    read + write, ~4 B/cell at fp16) plus its bool mask every tick, while
    the CSR update touches the same ~5 B per *fan-in-row* cell (row
    read + write + validity byte). Both sides scale by a similar factor,
    so the flip point stays in the fanin ≪ n_pre regime, but the absolute
    byte gap — which is what the 8 MB budget feels — grows with the
    rectangle.
    """
    area_dense = spec.pre_size * spec.post_size
    area_csr = spec.post_size * max(spec.fanin, 1)
    dense_bytes = 4 * area_dense
    csr_bytes = 8 * area_csr
    if spec.plastic:
        dense_bytes += 5 * area_dense
        csr_bytes += 5 * area_csr
    return dense_bytes >= _SPARSE_ADVANTAGE * csr_bytes


def _plan_buckets(
    specs: tuple[ProjectionSpec, ...], channels: int, pack_density: float,
    propagation: str = "packed",
) -> tuple[tuple[BucketSpec, ...], tuple[jax.Array, ...], tuple[jax.Array, ...]]:
    """Compile-time propagation plan for non-plastic, non-STP projections.

    Each eligible projection is first assigned an execution strategy:

    * ``propagation="sparse"`` forces every eligible projection onto the
      CSR fan-in gather path (one ``kind="sparse"`` bucket each);
    * ``propagation="auto"`` applies the bytes-per-tick cost model
      (:func:`_csr_wins`) per projection;
    * ``"packed"`` / ``"loop"`` keep every projection dense (unchanged
      seed/PR-1 behavior).

    Dense-assigned projections are then grouped by (delay, ring-channel);
    each group lowers to ONE block-dense matmul over the sorted union of
    its pre/post index ranges — a member's rows/cols are a *contiguous*
    span inside the union (ranges stay contiguous under sorted-union), so
    assembly is a static-slice add. A fused union rectangle stores zeros
    wherever member blocks don't cover it, so groups whose blocks fill
    less than ``pack_density`` of the rectangle are split into
    per-projection buckets (no wasted cells); either way every bucket
    shares the hoisted fp16→f32 decode and the single ring scatter-add,
    so the per-tick cost is pure matmul + one scatter. Plastic/STP
    projections are excluded — their weights change every tick, so the
    engine keeps per-projection matmuls for them (they too feed the fused
    scatter).
    """
    grouped: dict[tuple[int, int], list[int]] = {}
    sparse_js: list[int] = []
    for j, s in enumerate(specs):
        if s.plastic or s.stp is not None:
            continue
        channel = 0 if (channels == 1 or s.receptor == "exc") else 1
        go_sparse = (propagation == "sparse"
                     or (propagation == "auto" and _csr_wins(s)))
        if go_sparse:
            sparse_js.append(j)
        else:
            grouped.setdefault((s.delay_ms, channel), []).append(j)

    buckets: list[BucketSpec] = []
    pre_ids: list[jax.Array] = []
    post_ids: list[jax.Array] = []

    for j in sparse_js:
        s = specs[j]
        buckets.append(BucketSpec(
            delay_ms=s.delay_ms,
            channel=0 if (channels == 1 or s.receptor == "exc") else 1,
            p=s.pre_size, q=s.post_size,
            pre_start=s.pre_start, post_start=s.post_start,
            members=((j, 0, 0),), kind="sparse", fanin=s.fanin,
        ))
        # pre/post spans are contiguous by construction (single projection),
        # so the gather/scatter id tables are never consulted — keep empty
        # placeholders to preserve tuple alignment with static.buckets.
        pre_ids.append(jnp.zeros((0,), jnp.int32))
        post_ids.append(jnp.zeros((0,), jnp.int32))

    def unions(members: list[int]) -> tuple[np.ndarray, np.ndarray]:
        pres = np.unique(np.concatenate([
            np.arange(specs[j].pre_start,
                      specs[j].pre_start + specs[j].pre_size)
            for j in members
        ]))
        posts = np.unique(np.concatenate([
            np.arange(specs[j].post_start,
                      specs[j].post_start + specs[j].post_size)
            for j in members
        ]))
        return pres, posts

    def emit(delay_ms: int, channel: int, members: list[int]) -> None:
        pres, posts = unions(members)
        placed = tuple(
            (j,
             int(np.searchsorted(pres, specs[j].pre_start)),
             int(np.searchsorted(posts, specs[j].post_start)))
            for j in members
        )
        p, q = int(pres.size), int(posts.size)
        pre_contig = int(pres[-1]) - int(pres[0]) + 1 == p
        post_contig = int(posts[-1]) - int(posts[0]) + 1 == q
        buckets.append(BucketSpec(
            delay_ms=delay_ms, channel=channel, p=p, q=q,
            pre_start=int(pres[0]) if pre_contig else -1,
            post_start=int(posts[0]) if post_contig else -1,
            members=placed,
        ))
        pre_ids.append(jnp.asarray(pres, jnp.int32))
        post_ids.append(jnp.asarray(posts, jnp.int32))

    def fill(members: list[int]) -> float:
        pres, posts = unions(members)
        cells = sum(specs[j].pre_size * specs[j].post_size for j in members)
        return cells / float(pres.size * posts.size)

    for (delay_ms, channel), members in grouped.items():
        if len(members) > 1 and fill(members) >= pack_density:
            emit(delay_ms, channel, members)  # whole group fuses densely
            continue
        # Second chance: merge projections sharing the same pre range (their
        # post unions are typically adjacent groups -> near-100% fill), then
        # emit the rest per-projection.
        by_pre: dict[tuple[int, int], list[int]] = {}
        for j in members:
            by_pre.setdefault(
                (specs[j].pre_start, specs[j].pre_size), []
            ).append(j)
        for sub in by_pre.values():
            if len(sub) > 1 and fill(sub) >= pack_density:
                emit(delay_ms, channel, sub)
            else:
                for j in sub:
                    emit(delay_ms, channel, [j])
    return tuple(buckets), tuple(pre_ids), tuple(post_ids)


@dataclasses.dataclass
class CompiledNetwork:
    static: NetStatic
    params: NetParams
    state0: NetState
    ledger: MemoryLedger
    policy: PrecisionPolicy
    # Set by compile(partition=PartitionSpec(...)): the core-grid plan the
    # Engine routes through (repro.core.partition).
    partition: object | None = None

    @property
    def n_neurons(self) -> int:
        return self.static.n

    @property
    def n_synapses(self) -> int:
        # From compile-time metadata, not params.masks — CSR-stored
        # projections never materialize a dense mask on device.
        return int(sum(s.n_syn for s in self.static.projections))
