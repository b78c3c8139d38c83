"""Simulation engine: pure 1 ms-tick step function + ``lax.scan`` runner.

The MCU runs a host loop at the wall clock; on TPU the same tick semantics
are expressed as a pure function scanned over time. Order of operations per
tick follows CARLsim's kernel:

  1. read the delay-ring slot for tick t (currents that arrive now)
  2. CUBA: current = signed slot; COBA: decay conductances, add deliveries,
     derive current from (g, v)
  3. integrate neuron dynamics (Euler/RK4 substeps), detect + reset spikes
  4. draw generator (Poisson) spikes
  5. propagate spikes through every projection into slot (t + delay) mod D,
     scaling by STP where enabled  — fp16 weights, f32 matmul
  6. STDP / DA-STDP trace + weight updates

Execution strategy is selected by ``NetStatic`` (see ``repro.core.backend``):
``propagation="packed"`` (default) fuses all non-plastic projections into
one block-dense matmul per distinct (delay, receptor) bucket and one
scatter-add into the ring, with the fp16 → f32 weight decode hoisted out of
the tick scan; ``propagation="sparse"`` stores those projections CSR
(``[post, fanin]``) and computes drive by event-gated gather + segment-sum
(bytes/tick ∝ ``n_post × fanin``); ``propagation="auto"`` picks dense vs
sparse per projection by a bytes-per-tick cost model. ``backend="pallas"``
additionally routes neuron integration, the propagation matmuls/gathers,
and pair-based STDP through the Pallas TPU kernels (interpret mode on CPU).
``propagation="loop"`` is the seed per-projection reference path, kept for
benchmarking (``benchmarks/bench_engine.py``). ``run``/``run_batch``
pre-draw generator uniforms identically in every mode, so same-seed runs
are raster-comparable across modes.

Plasticity follows the same storage split: projections in
``static.plastic_csr`` keep weights / validity mask / DA eligibility as
``[post, fanin]`` CSR rows and run the gather + elementwise row updates
(``stdp_step_csr`` and friends, or the fused ``stdp_gather`` Pallas
kernel); dense-stored plastic projections run the seed outer-product
updates but share the fan-in-row *drive* (``backend.plastic_drive``) so
all non-loop modes stay bit-identical.

Throughput batching: :func:`run_batch` vmaps the scan over B independent
trials (per-trial RNG streams, shared weights) in one device program — the
packed weight images are decoded once and amortized across the batch.
Long-horizon runs can bound the generator pre-draw with ``gen_chunk``
(an outer scan draws uniforms per chunk; see :func:`run`).

Recording (``record=``, a jit-static argument):

* ``"raster"`` (default) — the seed behavior, bit-identical: outputs carry
  the full ``[T, N]`` bool spike raster.
* ``"monitors"`` — no raster is ever materialized. The compiled monitor
  specs (``static.monitors``, see ``repro.telemetry``) ride the scan carry
  as O(N)-or-smaller accumulators; outputs carry
  ``{"telemetry": {name: array}}``. This is the constant-memory long-run
  mode (telemetry state is independent of T; the pre-drawn generator
  uniforms remain the only O(T·n_gen) input buffer).
* ``"both"`` — raster and telemetry from the same ticks (the cross-check
  mode: streamed group rates are bit-for-bit equal to raster-derived ones).
* ``"none"`` — neither; the benchmark baseline for monitor overhead.

``record_v`` / ``record_i`` stay independent switches for ``[T, N]``
voltage/current traces (use ``telemetry.VoltageProbe`` for the streaming
equivalent on selected neurons).

Serving (``repro.serve`` rides these hooks): ``run(gen_base=...)`` swaps
the generator draw for a counter-keyed stream indexed by the absolute
tick, making runs call-split invariant (chunked sessions ≡ uninterrupted,
bitwise); ``tel_carry``/``return_tel_carry`` thread telemetry
accumulators across calls; ``active`` gates a scheduler lane silent.
Networks compiled with ``homeostasis_period=p`` segment the scan and
apply CARLsim's slow-timer synaptic scaling every p ticks
(:func:`_apply_homeostasis`) — the chunk-boundary homeostasis the
ROADMAP called for.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import backend as be
from repro.core import neurons as nrn
from repro.kernels import ops as kops
from repro.obs import watch as wat
from repro.telemetry import monitors as tel
from repro.core.conductance import coba_current, decay_and_deliver
from repro.core.network import CompiledNetwork, NetParams, NetState, NetStatic
from repro.core.plasticity import (
    DelaySTDPConfig,
    da_stdp_step,
    da_stdp_step_csr,
    delay_drive,
    delay_history,
    delay_stdp_step,
    homeostasis_step,
    homeostasis_step_csr,
)
from repro.core.synapses import propagate, stp_update

__all__ = ["StepOutput", "step", "run", "run_batch", "Engine"]


class StepOutput(NamedTuple):
    spikes: jax.Array  # [N] bool
    v: jax.Array  # [N] f32 membrane potential after update
    i_syn: jax.Array  # [N] f32 synaptic current delivered this tick


def step(
    static: NetStatic,
    params: NetParams,
    state: NetState,
    i_ext: jax.Array | None = None,
    dopamine: jax.Array | None = None,
    *,
    packed: tuple[jax.Array, ...] | None = None,
    gen_u: jax.Array | None = None,
    kick: jax.Array | None = None,
) -> tuple[NetState, StepOutput]:
    """One 1 ms tick. Pure; jit/scan-friendly.

    ``packed`` is the tuple of assembled f32 bucket weight images from
    :func:`repro.core.backend.assemble_packed`; ``run`` builds it once per
    device program so the scan body treats it as a loop constant. When
    calling ``step`` directly it may be omitted (assembled on the fly).

    ``gen_u`` is this tick's pre-drawn uniforms for the generator spans
    (``[static.n_gen]``, from ``run``'s batched draw outside the scan —
    ``_run_impl`` feeds it in EVERY propagation mode, loop included, so
    same-seed runs are raster-comparable across modes). When ``None`` the
    step draws per tick from ``state.key`` over the full [N] vector — the
    seed behavior, kept only for direct ``step`` calls. The two modes
    consume different RNG streams, so their rasters differ
    realization-wise (not statistically).

    ``kick`` is this tick's ``[static.kick_per_tick]`` kicked neuron ids
    (``run`` draws them; ids >= N are dropped): each gets
    ``static.kick_amp`` of input, added after the synaptic current.
    """
    f32 = jnp.float32
    t = state.t
    if (static.fused_kernel and i_ext is None
            and (gen_u is not None or static.n_gen == 0)):
        # Megakernel tick: phases 1–5 run as ONE Pallas program (ring
        # read/zero, IZH4, generator merge, tiled propagation, ring
        # commits) with the neuron/ring state VMEM-resident and weight
        # tiles streamed.  fused_kernel implies no plasticity/STP/COBA,
        # so phase 6 and the STP updates are vacuous.
        if packed is None:
            packed = be.assemble_fused(static, state.weights, params)
        return _step_kernel(static, params, state, packed, gen_u)
    if gen_u is None and static.n_gen > 0:
        key, k_gen = jax.random.split(state.key)
    else:
        # run() pre-split, or no generators at all (nothing consumes
        # per-tick RNG) — the carry key passes through untouched.
        key = state.key
    slot = jnp.mod(t, static.ring_len)

    # 1–2: delivery
    deliver = jax.lax.dynamic_index_in_dim(state.ring, slot, axis=0, keepdims=False)
    deliver = deliver.astype(f32)  # [N, C]
    ring = jax.lax.dynamic_update_index_in_dim(
        state.ring, jnp.zeros_like(deliver).astype(state.ring.dtype), slot, axis=0
    )
    cond = state.cond
    if static.coba is not None:
        cond = decay_and_deliver(static.coba, cond, deliver[:, 0], deliver[:, 1], static.dt)
        i_syn = coba_current(static.coba, cond, state.neurons.v)
    else:
        i_syn = deliver[:, 0]
    # Per-synapse-delay projections: the drive due now is read from each
    # pre group's trace history, added after the ring's delivery in
    # projection order. The gathered history also serves phase 6.
    hist = {}
    for j in static.delay_csr:
        spec = static.projections[j]
        hist[j] = delay_history(state.stdp[j], params.proj_hist_idx[j])
        i_syn = i_syn.at[spec.post_slice].add(
            delay_drive(static.stdp[j], hist[j], state.weights[j]))
    if i_ext is not None:
        i_syn = i_syn + i_ext.astype(f32)
    if kick is not None:
        i_syn = i_syn + jnp.zeros((static.n,), f32).at[kick].set(
            static.kick_amp, mode="drop")

    # 3: neuron dynamics (xla reference or fused pallas IZH4 kernel)
    new_neurons, spiked = be.update_neurons_dispatch(
        static, params, state.neurons, i_syn
    )

    # 4: Poisson generators (rate in Hz -> p per tick); two-phase schedule:
    # pulse rate during [0, until_ms), sustained rate after.
    t_ms = t.astype(f32) * static.dt
    if static.n_gen == 0:
        # No generators anywhere: skip the draw entirely (a generator-free
        # net would otherwise pay a threefry split + [N] uniforms per tick
        # for an all-False where).
        spikes = spiked
    elif gen_u is None:
        # Seed behavior: one uniform per neuron per tick from the carry key.
        in_pulse = t_ms < params.gen_until
        rate = jnp.where(in_pulse, params.gen_rate, params.gen_rate_after)
        p_fire = rate * (static.dt / 1000.0)
        gen_spikes = jax.random.uniform(k_gen, (static.n,), dtype=f32) < p_fire
        is_gen = params.neuron.model == nrn.NeuronModel.GENERATOR
        spikes = jnp.where(is_gen, gen_spikes, spiked)
    else:
        # Packed path: uniforms pre-drawn outside the scan, only for the
        # generator spans (generators are the sole per-tick RNG consumers).
        spikes = spiked
        off = 0
        for g0, sz in static.gen_spans:
            seg = slice(g0, g0 + sz)
            in_pulse = t_ms < params.gen_until[seg]
            rate = jnp.where(in_pulse, params.gen_rate[seg],
                             params.gen_rate_after[seg])
            gsp = gen_u[off:off + sz] < rate * (static.dt / 1000.0)
            spikes = spikes.at[g0:g0 + sz].set(gsp)
            off += sz

    # 5: propagation into future ring slots ("packed"/"sparse"/"auto" all
    # run the bucket plan; a bucket's kind selects matmul vs CSR gather;
    # backend="fused" collapses the whole plan into one gated dispatch)
    if static.propagation != "loop":
        if static.backend == "fused":
            if packed is None:
                packed = be.assemble_fused(static, state.weights, params)
            ring, new_stp = be.propagate_fused(
                static, params, state, spikes, ring, t, packed
            )
        else:
            if packed is None:
                packed = be.assemble_packed(static, state.weights)
            ring, new_stp = be.propagate_packed(
                static, params, state, spikes, ring, t, packed
            )
        new_stp = list(new_stp)
    else:
        ring, new_stp = _propagate_loop(static, state, spikes, ring, t)

    # 6: plasticity. CSR-stored projections (static.plastic_csr) run the
    # fan-in-row updates — gather + elementwise over [post, fanin], with
    # `mask` being the validity rows — instead of the dense outer products.
    new_weights, new_stdp = [], []
    da = dopamine if dopamine is not None else jnp.float32(0.0)
    for j, (spec, cfg, w, tr, mask) in enumerate(zip(
        static.projections, static.stdp, state.weights, state.stdp, params.masks
    )):
        if cfg is None:
            new_weights.append(w)
            new_stdp.append(None)
            continue
        pre_sp = spikes[spec.pre_slice]
        post_sp = spikes[spec.post_slice]
        if isinstance(cfg, DelaySTDPConfig):
            tr2, w2 = delay_stdp_step(cfg, tr, w, hist[j], mask, pre_sp,
                                      post_sp, t)
            new_weights.append(w2)
            new_stdp.append(tr2)
            continue
        idx = params.proj_csr_idx[j] if j in static.csr_projs else None
        if cfg.tau_elig is not None:
            if idx is not None:
                tr2, w2 = da_stdp_step_csr(cfg, tr, w, idx, mask, pre_sp,
                                           post_sp, da, static.dt)
            else:
                tr2, w2 = da_stdp_step(cfg, tr, w, mask, pre_sp, post_sp, da,
                                       static.dt)
        else:
            tr2, w2 = be.stdp_dispatch(static, cfg, tr, w, mask, pre_sp,
                                       post_sp, idx=idx)
        new_weights.append(w2)
        new_stdp.append(tr2)

    new_state = NetState(
        t=t + 1, key=key, neurons=new_neurons, ring=ring,
        weights=tuple(new_weights), stp=tuple(new_stp), stdp=tuple(new_stdp),
        cond=cond, homeo=state.homeo,
    )
    out = StepOutput(
        spikes=spikes, v=new_neurons.v.astype(f32), i_syn=i_syn
    )
    return new_state, out


def _step_kernel(static, params, state, payload, gen_u):
    """One tick via the fused Pallas megakernel (``static.fused_kernel``).

    The generator compare runs outside the kernel (same expression as the
    packed path's phase 4, vectorized over the spans into one [N] bool
    row) and the refractory countdown outside too (identically zero for
    the IZH4-only nets the kernel accepts — kept for NetState parity);
    everything else — ring read/zero, IZH4, spike merge, propagation,
    ring commits — is the single Pallas program.  Bit-identical to the
    ``backend="xla"`` tick across the whole parity matrix (asserted in
    tests), because every padded contribution is an exact ``+0.0`` and
    the shared weight tables are exactly representable.
    """
    f32 = jnp.float32
    t = state.t
    gen_row = jnp.zeros((static.n,), bool)
    if static.n_gen > 0:
        t_ms = t.astype(f32) * static.dt
        off = 0
        for g0, sz in static.gen_spans:
            seg = slice(g0, g0 + sz)
            in_pulse = t_ms < params.gen_until[seg]
            rate = jnp.where(in_pulse, params.gen_rate[seg],
                             params.gen_rate_after[seg])
            gsp = gen_u[off:off + sz] < rate * (static.dt / 1000.0)
            gen_row = gen_row.at[g0:g0 + sz].set(gsp)
            off += sz
    p = params.neuron
    is_gen = p.model == nrn.NeuronModel.GENERATOR
    v, u, spikes, ring2, i_syn = kops.fused_tick(
        static, state.neurons.v, state.neurons.u, state.ring[:, :, 0],
        gen_row, is_gen, p.a, p.b, p.c, p.d, t, payload.kernel)
    refrac = jnp.maximum(state.neurons.refrac - 1, 0).astype(jnp.int16)
    new_state = NetState(
        t=t + 1, key=state.key,
        neurons=nrn.NeuronState(v=v, u=u, refrac=refrac),
        ring=ring2[:, :, None], weights=state.weights, stp=state.stp,
        stdp=state.stdp, cond=state.cond, homeo=state.homeo,
    )
    return new_state, StepOutput(spikes=spikes, v=v.astype(f32),
                                 i_syn=i_syn)


def _propagate_loop(static, state, spikes, ring, t):
    """Seed reference path: Python loop over projections with per-projection
    ``dynamic_slice``/``dynamic_update_slice`` ring writes. Kept verbatim as
    the semantic oracle and the benchmark baseline for the packed path."""
    new_stp = []
    for spec, w, stp_state in zip(static.projections, state.weights, state.stp):
        contrib = propagate(spec, _proj(w), spikes, stp_state)  # [post] f32 signed
        dslot = jnp.mod(t + spec.delay_ms, static.ring_len)
        if static.ring_channels == 2:
            ch = 0 if spec.receptor == "exc" else 1
            contrib = jnp.abs(contrib)
        else:
            ch = 0
        patch = jax.lax.dynamic_slice(
            ring, (dslot, spec.post_start, ch), (1, spec.post_size, 1)
        )
        patch = patch + contrib.astype(ring.dtype)[None, :, None]
        ring = jax.lax.dynamic_update_slice(ring, patch, (dslot, spec.post_start, ch))
        if stp_state is not None:
            pre_sp = spikes[spec.pre_slice]
            new_stp.append(stp_update(spec.stp, stp_state, pre_sp, static.dt))
        else:
            new_stp.append(None)
    return ring, new_stp


def _proj(w: jax.Array):
    from repro.core.synapses import ProjectionParams

    return ProjectionParams(weight=w, mask=None)


_RECORD_MODES = ("raster", "monitors", "both", "none")


def _apply_homeostasis(static, state: NetState, counts: jax.Array,
                       active: jax.Array | None = None) -> NetState:
    """Chunk-boundary homeostasis — CARLsim's slow-timer synaptic scaling.

    Runs between scan segments (every ``static.homeo_period`` ticks), never
    inside the tick: ``counts`` holds each neuron's spike total over the
    elapsed segment, and passing it as the op's ``post_spikes`` with
    ``dt = period · static.dt`` makes the op's instantaneous-rate term
    ``counts · 1000 / chunk_ms`` — exactly the segment's mean rate in Hz —
    while the averaging decay becomes ``exp(-chunk_ms / tau_avg)``, one
    slow-timer update per boundary. CSR-stored projections run
    :func:`homeostasis_step_csr` on their fan-in rows, dense-stored ones
    :func:`homeostasis_step`; the per-synapse ``w · scale[post]`` product is
    identical in both layouts, so packed/sparse/auto stay bit-identical.

    ``active`` (scalar bool, serving lanes) gates the whole update: an idle
    lane is silent, and without the gate its below-target average would
    grow every plastic weight toward ``w_max`` while it waits.
    """
    chunk_ms = static.homeo_period * static.dt
    new_w = list(state.weights)
    new_h = list(state.homeo)
    for j, cfg in enumerate(static.homeo):
        if cfg is None:
            continue
        spec = static.projections[j]
        cnt = counts[spec.post_slice]
        fn = homeostasis_step_csr if j in static.csr_projs else homeostasis_step
        avg2, w2 = fn(cfg, state.homeo[j], state.weights[j], cnt, chunk_ms)
        if active is not None:
            avg2 = jnp.where(active, avg2, state.homeo[j])
            w2 = jnp.where(active, w2, state.weights[j])
        new_h[j], new_w[j] = avg2, w2
    return state._replace(weights=tuple(new_w), homeo=tuple(new_h))


def _run_impl(
    static: NetStatic,
    params: NetParams,
    state: NetState,
    n_steps: int,
    *,
    i_ext: jax.Array | None = None,  # [T, N] optional external current
    dopamine: jax.Array | None = None,  # [T] optional DA schedule
    record: str = "raster",
    record_v: bool = False,
    record_i: bool = False,
    gen_chunk: int | None = None,
    gen_base: jax.Array | None = None,  # session counter-keyed gen stream
    tel_carry: tuple | None = None,  # resume telemetry accumulators
    return_tel_carry: bool = False,
    watch_carry: tuple | None = None,  # resume watchpoint accumulators
    active: jax.Array | None = None,  # scalar bool: serving-lane gate
):
    if record not in _RECORD_MODES:
        raise ValueError(f"record must be one of {_RECORD_MODES}, got {record!r}")
    if gen_chunk is not None and gen_chunk < 1:
        raise ValueError(f"gen_chunk must be >= 1, got {gen_chunk}")
    if gen_base is not None and gen_chunk is not None:
        raise ValueError(
            "gen_base and gen_chunk are mutually exclusive — a session "
            "stream is already bounded per call by the chunk size")
    # A chunk covering the whole run degenerates to the whole-run draw
    # (bitwise identical, and the buffer is min(T, gen_chunk) ticks wide
    # either way — the O(gen_chunk) bound still holds).
    chunked = (gen_chunk is not None and static.n_gen > 0
               and gen_chunk < n_steps)
    if chunked and n_steps % gen_chunk:
        raise ValueError(
            f"gen_chunk ({gen_chunk}) must divide n_steps ({n_steps}) — the "
            "chunked pre-draw scans whole chunks"
        )
    has_homeo = (static.homeo_period > 0
                 and any(h is not None for h in static.homeo))
    if has_homeo:
        if n_steps % static.homeo_period:
            raise ValueError(
                f"n_steps ({n_steps}) must be a multiple of the homeostasis "
                f"period ({static.homeo_period}) — the slow timer fires at "
                "whole-segment boundaries (chunked serving calls must keep "
                "their chunk size a multiple of the period)")
        if chunked and gen_chunk != static.homeo_period:
            raise ValueError(
                f"gen_chunk ({gen_chunk}) must equal the homeostasis period "
                f"({static.homeo_period}) — both ride the same outer scan")
    want_raster = record in ("raster", "both")
    want_mon = record in ("monitors", "both")
    # Watchpoints are compiled into the network (NetStatic.watches), not
    # chosen per call: when present their accumulators ride EVERY run and
    # the final carry is always returned (outputs["watch_carry"]) so the
    # fold is never dead code. With watches=() the carry slot is an empty
    # pytree and the program is byte-identical to a watch-free build.
    want_watch = bool(static.watches)
    if want_mon and not static.monitors:
        raise ValueError(
            "record requests monitors but the network was compiled with "
            "monitors=() — pass monitor specs (or 'default') to compile()"
        )
    if return_tel_carry and not want_mon:
        raise ValueError("return_tel_carry requires record='monitors'/'both'")

    ie_xs = i_ext if i_ext is not None else jnp.zeros((n_steps, 0), jnp.float32)
    da_xs = (
        dopamine.reshape(n_steps, 1)
        if dopamine is not None
        else jnp.zeros((n_steps, 0), jnp.float32)
    )
    # Local step index for telemetry/watch strides; width-0 when neither
    # is active so the raster-mode program is byte-identical.
    ix_xs = (
        jnp.arange(n_steps, dtype=jnp.int32).reshape(n_steps, 1)
        if want_mon or want_watch
        else jnp.zeros((n_steps, 0), jnp.int32)
    )

    # Hoist the bucket weight-payload assembly (+ fp16 -> f32 decode) out
    # of the tick scan: non-plastic weights are loop-invariant, so the scan
    # body closes over the decoded images / CSR rows as constants.
    if static.propagation == "loop":
        packed = None
    elif static.backend == "fused":
        packed = be.assemble_fused(static, state.weights, params)
    else:
        packed = be.assemble_packed(static, state.weights)

    # Pre-draw all generator uniforms in one vectorized call outside the
    # scan (threefry on [T, n_gen] at once instead of a small per-tick draw
    # over the full [N]) and feed them as scan inputs. This applies to
    # EVERY propagation mode — including "loop" — so all modes consume the
    # same RNG stream and their rasters are directly comparable (the
    # cross-mode parity suite asserts bitwise equality on Synfire4).
    # Direct ``step`` calls (gen_u=None) keep the seed per-tick draw.
    #
    # ``gen_chunk`` bounds that buffer: instead of one [T, n_gen] draw, an
    # outer scan draws [gen_chunk, n_gen] per chunk from per-chunk keys
    # (``jax.random.split(k_draw, T // gen_chunk)``) — the only remaining
    # O(T·n_gen) allocation of a ``record="monitors"`` run becomes
    # O(gen_chunk·n_gen), enabling unbounded streaming horizons. KEYING
    # CHANGE: chunked runs consume a different (equally deterministic)
    # uniform stream than the whole-run draw — same seed ⇒ same raster at
    # a fixed chunk size, but chunked vs unchunked (or different chunk
    # sizes) are different realizations of the same generator statistics.
    # ``gen_base`` (sessions, repro.serve): a COUNTER-KEYED stream — tick
    # t's uniforms come from ``fold_in(gen_base, t)`` with t the *absolute*
    # tick (``state.t`` carries across calls), so the realized stimulus
    # depends only on (gen_base, t), never on how the horizon is cut into
    # calls. That is the chunked-serving bit-identity guarantee: one
    # run(T) and k chunked run(T/k) calls consume identical uniforms at
    # identical ticks. The carry key is left untouched (nothing else draws
    # per-tick RNG), so the final NetState is bitwise call-split-invariant
    # too. Yet another keyed stream than the whole-run or gen_chunk draws —
    # same generator statistics, different realization, equally
    # deterministic.
    k_draw = None
    if static.n_gen > 0 and gen_base is None:
        k_draw, k_carry = jax.random.split(state.key)
        state = state._replace(key=k_carry)
    if static.n_gen > 0 and gen_base is not None:
        ts = state.t + jnp.arange(n_steps, dtype=jnp.int32)
        tick_keys = jax.vmap(lambda i: jax.random.fold_in(gen_base, i))(ts)
        gu_xs = jax.vmap(lambda k: jax.random.uniform(
            k, (static.n_gen,), dtype=jnp.float32))(tick_keys)
    elif static.n_gen > 0 and not chunked:
        gu_xs = jax.random.uniform(k_draw, (n_steps, static.n_gen),
                                   dtype=jnp.float32)
    else:
        gu_xs = jnp.zeros((n_steps, 0), jnp.float32)
    if active is not None and gu_xs.shape[-1]:
        # Idle serving lanes draw no generator spikes (uniform 1.0 is never
        # < p): the network relaxes to rest and emits no events.
        gu_xs = jnp.where(active, gu_xs, 1.0)
    # Kick input: tick t's ids are ``randint(fold_in(base, t), (k,), 0, N)``
    # with t the absolute tick and base the session stream (``gen_base``),
    # else a key split from the carry — compile() keeps kicks and
    # generators apart, so the two never share a stream.
    kick_xs = jnp.zeros((n_steps, 0), jnp.int32)
    if static.kick_per_tick:
        base = gen_base
        if base is None:
            base, k_carry = jax.random.split(state.key)
            state = state._replace(key=k_carry)
        ts = state.t + jnp.arange(n_steps, dtype=jnp.int32)
        kick_xs = jax.vmap(lambda i: jax.random.randint(
            jax.random.fold_in(base, i), (static.kick_per_tick,), 0,
            static.n))(ts)
        if active is not None:
            kick_xs = jnp.where(active, kick_xs, static.n)  # dropped

    tel0 = (tel_carry if tel_carry is not None else
            tel.init_carry(static, n_steps)) if want_mon else ()
    watch0 = (watch_carry if watch_carry is not None else
              wat.init_carry(static)) if want_watch else ()
    # Per-neuron spike counts over the current homeostasis segment, reset
    # at each boundary (the slow timer's input; empty slot when disabled).
    cnt0 = jnp.zeros((static.n,), jnp.int32) if has_homeo else ()

    def body_wrap(carry, xs):
        st, tel_c, wat_c, cnt = carry
        ie, da, gu, kk, ix = xs
        ie = ie if ie.shape[-1] else None  # static shape: decided at trace time
        da = da[0] if da.shape[-1] else None
        gu = gu if gu.shape[-1] else None
        kk = kk if kk.shape[-1] else None
        new_state, out = step(static, params, st, ie, da, packed=packed,
                              gen_u=gu, kick=kk)
        if want_mon:
            # Monitors fold this tick's observables into the carry — pure
            # reads of the step output, so the dynamics (and the raster, if
            # also recorded) are untouched.
            tel_c, tel_ys = tel.update(static, tel_c, ix[0], out.spikes,
                                       out.v, new_state.weights, t=st.t)
        else:
            tel_ys = None
        if want_watch:
            # Watchpoints are the same pure-read fold: O(1) health
            # reductions that never feed back into the dynamics.
            wat_c = wat.update(static, wat_c, ix[0], out.spikes,
                               out.v, new_state.weights,
                               stdp=new_state.stdp)
        if has_homeo:
            cnt = cnt + out.spikes.astype(jnp.int32)
        ys = (out.spikes if want_raster else None,
              out.v if record_v else None,
              out.i_syn if record_i else None,
              tel_ys)
        return (new_state, tel_c, wat_c, cnt), ys

    # Segment the scan when anything fires at sub-run boundaries: the
    # homeostasis slow timer and/or the per-chunk generator draw. Both ride
    # ONE outer scan (their periods are forced equal above).
    seg_len = static.homeo_period if has_homeo else (
        gen_chunk if chunked else None)
    if seg_len is None:
        (final, tel_final, watch_final, _), ys = jax.lax.scan(
            body_wrap, (state, tel0, watch0, cnt0),
            (ie_xs, da_xs, gu_xs, kick_xs, ix_xs), length=n_steps)
    else:
        n_seg = n_steps // seg_len

        def resh(x):
            return x.reshape((n_seg, seg_len) + x.shape[1:])

        if chunked:
            xs = (jax.random.split(k_draw, n_seg),
                  resh(ie_xs), resh(da_xs), resh(kick_xs), resh(ix_xs))
        else:
            xs = (resh(ie_xs), resh(da_xs), resh(gu_xs), resh(kick_xs),
                  resh(ix_xs))

        def seg_body(carry, seg_xs):
            if chunked:
                key_c, ie_c, da_c, kk_c, ix_c = seg_xs
                gu_c = jax.random.uniform(key_c, (seg_len, static.n_gen),
                                          dtype=jnp.float32)
                if active is not None:
                    gu_c = jnp.where(active, gu_c, 1.0)
            else:
                ie_c, da_c, gu_c, kk_c, ix_c = seg_xs
            carry, seg_ys = jax.lax.scan(body_wrap, carry,
                                         (ie_c, da_c, gu_c, kk_c, ix_c),
                                         length=seg_len)
            if has_homeo:
                st, tel_c, wat_c, cnt = carry
                st = _apply_homeostasis(static, st, cnt, active)
                carry = (st, tel_c, wat_c, jnp.zeros_like(cnt))
            return carry, seg_ys

        (final, tel_final, watch_final, _), ys = jax.lax.scan(
            seg_body, (state, tel0, watch0, cnt0), xs, length=n_seg)
        # Per-tick outputs come back [n_seg, seg_len, ...]; flatten the
        # segment axes so every record mode sees the usual [T, ...].
        ys = jax.tree.map(
            lambda y: y.reshape((n_steps,) + y.shape[2:]), ys)
    spikes, v, i, tel_ys = ys
    outputs = {}
    if want_raster:
        outputs["spikes"] = spikes
    if record_v:
        outputs["v"] = v
    if record_i:
        outputs["i_syn"] = i
    if want_mon:
        outputs["telemetry"] = tel.collect(static, tel_final, tel_ys)
        if return_tel_carry:
            # Raw accumulators, resumable: feed back as ``tel_carry`` on
            # the next chunked call (repro.serve.SessionMonitors).
            outputs["tel_carry"] = tel_final
    if want_watch:
        # Raw watch accumulators — always returned for compiled watches
        # (feed back as ``watch_carry``; drain host-side with
        # ``repro.obs.watch.drain`` at chunk/flush boundaries).
        outputs["watch_carry"] = watch_final
    return final, outputs


@partial(jax.jit, static_argnames=("static", "n_steps", "record", "record_v",
                                   "record_i", "gen_chunk",
                                   "return_tel_carry"))
def run(
    static: NetStatic,
    params: NetParams,
    state: NetState,
    n_steps: int,
    *,
    i_ext: jax.Array | None = None,
    dopamine: jax.Array | None = None,
    record: str = "raster",
    record_v: bool = False,
    record_i: bool = False,
    gen_chunk: int | None = None,
    gen_base: jax.Array | None = None,
    tel_carry: tuple | None = None,
    return_tel_carry: bool = False,
    watch_carry: tuple | None = None,
    active: jax.Array | None = None,
):
    """Scan ``step`` for ``n_steps`` ticks; returns (state, outputs).

    ``record="raster"`` (default): outputs["spikes"] is the [T, N] bool
    raster (the paper's correctness metric is total spike count over 1 s of
    model time). ``record="monitors"``: no raster — outputs["telemetry"]
    holds the compiled in-scan monitor accumulators (constant device memory
    in T; see ``repro.telemetry``). ``"both"`` / ``"none"`` as named.

    ``gen_chunk`` (must divide ``n_steps``) draws the generator uniforms
    per chunk via an outer scan instead of one [T, n_gen] buffer — with
    ``record="monitors"`` the whole program is then O(gen_chunk) in the
    horizon. Chunked draws consume a different (still seed-deterministic)
    RNG stream than the whole-run draw; a chunk >= ``n_steps`` degenerates
    to the whole-run draw bitwise. See ``_run_impl``.

    Serving extensions (``repro.serve`` is the intended caller):

    * ``gen_base`` — counter-keyed generator stream: tick t draws from
      ``fold_in(gen_base, t)`` with t the absolute ``state.t``, making the
      run **call-split invariant**: one ``run(T)`` and k chunked calls of
      ``run(T/k)`` (state threaded through) produce bit-identical rasters,
      weights, and final state. Mutually exclusive with ``gen_chunk``.
    * ``tel_carry`` / ``return_tel_carry`` — resume the in-scan monitor
      accumulators from a previous call and hand the raw final carry back
      (``outputs["tel_carry"]``), so telemetry accumulates across an
      unbounded chunk sequence with periodic host flushes.
    * ``active`` — scalar bool lane gate: when False the generators are
      silenced and homeostasis holds, so an idle serving lane parks at rest
      and contributes no spike events.
    * ``watch_carry`` — resume in-scan watchpoint accumulators
      (``repro.obs.watch``; compiled via ``compile(watches=...)``). When
      the network carries watches, ``outputs["watch_carry"]`` is always
      returned; drain it host-side at chunk boundaries.

    Networks compiled with ``homeostasis_period=p`` apply CARLsim's
    slow-timer synaptic scaling every p ticks from in-scan segment spike
    counts (``n_steps`` must be a multiple of p; see
    :func:`_apply_homeostasis`).
    """
    return _run_impl(static, params, state, n_steps, i_ext=i_ext,
                     dopamine=dopamine, record=record, record_v=record_v,
                     record_i=record_i, gen_chunk=gen_chunk,
                     gen_base=gen_base, tel_carry=tel_carry,
                     return_tel_carry=return_tel_carry,
                     watch_carry=watch_carry, active=active)


@partial(jax.jit, static_argnames=("static", "n_steps", "batch", "record",
                                   "record_v", "record_i", "gen_chunk"))
def run_batch(
    static: NetStatic,
    params: NetParams,
    state: NetState,
    n_steps: int,
    batch: int,
    *,
    record: str = "raster",
    record_v: bool = False,
    record_i: bool = False,
    gen_chunk: int | None = None,
):
    """Simulate ``batch`` independent trials in ONE device program.

    Each trial forks its own RNG stream from ``state.key`` (so generator
    spike schedules differ per trial — B independent stimulus draws); all
    other initial state and the weights are shared and broadcast by vmap.
    The packed weight images are decoded once and amortized across the
    batch — this is the throughput-serving configuration, benchmarked by
    ``benchmarks/bench_engine.py`` at B ∈ {1, 8, 64}.

    Returns ``(final_states, outputs)`` with a leading ``[batch]`` axis on
    every leaf (``outputs["spikes"]``: [B, T, N]).
    """
    keys = jax.random.split(state.key, batch)
    if batch == 1:
        # No vmap for a single trial — keep event gating and the lean
        # non-batched program, just add the leading axis.
        res = _run_impl(static, params, state._replace(key=keys[0]), n_steps,
                        record=record, record_v=record_v, record_i=record_i,
                        gen_chunk=gen_chunk)
        return jax.tree.map(lambda x: x[None], res)

    # Event gating uses lax.cond on a per-trial predicate; under vmap that
    # lowers to "compute both branches + select", so turn it off — the
    # batched matmuls amortize the weight traffic anyway.
    static_b = dataclasses.replace(static, event_gated=False)

    def one_trial(key):
        return _run_impl(static_b, params, state._replace(key=key), n_steps,
                         record=record, record_v=record_v, record_i=record_i,
                         gen_chunk=gen_chunk)

    return jax.vmap(one_trial)(keys)


@dataclasses.dataclass
class Engine:
    """Convenience wrapper binding a compiled network."""

    net: CompiledNetwork

    def run(self, n_steps: int, state: NetState | None = None, **kw):
        state = state if state is not None else self.net.state0
        if self.net.partition is not None:
            return self._run_partitioned(n_steps, state, **kw)
        # Host-side span around the jit DISPATCH only — nothing inside the
        # traced computation changes, so results are bitwise identical
        # with obs on/off (tests/test_obs.py).
        with obs.span("dispatch", n_ticks=n_steps):
            out = run(self.net.static, self.net.params, state, n_steps,
                      **kw)
        obs.inc("repro_engine_ticks_total", float(n_steps))
        return out

    def _run_partitioned(self, n_steps: int, state: NetState,
                         record: str = "raster", **kw):
        """Route a partitioned network through its compiled lowering.

        The per-core programs support the raster/none record modes only
        (in-scan monitors are per-program state in v1); any other engine
        kwarg is a feature the partitioned path does not express yet, so
        reject loudly rather than silently diverge from ``run``."""
        from repro.core import partition as part

        if kw:
            raise part.PartitionError(
                "partitioned runs accept record='raster'/'none' only — "
                f"unsupported kwargs: {sorted(kw)}")
        plan = self.net.partition
        fn = (part.run_partitioned if plan.spec.lowering == "sequential"
              else part.run_partitioned_mesh)
        if not obs.enabled():
            return fn(self.net.static, plan, plan.run_params, state,
                      n_steps, record)
        with obs.span("partition_run", lowering=plan.spec.lowering,
                      n_cores=plan.n_cores, n_ticks=n_steps,
                      record=str(record)):
            out = fn(self.net.static, plan, plan.run_params, state,
                     n_steps, record)
        obs.inc("repro_partition_ticks_total", float(n_steps))
        obs.inc("repro_partition_exchange_bytes_total",
                float(plan.exchange.bytes_per_tick) * n_steps)
        obs.inc("repro_engine_ticks_total", float(n_steps))
        return out

    def run_batch(self, n_steps: int, batch: int,
                  state: NetState | None = None, **kw):
        """B independent trials in one device program; see :func:`run_batch`."""
        if self.net.partition is not None:
            from repro.core.partition import PartitionError

            raise PartitionError(
                "run_batch is not supported on a partitioned network — "
                "vmap over cores would replicate every core's tables per "
                "trial; run trials through a ServePool instead")
        state = state if state is not None else self.net.state0
        with obs.span("dispatch", n_ticks=n_steps, batch=batch):
            out = run_batch(self.net.static, self.net.params, state,
                            n_steps, batch, **kw)
        obs.inc("repro_engine_ticks_total", float(n_steps) * batch)
        return out

    def spike_counts(self, n_steps: int, **kw) -> jax.Array:
        _, out = self.run(n_steps, **kw)
        return out["spikes"].sum(axis=0)

    def run_monitored(self, n_steps: int, state: NetState | None = None,
                      **kw) -> tuple[NetState, dict]:
        """Constant-memory run: scan with in-scan monitors only (no [T, N]
        raster) and return ``(final_state, summary)`` where ``summary`` is
        the host-side ``repro.telemetry.summarize`` dict (exact group spike
        counts/rates, filtered rates, probe traces)."""
        from repro.telemetry import summarize

        final, out = self.run(n_steps, state=state, record="monitors", **kw)
        return final, summarize(self.net.static, out["telemetry"], n_steps)
