"""Long-term plasticity: pair-based STDP and dopamine-modulated STDP.

Part of CARLsim's "full feature set" the paper ports (STDP, neuromodulation).
Pair-based STDP with exponential windows is implemented with per-neuron
pre/post traces; DA-STDP keeps a per-synapse eligibility trace gated by a
scalar dopamine signal, CARLsim-style.

Every weight-touching op exists in two storage layouts:

* dense ``[n_pre, n_post]`` rectangles (``stdp_step`` / ``da_stdp_step`` /
  ``homeostasis_step``) — full outer products per tick, the seed layout;
* CSR fan-in rows ``[n_post, fanin]`` (``stdp_step_csr`` /
  ``da_stdp_step_csr`` / ``homeostasis_step_csr``) — the per-synapse update
  ``dw[q, k] = a⁺·pre_trace[idx[q, k]]·post_sp[q] −
  a⁻·pre_sp[idx[q, k]]·post_trace[q]`` as a gather + elementwise pass,
  O(n_post·fanin) work and bytes instead of O(n_pre·n_post).

Pair-based STDP is *per-synapse independent*: each weight's update reads
only its own value, the two per-neuron traces, and the two spike bits. The
CSR ops therefore express the exact same f32 expression tree per synapse as
the dense ops (same association, same clip, same storage-dtype cast), so a
CSR row and its dense twin stay **bit-identical** through any spike history
— the contract ``tests/test_properties.py`` asserts under hypothesis in
fp32 and fp16.

All exponential decay factors (``exp(-dt/tau)``) are compile-time Python
floats (``math.exp``): ``dt`` and every ``tau`` are static configuration,
so the scan body closes over a baked constant instead of carrying a
per-trace ``jnp.exp`` op.
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["STDPConfig", "STDPState", "stdp_step", "stdp_step_csr",
           "DASTDPState", "da_stdp_step", "da_stdp_step_csr",
           "DelaySTDPConfig", "DelaySTDPState", "init_delay_stdp_state",
           "delay_history", "delay_drive", "delay_stdp_step",
           "HomeostasisConfig", "homeostasis_step", "homeostasis_step_csr"]


@dataclasses.dataclass(frozen=True)
class STDPConfig:
    a_plus: float = 0.004
    a_minus: float = 0.0033
    tau_plus: float = 20.0  # ms
    tau_minus: float = 20.0  # ms
    w_min: float = 0.0
    w_max: float = 10.0
    # DA modulation (None -> plain STDP)
    tau_elig: float | None = None  # eligibility decay for DA-STDP


class STDPState(NamedTuple):
    pre_trace: jax.Array  # [n_pre] f32
    post_trace: jax.Array  # [n_post] f32


class DASTDPState(NamedTuple):
    pre_trace: jax.Array
    post_trace: jax.Array
    elig: jax.Array  # [n_pre, n_post] dense / [n_post, fanin] CSR


def init_stdp_state(n_pre: int, n_post: int) -> STDPState:
    return STDPState(
        pre_trace=jnp.zeros((n_pre,), jnp.float32),
        post_trace=jnp.zeros((n_post,), jnp.float32),
    )


def init_da_stdp_state(n_pre: int, n_post: int, dtype=jnp.float32,
                       *, fanin: int | None = None) -> DASTDPState:
    """``fanin`` selects the CSR eligibility layout ``[n_post, fanin]``
    (rides the fan-in rows); ``None`` keeps the dense ``[n_pre, n_post]``
    rectangle."""
    shape = (n_pre, n_post) if fanin is None else (n_post, fanin)
    return DASTDPState(
        pre_trace=jnp.zeros((n_pre,), jnp.float32),
        post_trace=jnp.zeros((n_post,), jnp.float32),
        elig=jnp.zeros(shape, dtype),
    )


def _trace_step(trace: jax.Array, spikes: jax.Array, tau: float, dt: float):
    # exp(-dt/tau) baked host-side: dt and tau are static config, so the
    # decay is a Python-float constant in the scan body, not a jnp.exp op.
    return trace * math.exp(-dt / tau) + spikes.astype(jnp.float32)


def _csr_deltas(cfg: STDPConfig, pre_t, post_t, idx, pre_spikes, post_spikes):
    """LTP/LTD terms on the fan-in rows; per-cell f32 association identical
    to the dense ``a·outer(·,·)`` path (``a · (pre_term · post_term)``)."""
    ii = idx.astype(jnp.int32)
    ltp = cfg.a_plus * (
        jnp.take(pre_t, ii, axis=0)
        * post_spikes.astype(jnp.float32)[:, None]
    )
    ltd = cfg.a_minus * (
        jnp.take(pre_spikes.astype(jnp.float32), ii, axis=0)
        * post_t[:, None]
    )
    return ltp, ltd


def stdp_step(
    cfg: STDPConfig,
    state: STDPState,
    weight: jax.Array,  # [pre, post] storage dtype
    mask: jax.Array,  # [pre, post] bool
    pre_spikes: jax.Array,  # [pre] bool
    post_spikes: jax.Array,  # [post] bool
    dt: float = 1.0,
) -> tuple[STDPState, jax.Array]:
    """One tick of pair-based STDP; returns (state', new_weight).

    LTP: post spike at t_post after pre trace -> Δw = +A⁺·pre_trace.
    LTD: pre spike at t_pre after post trace -> Δw = −A⁻·post_trace.
    Weights clipped to [w_min, w_max] and stored back in the storage dtype —
    plastic weights are exactly the fp16 data the paper moved to binary16.
    """
    pre_t = _trace_step(state.pre_trace, pre_spikes, cfg.tau_plus, dt)
    post_t = _trace_step(state.post_trace, post_spikes, cfg.tau_minus, dt)
    w = weight.astype(jnp.float32)
    ltp = cfg.a_plus * jnp.outer(pre_t, post_spikes.astype(jnp.float32))
    ltd = cfg.a_minus * jnp.outer(pre_spikes.astype(jnp.float32), post_t)
    w = jnp.clip(w + ltp - ltd, cfg.w_min, cfg.w_max)
    w = jnp.where(mask, w, 0.0).astype(weight.dtype)
    return STDPState(pre_trace=pre_t, post_trace=post_t), w


def stdp_step_csr(
    cfg: STDPConfig,
    state: STDPState,
    weight: jax.Array,  # [post, fanin] CSR rows, storage dtype
    idx: jax.Array,  # [post, fanin] int16/int32 presynaptic sources
    valid: jax.Array,  # [post, fanin] bool — False on row padding
    pre_spikes: jax.Array,  # [pre] bool
    post_spikes: jax.Array,  # [post] bool
    dt: float = 1.0,
) -> tuple[STDPState, jax.Array]:
    """Pair-based STDP on CSR fan-in rows: gather + elementwise,
    O(n_post·fanin). Bit-identical per synapse to :func:`stdp_step` — the
    row cell (q, k) computes the exact f32 expression the dense cell
    (idx[q, k], q) computes; ``valid`` plays the dense mask's role (padded
    cells would otherwise gather ``pre_trace[0]`` and drift off zero)."""
    pre_t = _trace_step(state.pre_trace, pre_spikes, cfg.tau_plus, dt)
    post_t = _trace_step(state.post_trace, post_spikes, cfg.tau_minus, dt)
    ltp, ltd = _csr_deltas(cfg, pre_t, post_t, idx, pre_spikes, post_spikes)
    w = weight.astype(jnp.float32)
    w = jnp.clip(w + ltp - ltd, cfg.w_min, cfg.w_max)
    w = jnp.where(valid, w, 0.0).astype(weight.dtype)
    return STDPState(pre_trace=pre_t, post_trace=post_t), w


def da_stdp_step(
    cfg: STDPConfig,
    state: DASTDPState,
    weight: jax.Array,
    mask: jax.Array,
    pre_spikes: jax.Array,
    post_spikes: jax.Array,
    dopamine: jax.Array,  # scalar DA concentration this tick
    dt: float = 1.0,
) -> tuple[DASTDPState, jax.Array]:
    """Dopamine-modulated STDP: STDP updates accumulate into an eligibility
    trace; the weight only moves when dopamine is present (dw = DA · elig)."""
    assert cfg.tau_elig is not None, "da_stdp_step requires tau_elig"
    pre_t = _trace_step(state.pre_trace, pre_spikes, cfg.tau_plus, dt)
    post_t = _trace_step(state.post_trace, post_spikes, cfg.tau_minus, dt)
    ltp = cfg.a_plus * jnp.outer(pre_t, post_spikes.astype(jnp.float32))
    ltd = cfg.a_minus * jnp.outer(pre_spikes.astype(jnp.float32), post_t)
    elig = state.elig.astype(jnp.float32)
    elig = elig * math.exp(-dt / cfg.tau_elig) + (ltp - ltd)
    w = weight.astype(jnp.float32) + dopamine * elig
    w = jnp.clip(w, cfg.w_min, cfg.w_max)
    w = jnp.where(mask, w, 0.0).astype(weight.dtype)
    new = DASTDPState(pre_trace=pre_t, post_trace=post_t,
                      elig=elig.astype(state.elig.dtype))
    return new, w


def da_stdp_step_csr(
    cfg: STDPConfig,
    state: DASTDPState,  # elig [post, fanin]
    weight: jax.Array,  # [post, fanin] CSR rows
    idx: jax.Array,  # [post, fanin]
    valid: jax.Array,  # [post, fanin] bool
    pre_spikes: jax.Array,
    post_spikes: jax.Array,
    dopamine: jax.Array,
    dt: float = 1.0,
) -> tuple[DASTDPState, jax.Array]:
    """DA-STDP on CSR fan-in rows: the eligibility trace shrinks from the
    dense ``[n_pre, n_post]`` rectangle to ``[n_post, fanin]`` — for the
    paper's fanin ≪ n_pre workloads this is where DA-modulated learning
    stops dominating the memory ledger. Synapse cells evolve bit-identically
    to :func:`da_stdp_step` (padded cells accumulate junk eligibility, as
    masked-out dense cells do, and are zeroed in the weight by ``valid``)."""
    assert cfg.tau_elig is not None, "da_stdp_step_csr requires tau_elig"
    pre_t = _trace_step(state.pre_trace, pre_spikes, cfg.tau_plus, dt)
    post_t = _trace_step(state.post_trace, post_spikes, cfg.tau_minus, dt)
    ltp, ltd = _csr_deltas(cfg, pre_t, post_t, idx, pre_spikes, post_spikes)
    elig = state.elig.astype(jnp.float32)
    elig = elig * math.exp(-dt / cfg.tau_elig) + (ltp - ltd)
    w = weight.astype(jnp.float32) + dopamine * elig
    w = jnp.clip(w, cfg.w_min, cfg.w_max)
    w = jnp.where(valid, w, 0.0).astype(weight.dtype)
    new = DASTDPState(pre_trace=pre_t, post_trace=post_t,
                      elig=elig.astype(state.elig.dtype))
    return new, w


# -- Izhikevich (2006) STDP on per-synapse delays --------------------------------
#
# The polychronization network's rule (``spnet.cpp``), on projections whose
# synapses each carry their own axonal delay. Its weights, accumulator and
# tables are stored fan-in-major, ``[fanin, n_post]``: column ``q`` is post
# neuron ``q``'s fan-in row, entries in ascending (pre, delay) order with
# the row's padding at its end.


@dataclasses.dataclass(frozen=True)
class DelaySTDPConfig:
    """Nearest-spike STDP with a once-a-period weight update (Izhikevich,
    *Polychronization: computation with spikes*, 2006; CARLsim's weight
    and weight-change update interval).

    Each pre neuron keeps a trace LTP, set to ``ltp_amp`` on its spike tick
    and multiplied by ``decay`` at every later tick; each post neuron a
    trace LTD, likewise with ``ltd_amp``. Per synapse ``p -> q`` of delay
    ``d``: when ``q`` fires at ``t``, ``sd += LTP_p`` as it stood after tick
    ``t - d``; when a spike of ``p`` arrives at ``t`` (emitted at
    ``t - d``), ``sd -= LTD_q`` as it stood after tick ``t - 1``. At every
    tick with ``t % update_every == update_every - 1``, after that tick's
    terms: ``w <- clip((w + bias) + sd, w_min, w_max)``, then
    ``sd <- sd_decay * sd``.
    """

    ltp_amp: float = 0.1
    ltd_amp: float = 0.12
    decay: float = 0.95
    update_every: int = 1000
    bias: float = 0.01
    sd_decay: float = 0.9
    w_min: float = 0.0
    w_max: float = 10.0

    def __post_init__(self):
        # A trace equals ltp_amp only on its spike tick (decay < 1), which
        # is how the history doubles as the pre neuron's spike record.
        if not (self.ltp_amp > 0 and 0 < self.decay < 1):
            raise ValueError("DelaySTDPConfig needs ltp_amp > 0 and "
                             "0 < decay < 1")
        if self.update_every < 1:
            raise ValueError("update_every must be >= 1")


class DelaySTDPState(NamedTuple):
    # [max_delay, n_pre] f32: row r holds each pre neuron's LTP trace as it
    # stood after tick t - 1 - r (at the start of tick t). A trace equals
    # ltp_amp exactly on its spike tick, so the history is also the record
    # of which pre neurons fired at t - 1 - r.
    ltp_hist: jax.Array
    ltd: jax.Array  # [n_post] f32: each post neuron's LTD after tick t - 1
    sd: jax.Array  # [fanin, n_post] weight dtype: weight-change accumulator


def init_delay_stdp_state(n_pre: int, n_post: int, max_delay: int,
                          fanin: int, dtype=jnp.float32) -> DelaySTDPState:
    return DelaySTDPState(
        ltp_hist=jnp.zeros((max_delay, n_pre), jnp.float32),
        ltd=jnp.zeros((n_post,), jnp.float32),
        sd=jnp.zeros((fanin, n_post), dtype),
    )


def _ordered_sum(terms: jax.Array) -> jax.Array:
    """``terms[0] + terms[1] + ... + terms[-1]`` over the leading axis, in
    float32 and in that order: the association is part of the result, so
    the same spikes and weights give the same sum on every backend (a
    ``sum`` reduction's order depends on shape and device)."""
    acc = terms[0]
    for k in range(1, terms.shape[0]):
        acc = acc + terms[k]
    return acc


def delay_history(state: DelaySTDPState, hist_idx: jax.Array) -> jax.Array:
    """Per synapse, the pre neuron's LTP trace as it stood after tick
    ``t - d`` (``d`` its delay): ``hist_idx [F, Q]`` holds
    ``(d - 1) * n_pre + pre``. Padding reads row 0, pre 0 and is masked by
    every consumer.

    The history is read as rows of two entries, the one wanted picked by
    the index's parity: XLA:TPU gathers 2-element rows ~2.8x faster than
    single elements (1.28 M slots: 3.3 ms against 9.2 ms on a v5e)."""
    flat = state.ltp_hist.reshape(-1)
    pairs = jnp.take(jnp.pad(flat, (0, flat.size % 2)).reshape(-1, 2),
                     hist_idx // 2, axis=0)
    return jnp.where(hist_idx % 2 == 0, pairs[..., 0], pairs[..., 1])


def delay_drive(cfg: DelaySTDPConfig, hist: jax.Array,
                weight: jax.Array) -> jax.Array:
    """Drive ``[n_post]`` due at tick ``t``: each post neuron's fan-in row
    summed left to right over the synapses whose pre spike arrives now
    (emitted at ``t - d``, i.e. the trace after ``t - d`` equals
    ``ltp_amp``). Padding carries weight 0, an exact ``+0.0``."""
    arrived = hist == jnp.float32(cfg.ltp_amp)
    return _ordered_sum(jnp.where(arrived, weight.astype(jnp.float32), 0.0))


def delay_stdp_step(
    cfg: DelaySTDPConfig,
    state: DelaySTDPState,
    weight: jax.Array,  # [F, Q] weight dtype
    hist: jax.Array,  # [F, Q] f32, :func:`delay_history` at this tick
    valid: jax.Array,  # [F, Q] bool — False on row padding
    pre_spikes: jax.Array,  # [n_pre] bool, tick t
    post_spikes: jax.Array,  # [n_post] bool, tick t
    t: jax.Array,  # absolute tick
) -> tuple[DelaySTDPState, jax.Array]:
    """One tick of :class:`DelaySTDPConfig` after the neuron update.

    ``sd <- (sd - LTD) + LTP`` with both terms elementwise per synapse,
    stored once in the weight dtype; then the traces step (set on a spike,
    else ``decay *``), and on the period's last tick the weight update."""
    f32 = jnp.float32
    arrived = (hist == f32(cfg.ltp_amp)) & valid
    ltd_term = jnp.where(arrived, state.ltd[None, :], 0.0)
    ltp_term = jnp.where(post_spikes[None, :] & valid, hist, 0.0)
    sd = ((state.sd.astype(f32) - ltd_term) + ltp_term).astype(state.sd.dtype)
    ltp_now = jnp.where(pre_spikes, f32(cfg.ltp_amp),
                        f32(cfg.decay) * state.ltp_hist[0])
    ltp_hist = jnp.concatenate([ltp_now[None], state.ltp_hist[:-1]])
    ltd = jnp.where(post_spikes, f32(cfg.ltd_amp), f32(cfg.decay) * state.ltd)

    def update(ws):
        w, sd = ws
        w2 = jnp.clip((w.astype(f32) + f32(cfg.bias)) + sd.astype(f32),
                      cfg.w_min, cfg.w_max)
        w2 = jnp.where(valid, w2, 0.0).astype(w.dtype)
        return w2, (f32(cfg.sd_decay) * sd.astype(f32)).astype(sd.dtype)

    every = cfg.update_every
    weight, sd = jax.lax.cond(t % every == every - 1, update, lambda ws: ws,
                              (weight, sd))
    return DelaySTDPState(ltp_hist=ltp_hist, ltd=ltd, sd=sd), weight


# -- homeostatic synaptic scaling (CARLsim setHomeostasis) ---------------------
#
# The engine applies these ops on CARLsim's SLOW TIMER, not per tick: at
# every chunk/segment boundary (``compile(homeostasis_period=p)``,
# ``engine._apply_homeostasis``) with ``post_spikes`` = the segment's
# per-neuron spike COUNTS and ``dt`` = the segment length in ms. The
# ``inst = counts · 1000/dt`` term is then exactly the segment's mean rate
# in Hz and the decay one ``exp(-segment/tau)`` step — the op works
# unchanged for both the per-tick (bool spikes, dt = tick) and boundary
# (counts, dt = period) cadences.


@dataclasses.dataclass(frozen=True)
class HomeostasisConfig:
    """Multiplicative synaptic scaling toward a target firing rate.

    Attach per connection (``NetworkBuilder.connect(homeostasis=...)``)
    together with ``compile(homeostasis_period=...)`` to run it on the
    engine's chunk-boundary slow timer (``repro.serve`` keeps the running
    average in ``NetState.homeo`` across serving chunks/checkpoints)."""

    target_hz: float = 10.0
    tau_avg_ms: float = 10_000.0  # firing-rate averaging window
    beta: float = 0.1  # scaling strength per second


def _homeostasis_scale(cfg: HomeostasisConfig, avg_rate, post_spikes, dt):
    """(new avg rate, per-post scale) shared by both storage layouts."""
    decay = math.exp(-dt / cfg.tau_avg_ms)  # compile-time constant
    inst = post_spikes.astype(jnp.float32) * (1000.0 / dt)  # Hz this tick
    new_avg = avg_rate * decay + inst * (1.0 - decay)
    err = (cfg.target_hz - new_avg) / jnp.maximum(cfg.target_hz, 1e-6)
    # per-tick scale clamped: large rate errors must not flip the sign or
    # blow up the multiplicative update (stability guard).
    scale = jnp.clip(1.0 + cfg.beta * err * (dt / 1000.0), 0.5, 1.5)
    return new_avg, scale


def homeostasis_step(
    cfg: HomeostasisConfig,
    avg_rate: jax.Array,  # [n_post] running average rate (Hz)
    weight: jax.Array,  # [pre, post]
    post_spikes: jax.Array,  # [post] bool
    dt: float = 1.0,
) -> tuple[jax.Array, jax.Array]:
    """Returns (new avg_rate, scaled weight). Incoming weights of a neuron
    firing above target shrink multiplicatively; below target they grow —
    the classic synaptic-scaling stabilizer on top of STDP."""
    new_avg, scale = _homeostasis_scale(cfg, avg_rate, post_spikes, dt)
    w = (weight.astype(jnp.float32) * scale[None, :]).astype(weight.dtype)
    return new_avg, w


def homeostasis_step_csr(
    cfg: HomeostasisConfig,
    avg_rate: jax.Array,  # [n_post]
    weight: jax.Array,  # [post, fanin] CSR rows
    post_spikes: jax.Array,  # [post] bool
    dt: float = 1.0,
) -> tuple[jax.Array, jax.Array]:
    """Homeostatic scaling on CSR fan-in rows. A dense column (all inputs
    of post neuron q) is a CSR *row*, so the per-post scale broadcasts over
    the fan-in axis — same per-synapse product as :func:`homeostasis_step`,
    O(n_post·fanin) traffic, padding stays exactly 0 (0 · scale)."""
    new_avg, scale = _homeostasis_scale(cfg, avg_rate, post_spikes, dt)
    w = (weight.astype(jnp.float32) * scale[:, None]).astype(weight.dtype)
    return new_avg, w
