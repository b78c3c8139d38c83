"""Spiking neuron models: Izhikevich 4/9-parameter, LIF — Euler and RK4.

CARLsim's "full feature set" that the paper ports to the MCU includes the
IZH4 model (eqs. 1–3 of the paper), the 9-parameter Izhikevich model, LIF,
and both forward-Euler and Runge-Kutta integration. All models are
implemented over per-neuron parameter arrays so heterogeneous networks
(RS + FS + generators in Synfire4) run as one fused update.

State is held in the policy's *storage* dtype (fp16 under the paper's
policy); all math runs in f32 — the softfp promotion analogue.
"""
from __future__ import annotations

import enum
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = [
    "NeuronModel",
    "NeuronParams",
    "NeuronState",
    "izh4",
    "izh9",
    "lif",
    "generator",
    "update_neurons",
]


class NeuronModel(enum.IntEnum):
    GENERATOR = 0  # spike generator (Poisson): no membrane dynamics
    IZH4 = 1
    IZH9 = 2
    LIF = 3


class NeuronParams(NamedTuple):
    """Per-neuron parameter arrays, all shape [N], f32 (params are small;
    the paper's memory pressure is synaptic, Table III)."""

    model: jax.Array  # int8 NeuronModel codes
    # Izhikevich (IZH4 uses a,b,c,d; IZH9 additionally C,k,vr,vt,vpeak)
    a: jax.Array
    b: jax.Array
    c: jax.Array
    d: jax.Array
    C: jax.Array
    k: jax.Array
    vr: jax.Array
    vt: jax.Array
    vpeak: jax.Array
    # LIF
    lif_tau: jax.Array
    lif_vth: jax.Array
    lif_vreset: jax.Array
    lif_vrest: jax.Array
    lif_r: jax.Array
    lif_tref: jax.Array


class NeuronState(NamedTuple):
    v: jax.Array  # [N] membrane potential (storage dtype)
    u: jax.Array  # [N] recovery variable (storage dtype)
    refrac: jax.Array  # [N] int16 refractory countdown (LIF)


# -- per-group parameter factories -------------------------------------------


def _full(n: int, val: float) -> jax.Array:
    return jnp.full((n,), val, jnp.float32)


def _defaults(n: int) -> dict:
    return dict(
        a=_full(n, 0.02), b=_full(n, 0.2), c=_full(n, -65.0), d=_full(n, 8.0),
        C=_full(n, 100.0), k=_full(n, 0.7), vr=_full(n, -60.0),
        vt=_full(n, -40.0), vpeak=_full(n, 30.0),
        lif_tau=_full(n, 10.0), lif_vth=_full(n, -50.0),
        lif_vreset=_full(n, -65.0), lif_vrest=_full(n, -65.0),
        lif_r=_full(n, 1.0), lif_tref=_full(n, 2.0),
    )


def izh4(n: int, a: float, b: float, c: float, d: float) -> NeuronParams:
    """IZH4 (paper eqs. 1–3): v' = 0.04v² + 5v + 140 − u + I; u' = a(bv − u)."""
    p = _defaults(n)
    p.update(a=_full(n, a), b=_full(n, b), c=_full(n, c), d=_full(n, d))
    return NeuronParams(model=jnp.full((n,), NeuronModel.IZH4, jnp.int8), **p)


def izh9(n: int, C: float, k: float, vr: float, vt: float, vpeak: float,
         a: float, b: float, c: float, d: float) -> NeuronParams:
    """9-parameter Izhikevich: C v' = k(v−vr)(v−vt) − u + I."""
    p = _defaults(n)
    p.update(a=_full(n, a), b=_full(n, b), c=_full(n, c), d=_full(n, d),
             C=_full(n, C), k=_full(n, k), vr=_full(n, vr), vt=_full(n, vt),
             vpeak=_full(n, vpeak))
    return NeuronParams(model=jnp.full((n,), NeuronModel.IZH9, jnp.int8), **p)


def lif(n: int, tau: float = 10.0, vth: float = -50.0, vreset: float = -65.0,
        vrest: float = -65.0, r: float = 1.0, tref: float = 2.0) -> NeuronParams:
    p = _defaults(n)
    p.update(lif_tau=_full(n, tau), lif_vth=_full(n, vth),
             lif_vreset=_full(n, vreset), lif_vrest=_full(n, vrest),
             lif_r=_full(n, r), lif_tref=_full(n, tref))
    return NeuronParams(model=jnp.full((n,), NeuronModel.LIF, jnp.int8), **p)


def generator(n: int) -> NeuronParams:
    p = _defaults(n)
    return NeuronParams(model=jnp.full((n,), NeuronModel.GENERATOR, jnp.int8), **p)


def concat_params(parts: list[NeuronParams]) -> NeuronParams:
    return NeuronParams(*[jnp.concatenate(f) for f in zip(*parts)])


# -- dynamics ------------------------------------------------------------------


def _derivs(p: NeuronParams, v: jax.Array, u: jax.Array, i_syn: jax.Array):
    """Coupled (dv/dt, du/dt) for all three dynamical models, selected per
    neuron. Elementwise waste of evaluating all models is negligible next to
    synaptic propagation."""
    # 0.04·v² + 5·v written factored, as (0.04·v + 5)·v: XLA's algebraic
    # simplifier rewrites the expanded form into exactly this, while a
    # Pallas TPU kernel evaluates what it is given — the factored form is
    # the one both compile to identical f32 arithmetic (bitwise parity of
    # the megakernel with this path on the chip).
    dv4 = (0.04 * v + 5.0) * v + 140.0 - u + i_syn
    du4 = p.a * (p.b * v - u)
    dv9 = (p.k * (v - p.vr) * (v - p.vt) - u + i_syn) / p.C
    du9 = p.a * (p.b * (v - p.vr) - u)
    dvl = (-(v - p.lif_vrest) + p.lif_r * i_syn) / p.lif_tau
    dul = jnp.zeros_like(u)
    is9 = p.model == NeuronModel.IZH9
    isl = p.model == NeuronModel.LIF
    dv = jnp.where(isl, dvl, jnp.where(is9, dv9, dv4))
    du = jnp.where(isl, dul, jnp.where(is9, du9, du4))
    return dv, du


def update_neurons(
    p: NeuronParams,
    state: NeuronState,
    i_syn: jax.Array,
    *,
    dt: float = 1.0,
    substeps: int = 2,
    method: str = "euler",
    state_dtype=jnp.float32,
) -> tuple[NeuronState, jax.Array]:
    """Advance all neurons one tick of ``dt`` ms; returns (state', spiked).

    ``substeps`` Euler half-steps per tick reproduce CARLsim's default
    integration (2 × 0.5 ms); ``method='rk4'`` gives the high-precision
    Runge-Kutta path the paper lists among the ported features.
    Math in f32, state stored back in ``state_dtype`` (fp16 policy).
    """
    v = state.v.astype(jnp.float32)
    u = state.u.astype(jnp.float32)
    i_syn = i_syn.astype(jnp.float32)
    h = dt / substeps

    if method == "euler":
        for _ in range(substeps):
            dv, du = _derivs(p, v, u, i_syn)
            v = v + h * dv
            u = u + h * du
    elif method == "rk4":
        for _ in range(substeps):
            k1v, k1u = _derivs(p, v, u, i_syn)
            k2v, k2u = _derivs(p, v + 0.5 * h * k1v, u + 0.5 * h * k1u, i_syn)
            k3v, k3u = _derivs(p, v + 0.5 * h * k2v, u + 0.5 * h * k2u, i_syn)
            k4v, k4u = _derivs(p, v + h * k3v, u + h * k3u, i_syn)
            v = v + (h / 6.0) * (k1v + 2 * k2v + 2 * k3v + k4v)
            u = u + (h / 6.0) * (k1u + 2 * k2u + 2 * k3u + k4u)
    else:
        raise ValueError(f"unknown integration method {method!r}")

    is_izh9 = p.model == NeuronModel.IZH9
    is_lif = p.model == NeuronModel.LIF
    is_gen = p.model == NeuronModel.GENERATOR

    thresh = jnp.where(is_lif, p.lif_vth, jnp.where(is_izh9, p.vpeak, 30.0))
    in_refrac = state.refrac > 0
    spiked = (v >= thresh) & ~is_gen & ~in_refrac

    # Reset rules (paper eq. 3): v ← c, u ← u + d for Izhikevich; LIF resets
    # to vreset and enters refractory.
    reset_v = jnp.where(is_lif, p.lif_vreset, p.c)
    v = jnp.where(spiked, reset_v, v)
    u = jnp.where(spiked & ~is_lif, u + p.d, u)
    # LIF refractory clamp
    v = jnp.where(is_lif & in_refrac, p.lif_vreset, v)
    refrac = jnp.where(
        spiked & is_lif,
        (p.lif_tref / dt).astype(jnp.int16),
        jnp.maximum(state.refrac - 1, 0).astype(jnp.int16),
    )
    # Generators hold resting potential.
    v = jnp.where(is_gen, p.c, v)
    u = jnp.where(is_gen, 0.0, u)

    new_state = NeuronState(
        v=v.astype(state_dtype), u=u.astype(state_dtype), refrac=refrac
    )
    return new_state, spiked


def init_neuron_state(p: NeuronParams, state_dtype=jnp.float32) -> NeuronState:
    """Rest state: v = c (vr for IZH9, vrest for LIF), u = b·v."""
    is9 = p.model == NeuronModel.IZH9
    isl = p.model == NeuronModel.LIF
    v0 = jnp.where(isl, p.lif_vrest, jnp.where(is9, p.vr, p.c))
    u0 = jnp.where(isl, 0.0, jnp.where(is9, 0.0, p.b * v0))
    n = p.model.shape[0]
    return NeuronState(
        v=v0.astype(state_dtype),
        u=u0.astype(state_dtype),
        refrac=jnp.zeros((n,), jnp.int16),
    )
