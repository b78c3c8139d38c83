"""jit'd dispatch wrappers for the Pallas kernels.

On TPU the compiled kernels run natively (interpret mode there is an
error, :func:`resolve_interpret`); everywhere else (CPU hosts, unit
tests) they execute through the Pallas interpreter so the kernel *logic*
is validated bit-for-bit against ``ref.py``. ``use_pallas``
lets the models swap between the XLA reference path (used by the dry-run,
which lowers for the production mesh) and the kernel path.
"""
from __future__ import annotations

import functools
import os
from functools import partial

import jax

from repro.kernels import flash_attn as _flash
from repro.kernels import fused_tick as _ftick
from repro.kernels import izh_update as _izh
from repro.kernels import stdp_update as _stdp
from repro.kernels import syn_matmul as _syn

__all__ = ["on_tpu", "env_interpret", "resolve_interpret",
           "InterpretOnTPUError", "izh4_update", "syn_matmul",
           "flash_attention", "stdp_update", "fused_tick"]

_FALSY = ("", "0", "false", "no", "off")


class InterpretOnTPUError(RuntimeError):
    """Interpret-mode Pallas was requested on a TPU backend — the chip's
    path must run the compiled kernels, never the interpreter."""


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def env_interpret() -> bool | None:
    """Tri-state ``REPRO_PALLAS_INTERPRET`` override: ``None`` when the
    variable is unset (auto-detect from the backend), else the parsed
    bool — ``1`` forces interpret mode (CI exercising the kernel code
    path deterministically off-TPU), ``0`` forces it off."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is None:
        return None
    return env.strip().lower() not in _FALSY


def resolve_interpret(requested: bool | None = None) -> bool:
    """Whether Pallas kernels run in interpret mode.

    ``requested`` (e.g. ``compile(pallas_interpret=...)``) wins over
    ``REPRO_PALLAS_INTERPRET``; with neither set, kernels are interpreted
    everywhere except on a TPU.  On a TPU backend a request for interpret
    mode — from either source — raises :class:`InterpretOnTPUError`
    instead of silently running the interpreter on the chip."""
    if requested is None:
        requested = env_interpret()
    if on_tpu():
        if requested:
            raise InterpretOnTPUError(
                "interpret-mode Pallas requested on a TPU backend (via "
                "pallas_interpret=True or REPRO_PALLAS_INTERPRET) — the chip "
                "runs the compiled kernels; unset the override")
        return False
    return True if requested is None else requested


@functools.cache
def _interpret() -> bool:
    """Evaluated once per process (the backend never changes mid-run;
    re-querying ``jax.default_backend()`` on every jit'd dispatch was
    wasted work)."""
    return resolve_interpret()


@partial(jax.jit, static_argnames=("dt", "substeps"))
def izh4_update(v, u, i_syn, a, b, c, d, *, dt: float = 1.0, substeps: int = 2):
    return _izh.izh4_update(v, u, i_syn, a, b, c, d, dt=dt, substeps=substeps,
                            interpret=_interpret())


@jax.jit
def syn_matmul(x, w):
    return _syn.syn_matmul(x, w, interpret=_interpret())


def fused_tick(static, v, u, ring, gen_row, is_gen, a, b, c, d, t, payload):
    """Single-program tick dispatch (called inside the engine's jitted
    scan body — no extra jit wrapper needed)."""
    return _ftick.fused_tick(static, v, u, ring, gen_row, is_gen, a, b, c,
                             d, t, payload, interpret=_interpret())


@partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = -1):
    return _flash.flash_attention(q, k, v, causal=causal, window=window,
                                  interpret=_interpret())


@partial(jax.jit, static_argnames=("a_plus", "a_minus", "w_min", "w_max"))
def stdp_update(w, mask, pre_trace, post_trace, pre_spikes, post_spikes, *,
                a_plus: float, a_minus: float, w_min: float, w_max: float):
    return _stdp.stdp_update(w, mask, pre_trace, post_trace, pre_spikes,
                             post_spikes, a_plus=a_plus, a_minus=a_minus,
                             w_min=w_min, w_max=w_max, interpret=_interpret())
