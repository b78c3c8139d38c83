"""Pallas kernel: fused event-driven STDP update on CSR fan-in rows.

The dense ``stdp_update`` kernel streams the full ``[n_pre, n_post]``
weight rectangle every tick. For plastic projections stored CSR
(``weights[n_post, fanin]``, ``indices[n_post, fanin]``) the per-synapse
pair-based update

    dw[q, k] = a⁺·pre_trace[idx[q, k]]·post_sp[q]
             − a⁻·pre_sp[idx[q, k]]·post_trace[q]

is a gather of the two per-neuron pre vectors followed by a pure
elementwise pass over the fan-in rows — O(n_post·fanin) weight traffic,
the regime that lets plastic projections fit the paper's 8 MB budget at
Synfire4×10 scale.

This kernel fuses the gather, both STDP terms, the clip, and the validity
mask into a single pass over the row storage. Because every op is
elementwise per row cell (the gather reads, never reduces), the kernel is
**bit-identical** to :func:`repro.kernels.ref.stdp_gather_ref` and to the
dense update at the corresponding cells — unlike the propagation sum there
is no accumulation-order freedom for padding to perturb.

Layout mirrors ``syn_gather``: grid over post blocks; the pre-sized trace
and spike rows stay resident in VMEM and are gathered per block with
``lane_take``; the fan-in axis is padded to the 128-lane width (padding
lands on ``valid=False`` cells, which the mask zeroes). Weights cross the
kernel boundary as f32 and the validity mask as int32 (Mosaic on v5e
loads neither 16-bit float nor bool tiles); the one storage-dtype
rounding happens after the call, exactly where the oracle rounds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.syn_gather import lane_take

LANE = 128
DEFAULT_BLOCK_Q = 256  # post neurons per grid step


def _stdp_gather_kernel(w_ref, idx_ref, valid_ref, pre_t_ref, pre_s_ref,
                        post_t_ref, post_s_ref, o_ref, *,
                        a_plus, a_minus, w_min, w_max):
    w = w_ref[...]  # [bq, Fp] f32
    idx = idx_ref[...]  # [bq, Fp] int32 (padding -> 0, masked below)
    valid = valid_ref[...] != 0  # [bq, Fp] int32 flags
    post_t = post_t_ref[...]  # [bq, 1]
    post_s = post_s_ref[...]  # [bq, 1]
    # a⁺·(pre_t[idx] · post_s) − a⁻·(pre_s[idx] · post_t): association
    # matches the jnp oracle (scalar × (gather × broadcast)) bit-for-bit.
    ltp = a_plus * (lane_take(pre_t_ref, idx) * post_s)
    ltd = a_minus * (lane_take(pre_s_ref, idx) * post_t)
    w = jnp.clip(w + ltp - ltd, w_min, w_max)
    o_ref[...] = jnp.where(valid, w, 0.0)


def stdp_gather(w, idx, valid, pre_trace, post_trace, pre_spikes,
                post_spikes, *, a_plus: float, a_minus: float,
                w_min: float, w_max: float,
                block_q: int = DEFAULT_BLOCK_Q, interpret: bool = False):
    """Fused CSR-row STDP: ``w`` [Q, F] storage dtype, ``idx``/``valid``
    [Q, F], traces/spikes [P]/[Q] f32. Returns the updated [Q, F] rows in
    the storage dtype."""
    q, f = w.shape
    assert idx.shape == (q, f) and valid.shape == (q, f), (idx.shape, w.shape)
    p = pre_trace.shape[0]
    if q == 0 or f == 0:
        return w
    bq = min(block_q, _ceil_to(q, 8))
    fp = _ceil_to(f, LANE)
    pp = _ceil_to(p, LANE)
    qp = -q % bq
    wp = jnp.pad(w.astype(jnp.float32), ((0, qp), (0, fp - f)))
    idxp = jnp.pad(idx.astype(jnp.int32), ((0, qp), (0, fp - f)))
    validp = jnp.pad(valid.astype(jnp.int32), ((0, qp), (0, fp - f)))
    pre_t = jnp.pad(pre_trace.astype(jnp.float32), (0, pp - p))[None, :]
    pre_s = jnp.pad(pre_spikes.astype(jnp.float32), (0, pp - p))[None, :]
    post_t = jnp.pad(post_trace.astype(jnp.float32), (0, qp))[:, None]
    post_s = jnp.pad(post_spikes.astype(jnp.float32), (0, qp))[:, None]
    grid = ((q + qp) // bq,)
    out = pl.pallas_call(
        functools.partial(_stdp_gather_kernel, a_plus=a_plus,
                          a_minus=a_minus, w_min=w_min, w_max=w_max),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, fp), lambda i: (i, 0)),
            pl.BlockSpec((bq, fp), lambda i: (i, 0)),
            pl.BlockSpec((bq, fp), lambda i: (i, 0)),
            pl.BlockSpec((1, pp), lambda i: (0, 0)),  # pre trace: resident
            pl.BlockSpec((1, pp), lambda i: (0, 0)),  # pre spikes: resident
            pl.BlockSpec((bq, 1), lambda i: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bq, fp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((q + qp, fp), jnp.float32),
        interpret=interpret,
        name="stdp_gather",
    )(wp, idxp, validp, pre_t, pre_s, post_t, post_s)
    return out[:q, :f].astype(w.dtype)


def _ceil_to(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult
