"""Pallas TPU kernel: fused pair-based STDP weight update.

Fuses the two rank-1 updates (LTP outer product + LTD outer product), the
clip, and the mask into a single pass over the fp16 weight matrix — CARLsim
walks synapses twice for this; one fused pass halves the weight-matrix
traffic, which dominates (the paper: synaptic memory is *the* limiting
factor). Weights cross the kernel boundary as f32 and the mask as int32
(Mosaic on v5e loads neither 16-bit float nor bool tiles); the result is
rounded to the storage dtype once, after the call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _stdp_kernel(w_ref, mask_ref, pre_t_ref, post_t_ref, pre_s_ref,
                 post_s_ref, o_ref, *, a_plus, a_minus, w_min, w_max):
    w = w_ref[...].astype(jnp.float32)  # [bp, bq]
    pre_t = pre_t_ref[...].astype(jnp.float32)  # [bp, 1]
    post_t = post_t_ref[...].astype(jnp.float32)  # [1, bq]
    pre_s = pre_s_ref[...].astype(jnp.float32)  # [bp, 1]
    post_s = post_s_ref[...].astype(jnp.float32)  # [1, bq]
    # a⁺·(pre_t ⊗ post_s) − a⁻·(pre_s ⊗ post_t); association matches the
    # jnp oracle (scalar × outer product) so results are bit-identical.
    w = w + a_plus * (pre_t * post_s) - a_minus * (pre_s * post_t)
    w = jnp.clip(w, w_min, w_max)
    o_ref[...] = jnp.where(mask_ref[...] != 0, w, 0.0)


def stdp_update(w, mask, pre_trace, post_trace, pre_spikes, post_spikes, *,
                a_plus: float, a_minus: float, w_min: float, w_max: float,
                block_p: int = 256, block_q: int = 256,
                interpret: bool = False):
    """Fused STDP for w [P, Q] (storage dtype), traces [P]/[Q] f32."""
    p, q = w.shape
    bp = min(block_p, _ceil_to(p, 8))
    bq = min(block_q, _ceil_to(q, 128))
    pp, qp = -p % bp, -q % bq
    wp = jnp.pad(w.astype(jnp.float32), ((0, pp), (0, qp)))
    maskp = jnp.pad(mask.astype(jnp.int32), ((0, pp), (0, qp)))
    pre_t = jnp.pad(pre_trace.astype(jnp.float32), (0, pp)).reshape(-1, 1)
    post_t = jnp.pad(post_trace.astype(jnp.float32), (0, qp)).reshape(1, -1)
    pre_s = jnp.pad(pre_spikes.astype(jnp.float32), (0, pp)).reshape(-1, 1)
    post_s = jnp.pad(post_spikes.astype(jnp.float32), (0, qp)).reshape(1, -1)
    out = pl.pallas_call(
        functools.partial(_stdp_kernel, a_plus=a_plus, a_minus=a_minus,
                          w_min=w_min, w_max=w_max),
        grid=((p + pp) // bp, (q + qp) // bq),
        in_specs=[
            pl.BlockSpec((bp, bq), lambda i, j: (i, j)),
            pl.BlockSpec((bp, bq), lambda i, j: (i, j)),
            pl.BlockSpec((bp, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bq), lambda i, j: (0, j)),
            pl.BlockSpec((bp, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bq), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bp, bq), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((p + pp, q + qp), jnp.float32),
        interpret=interpret,
        name="stdp_update",
    )(wp, maskp, pre_t, post_t, pre_s, post_s)
    return out[:p, :q].astype(w.dtype)


def _ceil_to(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult
