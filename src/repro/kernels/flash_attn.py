"""Pallas TPU kernel: GQA flash attention (causal / local-window), fwd.

The LM-substrate hot spot. Online-softmax attention blocked over KV so the
[Sq, Sk] score matrix never touches HBM; supports grouped-query attention
(q heads laid out kv-major) and RecurrentGemma-style local sliding windows.
KV arrives in the storage dtype (fp16/bf16 under the paper's policy) and is
decoded to f32 inside the tile — the same storage/compute split as the SNN
synapses.

Grid: (B, Hq, Sq/bq, Sk/bk), KV innermost; VMEM scratch carries the running
(max, denominator, accumulator) across KV blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, sq: int, sk: int,
                  bq: int, bk: int, k_steps: int):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # [bq, d]
    k = k_ref[0, 0].astype(jnp.float32)  # [bk, d]
    v = v_ref[0, 0].astype(jnp.float32)  # [bk, d]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [bq, bk]

    qi = pl.program_id(2)
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (sk - sq)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < sk  # KV padding
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, :1]  # [bq, 1] (lane-replicated scratch)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
    p = jnp.exp(s - m_new)  # [bq, bk]
    l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == k_steps - 1)
    def _emit():
        l = l_ref[:, :1]
        o = acc_ref[...] / jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = o.astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = -1,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q [B, Hq, Sq, D]; k, v [B, Hkv, Sk, D] (storage dtype ok); Hq % Hkv == 0.

    Returns [B, Hq, Sq, D] in q.dtype. Query positions are aligned to the
    *end* of the KV sequence (decode-friendly).
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    scale = float(scale if scale is not None else 1.0 / (d ** 0.5))

    bq = min(block_q, _ceil_to(sq, 8))
    bk = min(block_k, _ceil_to(sk, 128))
    dp = _ceil_to(d, 128)
    sqp, skp = -sq % bq, -sk % bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sqp), (0, dp - d)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, skp), (0, dp - d)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, skp), (0, dp - d)))
    qg, kg = (sq + sqp) // bq, (sk + skp) // bk

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        sq=sq, sk=sk, bq=bq, bk=bk, k_steps=kg,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, hq, qg, kg),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dp), lambda bb, h, i, kk: (bb, h, i, 0)),
            pl.BlockSpec((1, 1, bk, dp), lambda bb, h, i, kk, g=g: (bb, h // g, kk, 0)),
            pl.BlockSpec((1, 1, bk, dp), lambda bb, h, i, kk, g=g: (bb, h // g, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dp), lambda bb, h, i, kk: (bb, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq + sqp, dp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),  # running max (lane-replicated)
            pltpu.VMEM((bq, 128), jnp.float32),  # running denominator
            pltpu.VMEM((bq, dp), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
        name="flash_attn",
    )(qp, kp, vp)
    return out[:, :, :sq, :d]


def _ceil_to(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult
