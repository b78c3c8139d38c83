"""Pallas TPU kernel: blocked f32 matmul with f32 accumulate.

Used for SNN spike propagation (``spikes_f32 @ W``) on the per-op
``backend="pallas"`` path. Operands of any float storage dtype are
up-cast to f32 before the call (Mosaic on v5e cannot load 16-bit float
tiles, and the engine hands over the f32 images it decoded once per run
anyway). The MXU contraction runs at ``Precision.HIGHEST`` so f32 weights
that bf16 cannot represent (e.g. Synfire4-mini's ``w_inh=-6.667``) are
multiplied exactly, as on the XLA path.

Classic 3-D blocked matmul: grid (M/bm, N/bn, K/bk), K innermost, VMEM f32
scratch accumulator, tile sizes MXU-aligned (128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _emit():
        o_ref[...] = acc_ref[...]


def syn_matmul(x, w, *, block_m: int = 128, block_n: int = 128,
               block_k: int = 128, out_dtype=jnp.float32,
               interpret: bool = False):
    """``x [M, K] @ w [K, N] -> [M, N]`` in f32, cast to ``out_dtype``.

    Shapes are zero-padded up to block multiples (zero rows/cols contribute
    nothing to the accumulator).
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    bm, bn, bk = (min(block_m, _ceil_to(m, 8)), min(block_n, _ceil_to(n, 128)),
                  min(block_k, _ceil_to(k, 128)))
    mp, np_, kp = -m % bm, -n % bn, -k % bk
    xp = jnp.pad(x.astype(jnp.float32), ((0, mp), (0, kp)))
    wp = jnp.pad(w.astype(jnp.float32), ((0, kp), (0, np_)))
    mg, ng, kg = (m + mp) // bm, (n + np_) // bn, (k + kp) // bk
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=kg),
        grid=(mg, ng, kg),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + mp, n + np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="syn_matmul",
    )(xp, wp)
    return out[:m, :n].astype(out_dtype)


def _ceil_to(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult
