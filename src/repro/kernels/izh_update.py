"""Pallas TPU kernel: fused IZH4 neuron update + spike detection + reset.

The MCU inner loop the paper profiles — per-tick Izhikevich integration over
all neurons — as a single fused VPU pass: integrate (v, u) in f32,
detect/reset spikes, store back. State crosses the kernel boundary as f32
and spikes as int32 (Mosaic on v5e loads neither 16-bit float nor bool
tiles); the wrapper rounds the result to the storage dtype (fp16 under the
paper's policy) once, exactly where ``kernels.ref.izh4_ref`` rounds.
Fusion avoids materializing the intermediate derivative arrays in HBM.

Layout: neuron arrays are viewed as [rows, 128] (VPU lane width) and tiled
in (block_rows, 128) VMEM blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
DEFAULT_BLOCK_ROWS = 64  # (64, 128) f32 blocks = 32 KiB — comfortably VMEM


def _izh4_kernel(v_ref, u_ref, i_ref, a_ref, b_ref, c_ref, d_ref,
                 vo_ref, uo_ref, s_ref, *, dt: float, substeps: int):
    v = v_ref[...]
    u = u_ref[...]
    i_syn = i_ref[...]
    a = a_ref[...]
    b = b_ref[...]
    c = c_ref[...]
    d = d_ref[...]
    h = dt / substeps
    for _ in range(substeps):  # static unroll — substeps is compile-time
        # Simultaneous (dv, du) from the same (v, u) — identical expression
        # tree to neurons._derivs so the pallas backend is bit-exact with
        # the xla reference path.
        dv = (0.04 * v + 5.0) * v + 140.0 - u + i_syn
        du = a * (b * v - u)
        v = v + h * dv
        u = u + h * du
    spiked = v >= 30.0
    v = jnp.where(spiked, c, v)
    u = jnp.where(spiked, u + d, u)
    vo_ref[...] = v
    uo_ref[...] = u
    s_ref[...] = spiked.astype(jnp.int32)


def izh4_update(v, u, i_syn, a, b, c, d, *, dt: float = 1.0, substeps: int = 2,
                block_rows: int = DEFAULT_BLOCK_ROWS, interpret: bool = False):
    """Fused IZH4 tick for flat [N] arrays. Pads N to a (block_rows·128) grid."""
    n = v.shape[0]
    per_block = block_rows * LANE
    n_pad = -n % per_block
    rows = (n + n_pad) // LANE

    def prep(x):
        x = jnp.pad(x.astype(jnp.float32), (0, n_pad))
        return x.reshape(rows, LANE)

    args = tuple(prep(x) for x in (v, u, i_syn, a, b, c, d))
    grid = (rows // block_rows,)
    spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    vo, uo, sp = pl.pallas_call(
        functools.partial(_izh4_kernel, dt=dt, substeps=substeps),
        grid=grid,
        in_specs=[spec] * 7,
        out_specs=[spec] * 3,
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANE), jnp.int32),
        ],
        interpret=interpret,
        name="izh_update",
    )(*args)
    return (vo.reshape(-1)[:n].astype(v.dtype),
            uo.reshape(-1)[:n].astype(u.dtype), sp.reshape(-1)[:n] != 0)
