"""Pallas kernel: event-driven CSR fan-in gather + segment-sum propagation.

The dense ``syn_matmul`` path reads a ``[n_pre, n_post]`` weight rectangle
every tick even when each post neuron has only a few dozen presynaptic
partners — the fanin ≪ n_pre regime the paper's Synfire4 lives in
(1,200 neurons, fan-in ≈ tens). This kernel instead consumes the CSR
fan-in layout (``indices[n_post, fanin]``, ``weights[n_post, fanin]``):
per post neuron, gather the spike bits of its ``fanin`` sources and
reduce them against the fan-in weight row — bytes touched per tick scale
as ``n_post × fanin`` instead of ``n_pre × n_post``.

As in the packed path, the fp16 → f32 weight decode is hoisted out of the
tick scan (``repro.core.backend.assemble_packed`` decodes the CSR weight
rows once per run); the wrapper up-casts any storage dtype to f32 before
the call (Mosaic on v5e cannot load 16-bit float tiles). Ragged rows are
padded with ``index 0 / weight 0`` — the padded terms contribute an exact
``+0.0`` so the reduction is bitwise neutral.

Layout: grid over post blocks; the full (padded) spike row stays resident
in VMEM and is gathered per block with :func:`lane_take`. The fan-in axis
is padded to the 128-lane width; the output is one ``[1, Qp]`` row written
in ``(1, bq)`` lane blocks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
DEFAULT_BLOCK_Q = 256  # post neurons per grid step (a multiple of LANE)


def lane_take(row_ref, idx: jax.Array, base=0, n_chunks=None) -> jax.Array:
    """``g[r, f] = row_ref[0, idx[r, f]]`` as f32, for a VMEM-resident
    ``[1, Np]`` row (``Np`` a multiple of 128) and a ``[R, F]`` int32 index
    tile (``F`` a multiple of 128).

    Mosaic lowers a gather only inside one vreg (a 128-lane source), so the
    row is walked in 128-lane chunks: each chunk is broadcast over the
    tile's rows and gathered with ``take_along_axis`` per 128-column block,
    and the lanes whose index falls in the chunk keep the value. Every
    output cell is selected from exactly one chunk — no arithmetic touches
    it, so the result is bitwise a plain ``take``.

    ``base`` (a multiple of 128) and ``n_chunks`` restrict the walk to the
    window ``[base, base + 128 * n_chunks)``; either may be a traced scalar
    (e.g. read from SMEM). Indices outside the window read ``0.0``. The
    default walks the whole row."""
    rows, f = idx.shape
    c0 = base // LANE
    if n_chunks is None:
        n_chunks = row_ref.shape[1] // LANE - c0

    def chunk(c, g):
        off = pl.multiple_of(c * LANE, LANE)
        src = jnp.broadcast_to(
            row_ref[:, pl.ds(off, LANE)].astype(jnp.float32), (rows, LANE))
        loc = idx - off
        hit = (loc >= 0) & (loc < LANE)
        loc = jnp.where(hit, loc, 0)
        got = jnp.concatenate(
            [jnp.take_along_axis(src, loc[:, j:j + LANE], axis=1)
             for j in range(0, f, LANE)], axis=1)
        return jnp.where(hit, got, g)

    return jax.lax.fori_loop(c0, c0 + n_chunks, chunk,
                             jnp.zeros((rows, f), jnp.float32))


def _gather_kernel(s_ref, idx_ref, w_ref, o_ref):
    g = lane_take(s_ref, idx_ref[...])  # [bq, Fp] gathered spike bits
    o_ref[...] = (g * w_ref[...]).sum(axis=1)[None, :]


def syn_gather(spikes, idx, w, *, block_q: int = DEFAULT_BLOCK_Q,
               interpret: bool = False):
    """CSR fan-in drive: ``out[q] = Σ_k spikes[idx[q, k]] * w[q, k]``.

    ``spikes`` [P] f32 (the projection's presynaptic spike row),
    ``idx`` [Q, F] integer (any int dtype; promoted to int32),
    ``w`` [Q, F] storage dtype (fp16/bf16/f32; decoded to f32 before the
    call). Returns [Q] f32. Rows shorter than F must be padded with index 0
    and weight 0 (exact-zero contributions, bitwise neutral).
    """
    p = spikes.shape[0]
    q, f = idx.shape
    assert w.shape == (q, f), (idx.shape, w.shape)
    if q == 0 or f == 0:
        return jnp.zeros((q,), jnp.float32)
    bq = min(block_q, _ceil_to(q, LANE))
    fp = _ceil_to(f, LANE)
    pp = _ceil_to(p, LANE)
    qp = -q % bq
    sp = jnp.pad(spikes.astype(jnp.float32), (0, pp - p))[None, :]
    idxp = jnp.pad(idx.astype(jnp.int32), ((0, qp), (0, fp - f)))
    wp = jnp.pad(w.astype(jnp.float32), ((0, qp), (0, fp - f)))
    out = pl.pallas_call(
        _gather_kernel,
        grid=((q + qp) // bq,),
        in_specs=[
            pl.BlockSpec((1, pp), lambda i: (0, 0)),  # spike row: resident
            pl.BlockSpec((bq, fp), lambda i: (i, 0)),
            pl.BlockSpec((bq, fp), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, q + qp), jnp.float32),
        interpret=interpret,
        name="syn_gather",
    )(sp, idxp, wp)
    return out[0, :q]


def _ceil_to(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult
