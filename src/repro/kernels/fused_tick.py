"""Pallas megakernel: ONE program per simulation tick.

The per-tick phases the engine otherwise dispatches separately — delay-ring
read + slot zeroing, IZH4 integration, generator merge, bucketed synaptic
propagation, ring commits — execute as a single Pallas program in which the
ring, membrane state, and spike vector stay VMEM-resident for the whole
tick while the weight / CSR tiles stream through double-buffered DMA (the
standard Pallas grid pipeline: the next tile's copy overlaps the current
tile's compute).

Layout
------
Neuron-indexed vectors are ``[1, Np]`` rows (``Np`` = N padded to the
128-lane width plus enough slack that every tile window stays in bounds);
the ring is ``[L, Np]``.  Dense bucket images are stacked into one
``[Bd, Pp, Qp]`` operand streamed in ``(1, Pp, tile_q)`` column tiles; CSR
buckets concatenate their fan-in rows into ``[R, Fp]`` index/weight tables
streamed in ``(tile_r, Fp)`` row tiles — the in-kernel ``lane_take``
subsumes the standalone ``syn_gather`` lowering.  A scalar-prefetch schedule
(``meta[i] = (kind, sel, pre_start, post_off, kpos, qt, n_chunks)``) drives
both the BlockSpec index maps (which weight tile to DMA for grid step
``i``) and the in-kernel placement of each tile's drive.  A CSR row tile
belongs to one bucket, whose sources are one contiguous span: its
``pre_start``/``n_chunks`` name the 128-lane chunks that span covers, and
the gather walks only those (a chunk outside it holds no live index).
Every window offset in the schedule is a multiple of 128:
``assemble_kernel`` shifts each bucket's image (or CSR rows) inside its
tile by the bucket's offset from the lane boundary, so the kernel only
ever slices lane-aligned windows.

Grid step 0 runs the tick prologue (ring read → ``i_syn``, slot zeroing,
IZH4 update, generator overrides, spike vector, accumulator clear); every
step accumulates its tile's drive into the per-delay ``[K, 1, Np]``
accumulator; the final step runs the epilogue — one ring row
read-add-write per DISTINCT delay, mirroring the packed path's commit
exactly.

Dtypes at the boundary (what Mosaic accepts on TPU v5e): state, ring and
weights cross as f32 and bool rows as int32.  The storage dtype's rounding
is applied in-kernel at the same points the XLA path casts
(``_storage_round``; v5e has no f32→f16 convert, so fp16 rounds by bit
arithmetic) — the f32 carriers only ever hold storage-representable values,
and the wrapper's casts back to storage are exact.

Bitwise stance (same as the rest of ``kernels/``): padding rows/columns
carry weight ``+0.0`` so their contributions are exact zeros, and the
engine's accumulator cells are never ``-0.0`` — adding a padded tile is a
bitwise no-op.  With the exactly-representable weight tables the Synfire
configs use, any accumulation order gives the exact sum (the MXU contraction
runs at ``Precision.HIGHEST``), so the kernel raster is bit-identical to the
XLA fused/packed/sparse paths (asserted in ``tests/test_fused.py`` and, on
a TPU, by ``chip_smoke.py``); goldens validate the kernel against the
independent ``kernels.ref.fused_tick_ref`` oracle off the lane grid.

Eligibility is compiled into ``NetStatic.fused_kernel`` (see
``network._plan_fused``, which records the reason when a net is refused):
IZH4+generators only, Euler, CUBA single-channel ring, no plasticity/STP,
contiguous bucket spans.  The kernel engages on a TPU;
``REPRO_PALLAS_INTERPRET=1`` forces the interpreted kernel on other
backends (CI / goldens).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.kernels.syn_gather import lane_take

LANE = 128
_MAX_TILE_R = 512

# meta column indices (schedule rows, scalar-prefetched to SMEM)
_KIND, _SEL, _PRE, _POST, _KPOS, _QT, _NCH = range(7)


class KernelPayload(NamedTuple):
    """Loop-invariant operands + compile-time geometry of the fused tick.

    Built once per device program (``backend.assemble_fused``); the jnp
    members are closed over by the scan body, the ints parameterize the
    kernel trace."""

    meta: jax.Array  # [n_steps, 7] int32 tile schedule (scalar prefetch)
    w_stack: jax.Array  # [Bd, Pp, Qp] f32 stacked dense bucket images
    csr_idx: jax.Array  # [R, Fp] int32 global fan-in ids (pad -> 0)
    csr_w: jax.Array  # [R, Fp] f32 fan-in weights (pad -> +0.0)
    n_steps: int
    n_pad: int
    p_pad: int
    tile_q: int
    tile_r: int
    f_pad: int
    csr_chunks: int  # 128-lane chunks the CSR gather walks per tick
    csr_row_chunks: int  # ... were every tile to walk the whole spike row


def _ceil_to(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _lane_split(start: int) -> tuple[int, int]:
    """``start`` as (128-aligned window base, shift inside the window)."""
    base = start // LANE * LANE
    return base, start - base


def assemble_kernel(static, params, packed) -> KernelPayload:
    """Build the kernel payload from the assembled bucket images.

    Pure reshuffle of loop-invariant data (runs once per device program,
    outside the tick scan): dense images pad into the ``[Bd, Pp, Qp]``
    stack, CSR tables globalize their indices (``+ pre_start``) and pad
    rows to the ``tile_r`` grid, and the tile schedule is laid out as one
    int32 row per grid step.  Each image sits at its bucket's offset from
    the lane boundary (pre rows and post columns alike; CSR rows likewise)
    so that every window the schedule names starts on a lane boundary.

    A CSR row tile's gather window is its bucket's source span, rounded out
    to whole 128-lane chunks.  Pad cells (``idx 0``, weight ``+0.0``) may
    fall outside it and gather ``0.0`` instead of spike 0 — the same
    ``+0.0`` product.  Whenever the tables are concrete (an eager call, not
    a trace) every index with a non-zero weight is checked to lie inside
    its tile's window; the chunk counts go to the
    ``repro_fused_csr_chunks`` gauge."""
    plan = static.fused
    buckets = static.buckets
    dense_ids = [bi for bi, b in enumerate(buckets) if b.kind == "dense"]
    sparse_ids = [bi for bi, b in enumerate(buckets) if b.kind == "sparse"]
    kpos = {d: k for k, d in enumerate(plan.delays)}
    f32 = jnp.float32

    # -- dense stack geometry --------------------------------------------
    pre_sh = {bi: _lane_split(buckets[bi].pre_start)[1] for bi in dense_ids}
    post_sh = {bi: _lane_split(buckets[bi].post_start)[1] for bi in dense_ids}
    p_pad = _ceil_to(max((pre_sh[bi] + buckets[bi].p for bi in dense_ids),
                         default=1), LANE)
    q_max = max((post_sh[bi] + buckets[bi].q for bi in dense_ids), default=1)
    tile_q = LANE * max(1, min(plan.tile_q // LANE, _ceil_to(q_max, LANE) // LANE))
    q_pad = _ceil_to(q_max, tile_q)
    n_qt = q_pad // tile_q
    w_stack = jnp.zeros((max(1, len(dense_ids)), p_pad, q_pad), f32)
    for pos, bi in enumerate(dense_ids):
        b = buckets[bi]
        r0, c0 = pre_sh[bi], post_sh[bi]
        w_stack = w_stack.at[pos, r0:r0 + b.p, c0:c0 + b.q].set(packed[bi])

    # -- CSR row-tile geometry -------------------------------------------
    f_pad = _ceil_to(
        max((params.bucket_csr_idx[bi].shape[1] for bi in sparse_ids),
            default=1), LANE)
    tile_r = max(LANE, min(plan.tile_r // LANE * LANE, _MAX_TILE_R))
    row_blocks: list[tuple[jax.Array, jax.Array]] = []
    # (post_off, kpos, pre_base, n_chunks) per row tile
    csr_meta: list[tuple[int, int, int, int]] = []
    for bi in sparse_ids:
        b = buckets[bi]
        base, sh = _lane_split(b.post_start)
        pre_base, pre_sh = _lane_split(b.pre_start)
        n_win = -(-(pre_sh + b.p) // LANE)
        idx = params.bucket_csr_idx[bi].astype(jnp.int32) + b.pre_start
        w = packed[bi]
        rows = _ceil_to(sh + b.q, tile_r)
        pad = ((sh, rows - sh - b.q), (0, f_pad - idx.shape[1]))
        row_blocks.append((jnp.pad(idx, pad), jnp.pad(w, pad)))
        for rt in range(rows // tile_r):
            csr_meta.append((base + rt * tile_r, kpos[b.delay_ms],
                             pre_base, n_win))
    if row_blocks:
        csr_idx = jnp.concatenate([ib for ib, _ in row_blocks])
        csr_w = jnp.concatenate([wb for _, wb in row_blocks])
    else:
        csr_idx = jnp.zeros((tile_r, f_pad), jnp.int32)
        csr_w = jnp.zeros((tile_r, f_pad), f32)

    # -- tile schedule ----------------------------------------------------
    meta: list[list[int]] = []
    for pos, bi in enumerate(dense_ids):
        b = buckets[bi]
        pre_base = _lane_split(b.pre_start)[0]
        post_base = _lane_split(b.post_start)[0]
        for qt in range(n_qt):
            meta.append([0, pos, pre_base, post_base + qt * tile_q,
                         kpos[b.delay_ms], qt, 0])
    for rt, (post_off, k, pre_base, n_win) in enumerate(csr_meta):
        meta.append([1, rt, pre_base, post_off, k, 0, n_win])
    if not meta:  # projection-free net: one no-op step (prologue+epilogue)
        meta.append([-1, 0, 0, 0, 0, 0, 0])
    meta_np = np.asarray(meta, np.int32)

    slack = max(p_pad, q_pad, tile_r, LANE)
    n_pad = _ceil_to(static.n + slack, LANE)
    if not isinstance(csr_idx, jax.core.Tracer):
        _check_windows(meta_np, np.asarray(csr_idx), np.asarray(csr_w),
                       tile_r)
    csr_chunks = sum(n_win for *_, n_win in csr_meta)
    csr_row_chunks = len(csr_meta) * (n_pad // LANE)
    obs.gauge("repro_fused_csr_chunks", float(csr_chunks), walk="window")
    obs.gauge("repro_fused_csr_chunks", float(csr_row_chunks), walk="row")
    return KernelPayload(
        meta=jnp.asarray(meta_np),
        w_stack=w_stack, csr_idx=csr_idx, csr_w=csr_w,
        n_steps=len(meta), n_pad=n_pad, p_pad=p_pad,
        tile_q=tile_q, tile_r=tile_r, f_pad=f_pad,
        csr_chunks=csr_chunks, csr_row_chunks=csr_row_chunks,
    )


def _check_windows(meta: np.ndarray, idx: np.ndarray, w: np.ndarray,
                   tile_r: int) -> None:
    """Raise unless every CSR cell with a non-zero weight indexes a spike
    inside its row tile's gather window (schedule rows of kind 1, in tile
    order)."""
    tiles = meta[meta[:, _KIND] == 1]
    lo = np.repeat(tiles[:, _PRE], tile_r)[:, None]
    hi = lo + LANE * np.repeat(tiles[:, _NCH], tile_r)[:, None]
    n = len(lo)
    outside = (w[:n] != 0) & ((idx[:n] < lo) | (idx[:n] >= hi))
    if outside.any():
        r, f = np.argwhere(outside)[0]
        raise ValueError(
            f"CSR row {r}, fan-in slot {f}: index {idx[r, f]} with weight "
            f"{w[r, f]} lies outside its tile's gather window "
            f"[{lo[r, 0]}, {hi[r, 0]})")


def _bits(x, dtype):
    return jax.lax.bitcast_convert_type(x, dtype)


def _round_f16(x: jax.Array) -> jax.Array:
    """f32 → the nearest IEEE fp16 value (ties to even), kept in f32.

    Equal to ``x.astype(float16).astype(float32)``, written with integer
    and f32 arithmetic because Mosaic on v5e has no f32→f16 conversion.
    Normal range: drop 13 mantissa bits with round-half-even.  Subnormal
    range (``|x| < 2**-14``): the fp16 quantum there is ``2**-24``, the f32
    ulp of 0.5, so ``(|x| + 0.5) - 0.5`` rounds to it exactly.  Overflow
    goes to inf; the sign (incl. of zero) and NaN pass through."""
    b = _bits(x, jnp.int32)
    mag = b & 0x7FFFFFFF
    normal = _bits((mag + 0xFFF + ((mag >> 13) & 1)) & ~0x1FFF, jnp.float32)
    ax = _bits(mag, jnp.float32)
    r = jnp.where(ax < 2.0 ** -14, (ax + 0.5) - 0.5, normal)
    r = jnp.where(r > 65504.0, jnp.inf, r)
    out = _bits(_bits(r, jnp.int32) | (b & jnp.int32(-2 ** 31)), jnp.float32)
    return jnp.where(x != x, x, out)


def _storage_round(dtype):
    """In-kernel rounding of an f32 value to the storage ``dtype`` (result
    stays f32: it is the carrier the kernel reads and writes)."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.float32:
        return lambda x: x
    if dtype == jnp.float16:
        return _round_f16
    if dtype == jnp.bfloat16:
        return lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    raise TypeError(f"fused tick has no storage rounding for {dtype}")


def _tick_kernel(m_ref, t_ref, v_ref, u_ref, ring_ref, gen_ref, isg_ref,
                 a_ref, b_ref, c_ref, d_ref, w_ref, ci_ref, cw_ref,
                 vo_ref, uo_ref, so_ref, io_ref, ro_ref, acc_ref, *,
                 ring_len: int, dt: float, substeps: int,
                 delays: tuple[int, ...], n_steps: int,
                 p_pad: int, tile_q: int, tile_r: int,
                 state_round, ring_round):
    f32 = jnp.float32
    i = pl.program_id(0)
    t = t_ref[0]

    @pl.when(i == 0)
    def _prologue():
        slot = jax.lax.rem(t, ring_len)
        ro_ref[...] = ring_ref[...]
        i_syn = ring_ref[pl.ds(slot, 1), :]
        io_ref[...] = i_syn
        ro_ref[pl.ds(slot, 1), :] = jnp.zeros_like(i_syn)
        # IZH4 integration — identical expression tree to kernels.ref.
        # izh4_ref / the engine fast path, so state dtypes round-trip
        # bit-identically (f32 math, storage-dtype writeback).
        v = v_ref[...]
        u = u_ref[...]
        a = a_ref[...]
        b = b_ref[...]
        c = c_ref[...]
        d = d_ref[...]
        h = dt / substeps
        for _ in range(substeps):
            dv = (0.04 * v + 5.0) * v + 140.0 - u + i_syn
            du = a * (b * v - u)
            v = v + h * dv
            u = u + h * du
        spiked = v >= 30.0
        v = jnp.where(spiked, c, v)
        u = jnp.where(spiked, u + d, u)
        # Generator overrides in the engine's exact order (storage-dtype
        # rounding between the reset and the hold-at-rest writes).
        isg = isg_ref[...] != 0
        vo_ref[...] = state_round(jnp.where(isg, c, state_round(v)))
        uo_ref[...] = state_round(jnp.where(isg, 0.0, state_round(u)))
        so_ref[...] = jnp.where(isg, gen_ref[...], spiked.astype(jnp.int32))
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kind = m_ref[i, _KIND]

    @pl.when(kind == 0)
    def _dense_tile():
        ps = pl.multiple_of(m_ref[i, _PRE], LANE)
        po = pl.multiple_of(m_ref[i, _POST], LANE)
        k = m_ref[i, _KPOS]
        pre = so_ref[:, pl.ds(ps, p_pad)].astype(f32)
        drive = jax.lax.dot_general(
            pre, w_ref[0], (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=f32)  # [1, tile_q]
        acc_ref[k, :, pl.ds(po, tile_q)] += drive

    @pl.when(kind == 1)
    def _csr_tile():
        po = pl.multiple_of(m_ref[i, _POST], LANE)
        k = m_ref[i, _KPOS]
        # in-kernel fan-in gather over the bucket's source chunks only
        g = lane_take(so_ref, ci_ref[...], base=m_ref[i, _PRE],
                      n_chunks=m_ref[i, _NCH])
        drive = (g * cw_ref[...]).sum(axis=1)  # [tile_r]
        acc_ref[k, :, pl.ds(po, tile_r)] += drive[None]

    @pl.when(i == n_steps - 1)
    def _epilogue():
        # Ring commit for every distinct delay — same read-add-write (in
        # ring storage dtype) as the packed path's per-delay commits.
        for k, dly in enumerate(delays):
            dslot = jax.lax.rem(t + dly, ring_len)
            rrow = ro_ref[pl.ds(dslot, 1), :]
            arow = acc_ref[k]
            ro_ref[pl.ds(dslot, 1), :] = ring_round(rrow + ring_round(arow))


def fused_tick(static, v, u, ring, gen_row, is_gen, a, b, c, d, t,
               payload: KernelPayload, *, interpret: bool = False):
    """Run one tick as a single Pallas program.

    ``v``/``u`` [N] storage dtype, ``ring`` [L, N] (single-channel CUBA
    ring, storage dtype), ``gen_row`` [N] bool (this tick's pre-drawn
    generator spikes), ``is_gen`` [N] bool, ``a..d`` [N] IZH parameters,
    ``t`` scalar int32 tick.  Returns ``(v', u', spikes, ring', i_syn)``
    — exactly the engine's phase 1–5 outputs.
    """
    n = static.n
    kp = payload
    np_ = kp.n_pad
    f32 = jnp.float32

    def row(x, dtype=f32):
        return jnp.pad(x.astype(dtype), (0, np_ - n))[None]

    ring_p = jnp.pad(ring.astype(f32), ((0, 0), (0, np_ - n)))
    delays = static.fused.delays
    k_delays = max(1, len(delays))
    vec = pl.BlockSpec((1, np_), lambda i, m, tt: (0, 0))
    whole_ring = pl.BlockSpec(ring_p.shape, lambda i, m, tt: (0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # meta schedule + tick counter
        grid=(kp.n_steps,),
        in_specs=[
            vec, vec, whole_ring,  # v, u, ring
            vec, vec,  # gen_row, is_gen
            vec, vec, vec, vec,  # a, b, c, d
            # streamed tiles: the index maps read the prefetched schedule,
            # clamping to tile 0 on grid steps of the other kind (the
            # pipeline still double-buffers the matching steps' DMAs).
            pl.BlockSpec((1, kp.p_pad, kp.tile_q),
                         lambda i, m, tt: (jnp.where(m[i, _KIND] == 0,
                                                     m[i, _SEL], 0), 0,
                                           jnp.where(m[i, _KIND] == 0,
                                                     m[i, _QT], 0))),
            pl.BlockSpec((kp.tile_r, kp.f_pad),
                         lambda i, m, tt: (jnp.where(m[i, _KIND] == 1,
                                                     m[i, _SEL], 0), 0)),
            pl.BlockSpec((kp.tile_r, kp.f_pad),
                         lambda i, m, tt: (jnp.where(m[i, _KIND] == 1,
                                                     m[i, _SEL], 0), 0)),
        ],
        out_specs=[vec, vec, vec, vec, whole_ring],  # v', u', spikes, i_syn, ring'
        # per-delay accumulator rows; the delay is the leading (untiled)
        # dim, so picking row k at run time needs no sublane alignment
        scratch_shapes=[pltpu.VMEM((k_delays, 1, np_), f32)],
    )
    kern = functools.partial(
        _tick_kernel, ring_len=static.ring_len, dt=static.dt,
        substeps=static.substeps, delays=delays, n_steps=kp.n_steps,
        p_pad=kp.p_pad, tile_q=kp.tile_q, tile_r=kp.tile_r,
        state_round=_storage_round(v.dtype),
        ring_round=_storage_round(ring.dtype))
    v_o, u_o, sp_o, isyn_o, ring_o = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, np_), f32),
            jax.ShapeDtypeStruct((1, np_), f32),
            jax.ShapeDtypeStruct((1, np_), jnp.int32),
            jax.ShapeDtypeStruct((1, np_), f32),
            jax.ShapeDtypeStruct(ring_p.shape, f32),
        ],
        interpret=interpret,
        name="fused_tick",
    )(kp.meta, t.reshape(1).astype(jnp.int32),
      row(v), row(u), ring_p, row(gen_row, jnp.int32),
      row(is_gen, jnp.int32), row(a), row(b), row(c), row(d),
      kp.w_stack, kp.csr_idx, kp.csr_w)
    return (v_o[0, :n].astype(v.dtype), u_o[0, :n].astype(u.dtype),
            sp_o[0, :n] != 0, ring_o[:, :n].astype(ring.dtype),
            isyn_o[0, :n])
