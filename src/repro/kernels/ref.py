"""Pure-jnp oracles for every Pallas kernel (interpret-mode allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def izh4_ref(v, u, i_syn, a, b, c, d, *, dt: float = 1.0, substeps: int = 2):
    """IZH4 update + spike + reset; f32 math, storage dtype preserved."""
    out_dtype = v.dtype
    v = v.astype(jnp.float32)
    u = u.astype(jnp.float32)
    i_syn = i_syn.astype(jnp.float32)
    h = dt / substeps
    for _ in range(substeps):
        # Simultaneous derivatives (CARLsim evaluates dv and du from the
        # same pre-step state) — keeps the kernel bit-exact with the
        # engine's neurons._derivs euler path (same factored dv, see there).
        dv = (0.04 * v + 5.0) * v + 140.0 - u + i_syn
        du = a * (b * v - u)
        v = v + h * dv
        u = u + h * du
    spiked = v >= 30.0
    v = jnp.where(spiked, c, v)
    u = jnp.where(spiked, u + d, u)
    return v.astype(out_dtype), u.astype(out_dtype), spiked


def syn_matmul_ref(x, w):
    """x [M, K] @ w [K, N], storage-dtype weights decoded to f32 (softfp)."""
    return jnp.dot(
        x.astype(jnp.float32), w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def syn_gather_ref(spikes, idx, w):
    """CSR fan-in drive: ``out[q] = Σ_k spikes[idx[q, k]] * w[q, k]``.

    Same contract as :func:`repro.kernels.syn_gather.syn_gather` — padded
    entries must carry weight 0 so they contribute an exact ``+0.0``.
    """
    g = jnp.take(spikes.astype(jnp.float32), idx.astype(jnp.int32), axis=0)
    return (g * w.astype(jnp.float32)).sum(axis=1)


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = -1,
                        scale: float | None = None):
    """Exact GQA attention. q [B, Hq, S, D]; k/v [B, Hkv, S, D]; Hq % Hkv == 0.

    ``window > 0`` restricts attention to the last ``window`` positions
    (local sliding-window attention, RecurrentGemma-style).
    """
    b, hq, sq, dh = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / jnp.sqrt(dh).astype(jnp.float32)
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qf = qf.reshape(b, hkv, g, sq, dh)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kf)
    qpos = jnp.arange(sq)[:, None] + (sk - sq)  # align ends (decode-friendly)
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, vf)
    return o.reshape(b, hq, sq, dh).astype(q.dtype)


def fused_tick_ref(v, u, ring, gen_row, is_gen, a, b, c, d, t, *,
                   dense, csr, ring_len: int, dt: float = 1.0,
                   substeps: int = 2):
    """Whole-tick oracle for ``kernels.fused_tick`` — the engine's phase
    1–5 semantics written the straightforward jnp way on UNPADDED
    operands (an independent implementation: the kernel's lane padding,
    tile schedule, and clamped DMAs must all cancel out against this).

    ``ring`` [L, N] single-channel storage-dtype ring; ``dense`` iterates
    ``(pre_start, post_start, delay_ms, W[P, Q])``; ``csr`` iterates
    ``(post_start, delay_ms, idx[Q, F] global ids, w[Q, F])``.  Returns
    ``(v', u', spikes, ring', i_syn)``.
    """
    f32 = jnp.float32
    n = v.shape[0]
    slot = jnp.mod(t, ring_len)
    row = jax.lax.dynamic_index_in_dim(ring, slot, axis=0, keepdims=False)
    i_syn = row.astype(f32)
    ring = jax.lax.dynamic_update_index_in_dim(
        ring, jnp.zeros_like(row), slot, axis=0)
    v1, u1, spiked = izh4_ref(v, u, i_syn, a, b, c, d, dt=dt,
                              substeps=substeps)
    v2 = jnp.where(is_gen, c, v1.astype(f32)).astype(v.dtype)
    u2 = jnp.where(is_gen, 0.0, u1.astype(f32)).astype(u.dtype)
    spikes = jnp.where(is_gen, gen_row, spiked)
    sf = spikes.astype(f32)
    acc: dict[int, jax.Array] = {}
    for ps, qs, dly, w in dense:
        p, q = w.shape
        drive = jnp.dot(sf[ps:ps + p], w.astype(f32),
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=f32)
        a_ = acc.get(dly, jnp.zeros((n,), f32))
        acc[dly] = a_.at[qs:qs + q].add(drive)
    for qs, dly, idx, w in csr:
        drive = (jnp.take(sf, idx.astype(jnp.int32), axis=0)
                 * w.astype(f32)).sum(axis=1)
        a_ = acc.get(dly, jnp.zeros((n,), f32))
        acc[dly] = a_.at[qs:qs + drive.shape[0]].add(drive)
    for dly in sorted(acc):
        dslot = jnp.mod(t + dly, ring_len)
        r2 = jax.lax.dynamic_index_in_dim(ring, dslot, axis=0,
                                          keepdims=False)
        ring = jax.lax.dynamic_update_index_in_dim(
            ring, r2 + acc[dly].astype(ring.dtype), dslot, axis=0)
    return v2, u2, spikes, ring, i_syn


def stdp_update_ref(w, mask, pre_trace, post_trace, pre_spikes, post_spikes,
                    *, a_plus: float, a_minus: float, w_min: float, w_max: float):
    """Fused pair-based STDP weight update (storage-dtype weights)."""
    wf = w.astype(jnp.float32)
    ltp = a_plus * jnp.outer(pre_trace, post_spikes.astype(jnp.float32))
    ltd = a_minus * jnp.outer(pre_spikes.astype(jnp.float32), post_trace)
    wf = jnp.clip(wf + ltp - ltd, w_min, w_max)
    return jnp.where(mask, wf, 0.0).astype(w.dtype)


def stdp_gather_ref(w, idx, valid, pre_trace, post_trace, pre_spikes,
                    post_spikes, *, a_plus: float, a_minus: float,
                    w_min: float, w_max: float):
    """Pair-based STDP on CSR fan-in rows (``w``/``idx``/``valid``
    [Q, F]): ``dw[q, k] = a⁺·pre_t[idx[q, k]]·post_s[q] −
    a⁻·pre_s[idx[q, k]]·post_t[q]`` — pure gather + elementwise, so the
    kernel must match **bit-for-bit** (no reduction-order freedom). Same
    contract as :func:`repro.kernels.stdp_gather.stdp_gather`."""
    ii = idx.astype(jnp.int32)
    wf = w.astype(jnp.float32)
    post_s = post_spikes.astype(jnp.float32)[:, None]
    ltp = a_plus * (jnp.take(pre_trace.astype(jnp.float32), ii, axis=0) * post_s)
    ltd = a_minus * (jnp.take(pre_spikes.astype(jnp.float32), ii, axis=0)
                     * post_trace.astype(jnp.float32)[:, None])
    wf = jnp.clip(wf + ltp - ltd, w_min, w_max)
    return jnp.where(valid, wf, 0.0).astype(w.dtype)
