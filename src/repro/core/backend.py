"""Backend dispatch: kernel-backed fused tick vs. pure-XLA reference path.

The engine's hot path is selected by two ``NetStatic`` fields:

``propagation``
    * ``"packed"`` (default) — non-plastic projections are packed per the
      compile-time bucket plan (:class:`~repro.core.network.BucketSpec`):
      one block-dense ``[P, Q]`` matmul per (delay, receptor) bucket
      (density-adaptive: sparse unions split into per-projection blocks),
      with the fp16 → f32 weight decode hoisted out of the tick scan
      (assembled **once per run()**), matmuls event-gated on the source
      actually spiking, and one ring commit per DISTINCT delay instead of
      per-projection ``dynamic_slice``/``dynamic_update_slice`` writes.
      Plastic / STP projections keep per-projection matmuls (their weights
      mutate every tick) but feed the same per-delay ring commit.
    * ``"sparse"`` — non-plastic projections execute as CSR fan-in
      gather + segment-sum buckets (``kind="sparse"``): weights are stored
      as ``[post, fanin]`` rows, spike drive is an event-gated gather of
      each post neuron's ``fanin`` sources, so per-tick bytes scale as
      ``n_post × fanin`` instead of ``n_pre × n_post`` — the fanin ≪ n_pre
      regime the paper's Synfire workloads live in. The fp16 → f32 decode
      of the CSR weight rows is hoisted exactly like the packed images.
    * ``"auto"`` — per-projection bytes-per-tick cost model picks dense
      matmul vs sparse gather (``network._csr_wins``); small projections
      pack densely, large sparse-fan-in ones gather.
    * ``"loop"`` — the seed per-projection reference path, kept verbatim
      for benchmarking and as a semantic oracle.

    All non-loop modes share the same bucket machinery (event gating,
    per-delay ring commit); a bucket's ``kind`` selects matmul vs gather.
    With exactly-representable weights (the Synfire tables) a padded CSR
    row sums the same terms as the dense dot (padding contributes exact
    ``+0.0``), so all four modes produce bit-identical rasters — asserted
    on full Synfire4 by ``tests/test_backends.py`` and on random nets by
    ``tests/test_sparse.py``.

    **Plastic projections** (non-STP) never join buckets — their weights
    mutate every tick — but in every non-loop mode both their drive
    (:func:`plastic_drive`) and their STDP update (:func:`stdp_dispatch`)
    run on fan-in rows over ``NetParams.proj_csr_idx``: CSR-stored
    projections (``static.plastic_csr``, assigned by "sparse"/"auto") read
    their ``[post, fanin]`` rows directly; dense-stored ones gather the
    same rows out of the rectangle. Same terms, same order ⇒ packed,
    sparse, and auto stay bit-identical on plastic nets even after STDP
    pushes weights off the representable grid
    (``tests/test_plasticity_sparse.py``). "loop" keeps the seed dense
    dot + outer-product STDP as the semantic oracle.

``backend``
    * ``"xla"`` (default) — plain jnp ops everywhere.
    * ``"pallas"`` — neuron integration through the fused
      :func:`repro.kernels.izh_update.izh4_update` VPU kernel, propagation
      matmuls through :func:`repro.kernels.syn_matmul.syn_matmul` (fp16
      decode fused into the MXU feed), and pair-based STDP through
      :func:`repro.kernels.stdp_update.stdp_update`. With
      ``static.pallas_interpret`` (auto-set off-TPU) the same code path
      runs under the Pallas interpreter so CPU tests exercise it.

Bit-parity: both backends consume the *same* assembled f32 bucket images
and express the same f32 arithmetic (every propagation contraction runs at
``Precision.HIGHEST`` — a TPU's default f32 matmul would round the weights
to bf16); the pallas matmul is issued with a
single k-block (≤ ``_MAX_KBLOCK``) so its accumulation order matches
``jnp.dot`` at bucket sizes up to a few hundred — on CPU the two backends
produce bit-identical spike rasters, asserted by ``tests/test_backends.py``
on Synfire4-mini in both storage policies.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import neurons as nrn
from repro.core.plasticity import (
    STDPState,
    _trace_step,
    stdp_step,
    stdp_step_csr,
)
from repro.core.synapses import stp_update
from repro.kernels.izh_update import izh4_update
from repro.kernels.ref import izh4_ref
from repro.kernels.stdp_gather import stdp_gather
from repro.kernels.stdp_update import stdp_update as stdp_kernel
from repro.kernels.syn_gather import syn_gather
from repro.kernels.syn_matmul import syn_matmul

__all__ = [
    "assemble_packed",
    "assemble_fused",
    "FusedPayload",
    "update_neurons_dispatch",
    "propagate_packed",
    "propagate_fused",
    "plastic_drive",
    "stdp_dispatch",
]

# Largest single k-block handed to the pallas matmul. Below this the kernel
# reduces the whole contraction in one jnp.dot — same accumulation order as
# the xla path (bit-parity); beyond it the kernel falls back to k-blocking.
_MAX_KBLOCK = 4096


def assemble_packed(static, weights) -> tuple[jax.Array, ...]:
    """Assemble the per-bucket f32 weight payloads (decode hoisted).

    Dense buckets get their block-dense ``[P, Q]`` image; sparse buckets
    get their CSR weight rows ``[Q, fanin]`` decoded to f32 (the index
    table is static and lives in ``NetParams.bucket_csr_idx``).

    ``weights`` is the per-projection tuple from ``NetState``; only
    non-plastic projections appear in ``static.buckets`` so the payloads
    are loop-invariant — callers (``engine.run``) build them once per
    device program, outside the tick scan.
    """
    packed = []
    for b in static.buckets:
        if b.kind == "sparse":
            packed.append(weights[b.members[0][0]].astype(jnp.float32))
            continue
        if len(b.members) == 1 and (b.p, b.q) == (
            static.projections[b.members[0][0]].pre_size,
            static.projections[b.members[0][0]].post_size,
        ):
            # Singleton bucket covering exactly one projection block: the
            # decode IS the image (no zero-fill copy).
            packed.append(weights[b.members[0][0]].astype(jnp.float32))
            continue
        img = jnp.zeros((b.p, b.q), jnp.float32)
        for j, r0, c0 in b.members:
            spec = static.projections[j]
            img = img.at[r0:r0 + spec.pre_size, c0:c0 + spec.post_size].add(
                weights[j].astype(jnp.float32)
            )
        packed.append(img)
    return tuple(packed)


def _matmul(static, pre_row: jax.Array, w: jax.Array) -> jax.Array:
    """``pre_row [P] @ w [P, Q] -> [Q]`` via the selected backend."""
    if static.backend == "pallas":
        out = syn_matmul(
            pre_row[None, :], w,
            block_k=_MAX_KBLOCK,
            interpret=static.pallas_interpret,
        )
        return out[0]
    return jnp.dot(pre_row, w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def _gather(static, pre_row: jax.Array, idx: jax.Array, w: jax.Array) -> jax.Array:
    """CSR fan-in drive ``[Q] = Σ_k pre_row[idx[q, k]] · w[q, k]`` via the
    selected backend. ``w`` is the hoisted f32 CSR weight row payload;
    padded cells carry weight 0 (exact-zero contributions)."""
    if static.backend == "pallas":
        return syn_gather(pre_row, idx, w, interpret=static.pallas_interpret)
    return (jnp.take(pre_row, idx.astype(jnp.int32), axis=0) * w).sum(axis=1)


def plastic_drive(static, params, j: int, spec, w: jax.Array,
                  pre_row: jax.Array) -> jax.Array:
    """Fan-in-row drive of a plastic projection: ``[Q] = Σ_k
    pre_row[idx[q, k]] · w_row[q, k]`` over ``params.proj_csr_idx[j]``.

    Both storages feed the same expression: CSR-stored projections read
    their ``[Q, F]`` weight rows directly; dense-stored ones gather the
    rows out of the ``[P, Q]`` rectangle (sentinel-padded table — the
    appended zero row/slot makes padded terms exact ``+0.0``, matching the
    CSR 0-pad). Same row values, same ``[Q, F]`` reduce shape → packed
    (dense storage) and sparse (CSR storage) rasters are bit-identical
    even after STDP drives the weights off the representable grid.

    Deliberately plain jnp on BOTH backends: the per-synapse terms are
    identical across storages, so bit-parity only needs a *consistent*
    reduction — which the pallas ``syn_gather`` kernel cannot provide for
    off-grid weights (its lane padding reshapes the reduce, and XLA's
    reduce order is shape-dependent). The kernel stays on the non-plastic
    buckets, where exactly-representable weights make any order exact.
    """
    idx = params.proj_csr_idx[j].astype(jnp.int32)
    if j in static.csr_projs:
        rows = w.astype(jnp.float32)  # decoded per tick: weights mutate
        g = jnp.take(pre_row, idx, axis=0)
    else:
        w_ext = jnp.pad(w.astype(jnp.float32), ((0, 1), (0, 0)))
        rows = w_ext[idx, jnp.arange(spec.post_size)[:, None]]
        g = jnp.take(jnp.pad(pre_row, (0, 1)), idx, axis=0)
    return (g * rows).sum(axis=1)


def update_neurons_dispatch(static, params, neurons, i_syn):
    """Neuron integration step.

    IZH4-only euler networks (``static.izh4_only`` — the Synfire workloads)
    take a dedicated path: the pallas backend runs the fused VPU kernel,
    the xla backend the IZH4-specialized ``kernels.ref.izh4_ref`` update
    (one shared expression tree with the kernel) that skips the generic
    three-model ``_derivs`` selects (~2.5× fewer elementwise ops per tick,
    bit-identical values — the dead IZH9/LIF branches never influence the
    selected lanes). Everything else falls back to the generic reference.
    """
    state_dtype = neurons.v.dtype
    fast = static.izh4_only and static.method == "euler"
    if not fast:
        return nrn.update_neurons(
            params.neuron, neurons, i_syn,
            dt=static.dt, substeps=static.substeps, method=static.method,
            state_dtype=state_dtype,
        )

    p = params.neuron
    if static.backend == "pallas":
        v, u, spiked = izh4_update(
            neurons.v, neurons.u, i_syn.astype(jnp.float32),
            p.a, p.b, p.c, p.d,
            dt=static.dt, substeps=static.substeps,
            interpret=static.pallas_interpret,
        )
    else:
        v, u, spiked = izh4_ref(
            neurons.v, neurons.u, i_syn.astype(jnp.float32),
            p.a, p.b, p.c, p.d,
            dt=static.dt, substeps=static.substeps,
        )
    v = v.astype(jnp.float32)
    u = u.astype(jnp.float32)
    # Generator handling identical to update_neurons (generators hold
    # rest); refrac counts down and masks the spike flag, matching the
    # generic path for every reachable state — refrac > 0 only ever arises
    # for LIF neurons, which disable this fast path via izh4_only. (If
    # IZH4 ever gains a refractory period, note the kernel applies the
    # v>=30 reset before this mask while update_neurons resets only
    # non-refractory spikers.)
    is_gen = p.model == nrn.NeuronModel.GENERATOR
    in_refrac = neurons.refrac > 0
    spiked = spiked & ~is_gen & ~in_refrac
    v = jnp.where(is_gen, p.c, v).astype(state_dtype)
    u = jnp.where(is_gen, 0.0, u).astype(state_dtype)
    refrac = jnp.maximum(neurons.refrac - 1, 0).astype(jnp.int16)
    return nrn.NeuronState(v=v, u=u, refrac=refrac), spiked


def propagate_packed(static, params, state, spikes, ring, t, packed,
                     pre_row=None):
    """Fused propagation: bucket matmuls / CSR gathers + per-projection
    fallbacks for plastic/STP projections, merged into one ring commit per
    distinct delay.

    ``pre_row`` substitutes a different bool row for every PRE-side read
    (bucket slices, plastic/STP gathers, event-gating predicates) while the
    accumulator/ring stay sized by ``static.n``. Partitioned cores pass
    their imported-spike row here: a core's static tables hold pre
    coordinates in the core's import space but post coordinates in its
    local space, and nothing on the post side ever indexes the spike row.

    Returns ``(ring', new_stp)`` with ``new_stp`` aligned to
    ``static.projections``.
    """
    f32 = jnp.float32
    src = spikes if pre_row is None else pre_row
    spikes_f32 = src.astype(f32)
    coba = static.ring_channels == 2

    # Dense [N, C] f32 accumulator per distinct delay; contributions land in
    # it via static-slice adds (placement known at compile time), then one
    # full-row update per delay commits them to the ring — replacing the
    # seed's per-projection dynamic_slice/dynamic_update_slice pairs.
    acc: dict[int, jax.Array] = {}

    def emit(make_contrib, pred, delay_ms, channel, post_start, post_ids):
        """Accumulate one contribution; with event gating the matmul only
        runs when the source actually spiked this tick (a silent source
        contributes exact ±0, so skipping is bitwise neutral — the
        CARLsim insight that silent neurons must cost nothing)."""
        a = acc.get(delay_ms)
        if a is None:
            a = jnp.zeros((static.n, static.ring_channels), f32)

        def add(a):
            contrib = make_contrib()
            contrib = jnp.abs(contrib) if coba else contrib
            if post_start >= 0:  # contiguous post span -> static slice add
                q = contrib.shape[0]
                return a.at[post_start:post_start + q, channel].add(contrib)
            return a.at[post_ids, channel].add(contrib)

        if static.event_gated:
            acc[delay_ms] = jax.lax.cond(pred, add, lambda a: a, a)
        else:
            acc[delay_ms] = add(a)

    # 1. planned buckets (non-plastic projections): one matmul per dense
    #    bucket, one CSR gather + segment-sum per sparse bucket
    for bi, b in enumerate(static.buckets):
        if b.pre_start >= 0:  # contiguous pre union -> static slice
            pre = spikes_f32[b.pre_start:b.pre_start + b.p]
        else:
            pre = spikes_f32[params.bucket_pre_ids[bi]]
        if b.kind == "sparse":
            fn = (lambda pre=pre, bi=bi:
                  _gather(static, pre, params.bucket_csr_idx[bi], packed[bi]))
        else:
            fn = lambda pre=pre, bi=bi: _matmul(static, pre, packed[bi])
        emit(fn, pre.any() if static.event_gated else None,
             b.delay_ms, b.channel, b.post_start, params.bucket_post_ids[bi])

    # 2. per-projection fallback: plastic / STP projections (weights change
    #    every tick, so they cannot live in the hoisted packed image). Both
    #    run the fan-in-row drive over their compile-time idx table —
    #    O(post × fanin) for either storage, and the shared row arithmetic
    #    is what keeps dense- and CSR-stored plastic runs bit-identical.
    #    STP projections are CSR-stored in every non-loop mode: the per-pre
    #    u·x scale is applied to the spike row *before* the gather, so the
    #    old dense matmul fallback is gone from the hot loop entirely.
    new_stp = []
    for j, (spec, w, stp_state) in enumerate(
            zip(static.projections, state.weights, state.stp)):
        if not (spec.plastic or spec.stp is not None):
            new_stp.append(None)
            continue
        pre_sp = spikes_f32[spec.pre_slice]
        if stp_state is not None and spec.stp is not None:
            pre_sp = pre_sp * (stp_state.u * stp_state.x)
        channel = 0 if (not coba or spec.receptor == "exc") else 1
        fn = (lambda pre_sp=pre_sp, w=w, j=j, spec=spec:
              plastic_drive(static, params, j, spec, w, pre_sp))
        emit(fn,
             src[spec.pre_slice].any() if static.event_gated else None,
             spec.delay_ms, channel, spec.post_start, None)
        if stp_state is not None:
            new_stp.append(stp_update(spec.stp, stp_state,
                                      src[spec.pre_slice], static.dt))
        else:
            new_stp.append(None)

    # 3. commit the per-delay accumulators to the ring: one full-row
    # read-add-write per DISTINCT delay (K ≈ 2 for Synfire) instead of the
    # seed's per-PROJECTION dynamic-slice patches. Full-row dynamic updates
    # with an unbatched slot index stay cheap slice ops both at B=1 and
    # under vmap (a single lax.scatter would serialize on CPU and
    # re-batch poorly).
    for d in sorted(acc):
        slot = jnp.mod(t + d, static.ring_len)
        row = jax.lax.dynamic_index_in_dim(ring, slot, axis=0, keepdims=False)
        row = row + acc[d].astype(ring.dtype)
        ring = jax.lax.dynamic_update_index_in_dim(ring, row, slot, axis=0)
    return ring, tuple(new_stp)


class FusedPayload(NamedTuple):
    """Hoisted loop-invariant payloads for ``backend="fused"``.

    ``packed`` is the per-bucket f32 payload tuple (same as
    :func:`assemble_packed`); ``class_w`` stacks each multi-member dense
    shape class into one ``[B, P, Q]`` batch operand (``None`` for
    singleton classes, which keep the plain per-bucket dot); ``kernel``
    carries the Pallas megakernel's streamed operands + tile schedule
    when ``static.fused_kernel`` engages (else ``None``)."""

    packed: tuple[jax.Array, ...]
    class_w: tuple[jax.Array | None, ...]
    kernel: object | None = None


def assemble_fused(static, weights, params=None) -> FusedPayload:
    """Assemble the fused-tick payloads (decode + batching hoisted).

    Reuses the packed bucket images, then stacks same-shape dense buckets
    so the tick issues ONE batched contraction per shape class instead of
    one matmul per bucket — the op-count collapse that buys the fused
    speedup on dispatch-bound hosts.  With ``params`` given and
    ``static.fused_kernel`` set, also builds the megakernel payload
    (stacked weight tiles, globalized CSR tables, tile schedule)."""
    packed = assemble_packed(static, weights)
    class_w: list[jax.Array | None] = []
    for _, bids in static.fused.dense_classes:
        if len(bids) == 1:
            class_w.append(None)
        else:
            class_w.append(jnp.stack([packed[bi] for bi in bids]))
    kernel = None
    if static.fused_kernel and params is not None:
        from repro.kernels.fused_tick import assemble_kernel
        kernel = assemble_kernel(static, params, packed)
    return FusedPayload(packed=packed, class_w=tuple(class_w),
                        kernel=kernel)


def _bucket_pre(static, params, spikes_f32, bi):
    b = static.buckets[bi]
    if b.pre_start >= 0:
        return spikes_f32[b.pre_start:b.pre_start + b.p]
    return spikes_f32[params.bucket_pre_ids[bi]]


def propagate_fused(static, params, state, spikes, ring, t, payload):
    """One-dispatch expression of the tick's whole propagation phase.

    Same plan, same arithmetic as :func:`propagate_packed`, restructured
    by gating regime:

    * ``event_gated`` (sequential B=1 runs): per-bucket ``lax.cond``
      gating is kept — it is packed's real win (only the wavefront's
      bucket computes each tick) — but each cond now returns the small
      ``[Q]`` drive instead of threading the full ``[N, C]`` accumulator
      through both branches, and the accumulator add runs
      unconditionally.  Skipping a silent source is bitwise neutral: its
      contribution is exact ±0, and IEEE ``(+0) + (±0) = +0`` keeps the
      accumulator rows identical.
    * ungated (``vmap`` / ``run_batch``, where ``cond`` degenerates to
      ``select`` and both branches run anyway): dense buckets with the
      same ``[P, Q]`` shape run as ONE batched ``dot_general`` over
      stacked images (``FusedPayload.class_w``) into one ``[K, N, C]``
      accumulator (K = distinct delays); batching changes which *kernel*
      computes each row, not the order of adds within a row, so
      exactly-representable weight tables stay bit-identical (asserted
      across the whole parity matrix).

    Both regimes land contributions in plan-then-projection order and
    commit with the same per-delay ring writes as packed — the Pallas
    kernel epilogue mirrors this exactly.  Plastic / STP projections
    reuse :func:`plastic_drive` verbatim (same expression tree ⇒
    bit-identical even off the representable grid).  Returns
    ``(ring', new_stp)``.
    """
    f32 = jnp.float32
    plan = static.fused
    coba = static.ring_channels == 2
    delays = plan.delays
    K = len(delays)
    if K == 0:  # no projections: nothing to propagate
        return ring, tuple(None for _ in static.projections)
    kpos = {d: k for k, d in enumerate(delays)}

    def gated_acc():
        spikes_f32 = spikes.astype(f32)
        acc: dict[int, jax.Array] = {}

        def emit(fn, pred, q, delay_ms, channel, post_start, post_ids):
            drive = jax.lax.cond(pred, fn, lambda: jnp.zeros((q,), f32))
            drive = jnp.abs(drive) if coba else drive
            a = acc.get(delay_ms)
            if a is None:
                a = jnp.zeros((static.n, static.ring_channels), f32)
            if post_start >= 0:
                acc[delay_ms] = a.at[post_start:post_start + q,
                                     channel].add(drive)
            else:
                acc[delay_ms] = a.at[post_ids, channel].add(drive)

        for bi, b in enumerate(static.buckets):
            pre = _bucket_pre(static, params, spikes_f32, bi)
            if b.kind == "sparse":
                fn = (lambda pre=pre, bi=bi:
                      _gather(static, pre, params.bucket_csr_idx[bi],
                              payload.packed[bi]))
            else:
                fn = (lambda pre=pre, bi=bi:
                      _matmul(static, pre, payload.packed[bi]))
            emit(fn, pre.any(), b.q, b.delay_ms, b.channel, b.post_start,
                 params.bucket_post_ids[bi])
        for j, (spec, w, stp_state) in enumerate(
                zip(static.projections, state.weights, state.stp)):
            if not (spec.plastic or spec.stp is not None):
                continue
            pre_sp = spikes_f32[spec.pre_slice]
            if stp_state is not None and spec.stp is not None:
                pre_sp = pre_sp * (stp_state.u * stp_state.x)
            channel = 0 if (not coba or spec.receptor == "exc") else 1
            fn = (lambda pre_sp=pre_sp, w=w, j=j, spec=spec:
                  plastic_drive(static, params, j, spec, w, pre_sp))
            emit(fn, spikes[spec.pre_slice].any(), spec.post_size,
                 spec.delay_ms, channel, spec.post_start, None)
        return acc

    def compute(_):
        spikes_f32 = spikes.astype(f32)
        drives: dict[int, jax.Array] = {}
        for ci, (_, bids) in enumerate(plan.dense_classes):
            if payload.class_w[ci] is None:
                bi = bids[0]
                drives[bi] = _matmul(
                    static, _bucket_pre(static, params, spikes_f32, bi),
                    payload.packed[bi])
                continue
            rows = []
            for bi in bids:
                b = static.buckets[bi]
                rows.append(jnp.arange(b.pre_start, b.pre_start + b.p)
                            if b.pre_start >= 0 else params.bucket_pre_ids[bi])
            x = spikes_f32[jnp.stack(rows)]  # [B, P] one gather per class
            out = jax.lax.dot_general(
                x[:, None, :], payload.class_w[ci],
                dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=f32)  # [B, 1, Q]
            for bpos, bi in enumerate(bids):
                drives[bi] = out[bpos, 0]
        for bi in plan.sparse_ids:
            drives[bi] = _gather(
                static, _bucket_pre(static, params, spikes_f32, bi),
                params.bucket_csr_idx[bi], payload.packed[bi])

        acc = jnp.zeros((K, static.n, static.ring_channels), f32)
        # Bucket contributions land in PLAN order, then plastic/STP in
        # projection order — the exact per-delay accumulation order of
        # propagate_packed, so overlapping post spans sum identically.
        for bi, b in enumerate(static.buckets):
            contrib = jnp.abs(drives[bi]) if coba else drives[bi]
            k = kpos[b.delay_ms]
            if b.post_start >= 0:
                acc = acc.at[k, b.post_start:b.post_start + b.q,
                             b.channel].add(contrib)
            else:
                acc = acc.at[k, params.bucket_post_ids[bi],
                             b.channel].add(contrib)
        for j, (spec, w, stp_state) in enumerate(
                zip(static.projections, state.weights, state.stp)):
            if not (spec.plastic or spec.stp is not None):
                continue
            pre_sp = spikes_f32[spec.pre_slice]
            if stp_state is not None and spec.stp is not None:
                pre_sp = pre_sp * (stp_state.u * stp_state.x)
            contrib = plastic_drive(static, params, j, spec, w, pre_sp)
            contrib = jnp.abs(contrib) if coba else contrib
            channel = 0 if (not coba or spec.receptor == "exc") else 1
            acc = acc.at[kpos[spec.delay_ms],
                         spec.post_start:spec.post_start + spec.post_size,
                         channel].add(contrib)
        return acc

    if static.event_gated:
        acc_by_delay = gated_acc()
    else:
        acc = compute(None)
        acc_by_delay = {d: acc[k] for k, d in enumerate(delays)}

    for d in sorted(acc_by_delay):
        slot = jnp.mod(t + d, static.ring_len)
        row = jax.lax.dynamic_index_in_dim(ring, slot, axis=0, keepdims=False)
        row = row + acc_by_delay[d].astype(ring.dtype)
        ring = jax.lax.dynamic_update_index_in_dim(ring, row, slot, axis=0)

    new_stp = tuple(
        stp_update(spec.stp, st, spikes[spec.pre_slice], static.dt)
        if st is not None else None
        for spec, st in zip(static.projections, state.stp))
    return ring, new_stp


def stdp_dispatch(static, cfg, tr, w, mask, pre_sp, post_sp, idx=None):
    """Pair-based STDP step for either storage layout.

    ``idx is None`` — dense ``[pre, post]`` weights: the pallas backend
    fuses the two rank-1 updates + clip + mask into one pass over the fp16
    weight matrix (``kernels.stdp_update``); xla runs ``stdp_step``.

    ``idx`` given — CSR fan-in rows ``[post, fanin]`` (``mask`` is then the
    validity rows): the pallas backend runs the fused gather-row kernel
    (``kernels.stdp_gather``), xla the jnp row update ``stdp_step_csr``.
    Both are pure gather + elementwise, so the two backends — and the
    dense twin cells — stay bit-identical.
    """
    if idx is not None:
        if static.backend != "pallas" or cfg.tau_elig is not None:
            return stdp_step_csr(cfg, tr, w, idx, mask, pre_sp, post_sp,
                                 static.dt)
        pre_t = _trace_step(tr.pre_trace, pre_sp, cfg.tau_plus, static.dt)
        post_t = _trace_step(tr.post_trace, post_sp, cfg.tau_minus, static.dt)
        w2 = stdp_gather(
            w, idx, mask, pre_t, post_t,
            pre_sp.astype(jnp.float32), post_sp.astype(jnp.float32),
            a_plus=cfg.a_plus, a_minus=cfg.a_minus,
            w_min=cfg.w_min, w_max=cfg.w_max,
            interpret=static.pallas_interpret,
        )
        return STDPState(pre_trace=pre_t, post_trace=post_t), w2
    if static.backend != "pallas" or cfg.tau_elig is not None:
        return stdp_step(cfg, tr, w, mask, pre_sp, post_sp, static.dt)
    pre_t = _trace_step(tr.pre_trace, pre_sp, cfg.tau_plus, static.dt)
    post_t = _trace_step(tr.post_trace, post_sp, cfg.tau_minus, static.dt)
    w2 = stdp_kernel(
        w, mask, pre_t, post_t,
        pre_sp.astype(jnp.float32), post_sp.astype(jnp.float32),
        a_plus=cfg.a_plus, a_minus=cfg.a_minus,
        w_min=cfg.w_min, w_max=cfg.w_max,
        interpret=static.pallas_interpret,
    )
    return STDPState(pre_trace=pre_t, post_trace=post_t), w2
