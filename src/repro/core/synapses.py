"""Synaptic projections: dense delay-bucketed weights + STP.

Hardware adaptation (DESIGN.md §2): CARLsim stores an AoS synapse list and
walks it per spike — efficient on a scalar M33, hostile to the MXU. We store
each projection as a dense ``[n_pre, n_post]`` matrix in the policy's storage
dtype (**fp16 under the paper's policy — this is the paper's headline
technique**) plus a bool mask, and propagate spikes with one
``spikes_f32 @ W_f32`` matmul per projection. Axonal delays become a ring of
per-tick current accumulators: a spike at tick t with delay d lands in ring
slot (t + d) mod D.

Short-term plasticity (STP) follows CARLsim's Tsodyks–Markram form with
per-presynaptic-neuron (u, x) state.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "CSRFanin",
    "ProjectionSpec",
    "ProjectionParams",
    "STPConfig",
    "STPState",
    "build_csr_direct",
    "build_fixed_fanin",
    "csr_layout",
    "csr_to_dense",
    "dense_to_csr",
    "propagate",
    "stp_update",
]


@dataclasses.dataclass(frozen=True)
class STPConfig:
    """Tsodyks–Markram short-term plasticity (CARLsim ``setSTP``)."""

    u0: float = 0.45  # utilization increment U
    tau_f: float = 50.0  # facilitation time constant (ms)
    tau_d: float = 750.0  # depression time constant (ms)


@dataclasses.dataclass(frozen=True)
class ProjectionSpec:
    """Static description of one connection group (paper Table II row).

    ``fanin``/``n_syn`` are filled in at compile time from the realized
    connectivity mask (max in-degree over post neurons / total synapse
    count) — the planner's sparse-vs-dense cost model and the CSR row
    width both key off the *realized* fan-in, which for the Bernoulli
    connect mode exceeds the nominal Table II value.
    """

    name: str
    pre_start: int
    pre_size: int
    post_start: int
    post_size: int
    delay_ms: int
    receptor: str  # "exc" (AMPA/NMDA) or "inh" (GABAa/GABAb)
    plastic: bool = False
    stp: STPConfig | None = None
    fanin: int = 0  # realized max in-degree (compile-time)
    n_syn: int = 0  # realized synapse count (compile-time)

    @property
    def pre_slice(self) -> slice:
        return slice(self.pre_start, self.pre_start + self.pre_size)

    @property
    def post_slice(self) -> slice:
        return slice(self.post_start, self.post_start + self.post_size)


class ProjectionParams(NamedTuple):
    weight: jax.Array  # [pre, post] storage dtype (fp16 policy) — signed
    mask: jax.Array  # [pre, post] bool — which synapses exist


class STPState(NamedTuple):
    u: jax.Array  # [pre] facilitation
    x: jax.Array  # [pre] depression resource


def build_fixed_fanin(
    rng: np.random.Generator,
    spec: ProjectionSpec,
    fanin: int,
    weight: float,
    *,
    storage_dtype=jnp.float32,
) -> ProjectionParams:
    """Fixed fan-in random connectivity (paper Table II: "Connections, per
    neuron"): each post neuron draws ``fanin`` distinct pre neurons.

    Built host-side with a seeded numpy Generator so network construction is
    deterministic and never touches device RNG (paper load step 2 only stores
    generator state).

    Vectorized: one batched uniform draw + per-row argsort replaces the
    per-post-neuron ``rng.choice`` loop (O(1) host calls instead of
    O(n_post)); each post neuron still draws exactly ``fanin`` distinct pre
    neurons uniformly. Determinism guarantee is unchanged (same seed → same
    mask), but the masks differ from the pre-vectorization per-column
    ``choice`` draws — a documented seed change (spike-count assertions are
    range-based and unaffected).
    """
    n_pre, n_post = spec.pre_size, spec.post_size
    if fanin > n_pre:
        raise ValueError(f"{spec.name}: fanin {fanin} > pre group size {n_pre}")
    # Random permutation per post neuron via argsort of iid uniforms (ties
    # have probability 0 in float64); first `fanin` entries are a uniform
    # without-replacement sample.
    order = np.argsort(rng.random((n_post, n_pre)), axis=1)[:, :fanin]
    mask = np.zeros((n_pre, n_post), dtype=bool)
    mask[order.reshape(-1), np.repeat(np.arange(n_post), fanin)] = True
    w = np.where(mask, np.float32(weight), np.float32(0.0))
    return ProjectionParams(
        weight=jnp.asarray(w, storage_dtype), mask=jnp.asarray(mask)
    )


def build_bernoulli(
    rng: np.random.Generator,
    spec: ProjectionSpec,
    fanin: int,
    weight: float,
    *,
    storage_dtype=jnp.float32,
) -> ProjectionParams:
    """CARLsim-style probabilistic connect: each (pre, post) pair exists with
    p = fanin / n_pre, so the *expected* fan-in matches Table II's
    "Connections per neuron" but with binomial variance — the variance is
    what makes small scaled-down networks (Synfire4-mini) let the wave die
    out, as observed in the paper (412 spikes / 30 s)."""
    n_pre, n_post = spec.pre_size, spec.post_size
    p = fanin / n_pre
    mask = rng.random((n_pre, n_post)) < p
    w = np.where(mask, np.float32(weight), np.float32(0.0))
    return ProjectionParams(
        weight=jnp.asarray(w, storage_dtype), mask=jnp.asarray(mask)
    )


class CSRFanin(NamedTuple):
    """Fixed-width CSR fan-in layout of one projection.

    ``idx[q, k]`` is the k-th presynaptic source of post neuron ``q``
    (local to the projection's pre group, ascending within a row);
    ``weight[q, k]`` the matching synaptic weight in the storage dtype.
    Rows with fewer than ``fanin`` synapses are padded with index 0 and
    weight 0 — an exact-zero contribution, so every consumer (oracle and
    Pallas kernel) treats padding as bitwise neutral. ``idx`` uses int16
    when the pre group fits (halving index bytes against the paper's
    8 MB budget), int32 otherwise.

    ``valid[q, k]`` marks real synapses vs row padding. Propagation never
    needs it (padding weights are exact zeros), but *plastic* CSR rows do:
    STDP would otherwise grow the padded cells (their Δw gathers
    ``pre_trace[0]``), so the CSR weight updates mask with ``valid``
    exactly where the dense updates mask with the ``[pre, post]`` bool
    mask. :func:`dense_to_csr` returns it as host-side numpy — only
    plastic projections put it on device (``network.compile`` converts
    the rows it keeps as ``NetParams.masks``); non-plastic builds never
    pay the transfer.
    """

    idx: jax.Array  # [post, fanin] int16/int32
    weight: jax.Array  # [post, fanin] storage dtype
    valid: jax.Array | np.ndarray  # [post, fanin] bool — False on padding


def build_csr_direct(
    rng: np.random.Generator,
    spec: ProjectionSpec,
    fanin: int,
    weight: float,
    *,
    mode: str = "prob",
    storage_dtype=jnp.float32,
    chunk: int = 2048,
) -> CSRFanin:
    """Build a constant-weight random projection straight into CSR fan-in
    rows, never materializing the dense ``[pre, post]`` mask.

    The dense builders allocate pre×post cells per projection, which caps
    network construction near Synfire4×10 (a ×100 scale-up would need
    ~10 GB of host scratch). This path samples each post neuron's distinct
    pre sources directly: ``mode="prob"`` draws binomial(n_pre, fanin/n_pre)
    row counts (matching :func:`build_bernoulli`'s per-pair Bernoulli
    semantics), ``mode="fanin"`` uses exactly ``fanin`` per row (matching
    :func:`build_fixed_fanin`). Rows follow the :func:`csr_layout`
    contract — ascending pre index over a valid prefix, index 0 / weight 0
    padding — so every CSR consumer treats the output identically to a
    dense-then-converted build. Same seed → same network, but the draws
    differ from the dense builders' (documented, like the PR 1
    vectorization seed change); ``network.compile`` only routes
    projections here above its dense-cells threshold, so every existing
    config's connectivity is untouched.
    """
    n_pre, n_post = spec.pre_size, spec.post_size
    if fanin > n_pre:
        raise ValueError(f"{spec.name}: fanin {fanin} > pre group size {n_pre}")
    if mode == "prob":
        counts = rng.binomial(n_pre, fanin / n_pre, size=n_post)
        counts = np.minimum(counts, n_pre).astype(np.int64)
    elif mode == "fanin":
        counts = np.full(n_post, fanin, dtype=np.int64)
    else:
        raise ValueError(f"unknown connect mode {mode!r}")
    f = max(int(counts.max()), 1)
    idx = np.zeros((n_post, f), dtype=np.int64)
    valid = np.arange(f)[None, :] < counts[:, None]  # [post, f] prefix
    for q0 in range(0, n_post, chunk):
        q1 = min(q0 + chunk, n_post)
        r = rng.random((q1 - q0, n_pre), dtype=np.float32)
        if f < n_pre:
            # f smallest uniforms per row (unordered), then order them by
            # value: the first counts[q] are the counts[q] smallest of the
            # whole row — a uniform without-replacement sample, exactly as
            # the dense builders' argsort-prefix draws.
            cand = np.argpartition(r, f, axis=1)[:, :f]
            sub = np.take_along_axis(r, cand, axis=1)
            cand = np.take_along_axis(cand, np.argsort(sub, axis=1), axis=1)
        else:  # f == n_pre: full permutation keeps partial rows uniform
            cand = np.argsort(r, axis=1)
        # ascending pre index over the valid prefix, 0 on padding
        cand = np.where(valid[q0:q1], cand, np.int64(n_pre))
        cand.sort(axis=1)
        idx[q0:q1] = np.where(valid[q0:q1], cand, 0)
    wq = np.where(valid, np.float32(weight), np.float32(0.0))
    idx_dtype = np.int16 if n_pre <= np.iinfo(np.int16).max else np.int32
    return CSRFanin(
        idx=jnp.asarray(idx.astype(idx_dtype)),
        weight=jnp.asarray(wq, storage_dtype),
        valid=valid,
    )


def csr_layout(
    mask: np.ndarray | jax.Array, *, fanin: int | None = None
) -> tuple[np.ndarray, np.ndarray]:
    """Host-side CSR fan-in layout of a dense bool mask: ``(idx, valid)``
    numpy arrays, both ``[post, fanin]``, ascending pre index per row
    (a stable argsort over ``~mask`` floats the True entries to the front
    of each column in index order, so CSR reduction order matches the
    dense matmul's index order), ``idx = 0`` on padding.

    Shared by :func:`dense_to_csr` and the compile-time sentinel tables of
    dense-stored plastic projections (``network.compile``) — the latter
    needs only the index geometry, never the quantized weight rows.
    """
    m = np.asarray(mask)
    counts = m.sum(axis=0)
    f = int(counts.max()) if fanin is None else fanin
    order = np.argsort(~m, axis=0, kind="stable")[:f]  # [f, post]
    valid = np.arange(f)[:, None] < counts[None, :]  # [f, post]
    idx = np.where(valid, order, 0).T  # [post, f]
    return idx, np.ascontiguousarray(valid.T)


def dense_to_csr(
    mask: np.ndarray | jax.Array,
    weight: np.ndarray | jax.Array,
    *,
    fanin: int | None = None,
    storage_dtype=None,
) -> CSRFanin:
    """Convert a dense ``[pre, post]`` (mask, weight) pair to CSR fan-in.

    Host-side numpy (compile time only); row order per :func:`csr_layout`.
    """
    m = np.asarray(mask)
    w = np.asarray(weight, np.float32)
    n_pre = m.shape[0]
    idx, valid = csr_layout(m, fanin=fanin)
    wq = np.where(valid, np.take_along_axis(w.T, idx, axis=1), 0.0)
    idx_dtype = np.int16 if n_pre <= np.iinfo(np.int16).max else np.int32
    if storage_dtype is None:
        src = np.asarray(weight).dtype
        storage_dtype = np.float32 if src == np.float64 else src
    return CSRFanin(
        idx=jnp.asarray(idx.astype(idx_dtype)),
        weight=jnp.asarray(wq, storage_dtype),
        valid=valid,
    )


def csr_to_dense(csr: CSRFanin, n_pre: int) -> np.ndarray:
    """Scatter CSR fan-in rows back to the dense ``[pre, post]`` f32 image.

    Host-side (numpy); the inverse of :func:`dense_to_csr` up to the exact
    zeros on padded cells. Used by the parity suites to compare plastic
    CSR weights against their dense twins bit-for-bit."""
    idx = np.asarray(csr.idx)
    w = np.asarray(csr.weight, np.float32)
    valid = np.asarray(csr.valid)
    n_post, fanin = idx.shape
    out = np.zeros((n_pre, n_post), np.float32)
    cols = np.broadcast_to(np.arange(n_post)[:, None], (n_post, fanin))
    out[idx[valid], cols[valid]] = w[valid]
    return out


def propagate(
    spec: ProjectionSpec,
    params: ProjectionParams,
    spikes: jax.Array,  # [N] bool, full network spike vector
    stp_state: STPState | None,
) -> jax.Array:
    """Synaptic current contribution of this projection: [post_size] f32.

    fp16 weights are up-cast to f32 *at the matmul* (softfp analogue),
    which runs at ``Precision.HIGHEST``: a TPU's default f32 matmul rounds
    its inputs to bf16, and weights such as Synfire4-mini's ``-6.667`` do
    not survive that.
    """
    pre_spikes = spikes[spec.pre_slice].astype(jnp.float32)
    if stp_state is not None and spec.stp is not None:
        # Effective weight scale A = u⁺·x per presynaptic neuron.
        pre_spikes = pre_spikes * (stp_state.u * stp_state.x)
    w = params.weight.astype(jnp.float32)
    return jnp.dot(pre_spikes, w, precision=jax.lax.Precision.HIGHEST)


def stp_update(
    cfg: STPConfig, state: STPState, pre_spikes: jax.Array, dt: float
) -> STPState:
    """Tsodyks–Markram: on a spike u += U(1−u) then x −= u⁺x; continuous
    recovery du/dt = −u/τ_F, dx/dt = (1−x)/τ_D."""
    s = pre_spikes.astype(jnp.float32)
    u = state.u.astype(jnp.float32)
    x = state.x.astype(jnp.float32)
    u_plus = u + cfg.u0 * (1.0 - u) * s
    x_minus = x - u_plus * x * s
    u_rec = u_plus - dt * u_plus / cfg.tau_f
    x_rec = x_minus + dt * (1.0 - x_minus) / cfg.tau_d
    return STPState(u=u_rec.astype(state.u.dtype), x=x_rec.astype(state.x.dtype))


def init_stp_state(cfg: STPConfig, n_pre: int, dtype=jnp.float32) -> STPState:
    return STPState(
        u=jnp.full((n_pre,), cfg.u0, dtype), x=jnp.ones((n_pre,), dtype)
    )
