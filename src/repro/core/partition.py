"""Compile-time core-grid partitioner: split one network into fixed-budget
cores exchanging spikes, bit-identical to the single-program engine.

The paper's RP2350 runs the whole feature set inside 8.477 MB on a
dual-core MCU; the TrueNorth/Loihi lineage (and SpikeHard's ``core_grid``)
scale the same way — many fixed-size cores, each holding a slab of neurons
plus every synapse *targeting* them, exchanging spike packets per tick.
This module reproduces that compilation step on top of the existing
engine:

* :func:`plan_partition` cuts the neuron axis ``[0, N)`` into contiguous
  per-core ranges under a byte budget (or into a fixed core count), then
  derives for each core an independent ``NetStatic``/``NetParams`` pair —
  its own delay ring, its own slice of every bucket/CSR table, its own
  :class:`~repro.memory.ledger.MemoryLedger` child enforcing the paper's
  per-core ceiling — plus a spike-exchange plan (which global spike ids
  each core imports, and the implied bytes/tick on every core↔core edge).

* The **key invariant** is that per-core plans are *column slices of the
  global bucket plan*, never re-planned: a core's bucket keeps the full
  global pre union (imported into a compact "ext" coordinate space) and
  slices only the post axis, so every f32 accumulation regroups exactly as
  in the unpartitioned engine and both lowerings are **bitwise identical**
  to it across propagation modes, backends, and precisions (asserted in
  ``tests/test_partition.py``). ``backend.propagate_packed`` reads all
  pre-side operands through its ``pre_row`` argument for this — post
  coordinates never index the spike row, so a core only needs its import
  row.

* Two lowerings of the same plan: :func:`run_partitioned` scans all cores
  sequentially in one device program (single-host path; phase A on every
  core, concatenate the global spike row, then phase B per core), and
  :func:`run_partitioned_mesh` shard_maps cores across a device mesh with
  one ``all_gather`` per tick as the exchange collective. Both share the
  same per-core phase helpers, so mesh ≡ sequential ≡ unpartitioned.

v1 scope (typed :class:`PartitionError` otherwise): plastic/STP
projections never split across cores — the cut treats each plasticity
cluster (pre ∪ post groups, closed under contiguity) as atomic — and the
mesh lowering covers the non-plastic/CUBA feature set; homeostasis,
``propagation="loop"``, batching, and in-scan monitors stay on the
single-program engine.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import backend as be
from repro.core import neurons as nrn
from repro.core.conductance import coba_current, decay_and_deliver
from repro.core.network import (
    BucketSpec,
    GroupSpec,
    NetParams,
    NetState,
    NetStatic,
)
from repro.core.plasticity import da_stdp_step, da_stdp_step_csr
from repro.core.synapses import stp_update
from repro.memory.ledger import MCU_BUDGET_BYTES, MemoryBudgetError

__all__ = [
    "PartitionError",
    "PartitionSpec",
    "CorePlan",
    "ExchangePlan",
    "PartitionPlan",
    "plan_partition",
    "run_partitioned",
    "run_partitioned_mesh",
]


class PartitionError(ValueError):
    """A network cannot be cut under the requested partition spec (atom
    over budget, plastic cluster split, unsupported feature, ...)."""


@dataclasses.dataclass(frozen=True)
class PartitionSpec:
    """User-facing partition request (``network.compile(partition=...)``).

    Exactly one sizing mode: ``n_cores`` fixes the core count (byte-
    balanced cut), else ``core_budget_bytes`` packs greedily under the
    per-core ceiling (default: the paper's 8.477 MB MCU budget). When both
    are given, ``n_cores`` drives the cut and the budget is still enforced
    on every core's ledger. ``lowering`` picks the execution strategy:
    ``"sequential"`` (one device program looping cores) or ``"mesh"``
    (shard_map + all_gather across ``mesh_axis``). ``split_groups=False``
    restricts cuts to group boundaries (whole populations per core).
    ``fill_frac`` is the greedy packer's *target* fill of the byte budget —
    the budget itself stays the hard per-core ceiling on every core's
    ledger; packing below it keeps the cores out of ``obs.health``'s warn
    band (90%) and leaves run-time headroom, the same discipline the paper
    applies to the MCU ceiling.
    """

    n_cores: int | None = None
    core_budget_bytes: int | None = MCU_BUDGET_BYTES
    lowering: str = "sequential"
    mesh_axis: str = "cores"
    split_groups: bool = True
    fill_frac: float = 0.85


class _ProjCut(NamedTuple):
    """How one global projection maps into a core: ``kind`` is ``"full"``
    (intact — plastic/STP owner), ``"csr_rows"`` (CSR weight/idx rows
    ``[c0:c1]``), or ``"dense_cols"`` (dense weight columns ``[:, c0:c1]``);
    ``mutable`` marks weights the core rewrites (reassembly reads them
    back from the owner)."""

    gj: int
    kind: str
    c0: int
    c1: int
    mutable: bool


@dataclasses.dataclass(frozen=True)
class CorePlan:
    """One core's compiled slice: neurons ``[lo, hi)`` of the global index
    space, a per-core ``NetStatic`` whose pre coordinates live in the
    core's import ("ext") space, the projection cut list, the core's
    generator-uniform column range, and the verified ledger bytes."""

    index: int
    lo: int
    hi: int
    static: NetStatic
    proj_cuts: tuple[_ProjCut, ...]
    gc0: int
    gc1: int
    n_ext: int
    bytes_total: int


@dataclasses.dataclass(frozen=True)
class ExchangePlan:
    """Inter-core spike traffic: ``edges`` holds ``(src, dst, n_ids)`` for
    every core pair where ``dst`` imports ``n_ids`` of ``src``'s spikes;
    ``bytes_per_tick`` models 1 byte per imported spike flag per tick —
    the cost the run-time exchange counters validate against the trace."""

    edges: tuple[tuple[int, int, int], ...]
    bytes_per_tick: int


@dataclasses.dataclass(eq=False)
class PartitionPlan:
    """The full compiled partition. Hashable by identity (jit-static);
    carries the per-core params/import tables as run-time operands and the
    per-core ledgers for the sizing report."""

    spec: PartitionSpec
    n: int
    cores: tuple[CorePlan, ...]
    exchange: ExchangePlan
    params: tuple[NetParams, ...]
    ext_idx: tuple[jax.Array, ...]  # per core: [n_ext] int32 global ids
    ext_ids: tuple[np.ndarray, ...]  # host copy (mesh import tables)
    ledgers: tuple = ()

    @property
    def n_cores(self) -> int:
        return len(self.cores)

    @property
    def run_params(self):
        """Operand pytree for the partitioned runners."""
        return (self.params, self.ext_idx)

    def core_bytes(self) -> dict[int, int]:
        return {cp.index: cp.bytes_total for cp in self.cores}


# ---------------------------------------------------------------------------
# planning


def _group_index(groups, start: int, size: int, what: str) -> int:
    for gi, g in enumerate(groups):
        if g.start <= start and start + size <= g.start + g.size:
            return gi
    raise PartitionError(f"{what}: span [{start}, {start + size}) does not "
                         "lie inside any group")


def _atomic_spans(static: NetStatic) -> list[tuple[int, int, str]]:
    """Neuron spans that must stay intra-core: each plastic/STP cluster's
    group set, closed under union-find + contiguity (a core is a contiguous
    range, so a cluster spanning groups 2 and 5 pins 3 and 4 too)."""
    groups = static.groups
    parent = list(range(len(groups)))

    def find(a):
        while parent[a] != a:
            parent[a] = parent[parent[a]]
            a = parent[a]
        return a

    def union(a, b):
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[rb] = ra

    constrained: set[int] = set()
    for j, s in enumerate(static.projections):
        if not (s.plastic or s.stp is not None):
            continue
        gp = _group_index(groups, s.pre_start, s.pre_size, s.name)
        gq = _group_index(groups, s.post_start, s.post_size, s.name)
        union(gp, gq)
        constrained.add(find(gp))
    # contiguity closure: widen every constrained cluster to its full group
    # interval until nothing moves
    changed = True
    while changed:
        changed = False
        constrained = {find(r) for r in constrained}
        for r in list(constrained):
            members = [gi for gi in range(len(groups)) if find(gi) == r]
            for gi in range(min(members), max(members) + 1):
                if find(gi) != find(r):
                    union(r, gi)
                    changed = True
        constrained = {find(r) for r in constrained}
    spans = []
    for r in constrained:
        members = [gi for gi in range(len(groups)) if find(gi) == r]
        lo_g, hi_g = groups[min(members)], groups[max(members)]
        names = ", ".join(groups[gi].name for gi in members)
        spans.append((lo_g.start, hi_g.start + hi_g.size, names))
    return sorted(spans)


def _leaf_bytes_per_item(tree) -> int:
    return int(sum(np.dtype(x.dtype).itemsize for x in jax.tree.leaves(tree)))


def _byte_density(static: NetStatic, params: NetParams,
                  state: NetState) -> np.ndarray:
    """Per-neuron device bytes, mirroring what each core's ledger will
    register — the cut's cost model (the authoritative check re-registers
    the real per-core arrays afterwards)."""
    n = static.n
    rho = np.zeros(n, np.float64)
    sdt = np.dtype(state.neurons.v.dtype).itemsize
    # generator schedule rows (3 × f32), neuron state v/u + refrac,
    # conductances, per-neuron model params, delay ring
    rho += 12.0
    rho += 2 * sdt + 2
    if state.cond is not None:
        rho += 2 * sdt
    rho += _leaf_bytes_per_item(params.neuron)
    rho += static.ring_len * static.ring_channels * sdt
    csr_projs = static.csr_projs
    for j, s in enumerate(static.projections):
        w = state.weights[j]
        wdt = np.dtype(w.dtype).itemsize
        post = slice(s.post_start, s.post_start + s.post_size)
        pre = slice(s.pre_start, s.pre_start + s.pre_size)
        if j in csr_projs:
            f = w.shape[1]
            idt = np.dtype(params.proj_csr_idx[j].dtype).itemsize
            rho[post] += f * (wdt + idt)
            if s.plastic:
                rho[post] += f  # validity rows
        else:
            rho[post] += s.pre_size * wdt
            if s.plastic:
                rho[post] += s.pre_size  # dense bool mask
                if params.proj_csr_idx[j] is not None:
                    t = params.proj_csr_idx[j]
                    rho[post] += t.shape[1] * np.dtype(t.dtype).itemsize
        if s.stp is not None:
            rho[pre] += 2 * sdt
        tr = state.stdp[j]
        if tr is not None:
            for leaf in jax.tree.leaves(tr):
                per = np.dtype(leaf.dtype).itemsize
                if leaf.shape and leaf.shape[0] == s.pre_size \
                        and leaf.ndim == 1:
                    rho[pre] += per
                else:  # post_trace / eligibility attribute to post neurons
                    rho[post] += (leaf.size // max(s.post_size, 1)) * per
    return rho


def _cut_points(static: NetStatic, spec: PartitionSpec,
                rho: np.ndarray, eff_budget: float | None) -> list[int]:
    """Choose core boundaries over the neuron axis: greedy fill under
    ``eff_budget``, or a byte-balanced ``n_cores`` snap — both restricted
    to allowed cut positions (outside atomic spans; group boundaries only
    when ``split_groups=False``)."""
    n = static.n
    allowed = np.ones(n + 1, bool)
    if not spec.split_groups:
        allowed[:] = False
        for g in static.groups:
            allowed[g.start] = True
        allowed[n] = True
    allowed[0] = False
    spans = _atomic_spans(static)
    for a, b, _names in spans:
        allowed[a + 1:b] = False
    cum = np.concatenate([[0.0], np.cumsum(rho)])

    def atom_at(i: int) -> tuple[int, int, str]:
        for a, b, names in spans:
            if a <= i < b:
                return a, b, names
        return i, i + 1, "(single neuron)"

    if spec.n_cores is not None:
        k = spec.n_cores
        if k < 1:
            raise PartitionError(f"n_cores must be >= 1, got {k}")
        if not spec.split_groups and k > len(static.groups):
            raise PartitionError(
                f"n_cores={k} exceeds the {len(static.groups)} groups and "
                "split_groups=False forbids cutting inside a group")
        cuts = [0]
        cand = np.flatnonzero(allowed)
        for c in range(1, k):
            target = cum[-1] * c / k
            pos = np.searchsorted(cum[cand], target)
            best = None
            for p in (pos - 1, pos, pos + 1):
                if 0 <= p < cand.size and cand[p] > cuts[-1] \
                        and cand[p] < n - (k - 1 - c):
                    d = abs(cum[cand[p]] - target)
                    if best is None or d < best[0]:
                        best = (d, int(cand[p]))
            if best is None:
                # fall back to the first allowed position past the previous
                # cut that still leaves room for the remaining cores
                later = cand[(cand > cuts[-1]) & (cand < n)]
                if later.size == 0:
                    raise PartitionError(
                        f"cannot place {k} cores: only "
                        f"{len(cuts)} feasible cut(s) — atomic plasticity "
                        "spans leave too few boundaries")
                best = (0.0, int(later[0]))
            cuts.append(best[1])
        cuts.append(n)
        if len(set(cuts)) != k + 1:
            raise PartitionError(
                f"cannot place {k} distinct cores over {n} neurons with "
                "the allowed cut positions")
        return cuts

    assert eff_budget is not None
    cuts = [0]
    lo = 0
    while lo < n:
        hi_max = int(np.searchsorted(cum, cum[lo] + eff_budget,
                                     side="right")) - 1
        if hi_max >= n:
            cuts.append(n)
            break
        h = hi_max
        while h > lo and not allowed[h]:
            h -= 1
        if h <= lo:
            a, b, names = atom_at(lo if hi_max <= lo else hi_max)
            need = cum[b] - cum[a]
            if need <= float(spec.core_budget_bytes) and b > lo:
                # The atom overflows the *fill target* but fits the hard
                # ceiling. It is indivisible, so take it whole — the
                # authoritative ledger verify still enforces the budget.
                cuts.append(b)
                lo = b
                continue
            raise PartitionError(
                f"core budget {spec.core_budget_bytes / 1024**2:.3f} MB "
                f"cannot hold the atomic span [{a}, {b}) ({names}): it "
                f"needs ~{need / 1024**2:.3f} MB — raise the budget or "
                "break the plasticity cluster")
        cuts.append(h)
        lo = h
    return cuts


def _bucket_arrays(static, params, bi, b):
    """Global (pres, posts) id arrays of bucket ``bi``."""
    if b.pre_start >= 0:
        pres = np.arange(b.pre_start, b.pre_start + b.p)
    else:
        pres = np.asarray(params.bucket_pre_ids[bi])
    if b.post_start >= 0:
        posts = np.arange(b.post_start, b.post_start + b.q)
    else:
        posts = np.asarray(params.bucket_post_ids[bi])
    return pres, posts


def _build_core(static, params, state, c, lo, hi):
    """Derive one core's (NetStatic, NetParams, proj_cuts, ext ids,
    gen-column range). Pre coordinates in the returned static/params live
    in the core's ext space; post coordinates are core-local."""
    csr_projs = static.csr_projs
    specs = static.projections

    # -- which projections land here, and how -------------------------------
    proj_map: list[int] = []
    proj_cuts: list[_ProjCut] = []
    for j, s in enumerate(specs):
        intact = s.plastic or s.stp is not None
        if intact:
            if s.post_start >= lo and s.post_start + s.post_size <= hi:
                if not (s.pre_start >= lo and
                        s.pre_start + s.pre_size <= hi):
                    raise PartitionError(
                        f"plastic/STP projection {s.name} spans cores — "
                        "the cut must keep its cluster intact")
                proj_map.append(j)
                proj_cuts.append(_ProjCut(
                    j, "full", 0, s.post_size,
                    mutable=(static.stdp[j] is not None
                             or s.stp is not None)))
            elif not (s.post_start + s.post_size <= lo
                      or s.post_start >= hi):
                raise PartitionError(
                    f"plastic/STP projection {s.name} split by the cut at "
                    f"[{lo}, {hi}) — plan_partition must not produce this")
            continue
        c0 = max(s.post_start, lo) - s.post_start
        c1 = min(s.post_start + s.post_size, hi) - s.post_start
        if c1 <= c0:
            continue
        proj_map.append(j)
        kind = "csr_rows" if j in csr_projs else "dense_cols"
        proj_cuts.append(_ProjCut(j, kind, c0, c1, mutable=False))

    # -- ext space: every global pre id any kept table reads ----------------
    need: list[np.ndarray] = []
    kept_buckets: list[tuple[int, BucketSpec, np.ndarray, np.ndarray, int,
                             int]] = []
    for bi, b in enumerate(static.buckets):
        pres, posts = _bucket_arrays(static, params, bi, b)
        s_ = int(np.searchsorted(posts, lo))
        e_ = int(np.searchsorted(posts, hi))
        if e_ <= s_:
            continue
        kept_buckets.append((bi, b, pres, posts, s_, e_))
        need.append(pres)
    for cut in proj_cuts:
        if cut.kind == "full":
            s = specs[cut.gj]
            need.append(np.arange(s.pre_start, s.pre_start + s.pre_size))
    ext = (np.unique(np.concatenate(need)) if need
           else np.zeros((0,), np.int64))

    def ext_pos(gid: int) -> int:
        return int(np.searchsorted(ext, gid))

    # A CSR projection's idx table is aliased between bucket_csr_idx and
    # proj_csr_idx in the global params; slice it once per (table, range)
    # so the per-core params keep the alias and the core ledger doesn't
    # double-count the rows.
    _slices: dict[tuple[int, int, int], jax.Array] = {}

    def row_slice(table, a, b_):
        k = (id(table), a, b_)
        if k not in _slices:
            _slices[k] = table[a:b_]
        return _slices[k]

    # -- per-core group slices ---------------------------------------------
    groups_c: list[GroupSpec] = []
    for g in static.groups:
        a, b_ = max(g.start, lo), min(g.start + g.size, hi)
        if b_ <= a:
            continue
        groups_c.append(dataclasses.replace(g, start=a - lo, size=b_ - a))
    gen_sorted = [(g.start, g.size) for g in static.groups if g.is_generator]
    gc0 = sum(min(sz, max(0, min(g0 + sz, lo) - g0))
              for g0, sz in gen_sorted)
    gc1 = sum(min(sz, max(0, min(g0 + sz, hi) - g0))
              for g0, sz in gen_sorted)

    # -- per-core projection specs / params / state cuts --------------------
    specs_c: list = []
    masks_c: list = []
    proj_idx_c: list = []
    for cut in proj_cuts:
        s = specs[cut.gj]
        if cut.kind == "full":
            specs_c.append(dataclasses.replace(
                s, pre_start=ext_pos(s.pre_start),
                post_start=s.post_start - lo))
            masks_c.append(params.masks[cut.gj])
            proj_idx_c.append(params.proj_csr_idx[cut.gj])
        else:
            specs_c.append(dataclasses.replace(
                s, pre_start=ext_pos(s.pre_start),
                post_start=max(s.post_start, lo) - lo,
                post_size=cut.c1 - cut.c0))
            masks_c.append(None)  # never read on the non-plastic path
            t = params.proj_csr_idx[cut.gj]
            proj_idx_c.append(None if t is None
                              else row_slice(t, cut.c0, cut.c1))

    # -- per-core buckets (post slices of the global plan) ------------------
    buckets_c: list[BucketSpec] = []
    bpre_c: list[jax.Array] = []
    bpost_c: list[jax.Array] = []
    bidx_c: list[jax.Array | None] = []
    local_j = {gj: lj for lj, gj in enumerate(proj_map)}
    for bi, b, pres, posts, s_, e_ in kept_buckets:
        posts_c = posts[s_:e_]
        q_c = e_ - s_
        members = []
        for (j, r0, c0) in b.members:
            qj = specs[j].post_size
            ms, me = max(c0, s_), min(c0 + qj, e_)
            if me <= ms:
                continue
            members.append((local_j[j], r0, ms - s_))
        post_contig = int(posts_c[-1]) - int(posts_c[0]) + 1 == q_c
        if b.pre_start >= 0:
            pre_start_c = ext_pos(b.pre_start)
            bpre_c.append(jnp.zeros((0,), jnp.int32))
        else:
            pre_start_c = -1
            bpre_c.append(jnp.asarray(
                np.searchsorted(ext, pres).astype(np.int32)))
        buckets_c.append(dataclasses.replace(
            b, q=q_c,
            pre_start=pre_start_c,
            post_start=int(posts_c[0]) - lo if post_contig else -1,
            members=tuple(members)))
        bpost_c.append(
            jnp.zeros((0,), jnp.int32) if post_contig
            else jnp.asarray((posts_c - lo).astype(np.int32)))
        gi = params.bucket_csr_idx[bi]
        bidx_c.append(None if gi is None else row_slice(gi, s_, e_))

    static_c = dataclasses.replace(
        static,
        n=hi - lo,
        groups=tuple(groups_c),
        projections=tuple(specs_c),
        stdp=tuple(static.stdp[cut.gj] for cut in proj_cuts),
        backend="xla" if static.backend == "fused" else static.backend,
        buckets=tuple(buckets_c),
        plastic_csr=tuple(sorted(local_j[j] for j in static.plastic_csr
                                 if j in local_j)),
        stp_csr=tuple(sorted(local_j[j] for j in static.stp_csr
                             if j in local_j)),
        fused=None,
        fused_kernel=False,
        monitors=(),
        homeo=tuple(None for _ in proj_cuts),
        homeo_period=0,
    )
    params_c = NetParams(
        neuron=jax.tree.map(lambda x: x[lo:hi], params.neuron),
        masks=tuple(masks_c),
        gen_rate=params.gen_rate[lo:hi],
        gen_until=params.gen_until[lo:hi],
        gen_rate_after=params.gen_rate_after[lo:hi],
        bucket_pre_ids=tuple(bpre_c),
        bucket_post_ids=tuple(bpost_c),
        bucket_csr_idx=tuple(bidx_c),
        proj_csr_idx=tuple(proj_idx_c),
    )
    return static_c, params_c, tuple(proj_cuts), ext, gc0, gc1


class _CoreState(NamedTuple):
    neurons: nrn.NeuronState
    ring: jax.Array
    cond: object | None
    weights: tuple
    stp: tuple
    stdp: tuple


def _split_state(plan: PartitionPlan, static: NetStatic,
                 state: NetState) -> tuple[_CoreState, ...]:
    """Slice a GLOBAL NetState into per-core states (in-graph; cheap
    loop-invariant slices)."""
    out = []
    for cp in plan.cores:
        lo, hi = cp.lo, cp.hi
        neurons = jax.tree.map(lambda x: x[lo:hi], state.neurons)
        ring = state.ring[:, lo:hi]
        cond = (None if state.cond is None
                else jax.tree.map(lambda x: x[lo:hi], state.cond))
        ws, stps, stdps = [], [], []
        for cut in cp.proj_cuts:
            w = state.weights[cut.gj]
            if cut.kind == "full":
                ws.append(w)
                stps.append(state.stp[cut.gj])
                stdps.append(state.stdp[cut.gj])
            elif cut.kind == "csr_rows":
                ws.append(w[cut.c0:cut.c1])
                stps.append(None)
                stdps.append(None)
            else:
                ws.append(w[:, cut.c0:cut.c1])
                stps.append(None)
                stdps.append(None)
        out.append(_CoreState(neurons, ring, cond, tuple(ws), tuple(stps),
                              tuple(stdps)))
    return tuple(out)


def _register_core_ledger(ledger_parent, cp_index, static_c, params_c,
                          core_state, ext, budget):
    """Authoritative per-core sizing: register the real per-core arrays on
    a child ledger mirroring the compile() stages (raises
    MemoryBudgetError over budget)."""
    led = ledger_parent.child(f"core{cp_index}", budget=budget)
    with led.stage("2. Random Gen."):
        led.register("rng", (params_c.gen_rate, params_c.gen_until,
                             params_c.gen_rate_after))
    with led.stage("3. Conn. Info"):
        masks = tuple(m for m in params_c.masks if m is not None)
        if masks:
            led.register("masks", masks)
        seen: dict[int, jax.Array] = {}
        for t in (params_c.bucket_csr_idx + params_c.proj_csr_idx
                  + params_c.bucket_pre_ids + params_c.bucket_post_ids):
            if t is not None and t.size and id(t) not in seen:
                seen[id(t)] = t
        if seen:
            led.register("csr.indices", tuple(seen.values()))
        if ext.size:
            led.register("exchange.import",
                         jax.ShapeDtypeStruct((ext.size,), jnp.int32))
    with led.stage("4. Syn. State"):
        led.register("weights", core_state.weights)
        led.register("ring", core_state.ring)
        stp = tuple(s for s in core_state.stp if s is not None)
        if stp:
            led.register("stp", stp)
    with led.stage("5. Neuron State"):
        led.register("neuron.state", core_state.neurons)
        if core_state.cond is not None:
            led.register("conductances", core_state.cond)
    with led.stage("6. Group State"):
        led.register("neuron.params", params_c.neuron)
    with led.stage("7. Auxiliary Data"):
        tr = tuple(s for s in core_state.stdp if s is not None)
        if tr:
            led.register("stdp.traces", tr)
    return led


def plan_partition(net, spec: PartitionSpec) -> PartitionPlan:
    """Cut ``net`` (a CompiledNetwork) into cores per ``spec``.

    Validates the v1 feature envelope, cuts the neuron axis under the byte
    budget (or into ``n_cores``), derives every core's static/params/ext
    tables, verifies each core on a child ledger (retrying with a tighter
    fill target when the density model under-counted), and publishes the
    plan through ``repro.obs`` (spans + per-core byte gauges)."""
    static, params, state = net.static, net.params, net.state0
    if spec.n_cores is None and spec.core_budget_bytes is None:
        raise PartitionError(
            "PartitionSpec needs n_cores or core_budget_bytes")
    if spec.lowering not in ("sequential", "mesh"):
        raise PartitionError(f"unknown lowering {spec.lowering!r}")
    if static.propagation == "loop":
        raise PartitionError(
            "propagation='loop' cannot be partitioned — the seed oracle "
            "has no bucket plan to slice; use packed/sparse/auto")
    if static.homeo_period or any(h is not None for h in static.homeo):
        raise PartitionError(
            "homeostasis is not supported under partitioning (v1) — the "
            "slow timer would need a cross-core spike-count reduction")
    if spec.lowering == "mesh":
        if any(s.plastic or s.stp is not None for s in static.projections):
            raise PartitionError(
                "lowering='mesh' covers non-plastic networks in v1 — "
                "plastic/STP cores run under lowering='sequential'")
        if static.coba is not None:
            raise PartitionError(
                "lowering='mesh' does not support conductance (COBA) "
                "networks in v1")

    with obs.span("partition_plan", n=static.n,
                  lowering=spec.lowering,
                  n_cores=spec.n_cores or 0,
                  budget=float(spec.core_budget_bytes or 0)):
        rho = _byte_density(static, params, state)
        eff = (float(spec.core_budget_bytes) * spec.fill_frac
               if spec.core_budget_bytes else None)
        last_err: Exception | None = None
        for _attempt in range(4):
            cuts = _cut_points(static, spec, rho,
                               None if spec.n_cores is not None else eff)
            try:
                plan = _materialize(net, spec, cuts)
                break
            except MemoryBudgetError as e:
                last_err = e
                if spec.n_cores is not None or eff is None:
                    raise PartitionError(
                        f"a core exceeds the per-core budget: {e}") from e
                eff *= 0.95  # density under-counted; tighten the fill
        else:
            raise PartitionError(
                f"could not fit cores under "
                f"{spec.core_budget_bytes / 1024**2:.3f} MB after retries: "
                f"{last_err}") from last_err

    for cp in plan.cores:
        obs.gauge("repro_partition_core_bytes", float(cp.bytes_total),
                  core=str(cp.index))
    obs.gauge("repro_partition_cores", float(plan.n_cores))
    obs.gauge("repro_partition_exchange_bytes_per_tick",
              float(plan.exchange.bytes_per_tick))
    return plan


def _materialize(net, spec: PartitionSpec, cuts: list[int]) -> PartitionPlan:
    static, params, state = net.static, net.params, net.state0
    cores: list[CorePlan] = []
    params_l: list[NetParams] = []
    ext_l: list[jax.Array] = []
    ext_np: list[np.ndarray] = []
    ledgers = []
    pending = []
    for ci in range(len(cuts) - 1):
        lo, hi = cuts[ci], cuts[ci + 1]
        static_c, params_c, proj_cuts, ext, gc0, gc1 = _build_core(
            static, params, state, ci, lo, hi)
        pending.append((ci, lo, hi, static_c, params_c, proj_cuts, ext,
                        gc0, gc1))
    # per-core authoritative sizing (may raise MemoryBudgetError -> re-cut)
    probe_plan = _ProbePlan(tuple(
        CorePlan(ci, lo, hi, static_c, proj_cuts, gc0, gc1, ext.size, 0)
        for ci, lo, hi, static_c, _params_c, proj_cuts, ext, gc0, gc1
        in pending))
    split_probe = _split_state(probe_plan, static, state)
    for ci, lo, hi, static_c, params_c, proj_cuts, ext, gc0, gc1 in pending:
        led = _register_core_ledger(
            net.ledger, ci, static_c, params_c, split_probe[ci], ext,
            spec.core_budget_bytes)
        ledgers.append(led)
        cores.append(CorePlan(ci, lo, hi, static_c, proj_cuts, gc0, gc1,
                              int(ext.size), int(led.total_used)))
        params_l.append(params_c)
        ext_l.append(jnp.asarray(ext.astype(np.int32)))
        ext_np.append(ext)

    # exchange plan: who imports whose spikes
    edges: dict[tuple[int, int], int] = {}
    for cp, ext in zip(cores, ext_np):
        if not ext.size:
            continue
        owner = np.searchsorted(np.asarray(cuts), ext, side="right") - 1
        for src in np.unique(owner):
            if int(src) == cp.index:
                continue
            n_ids = int((owner == src).sum())
            edges[(int(src), cp.index)] = n_ids
    exchange = ExchangePlan(
        edges=tuple((s, d, n_) for (s, d), n_ in sorted(edges.items())),
        bytes_per_tick=int(sum(edges.values())),
    )
    return PartitionPlan(
        spec=spec, n=static.n, cores=tuple(cores), exchange=exchange,
        params=tuple(params_l), ext_idx=tuple(ext_l), ext_ids=tuple(ext_np),
        ledgers=tuple(ledgers),
    )


@dataclasses.dataclass(eq=False)
class _ProbePlan:
    """Just enough of a PartitionPlan for _split_state during sizing."""

    cores: tuple[CorePlan, ...]


# ---------------------------------------------------------------------------
# execution — shared per-core phase helpers (both lowerings call these, so
# they are bitwise-identical to each other by construction and to the
# unpartitioned step() by the column-slice invariant)


def _phase_a(cs: NetStatic, par: NetParams, neurons, ring, cond, t, gu_c):
    """Tick phases 1–4 for one core: ring delivery, (COBA,) neuron update,
    generator merge. Mirrors ``engine.step`` op-for-op on the core's rows."""
    f32 = jnp.float32
    slot = jnp.mod(t, cs.ring_len)
    deliver = jax.lax.dynamic_index_in_dim(ring, slot, axis=0,
                                           keepdims=False)
    deliver = deliver.astype(f32)
    ring = jax.lax.dynamic_update_index_in_dim(
        ring, jnp.zeros_like(deliver).astype(ring.dtype), slot, axis=0)
    if cs.coba is not None:
        cond = decay_and_deliver(cs.coba, cond, deliver[:, 0],
                                 deliver[:, 1], cs.dt)
        i_syn = coba_current(cs.coba, cond, neurons.v)
    else:
        i_syn = deliver[:, 0]
    new_neurons, spiked = be.update_neurons_dispatch(cs, par, neurons, i_syn)
    spikes = spiked
    if cs.n_gen > 0:
        t_ms = t.astype(f32) * cs.dt
        off = 0
        for g0, sz in cs.gen_spans:
            seg = slice(g0, g0 + sz)
            in_pulse = t_ms < par.gen_until[seg]
            rate = jnp.where(in_pulse, par.gen_rate[seg],
                             par.gen_rate_after[seg])
            gsp = gu_c[off:off + sz] < rate * (cs.dt / 1000.0)
            spikes = spikes.at[g0:g0 + sz].set(gsp)
            off += sz
    return new_neurons, ring, cond, spikes


def _phase_b(cs: NetStatic, par: NetParams, core_state: _CoreState,
             spikes_local, ext_row, ring, t, packed_c):
    """Tick phases 5–6 for one core: propagation off the imported spike row
    (``pre_row=ext_row``) and intra-core plasticity. Mirrors ``engine.step``
    with pre-side reads in ext coordinates."""
    ring2, new_stp = be.propagate_packed(
        cs, par, core_state, ext_row, ring, t, packed_c, pre_row=ext_row)
    new_weights, new_stdp = [], []
    da = jnp.float32(0.0)
    for j, (spec, cfg, w, tr, mask) in enumerate(zip(
            cs.projections, cs.stdp, core_state.weights, core_state.stdp,
            par.masks)):
        if cfg is None:
            new_weights.append(w)
            new_stdp.append(None)
            continue
        pre_sp = ext_row[spec.pre_slice]
        post_sp = spikes_local[spec.post_slice]
        idx = par.proj_csr_idx[j] if j in cs.csr_projs else None
        if cfg.tau_elig is not None:
            if idx is not None:
                tr2, w2 = da_stdp_step_csr(cfg, tr, w, idx, mask, pre_sp,
                                           post_sp, da, cs.dt)
            else:
                tr2, w2 = da_stdp_step(cfg, tr, w, mask, pre_sp, post_sp,
                                       da, cs.dt)
        else:
            tr2, w2 = be.stdp_dispatch(cs, cfg, tr, w, mask, pre_sp,
                                       post_sp, idx=idx)
        new_weights.append(w2)
        new_stdp.append(tr2)
    return ring2, tuple(new_stp), tuple(new_weights), tuple(new_stdp)


def _reassemble(plan: PartitionPlan, state: NetState, cores_f, t_final,
                key) -> NetState:
    """Concatenate per-core final states back into one global NetState."""
    neurons = jax.tree.map(lambda *xs: jnp.concatenate(xs),
                           *[c.neurons for c in cores_f])
    ring = jnp.concatenate([c.ring for c in cores_f], axis=1)
    cond = (None if state.cond is None else
            jax.tree.map(lambda *xs: jnp.concatenate(xs),
                         *[c.cond for c in cores_f]))
    weights = list(state.weights)
    stp = list(state.stp)
    stdp = list(state.stdp)
    for cp, cf in zip(plan.cores, cores_f):
        for lj, cut in enumerate(cp.proj_cuts):
            if cut.mutable:
                weights[cut.gj] = cf.weights[lj]
                stp[cut.gj] = cf.stp[lj]
                stdp[cut.gj] = cf.stdp[lj]
    return NetState(
        t=t_final, key=key, neurons=neurons, ring=ring,
        weights=tuple(weights), stp=tuple(stp), stdp=tuple(stdp),
        cond=cond, homeo=state.homeo,
    )


def _draw_key_and_uniforms(static, state, n_steps):
    """Generator pre-draw, identical to ``_run_impl``'s whole-run path:
    split the carry key iff generators exist, draw [T, n_gen] uniforms."""
    if static.n_gen > 0:
        k_draw, k_carry = jax.random.split(state.key)
        gu_xs = jax.random.uniform(k_draw, (n_steps, static.n_gen),
                                   dtype=jnp.float32)
        return k_carry, gu_xs
    return state.key, jnp.zeros((n_steps, 0), jnp.float32)


def _check_record(record: str) -> None:
    if record not in ("raster", "none"):
        raise PartitionError(
            f"partitioned runs support record='raster'/'none', got "
            f"{record!r} — in-scan monitors are per-program (v1)")


@partial(jax.jit, static_argnames=("static", "plan", "n_steps", "record"))
def run_partitioned(static, plan: PartitionPlan, pparams, state: NetState,
                    n_steps: int, record: str = "raster"):
    """Sequential lowering: one device program scans all cores.

    Per tick: phase A on every core → concatenate the global spike row →
    gather each core's import row → phase B per core. Returns
    ``(final_global_state, outputs)`` exactly like ``engine.run`` (the
    raster is the global ``[T, N]`` bool matrix)."""
    _check_record(record)
    core_params, ext_idx = pparams
    key, gu_xs = _draw_key_and_uniforms(static, state, n_steps)
    state = state._replace(key=key)
    cores0 = _split_state(plan, static, state)
    packed = tuple(
        be.assemble_packed(cp.static, cs.weights)
        for cp, cs in zip(plan.cores, cores0)
    )

    def body(carry, gu):
        t, cores = carry
        a_out = []
        spikes_parts = []
        for c, cp in enumerate(plan.cores):
            st_c = cores[c]
            neu, ring, cond, spk = _phase_a(
                cp.static, core_params[c], st_c.neurons, st_c.ring,
                st_c.cond, t, gu[cp.gc0:cp.gc1])
            a_out.append((neu, ring, cond))
            spikes_parts.append(spk)
        spikes = (jnp.concatenate(spikes_parts)
                  if len(spikes_parts) > 1 else spikes_parts[0])
        new_cores = []
        for c, cp in enumerate(plan.cores):
            neu, ring, cond = a_out[c]
            ext_row = (spikes[ext_idx[c]] if cp.n_ext
                       else jnp.zeros((0,), bool))
            ring2, stp2, w2, stdp2 = _phase_b(
                cp.static, core_params[c], cores[c], spikes_parts[c],
                ext_row, ring, t, packed[c])
            new_cores.append(_CoreState(neu, ring2, cond, w2, stp2, stdp2))
        ys = spikes if record == "raster" else None
        return (t + 1, tuple(new_cores)), ys

    (t_f, cores_f), ys = jax.lax.scan(body, (state.t, cores0), gu_xs,
                                      length=n_steps)
    final = _reassemble(plan, state, cores_f, t_f, key)
    outputs = {"spikes": ys} if record == "raster" else {}
    return final, outputs


def run_partitioned_mesh(static, plan: PartitionPlan, pparams,
                         state: NetState, n_steps: int,
                         record: str = "raster", mesh=None):
    """Mesh lowering: shard_map the cores across a device mesh, one
    ``all_gather`` per tick as the spike exchange.

    Each device runs its core's phases via ``lax.switch`` over per-core
    branch closures (cores have different shapes, so operands are padded
    to the widest core and branches slice/re-pad); the gathered padded
    spike rows form the flat import space every core's precomputed flat
    index table reads from. Shares :func:`_phase_a` / ``propagate_packed``
    with the sequential lowering, so the two are bitwise identical.

    Non-plastic/CUBA networks only (enforced at plan time). Returns
    ``(final_global_state, outputs)`` like :func:`run_partitioned`."""
    from repro.core.distributed import core_mesh
    from jax.sharding import PartitionSpec as P

    _check_record(record)
    core_params, ext_idx = pparams
    k = plan.n_cores
    axis = plan.spec.mesh_axis
    if mesh is None:
        mesh = core_mesh(k, axis=axis)
    if mesh.devices.size != k:
        raise PartitionError(
            f"mesh has {mesh.devices.size} devices but the plan has {k} "
            "cores — they must match 1:1")
    n_pad = max(cp.hi - cp.lo for cp in plan.cores)
    key, gu_xs = _draw_key_and_uniforms(static, state, n_steps)
    state = state._replace(key=key)
    cores0 = _split_state(plan, static, state)
    packed = tuple(
        be.assemble_packed(cp.static, cs.weights)
        for cp, cs in zip(plan.cores, cores0)
    )
    # flat import tables: global id g owned by core s at local offset r
    # lands at s*n_pad + r in the gathered padded row
    lows = np.asarray([cp.lo for cp in plan.cores])
    bounds = np.asarray([cp.lo for cp in plan.cores] + [plan.n])
    flat_idx = []
    for ext in plan.ext_ids:
        owner = np.searchsorted(bounds, ext, side="right") - 1
        flat_idx.append(jnp.asarray(
            (owner * n_pad + (ext - lows[owner])).astype(np.int32)))

    def pad_n(x, axis_=0):
        n_c = x.shape[axis_]
        if n_c == n_pad:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis_] = (0, n_pad - n_c)
        return jnp.pad(x, widths)

    neurons_st = jax.tree.map(
        lambda *xs: jnp.stack([pad_n(x) for x in xs]),
        *[c.neurons for c in cores0])
    ring_st = jnp.stack([pad_n(c.ring, 1) for c in cores0])

    def branch_a(c):
        cp = plan.cores[c]
        n_c = cp.hi - cp.lo

        def fn(neurons_p, ring_p, t, gu):
            neu = jax.tree.map(lambda x: x[:n_c], neurons_p)
            neu2, ring2, _cond, spk = _phase_a(
                cp.static, core_params[c], neu, ring_p[:, :n_c], None, t,
                gu[cp.gc0:cp.gc1])
            neu2 = jax.tree.map(
                lambda x, p0: jax.lax.dynamic_update_slice(
                    p0, x, (0,) * x.ndim),
                neu2, neurons_p)
            ring2 = jax.lax.dynamic_update_slice(
                ring_p, ring2, (0, 0, 0))
            return neu2, ring2, pad_n(spk)
        return fn

    def branch_b(c):
        cp = plan.cores[c]
        n_c = cp.hi - cp.lo
        cs0 = cores0[c]

        def fn(ring_p, flat_spikes, t):
            ext_row = (flat_spikes[flat_idx[c]] if cp.n_ext
                       else jnp.zeros((0,), bool))
            local = flat_spikes[c * n_pad:c * n_pad + n_c]
            ring2, _stp, _w, _tr = _phase_b(
                cp.static, core_params[c], cs0, local, ext_row,
                ring_p[:, :n_c], t, packed[c])
            return jax.lax.dynamic_update_slice(ring_p, ring2, (0, 0, 0))
        return fn

    branches_a = [branch_a(c) for c in range(k)]
    branches_b = [branch_b(c) for c in range(k)]
    want_raster = record == "raster"

    @jax.jit
    @partial(jax.shard_map, mesh=mesh,
              in_specs=(jax.tree.map(lambda _: P(axis), neurons_st),
                        P(axis), P(), P()),
              out_specs=(jax.tree.map(lambda _: P(axis), neurons_st),
                         P(axis),
                         P(None, axis) if want_raster else P()),
              check_vma=False)
    def shard_run(neurons_in, ring_in, gu_in, t0):
        ci = jax.lax.axis_index(axis)
        neurons = jax.tree.map(lambda x: x[0], neurons_in)
        ring = ring_in[0]

        def body(carry, gu):
            t, neurons, ring = carry
            neurons2, ring2, spk_pad = jax.lax.switch(
                ci, branches_a, neurons, ring, t, gu)
            flat = jax.lax.all_gather(spk_pad, axis).reshape(-1)
            ring3 = jax.lax.switch(ci, branches_b, ring2, flat, t)
            return (t + 1, neurons2, ring3), (spk_pad if want_raster
                                              else None)

        (_tf, neu_f, ring_f), ys = jax.lax.scan(
            body, (t0, neurons, ring), gu_in, length=n_steps)
        neu_f = jax.tree.map(lambda x: x[None], neu_f)
        if want_raster:
            return neu_f, ring_f[None], ys
        return neu_f, ring_f[None], jnp.zeros((0,), bool)

    neu_out, ring_out, ys = shard_run(neurons_st, ring_st, gu_xs, state.t)
    # unpad + reassemble on the host side of the dispatch
    cores_f = []
    for c, cp in enumerate(plan.cores):
        n_c = cp.hi - cp.lo
        cs0 = cores0[c]
        cores_f.append(_CoreState(
            neurons=jax.tree.map(lambda x: x[c, :n_c], neu_out),
            ring=ring_out[c][:, :n_c],
            cond=None, weights=cs0.weights, stp=cs0.stp, stdp=cs0.stdp))
    final = _reassemble(plan, state, cores_f, state.t + n_steps, key)
    outputs = {}
    if want_raster:
        raster = jnp.concatenate(
            [ys[:, c * n_pad:c * n_pad + (cp.hi - cp.lo)]
             for c, cp in enumerate(plan.cores)], axis=1)
        outputs["spikes"] = raster
    return final, outputs
