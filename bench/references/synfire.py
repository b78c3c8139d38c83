"""Plain reference for Synfire networks: the paper's equations, tick by tick.

Written from the paper's Tables I-II and the configuration file alone; it
imports nothing of the simulator under test and takes nothing it made. It
draws its own connectivity from the seed, keys its own stimulus streams, and
returns what a chunked run reports: exact spike counts per group per chunk.

The semantics it fixes, per 1 ms tick ``t``:

1. Input: the synaptic current due at ``t`` (the delay ring's slot
   ``t mod (max_delay + 1)``), then that slot is cleared.
2. IZH4 (paper eqs. 1-3): two forward-Euler half steps of 0.5 ms,
   ``v' = 0.04 v^2 + 5 v + 140 - u + I`` and ``u' = a (b v - u)``, computed
   in float32 from the stored ``v``/``u``; ``v >= 30`` spikes and resets
   ``v <- c, u <- u + d``; the result is stored in the storage dtype.
3. Poisson generators: generator ``i`` fires when the ``i``-th float32
   uniform of ``fold_in(stream_key, t)`` is below ``rate * 1 ms``, at the
   pulse rate while ``t < pulse_ms`` and the sustained rate after.
4. Propagation: each projection adds ``weight`` times the presynaptic spikes
   into slot ``t + delay`` of the ring, held in the storage dtype.

Connectivity, in Table II order from one ``numpy.random.default_rng(seed)``:
``"prob"`` keeps each (pre, post) pair with ``p = fanin / n_pre`` from one
``random((n_pre, n_post))`` draw (CARLsim's random connect); ``"fanin"``
gives each post neuron the first ``fanin`` entries of an argsort of one
``random((n_post, n_pre))`` row (exactly ``fanin`` distinct sources).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

IZH4_EXC = (0.02, 0.2, -65.0, 8.0)  # Table I: regular spiking (a, b, c, d)
IZH4_INH = (0.1, 0.2, -65.0, 2.0)  # Table I: fast spiking
V_PEAK = 30.0


@dataclasses.dataclass(frozen=True, eq=False)
class Projection:
    pre: int  # group index
    post: int
    weight: float
    delay: int
    mask: np.ndarray  # [n_pre, n_post] bool


@dataclasses.dataclass(frozen=True, eq=False)
class Network:
    names: tuple[str, ...]  # group names, in index order
    sizes: tuple[int, ...]
    n_gen: int  # group 0 is the generator group
    params: np.ndarray  # [4, N] float32 a, b, c, d (generators hold the exc row)
    projections: tuple[Projection, ...]
    pulse_hz: float
    pulse_ms: float
    rate_hz: float
    storage: str  # numpy/jax dtype name of stored state

    @property
    def n(self) -> int:
        return sum(self.sizes)

    @property
    def starts(self) -> tuple[int, ...]:
        return tuple(int(s) for s in np.cumsum((0,) + self.sizes[:-1]))

    @property
    def max_delay(self) -> int:
        return max(p.delay for p in self.projections)

    def n_synapses(self) -> int:
        return int(sum(p.mask.sum() for p in self.projections))

    def out_degree(self) -> np.ndarray:
        """Mean synapses leaving one neuron of each group, [groups]."""
        out = np.zeros(len(self.sizes))
        for p in self.projections:
            out[p.pre] += p.mask.sum() / self.sizes[p.pre]
        return out


def _draw(rng, n_pre: int, n_post: int, fanin: int, mode: str) -> np.ndarray:
    if mode == "prob":
        return rng.random((n_pre, n_post)) < fanin / n_pre
    if mode == "fanin":
        order = np.argsort(rng.random((n_post, n_pre)), axis=1)[:, :fanin]
        mask = np.zeros((n_pre, n_post), bool)
        mask[order.reshape(-1), np.repeat(np.arange(n_post), fanin)] = True
        return mask
    raise ValueError(f"unknown connect mode {mode!r}")


def build(network: dict, seed: int) -> Network:
    """The network a configuration's ``network`` block describes, with its
    connectivity drawn from ``seed``."""
    k = network["n_segments"]
    names = ["Cstim"]
    sizes = [network["n_stim"]]
    for i in range(k):
        names += [f"Cexc{i}", f"Cinh{i}"]
        sizes += [network["n_exc"], network["n_inh"]]
    gi = {name: i for i, name in enumerate(names)}
    fe, fi = network["fanin_exc"], network["fanin_inh"]
    we, wd, wi = network["w_exc"], network["w_inh_drive"], network["w_inh"]
    dff, dinh = network["delay_ff"], network["delay_inh"]
    # Table II, in its row order.
    rows = [("Cstim", "Cexc0", fe, we, dff), ("Cstim", "Cinh0", fe, wd, dff)]
    for i in range(k - 1):
        rows += [(f"Cexc{i}", f"Cexc{i + 1}", fe, we, dff),
                 (f"Cexc{i}", f"Cinh{i + 1}", fe, wd, dff),
                 (f"Cinh{i + 1}", f"Cexc{i + 1}", fi, wi, dinh)]
    rows += [(f"Cexc{k - 1}", "Cexc0", fe, we, dff),
             (f"Cexc{k - 1}", "Cinh0", fe, wd, dff)]
    rng = np.random.default_rng(seed)
    projections = tuple(
        Projection(gi[a], gi[b], float(w), int(d),
                   _draw(rng, sizes[gi[a]], sizes[gi[b]], f,
                         network["connect_mode"]))
        for a, b, f, w, d in rows)
    params = np.concatenate(
        [np.repeat(np.asarray(IZH4_INH if name.startswith("Cinh") else IZH4_EXC,
                              np.float32)[:, None], size, axis=1)
         for name, size in zip(names, sizes)], axis=1)
    return Network(names=tuple(names), sizes=tuple(sizes), n_gen=sizes[0],
                   params=params, projections=projections,
                   pulse_hz=network["stim_pulse_hz"],
                   pulse_ms=network["stim_pulse_ms"],
                   rate_hz=network["stim_rate_hz"],
                   storage=network["storage_dtype"])


@dataclasses.dataclass(frozen=True)
class _Plan:
    """The static part of a network: what the compiled program depends on.
    Connectivity is passed as arrays, so one program serves every seed."""

    sizes: tuple[int, ...]
    n_gen: int
    wiring: tuple[tuple[int, int, int], ...]  # (pre, post, delay)
    pulse_hz: float
    pulse_ms: float
    rate_hz: float
    storage: str

    @property
    def starts(self) -> tuple[int, ...]:
        return tuple(int(s) for s in np.cumsum((0,) + self.sizes[:-1]))


def _plan(net: Network) -> _Plan:
    return _Plan(sizes=net.sizes, n_gen=net.n_gen,
                 wiring=tuple((p.pre, p.post, p.delay) for p in net.projections),
                 pulse_hz=net.pulse_hz, pulse_ms=net.pulse_ms,
                 rate_hz=net.rate_hz, storage=net.storage)


def _tick_fn(plan: _Plan, params, weights):
    """One tick for one stream."""
    st = jnp.dtype(plan.storage)
    f32 = jnp.float32
    a, b, c, d = (params[i, plan.n_gen:] for i in range(4))
    starts, sizes = plan.starts, plan.sizes
    ring_len = max(dl for _, _, dl in plan.wiring) + 1
    p_pulse = np.float32(plan.pulse_hz) * np.float32(0.001)
    p_after = np.float32(plan.rate_hz) * np.float32(0.001)

    def tick(carry, xs):
        v, u, ring = carry  # v, u: [N - n_gen] storage; ring [L, N] storage
        t, uni = xs
        slot = t % ring_len
        i_syn = ring[slot, plan.n_gen:].astype(f32)
        ring = ring.at[slot].set(jnp.zeros((), st))
        vf, uf = v.astype(f32), u.astype(f32)
        for _ in range(2):
            # 0.04 v^2 + 5 v in Horner form, the form XLA folds it into.
            dv = (0.04 * vf + 5.0) * vf + 140.0 - uf + i_syn
            du = a * (b * vf - uf)
            vf = vf + 0.5 * dv
            uf = uf + 0.5 * du
        fired = vf >= V_PEAK
        vf = jnp.where(fired, c, vf)
        uf = jnp.where(fired, uf + d, uf)
        p = jnp.where(t.astype(f32) < plan.pulse_ms, p_pulse, p_after)
        spikes = jnp.concatenate([uni < p, fired]).astype(f32)
        for (pre, post, delay), w in zip(plan.wiring, weights):
            s = spikes[starts[pre]:starts[pre] + sizes[pre]]
            drive = jnp.dot(s, w, precision=jax.lax.Precision.HIGHEST)
            dslot = (t + delay) % ring_len
            row = jax.lax.dynamic_slice(ring, (dslot, starts[post]),
                                        (1, sizes[post]))
            row = (row.astype(f32) + drive[None]).astype(st)
            ring = jax.lax.dynamic_update_slice(ring, row, (dslot, starts[post]))
        counts = jnp.stack([spikes[s0:s0 + n].sum() for s0, n in
                            zip(starts, sizes)]).astype(jnp.int32)
        return (vf.astype(st), uf.astype(st), ring), counts

    return tick


@partial(jax.jit, static_argnums=(0, 4, 5))
def _simulate(plan: _Plan, params, weights, keys, n_chunks: int, chunk: int):
    tick = _tick_fn(plan, params, weights)
    st = jnp.dtype(plan.storage)
    b, c = params[1, plan.n_gen:], params[2, plan.n_gen:]
    ring_len = max(dl for _, _, dl in plan.wiring) + 1
    rest = (c.astype(st), (b * c).astype(st),
            jnp.zeros((ring_len, sum(plan.sizes)), st))

    def one_stream(key):
        def one_chunk(carry, i):
            ts = i * chunk + jnp.arange(chunk, dtype=jnp.int32)
            uni = jax.vmap(lambda t: jax.random.uniform(
                jax.random.fold_in(key, t), (plan.n_gen,), jnp.float32))(ts)
            carry, counts = jax.lax.scan(tick, carry, (ts, uni))
            return carry, counts.sum(axis=0)

        _, per_chunk = jax.lax.scan(one_chunk, rest,
                                    jnp.arange(n_chunks, dtype=jnp.int32))
        return per_chunk

    return jax.vmap(one_stream)(keys)


def simulate(net: Network, seeds, n_chunks: int, chunk: int) -> np.ndarray:
    """Spike counts ``[stream, chunk, group]`` of ``n_chunks`` chunks of
    ``chunk`` ticks from rest (``v = c``, ``u = b c``), one stream per
    stimulus seed (``jax.random.key(seed)``), all on one network."""
    keys = jnp.stack([jax.random.key(int(s)) for s in seeds])
    weights = tuple(jnp.asarray(np.where(p.mask, np.float32(p.weight), 0.0))
                    for p in net.projections)
    return np.asarray(_simulate(_plan(net), jnp.asarray(net.params), weights,
                                keys, int(n_chunks), int(chunk)))
