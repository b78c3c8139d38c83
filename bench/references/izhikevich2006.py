"""Plain reference for Izhikevich's polychronization network (Neural
Computation 18(2):245-282, 2006; its code ``spnet.cpp``), tick by tick.

Written from the paper, ``spnet.cpp`` and the configuration file alone; it
imports nothing of the simulator under test and takes nothing it made. It
draws its own connectivity from the seed, keys its own kick streams, and
returns what a chunked run reports: exact spike counts per group per chunk.

Conventions: a spike at tick ``t`` is one whose update crossed ``v >= 30``
in tick ``t`` (the neuron resets in that tick; spnet detects it at the
start of ``t + 1``); a spike at ``t`` on a synapse of delay ``d`` ms is
input to the update of tick ``t + d`` (spnet's delay index ``d - 1``).

Per 1 ms tick ``t``:

1. Input, in float32: the inhibitory input (weight -5 per inhibitory spike
   of tick ``t - 1`` on a synapse into the neuron: -5 times the product of
   the spike row and the 0/1 connection matrix, a whole number that any
   order of summation gives exactly; held in the storage dtype as the
   simulator's delay ring holds it), plus the excitatory
   drive, plus the kick (20 on each of the ``k = N / 1000`` neurons of
   ``randint(fold_in(stream_key, t), (k,), 0, N)``, once even if drawn
   twice). The excitatory drive of post neuron ``q`` is its fan-in row
   (ascending pre index, padded at the end with weight 0 up to the next
   power of two of the widest row) summed left to right in float32 over
   the synapses whose pre spike arrives now: the pre neuron's LTP trace
   after tick ``t - d`` equals 0.1 exactly when it fired at ``t - d``.
2. IZH4: two forward-Euler half steps of 0.5 ms of both ``v`` and ``u``
   from the stored state, ``v >= 30`` spikes and resets ``v <- c,
   u <- u + d``; stored in the storage dtype.
3. STDP on excitatory synapses (``sd`` stored in the storage dtype):
   ``sd <- (sd - LTD_q) + LTP_p`` where the first term applies on a
   synapse whose pre spike arrived at ``t`` (``LTD_q`` after ``t - 1``)
   and the second when ``q`` fired at ``t`` (``LTP_p`` after ``t - d``).
   Then the traces: ``LTP <- 0.1`` on a spike, else ``0.95 LTP``; ``LTD``
   likewise with 0.12.
4. On ``t % 1000 == 999``: ``w <- clip((w + 0.01) + sd, 0, 10)``, then
   ``sd <- 0.9 sd``.

Connectivity, from one ``numpy.random.default_rng(seed)``: the excitatory
neurons, then the inhibitory ones, each draw one float64 row of keys over
their candidate targets (all N, or the excitatory neurons), their own
column set to 2; synapse ``j`` goes to the column of the ``j``-th smallest
key, with delay ``j // (M / D) + 1`` for an excitatory neuron and 1 for an
inhibitory one.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

IZH4_EXC = (0.02, 0.2, -65.0, 8.0)  # regular spiking (a, b, c, d)
IZH4_INH = (0.1, 0.2, -65.0, 2.0)  # fast spiking
V_PEAK = 30.0
LTP_AMP, LTD_AMP, DECAY = 0.1, 0.12, 0.95
BIAS, SD_DECAY, W_MAX = 0.01, 0.9, 10.0
PERIOD = 1000


@dataclasses.dataclass(frozen=True)
class Mask:
    """What ``bench/work.py`` reads of a projection's synapses: their
    count and the (pre, post) shape."""

    shape: tuple[int, int]
    n: int

    def sum(self) -> int:
        return self.n


@dataclasses.dataclass(frozen=True, eq=False)
class Projection:
    pre: int  # group index
    post: tuple[int, ...]  # group indices of the target span
    mask: Mask


@dataclasses.dataclass(frozen=True, eq=False)
class Network:
    names: tuple[str, ...]
    sizes: tuple[int, ...]
    fanout: int
    max_delay: int
    w_exc: float
    w_inh: float
    kicks: int
    kick_amp: float
    storage: str
    exc_idx: np.ndarray  # [F, N] pre index per fan-in slot (0 on padding)
    exc_delay: np.ndarray  # [F, N] delay (1 on padding)
    exc_valid: np.ndarray  # [F, N]
    inh_conn: np.ndarray  # [n_inh, n_exc] bool: inhibitory synapses
    n_gen: int = 0

    @property
    def n(self) -> int:
        return sum(self.sizes)

    @property
    def projections(self) -> tuple[Projection, ...]:
        ne, ni = self.sizes
        return (Projection(0, (0, 1), Mask((ne, self.n),
                                           int(self.exc_valid.sum()))),
                Projection(1, (0,), Mask((ni, ne), int(self.inh_conn.sum()))))

    def out_degree(self) -> np.ndarray:
        """Synapses leaving one neuron of each group, [groups]."""
        return np.full(len(self.sizes), float(self.fanout))


def _fanout(rng, n_pre: int, n_post: int, m: int, own: int | None):
    """[n_pre, m] targets: per pre neuron the columns of its m smallest
    keys, smallest first; ``own`` is the post column of pre neuron 0 when
    the pre group lies inside the post span."""
    out = np.empty((n_pre, m), np.int64)
    for p0 in range(0, n_pre, 256):
        p1 = min(p0 + 256, n_pre)
        keys = rng.random((p1 - p0, n_post))
        if own is not None:
            keys[np.arange(p1 - p0), own + np.arange(p0, p1)] = 2.0
        part = np.argpartition(keys, m - 1, axis=1)[:, :m]
        first = np.argsort(np.take_along_axis(keys, part, axis=1), axis=1)
        out[p0:p1] = np.take_along_axis(part, first, axis=1)
    return out


def _fanin(targets: np.ndarray, n_post: int, delays: np.ndarray):
    """Fan-in-major rows ``[F, n_post]`` of a fan-out draw: each post
    neuron's synapses by ascending pre index, padded to a power of two."""
    n_pre, m = targets.shape
    pre = np.repeat(np.arange(n_pre), m)
    post = targets.reshape(-1)
    dly = np.tile(np.asarray(delays, np.int32), n_pre)
    order = np.lexsort((pre, post))  # by post, then pre
    pre, post, dly = pre[order], post[order], dly[order]
    count = np.bincount(post, minlength=n_post)
    f = 1 << max(int(count.max()) - 1, 0).bit_length()
    slot = np.arange(post.size) - np.repeat(np.cumsum(count) - count, count)
    idx = np.zeros((f, n_post), np.int32)
    delay = np.ones((f, n_post), np.int32)
    valid = np.zeros((f, n_post), bool)
    idx[slot, post], delay[slot, post], valid[slot, post] = pre, dly, True
    return idx, delay, valid


def build(network: dict, seed: int) -> Network:
    """The network a configuration's ``network`` block describes, with its
    connectivity drawn from ``seed``."""
    ne, ni = network["n_exc"], network["n_inh"]
    m, dmax = network["fanout"], network["max_delay"]
    n = ne + ni
    rng = np.random.default_rng(seed)
    exc_t = _fanout(rng, ne, n, m, own=0)
    inh_t = _fanout(rng, ni, ne, m, own=None)
    exc_delays = np.arange(m) // (m // dmax) + 1
    ei, ed, ev = _fanin(exc_t, n, exc_delays)
    inh_conn = np.zeros((ni, ne), bool)
    inh_conn[np.arange(ni)[:, None], inh_t] = True
    return Network(names=("exc", "inh"), sizes=(ne, ni), fanout=m,
                   max_delay=dmax, w_exc=float(network["w_exc"]),
                   w_inh=float(network["w_inh"]),
                   kicks=int(network["kicks_per_tick"]),
                   kick_amp=float(network["kick_amp"]),
                   storage=network["storage_dtype"],
                   exc_idx=ei, exc_delay=ed, exc_valid=ev,
                   inh_conn=inh_conn)


@dataclasses.dataclass(frozen=True)
class _Plan:
    n_exc: int
    n_inh: int
    max_delay: int
    kicks: int
    kick_amp: float
    w_inh: float
    storage: str


def _row_sum(terms):
    acc = terms[0]
    for k in range(1, terms.shape[0]):
        acc = acc + terms[k]
    return acc


def _take(table, idx):
    """``table[idx]``, read as rows of two entries and picked by the
    index's parity: the same values, ~2.8x faster on a TPU, whose gather of
    single elements is slow."""
    pairs = jnp.take(jnp.pad(table, (0, table.size % 2)).reshape(-1, 2),
                     idx // 2, axis=0)
    return jnp.where(idx % 2 == 0, pairs[..., 0], pairs[..., 1])


def _tick_fn(plan: _Plan, tables):
    f32 = jnp.float32
    st = jnp.dtype(plan.storage)
    ne, n = plan.n_exc, plan.n_exc + plan.n_inh
    hist_idx, valid, inh_conn, a, b, c, d = tables

    def tick(carry, xs):
        v, u, w, sd, ltp, ltd, inh_prev = carry
        t, kick = xs
        g = _take(ltp.reshape(-1), hist_idx)  # LTP_p after t - d
        arrived = (g == f32(LTP_AMP)) & valid
        exc_in = _row_sum(jnp.where(arrived, w.astype(f32), 0.0))
        inh_in = (f32(plan.w_inh) * (inh_prev.astype(f32) @ inh_conn)
                  ).astype(st).astype(f32)
        i_in = jnp.concatenate([inh_in, jnp.zeros((n - ne,), f32)]) + exc_in
        i_in = i_in + jnp.zeros((n,), f32).at[kick].set(f32(plan.kick_amp))
        vf, uf = v.astype(f32), u.astype(f32)
        for _ in range(2):
            dv = (0.04 * vf + 5.0) * vf + 140.0 - uf + i_in
            du = a * (b * vf - uf)
            vf = vf + 0.5 * dv
            uf = uf + 0.5 * du
        fired = vf >= V_PEAK
        vf = jnp.where(fired, c, vf)
        uf = jnp.where(fired, uf + d, uf)
        sd = ((sd.astype(f32) - jnp.where(arrived, ltd[None, :], 0.0))
              + jnp.where(fired[None, :] & valid, g, 0.0)).astype(st)
        ltp_now = jnp.where(fired[:ne], f32(LTP_AMP), f32(DECAY) * ltp[0])
        ltp = jnp.concatenate([ltp_now[None], ltp[:-1]])
        ltd = jnp.where(fired, f32(LTD_AMP), f32(DECAY) * ltd)

        def update(ws):
            w, sd = ws
            w2 = jnp.clip((w.astype(f32) + f32(BIAS)) + sd.astype(f32),
                          0.0, W_MAX)
            return (jnp.where(valid, w2, 0.0).astype(st),
                    (f32(SD_DECAY) * sd.astype(f32)).astype(st))

        w, sd = jax.lax.cond(t % PERIOD == PERIOD - 1, update, lambda x: x,
                             (w, sd))
        counts = jnp.stack([fired[:ne].sum(), fired[ne:].sum()]).astype(
            jnp.int32)
        return (vf.astype(st), uf.astype(st), w, sd, ltp, ltd,
                fired[ne:]), counts

    return tick


@partial(jax.jit, static_argnums=(0, 4, 5, 6))
def _simulate(plan: _Plan, tables, w0, keys, n_chunks: int, chunk: int,
              weights: bool):
    tick = _tick_fn(plan, tables)
    st = jnp.dtype(plan.storage)
    b, c = tables[4], tables[5]
    n = plan.n_exc + plan.n_inh
    rest = (c.astype(st), (b * c).astype(st), w0.astype(st),
            jnp.zeros(w0.shape, st),
            jnp.zeros((plan.max_delay, plan.n_exc), jnp.float32),
            jnp.zeros((n,), jnp.float32), jnp.zeros((plan.n_inh,), bool))

    def one_stream(key):
        def one_chunk(carry, i):
            ts = i * chunk + jnp.arange(chunk, dtype=jnp.int32)
            kicks = jax.vmap(lambda t: jax.random.randint(
                jax.random.fold_in(key, t), (plan.kicks,), 0, n))(ts)
            carry, counts = jax.lax.scan(tick, carry, (ts, kicks))
            counts = counts.sum(axis=0)
            return carry, (counts, carry[2]) if weights else counts

        _, per_chunk = jax.lax.scan(one_chunk, rest,
                                    jnp.arange(n_chunks, dtype=jnp.int32))
        return per_chunk

    return jax.vmap(one_stream)(keys)


def simulate(net: Network, seeds, n_chunks: int, chunk: int, *,
             weights: bool = False):
    """Spike counts ``[stream, chunk, group]`` of ``n_chunks`` chunks of
    ``chunk`` ticks from rest (``v = c``, ``u = b c``, weights 6 and -5,
    traces and ``sd`` 0), one stream per kick seed
    (``jax.random.key(seed)``), all on one network. With ``weights``, also
    the excitatory ``w`` ``[stream, chunk, F, N]`` (fan-in-major, as the
    rows above) at the end of every chunk."""
    ne, ni = net.sizes
    params = np.concatenate(
        [np.repeat(np.asarray(p, np.float32)[:, None], size, axis=1)
         for p, size in ((IZH4_EXC, ne), (IZH4_INH, ni))], axis=1)
    hist_idx = np.where(net.exc_valid,
                        (net.exc_delay - 1) * ne + net.exc_idx, 0)
    tables = (jnp.asarray(hist_idx, jnp.int32), jnp.asarray(net.exc_valid),
              jnp.asarray(net.inh_conn, jnp.float32),
              *(jnp.asarray(params[i]) for i in range(4)))
    w0 = jnp.asarray(np.where(net.exc_valid, np.float32(net.w_exc), 0.0))
    plan = _Plan(n_exc=ne, n_inh=ni, max_delay=net.max_delay,
                 kicks=net.kicks, kick_amp=net.kick_amp, w_inh=net.w_inh,
                 storage=net.storage)
    keys = jnp.stack([jax.random.key(int(s)) for s in seeds])
    with jax.default_matmul_precision("highest"):
        out = _simulate(plan, tables, w0, keys, int(n_chunks), int(chunk),
                        bool(weights))
    return jax.tree.map(np.asarray, out)
