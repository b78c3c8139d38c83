"""Izhikevich's polychronization network (2006) through the normal path:
``build_izhikevich2006`` -> ``NetworkBuilder.compile`` -> ``Engine`` /
``Session``, against a plain copy of its semantics written here.

The copy (``_reference``) follows the rule tick by tick in plain
``jax.numpy`` from its own connectivity draw; the program must agree with
it bit for bit: the raster at every tick, ``w`` and ``sd`` after every
weight update, in fp32 and in fp16.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.izhikevich2006 import (
    IZH2006,
    SPNET_STDP,
    build_izhikevich2006,
    spnet_delays,
)
from repro.core import Engine, NetworkBuilder, izh4
from repro.core.plasticity import (
    DelaySTDPConfig,
    delay_drive,
    delay_history,
    delay_stdp_step,
    init_delay_stdp_state,
)
from repro.obs import watch as wat
from repro.serve import Session

N_EXC, N_INH, M, D = IZH2006.n_exc, IZH2006.n_inh, IZH2006.fanout, IZH2006.max_delay
N = N_EXC + N_INH
f32 = np.float32


# -- the plain copy of the semantics -------------------------------------------

def _draw(rng, n_pre, n_post, own):
    out = np.empty((n_pre, M), np.int64)
    keys = rng.random((n_pre, n_post))
    if own is not None:
        keys[np.arange(n_pre), own + np.arange(n_pre)] = 2.0
    for p in range(n_pre):
        out[p] = np.argsort(keys[p])[:M]
    return out


def _rows(targets, n_post, delays):
    rows = [[] for _ in range(n_post)]
    for p, ts in enumerate(targets):
        for j, q in enumerate(ts):
            rows[q].append((p, delays[j]))
    f = 1 << (max(len(r) for r in rows) - 1).bit_length()
    idx = np.zeros((f, n_post), np.int32)
    dly = np.ones((f, n_post), np.int32)
    valid = np.zeros((f, n_post), bool)
    for q, r in enumerate(rows):
        for k, (p, d) in enumerate(sorted(r)):
            idx[k, q], dly[k, q], valid[k, q] = p, d, True
    return idx, dly, valid


def _connectivity(seed):
    rng = np.random.default_rng(seed)
    exc = _draw(rng, N_EXC, N, own=0)
    inh = _draw(rng, N_INH, N_EXC, own=None)
    return exc, inh


def _reference(seed, stream, storage, n_ticks):
    """Raster [T, N] and (w, sd) after each weight update."""
    st = jnp.dtype(storage)
    exc, inh = _connectivity(seed)
    ei, ed, ev = _rows(exc, N, np.asarray(spnet_delays(M, D)))
    ii, _, iv = _rows(inh, N_EXC, np.ones(M, np.int32))
    hidx = jnp.asarray(np.where(ev, (ed - 1) * N_EXC + ei, 0))
    ev, ii, iv = jnp.asarray(ev), jnp.asarray(ii), jnp.asarray(iv)
    a = jnp.asarray(np.r_[np.full(N_EXC, 0.02), np.full(N_INH, 0.1)], jnp.float32)
    b = jnp.full((N,), 0.2, jnp.float32)
    c = jnp.full((N,), -65.0, jnp.float32)
    d = jnp.asarray(np.r_[np.full(N_EXC, 8.0), np.full(N_INH, 2.0)], jnp.float32)
    key = jax.random.key(stream)

    def row_sum(x):
        acc = x[0]
        for k in range(1, x.shape[0]):
            acc = acc + x[k]
        return acc

    def tick(carry, t):
        v, u, w, sd, ltp, ltd, inh_prev = carry
        g = ltp.reshape(-1)[hidx]
        arrived = (g == f32(0.1)) & ev
        i_exc = row_sum(jnp.where(arrived, w.astype(jnp.float32), 0.0))
        i_inh = row_sum(jnp.where(iv & inh_prev[ii], f32(-5.0), 0.0))
        i_in = jnp.concatenate([i_inh.astype(st).astype(jnp.float32),
                                jnp.zeros(N_INH, jnp.float32)]) + i_exc
        kick = jax.random.randint(jax.random.fold_in(key, t), (1,), 0, N)
        i_in = i_in + jnp.zeros(N, jnp.float32).at[kick].set(f32(20.0))
        vf, uf = v.astype(jnp.float32), u.astype(jnp.float32)
        for _ in range(2):
            dv = (0.04 * vf + 5.0) * vf + 140.0 - uf + i_in
            du = a * (b * vf - uf)
            vf, uf = vf + 0.5 * dv, uf + 0.5 * du
        fired = vf >= 30.0
        vf, uf = jnp.where(fired, c, vf), jnp.where(fired, uf + d, uf)
        sd = ((sd.astype(jnp.float32) - jnp.where(arrived, ltd[None], 0.0))
              + jnp.where(fired[None] & ev, g, 0.0)).astype(st)
        ltp = jnp.concatenate([jnp.where(fired[:N_EXC], f32(0.1),
                                         f32(0.95) * ltp[0])[None], ltp[:-1]])
        ltd = jnp.where(fired, f32(0.12), f32(0.95) * ltd)

        def update(ws):
            w, sd = ws
            w2 = jnp.clip((w.astype(jnp.float32) + f32(0.01))
                          + sd.astype(jnp.float32), 0.0, 10.0)
            return (jnp.where(ev, w2, 0.0).astype(st),
                    (f32(0.9) * sd.astype(jnp.float32)).astype(st))

        w, sd = jax.lax.cond(t % 1000 == 999, update, lambda x: x, (w, sd))
        return (vf.astype(st), uf.astype(st), w, sd, ltp, ltd,
                fired[N_EXC:]), fired

    carry = (c.astype(st), (b * c).astype(st),
             jnp.where(ev, f32(6.0), 0.0).astype(st),
             jnp.zeros(ev.shape, st), jnp.zeros((D, N_EXC), jnp.float32),
             jnp.zeros(N, jnp.float32), jnp.zeros(N_INH, bool))
    scan = jax.jit(lambda c, ts: jax.lax.scan(tick, c, ts))
    rasters, snaps = [], []
    for t0 in range(0, n_ticks, 1000):
        carry, r = scan(carry, jnp.arange(t0, t0 + 1000, dtype=jnp.int32))
        rasters.append(np.asarray(r))
        snaps.append((np.asarray(carry[2]), np.asarray(carry[3])))
    return np.concatenate(rasters), snaps


# -- the published network against the copy ------------------------------------

@pytest.mark.parametrize("policy,storage", [("fp32", "float32"),
                                            ("fp16", "float16")])
def test_matches_reference_bitwise(policy, storage):
    """2,000 ticks, two weight updates: equal rasters at every tick, and
    ``w`` / ``sd`` equal bit for bit after each update."""
    net = build_izhikevich2006(IZH2006, policy=policy, seed=3)
    session = Session.create(net, seed=17, monitors=False)
    raster, snaps = [], []
    for _ in range(2):
        raster.append(session.spike_raster(1000))
        snaps.append((np.asarray(session.state.weights[0]),
                      np.asarray(session.state.stdp[0].sd)))
    want_raster, want_snaps = _reference(3, 17, storage, 2000)
    got = np.concatenate(raster)
    assert got.shape == want_raster.shape
    assert got.sum() > 1000  # the network is active
    np.testing.assert_array_equal(got, want_raster)
    for (w, sd), (w_ref, sd_ref) in zip(snaps, want_snaps):
        assert w.dtype == np.dtype(storage) and sd.dtype == np.dtype(storage)
        np.testing.assert_array_equal(w.view(np.uint8), w_ref.view(np.uint8))
        np.testing.assert_array_equal(sd.view(np.uint8),
                                      sd_ref.view(np.uint8))
    assert np.abs(snaps[1][0] - 6.0 * (snaps[1][0] > 0)).max() > 0  # learnt


# -- telemetry: the weight monitor, its counter, the NaN sentinel --------------

def _updates_counted() -> float:
    return obs.registry().counter("repro_stdp_weight_updates_total").value() or 0.0


def test_weight_monitor_and_update_counter():
    """Each flush reports the plastic weights as the last update left them
    (mean, share at 0, share at 10 over the live synapses) and the updates
    since the last flush; the counter rises by one per 1,000 ticks."""
    net = build_izhikevich2006(IZH2006, policy="fp32", seed=3)
    session = Session.create(net, seed=17)
    valid = np.asarray(net.params.masks[0])
    c0 = _updates_counted()
    for n_ticks, updates in ((1000, 1), (1000, 1), (500, 0), (500, 1)):
        session.run(n_ticks)
        out = session.flush()
        assert out["weight_updates"] == updates
        live = np.asarray(session.state.weights[0])[valid]
        (mean, at_min, at_max), = np.asarray(out["plastic_weights"])
        assert mean == pytest.approx(live.mean(), rel=1e-5)
        assert at_min == pytest.approx((live == 0).mean(), abs=1e-7)
        assert at_max == pytest.approx((live == 10).mean(), abs=1e-7)
    assert _updates_counted() - c0 == 3
    assert 5.0 < mean < 10.0


def test_nonfinite_watch_reads_sd():
    """A NaN in the weight-change accumulator ``sd`` alone trips the
    NonFinite sentinel; the same run without it does not."""
    net = build_izhikevich2006(IZH2006, policy="fp16", seed=3, monitors=None,
                               watches=(wat.NonFinite(),))
    tripped = []
    for poison in (False, True):
        session = Session.create(net, seed=17, monitors=False)
        if poison:
            st = session.state.stdp[0]
            session.state = session.state._replace(stdp=(
                st._replace(sd=st.sd.at[3, 7].set(jnp.nan)),
                *session.state.stdp[1:]))
        session.run(20, record="none")
        assert np.isfinite(np.asarray(session.state.weights[0])).all()
        (v,) = session.check_watches()
        tripped.append(v.tripped)
    assert tripped == [False, True]


# -- connectivity, kicks -------------------------------------------------------

def _synapses(net):
    """(pre, post, delay) of every exc synapse, from the compiled tables."""
    hidx = np.asarray(net.params.proj_hist_idx[0])
    valid = np.asarray(net.params.masks[0])
    k, q = np.nonzero(valid)
    return hidx[k, q] % N_EXC, q, hidx[k, q] // N_EXC + 1


def test_connectivity_invariants():
    net = build_izhikevich2006(IZH2006, policy="fp32", seed=5)
    pre, post, delay = _synapses(net)
    assert pre.size == N_EXC * M
    assert not np.any(pre == post)  # no self-synapses
    pairs = pre.astype(np.int64) * N + post
    assert np.unique(pairs).size == pairs.size  # distinct targets
    np.testing.assert_array_equal(np.bincount(pre, minlength=N_EXC),
                                  np.full(N_EXC, M))
    per_delay = np.zeros((N_EXC, D), int)
    np.add.at(per_delay, (pre, delay - 1), 1)
    assert (per_delay == M // D).all()  # 5 synapses per delay 1..20
    # fan-in rows ascend in pre index
    hidx = np.asarray(net.params.proj_hist_idx[0])
    valid = np.asarray(net.params.masks[0])
    rows = np.where(valid, hidx % N_EXC, N_EXC)
    assert (np.diff(rows, axis=0) >= 0).all()
    # inhibitory: M distinct excitatory targets each, delay 1, weight -5
    inh = net.static.projections[1]
    assert (inh.pre_start, inh.post_start, inh.post_size) == (N_EXC, 0, N_EXC)
    assert inh.delay_ms == 1 and inh.n_syn == N_INH * M
    idx = np.asarray(net.params.proj_csr_idx[1])
    w = np.asarray(net.state0.weights[1])
    live = w != 0
    assert set(np.unique(w[live])) == {-5.0}
    np.testing.assert_array_equal(
        np.bincount(idx[live], minlength=N_INH), np.full(N_INH, M))


def test_kick_draw_set_semantics():
    """Each tick, the k ids of ``randint(fold_in(key, t), (k,), 0, N)`` get
    the kick once; a 1000 kick fires exactly the drawn set."""
    b = NetworkBuilder(seed=0)
    b.add_group("g", izh4(40, a=0.02, b=0.2, c=-65.0, d=8.0))
    b.add_kicks(6, 1000.0)
    net = b.compile(policy="fp32")
    session = Session.create(net, seed=9, monitors=False)
    raster = session.spike_raster(60)
    key = jax.random.key(9)
    dupes = 0
    for t in range(60):
        ids = np.asarray(jax.random.randint(jax.random.fold_in(key, t), (6,),
                                            0, 40))
        want = np.zeros(40, bool)
        want[ids] = True
        np.testing.assert_array_equal(raster[t], want)
        dupes += len(ids) - len(set(ids.tolist()))
    assert dupes > 0  # some tick drew a neuron twice and it fired once


def test_kicks_refuse_the_megakernel():
    """The megakernel draws no kicks: a kicked static network compiled for
    ``backend="fused"`` names the refusal and runs the kicks elsewhere,
    raster equal to ``backend="xla"``."""
    rasters = []
    for backend in ("xla", "fused"):
        b = NetworkBuilder(seed=0)
        b.add_group("g", izh4(40, a=0.02, b=0.2, c=-65.0, d=8.0))
        b.add_group("h", izh4(40, a=0.02, b=0.2, c=-65.0, d=8.0))
        b.connect("g", "h", fanin=5, weight=3.0, delay_ms=2)
        b.add_kicks(6, 1000.0)
        net = b.compile(policy="fp32", backend=backend)
        session = Session.create(net, seed=9, monitors=False)
        rasters.append(session.spike_raster(50))
    assert not net.static.fused_kernel
    assert net.static.fused.kernel_reason == "kick input; the kernel draws no kicks"
    np.testing.assert_array_equal(*rasters)
    assert rasters[0][:, 40:].any()  # kicks reach h through the synapses


# -- the rule, by hand ---------------------------------------------------------

def _pair_net(d_syn, every=50):
    """Three neurons, each with fan-out 2 (to the other two) at delays
    ``d_syn`` per slot, weight 0.5: input under a kick of 0 never fires
    them, so ``i_ext`` alone sets the spike times."""
    cfg = dataclasses.replace(SPNET_STDP, update_every=every)
    b = NetworkBuilder(seed=1)
    b.add_group("a", izh4(3, a=0.02, b=0.2, c=-65.0, d=8.0))
    b.connect("a", "a", mode="fanout", fanout=2, weight=0.5,
              delay_ms=d_syn, stdp=cfg)
    return b.compile(policy="fp32", propagation="sparse", monitors=None)


def _run_forced(net, fire, n_ticks):
    i_ext = np.zeros((n_ticks, 3), np.float32)
    for t, i in fire:
        i_ext[t, i] = 1000.0
    final, out = Engine(net).run(n_ticks, i_ext=jnp.asarray(i_ext))
    raster = np.asarray(out["spikes"])
    assert sorted(zip(*np.nonzero(raster))) == sorted(fire)
    return final


def _cell(net, pre, post):
    hidx = np.asarray(net.params.proj_hist_idx[0])
    valid = np.asarray(net.params.masks[0])
    ks = [k for k in range(hidx.shape[0])
          if valid[k, post] and hidx[k, post] % 3 == pre]
    assert len(ks) == 1
    return ks[0], int(hidx[ks[0], post] // 3 + 1)


def _trace(amp, spikes, after):
    """A trace ``amp`` on a spike, times f32(0.95) each later tick, as it
    stood after tick ``after`` (0 before any spike)."""
    last = [s for s in spikes if s <= after]
    if not last:
        return f32(0.0)
    x = f32(amp)
    for _ in range(after - max(last)):
        x = f32(f32(0.95) * x)
    return x


def test_rule_by_hand_ltp_ltd():
    """Neuron 0 fires at 3 and 12, 1 at 10, 2 at 6 and 15. Per synapse p -> q of
    delay d, tick by tick: at an arrival (p fired at t - d) sd -= LTD_q
    after t - 1, then if q fires at t, sd += LTP_p after t - d."""
    net = _pair_net((2, 5))
    times = {0: [3, 12], 1: [10], 2: [6, 15]}
    fire = [(t, i) for i, ts in times.items() for t in ts]
    final = _run_forced(net, fire, 40)
    sd = np.asarray(final.stdp[0].sd)
    checked, moved = set(), 0
    for pre, post in ((0, 1), (0, 2), (1, 0), (1, 2), (2, 0), (2, 1)):
        k, d = _cell(net, pre, post)
        want = f32(0.0)
        for t in range(40):
            if t - d in times[pre]:
                want = f32(want - _trace(0.12, times[post], t - 1))
            if t in times[post]:
                want = f32(want + _trace(0.1, times[pre], t - d))
        assert sd[k, post] == want
        checked.add(d)
        moved += want != 0
    assert checked == {2, 5} and moved >= 4
    # no update yet (every = 50): weights untouched
    w = np.asarray(final.weights[0])
    assert set(np.unique(w[np.asarray(net.params.masks[0])])) == {0.5}


def test_rule_by_hand_update_bias_decay_clip():
    """At ``t % every == every - 1``: w <- clip((w + 0.01) + sd, 0, 10),
    then sd <- 0.9 sd; padding stays 0."""
    cfg = DelaySTDPConfig()
    valid = jnp.asarray([[True, True, True, False]])
    w = jnp.asarray([[6.0, 6.0, 6.0, 0.0]], jnp.float32)
    state = init_delay_stdp_state(1, 4, 1, 1)
    state = state._replace(sd=jnp.asarray([[5.0, -7.0, 0.5, 0.0]], jnp.float32))
    hist = jnp.zeros((1, 4), jnp.float32)
    quiet = jnp.zeros((4,), bool)
    for t, updated in ((998, False), (999, True)):
        st2, w2 = delay_stdp_step(cfg, state, w, hist, valid,
                                  jnp.zeros((1,), bool), quiet,
                                  jnp.int32(t))
        if not updated:
            np.testing.assert_array_equal(np.asarray(w2), np.asarray(w))
    np.testing.assert_array_equal(
        np.asarray(w2), [[10.0, 0.0, f32(f32(6.0 + f32(0.01)) + f32(0.5)), 0.0]])
    np.testing.assert_array_equal(
        np.asarray(st2.sd), [[f32(0.9) * f32(5.0), f32(0.9) * f32(-7.0),
                              f32(0.9) * f32(0.5), 0.0]])


# -- the layout against the plain per-synapse loop -----------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_layout_matches_per_synapse_loop(seed):
    """The same spikes and weights: the fan-in-major rows the builder
    chose (``delay_drive`` / ``delay_stdp_step``) and a loop over the
    synapse list give the same drive and ``sd``, bit for bit."""
    rng = np.random.default_rng(seed)
    n_pre, n_post, dmax, f = 7, 5, 4, 8
    syn = sorted({(int(rng.integers(n_pre)), int(rng.integers(n_post)))
                  for _ in range(20)})
    syn = [(p, q, int(rng.integers(1, dmax + 1)), f32(rng.uniform(0, 10)))
           for p, q in syn]
    hidx = np.zeros((f, n_post), np.int32)
    valid = np.zeros((f, n_post), bool)
    w = np.zeros((f, n_post), np.float32)
    sd0 = np.zeros((f, n_post), np.float32)
    fill = np.zeros(n_post, int)
    for p, q, d, wt in syn:  # ascending (pre, post): rows ascend in pre
        k = fill[q]
        fill[q] += 1
        hidx[k, q], valid[k, q], w[k, q] = (d - 1) * n_pre + p, True, wt
        sd0[k, q] = f32(rng.normal())
    ltp = np.where(rng.random((dmax, n_pre)) < 0.3, f32(0.1),
                   rng.random((dmax, n_pre)).astype(np.float32) * f32(0.09))
    ltd = rng.random(n_post).astype(np.float32) * f32(0.12)
    post = rng.random(n_post) < 0.5
    pre = rng.random(n_pre) < 0.5
    cfg = DelaySTDPConfig()
    state = init_delay_stdp_state(n_pre, n_post, dmax, f)._replace(
        ltp_hist=jnp.asarray(ltp), ltd=jnp.asarray(ltd), sd=jnp.asarray(sd0))
    hist = delay_history(state, jnp.asarray(hidx))
    drive = np.asarray(delay_drive(cfg, hist, jnp.asarray(w)))
    st2, _ = delay_stdp_step(cfg, state, jnp.asarray(w), hist,
                             jnp.asarray(valid), jnp.asarray(pre),
                             jnp.asarray(post), jnp.int32(0))
    want_drive = np.zeros(n_post, np.float32)
    want_sd = sd0.copy()
    fill[:] = 0
    for p, q, d, wt in syn:
        k = fill[q]
        fill[q] += 1
        arrived = ltp[d - 1, p] == f32(0.1)
        if arrived:
            want_drive[q] = f32(want_drive[q] + wt)
        val = want_sd[k, q]
        if arrived:
            val = f32(val - ltd[q])
        if post[q]:
            val = f32(val + ltp[d - 1, p])
        want_sd[k, q] = val
    np.testing.assert_array_equal(drive, want_drive)
    np.testing.assert_array_equal(np.asarray(st2.sd), want_sd)
