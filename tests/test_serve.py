"""Serving runtime (`repro.serve`): chunked sessions, lane scheduler,
chunk-boundary homeostasis, checkpoint/restore.

The load-bearing contract is **call-split invariance**: a session advanced
as k chunks is bit-identical (rasters, weights, final state) to one
uninterrupted ``Engine.run`` over the same counter-keyed stimulus stream —
in every propagation mode × backend, fp32 and fp16, plastic or not, with
the homeostasis slow timer firing at the same absolute boundaries either
way. Everything else (flush accounting, scheduler lanes, checkpoints)
layers on top of that invariance and is tested against it.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.izhikevich2006 import IZH2006, build_izhikevich2006
from repro.configs.synfire4 import SYNFIRE4_MINI, CHAIN_STDP, build_synfire
from repro.core import Engine, NetworkBuilder, STDPConfig, izh4
from repro.core.plasticity import HomeostasisConfig
from repro.serve import (
    LaneScheduler,
    Session,
    restore_session,
    save_session,
)
from repro.telemetry import GroupRate, PlasticWeights, SpikeCount

MODES = [("packed", "xla"), ("sparse", "xla"), ("auto", "xla"),
         ("packed", "pallas"), ("sparse", "pallas"), ("auto", "pallas")]

HOMEO = HomeostasisConfig(target_hz=8.0, tau_avg_ms=500.0, beta=1.0)


def _mini(policy, prop, backend, *, plastic=False, homeo=False):
    return build_synfire(
        SYNFIRE4_MINI, policy=policy, propagation=prop, backend=backend,
        stdp_chain=CHAIN_STDP if plastic else None,
        homeo_chain=HOMEO if (plastic and homeo) else None,
        homeostasis_period=40 if (plastic and homeo) else 0,
    )


def _weights_f32(state):
    return tuple(np.asarray(w.astype(jnp.float32)) for w in state.weights)


def _chunked_vs_whole(net, n_ticks, chunk):
    """(whole_raster, cat_raster, whole_final, chunked_final) over the
    session stream."""
    eng = Engine(net)
    key = jax.random.key(11)
    whole_final, whole = eng.run(n_ticks, gen_base=key)
    sess = Session.create(eng, key=key, monitors=False)
    parts = [sess.spike_raster(chunk) for _ in range(n_ticks // chunk)]
    return (np.asarray(whole["spikes"]), np.concatenate(parts, axis=0),
            whole_final, sess.state)


class TestChunkedSessionParity:
    """One run(T) ≡ k chunked run(T/k) calls, bitwise, across the engine
    matrix — the serving guarantee the whole subsystem rests on."""

    @pytest.mark.parametrize("prop,backend", MODES)
    @pytest.mark.parametrize("policy", ["fp32", "fp16"])
    def test_nonplastic_bitwise(self, prop, backend, policy):
        net = _mini(policy, prop, backend)
        whole, cat, wf, cf = _chunked_vs_whole(net, 150, 15)  # 10 chunks
        assert np.array_equal(whole, cat)
        assert whole.sum() > 0, "wave must actually ignite"
        for a, b in zip(_weights_f32(wf), _weights_f32(cf)):
            assert np.array_equal(a, b)

    @pytest.mark.parametrize("prop,backend", MODES)
    @pytest.mark.parametrize("policy", ["fp32", "fp16"])
    def test_plastic_homeostatic_bitwise(self, prop, backend, policy):
        """STDP running every tick + homeostasis firing every 40 ticks:
        chunks of 40 (one slow-timer period each) against one run(120).
        Weights leave the representable grid, so this exercises the
        fan-in-row drive parity too."""
        net = _mini(policy, prop, backend, plastic=True, homeo=True)
        whole, cat, wf, cf = _chunked_vs_whole(net, 120, 40)
        assert np.array_equal(whole, cat)
        for a, b in zip(_weights_f32(wf), _weights_f32(cf)):
            assert np.array_equal(a, b)
        for a, b in zip(wf.homeo, cf.homeo):
            assert (a is None) == (b is None)
            if a is not None:
                assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_final_state_fully_identical(self):
        """Beyond rasters/weights: the entire final NetState pytree is
        call-split invariant (ring phase, traces, carry key, homeostasis
        averages) — what makes mid-stream checkpoint/migration exact."""
        net = _mini("fp16", "sparse", "xla", plastic=True, homeo=True)
        _, _, wf, cf = _chunked_vs_whole(net, 120, 40)
        flat_w = jax.tree.leaves(jax.tree.map(
            lambda x: x if not hasattr(x, "dtype") or not jnp.issubdtype(
                x.dtype, jax.dtypes.prng_key) else jax.random.key_data(x),
            wf))
        flat_c = jax.tree.leaves(jax.tree.map(
            lambda x: x if not hasattr(x, "dtype") or not jnp.issubdtype(
                x.dtype, jax.dtypes.prng_key) else jax.random.key_data(x),
            cf))
        for a, b in zip(flat_w, flat_c):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_chunk_misaligned_with_homeostasis_period_raises(self):
        net = _mini("fp16", "sparse", "xla", plastic=True, homeo=True)
        sess = Session.create(net, monitors=False)
        with pytest.raises(ValueError, match="homeostasis"):
            sess.run(30, record="raster")  # period is 40

    def test_gen_base_excludes_gen_chunk(self):
        eng = Engine(_mini("fp16", "packed", "xla"))
        with pytest.raises(ValueError, match="mutually exclusive"):
            eng.run(100, gen_base=jax.random.key(0), gen_chunk=50)


class TestHomeostasisSlowTimer:
    def test_scaling_moves_weights_toward_target(self):
        """A chain driven above its target rate must see its plastic
        incoming weights shrink relative to the homeostasis-free twin."""
        plain = Engine(_mini("fp32", "sparse", "xla", plastic=True))
        homeo = Engine(_mini("fp32", "sparse", "xla", plastic=True,
                             homeo=True))
        key = jax.random.key(2)
        fp, _ = plain.run(400, gen_base=key)
        fh, _ = homeo.run(400, gen_base=key)
        changed = [
            j for j, h in enumerate(homeo.net.static.homeo) if h is not None
        ]
        assert changed, "mini chain must carry homeostasis configs"
        assert any(
            not np.array_equal(_weights_f32(fp)[j], _weights_f32(fh)[j])
            for j in changed
        )
        for j in changed:
            assert float(np.asarray(fh.homeo[j]).max()) > 0.0

    def test_period_required_with_configs(self):
        with pytest.raises(ValueError, match="homeostasis_period"):
            build_synfire(SYNFIRE4_MINI, policy="fp16",
                          stdp_chain=CHAIN_STDP, homeo_chain=HOMEO)

    def test_period_without_configs_raises(self):
        with pytest.raises(ValueError, match="no connection"):
            build_synfire(SYNFIRE4_MINI, policy="fp16",
                          homeostasis_period=10)

    def test_non_plastic_homeostasis_rejected(self):
        net = NetworkBuilder(seed=0)
        net.add_spike_generator("g", 8, rate_hz=100.0)
        net.add_group("n", izh4(4, a=0.02, b=0.2, c=-65.0, d=8.0))
        net.connect("g", "n", fanin=4, weight=1.0, delay_ms=1,
                    stp=None, homeostasis=HOMEO)
        # connect() marks homeostatic projections plastic, so this compiles
        # — the engine treats it as plastic-without-STDP (weights re-read
        # per tick, scaled at boundaries, untouched between them).
        c = net.compile(policy="fp32", homeostasis_period=20)
        _, out = Engine(c).run(40)
        assert np.asarray(out["spikes"]).sum() > 0

    def test_divisibility_enforced(self):
        net = _mini("fp16", "packed", "xla", plastic=True, homeo=True)
        with pytest.raises(ValueError, match="multiple of the homeostasis"):
            Engine(net).run(130)


class TestSessionMonitors:
    def test_flush_sums_equal_uninterrupted_counts(self):
        eng = Engine(build_synfire(SYNFIRE4_MINI, policy="fp16"))
        sess = Session.create(eng, seed=5)
        flushes = []
        for _ in range(4):
            sess.run(50)
            flushes.append(sess.flush())
        _, whole = eng.run(200, gen_base=sess.gen_key, record="monitors")
        want = np.asarray(whole["telemetry"]["spike_count"])
        got = sum(f["spike_count"] for f in flushes)
        assert np.array_equal(got, want)
        assert sum(f["n_ticks"] for f in flushes) == 200

    def test_flush_rezeroes_counts_keeps_rate_filter(self):
        sess = Session.create(build_synfire(SYNFIRE4_MINI, policy="fp16"),
                              seed=1)
        sess.run(60)
        first = sess.flush()
        assert first["spike_count"].sum() > 0
        again = sess.flush()
        # counts are windowed sums: drained and re-zeroed
        assert again["spike_count"].sum() == 0
        assert again["n_ticks"] == 0
        # the GroupRate EMA is a level, not an accumulator: flushing must
        # not reset it (a reset would bias every post-flush reading low)
        assert np.array_equal(again["group_rate"], first["group_rate"])
        assert first["group_rate"].max() > 0

    def test_flush_before_first_chunk_raises(self):
        sess = Session.create(build_synfire(SYNFIRE4_MINI, policy="fp16"))
        with pytest.raises(RuntimeError, match="flush"):
            sess.flush()

    def test_no_raster_in_monitor_chunks(self):
        sess = Session.create(build_synfire(SYNFIRE4_MINI, policy="fp16"))
        out = sess.run(50)
        assert "spikes" not in out
        assert "tel_carry" not in out  # absorbed into the session
        assert "telemetry" in out


def _plastic_mini():
    """100 neurons with all three cumulative monitors (SpikeCount,
    GroupRate, PlasticWeights): Izhikevich 2006's rule, one weight update
    per 1,000 ticks."""
    cfg = dataclasses.replace(IZH2006, name="izh_mini", n_exc=80, n_inh=20,
                              fanout=20, max_delay=5)
    return build_izhikevich2006(cfg, policy="fp16", seed=3)


def _eager_drain(static, carry):
    """The per-group reductions as the eager flush ran them, op by op, on
    a host copy of the carry."""
    out = {}
    for s, c in zip(static.monitors, jax.tree.map(jnp.asarray, carry)):
        if isinstance(s, SpikeCount):
            out[s.name] = np.asarray(jnp.stack([
                c[g.start:g.start + g.size].sum() for g in static.groups]))
        elif isinstance(s, GroupRate):
            out[s.name] = np.asarray(jnp.stack([
                c[g.start:g.start + g.size].mean() for g in static.groups]))
        elif isinstance(s, PlasticWeights):
            out[s.name] = np.asarray(c[0])
            out["weight_updates"] = int(c[1])
    return out


def _flush_compiles():
    return obs.registry().counter("repro_compiles_total").value(site="flush")


class TestCompiledFlush:
    """The flush is one compiled program and one host copy, bitwise the
    eager per-group reductions it replaced."""

    @pytest.mark.parametrize("which", ["synfire", "plastic"])
    def test_flush_is_bitwise_the_eager_reduction(self, which):
        net = (_plastic_mini() if which == "plastic"
               else build_synfire(SYNFIRE4_MINI, policy="fp16"))
        static = net.static
        sess = Session.create(net, seed=7)
        totals = []
        for n_ticks in (1000, 500):
            sess.run(n_ticks)
            before = jax.tree.map(np.asarray, sess.monitors.carry)
            want = _eager_drain(static, before)
            got = sess.flush()
            assert got.keys() == want.keys() | {"n_ticks"}
            for k, v in want.items():
                assert np.asarray(got[k]).dtype == np.asarray(v).dtype, k
                assert np.asarray(got[k]).tobytes() == \
                    np.asarray(v).tobytes(), k
            after = jax.tree.map(np.asarray, sess.monitors.carry)
            for s, b, a in zip(static.monitors, before, after):
                if isinstance(s, SpikeCount):
                    assert not a.any()
                elif isinstance(s, GroupRate):
                    assert a.tobytes() == b.tobytes()  # the level is kept
                elif isinstance(s, PlasticWeights):
                    assert a[0].tobytes() == b[0].tobytes() and a[1] == 0
            totals.append(got["spike_count"].sum())
        assert totals[0] > 0

    def test_flush_sums_equal_one_uninterrupted_run(self):
        net = _plastic_mini()
        eng = Engine(net)
        sess = Session.create(eng, seed=9)
        flushes = []
        for n_ticks in (400, 600, 1000):
            sess.run(n_ticks)
            flushes.append(sess.flush())
        _, whole = eng.run(2000, gen_base=sess.gen_key, record="monitors")
        want = jax.tree.map(np.asarray, whole["telemetry"])
        assert np.array_equal(sum(f["spike_count"] for f in flushes),
                              want["spike_count"])
        assert sum(f["weight_updates"] for f in flushes) == \
            want["weight_updates"] == 2
        # levels: the last flush reads what the whole run's collect gives
        for k in ("group_rate", "plastic_weights"):
            assert flushes[-1][k].tobytes() == want[k].tobytes(), k

    def test_one_compile_serves_every_session_of_an_engine(self):
        """After one warm flush, more flushes (and the first flush of new
        sessions on the same Engine) compile nothing; the first flush on a
        cold cache compiles exactly one program."""
        eng = Engine(_plastic_mini())
        sess = Session.create(eng, seed=1)
        sess.run(100)
        jax.clear_caches()
        try:
            c0 = _flush_compiles()
            sess.flush()
            assert _flush_compiles() == c0 + 1
            reads = obs.registry().counter("repro_flush_host_reads_total")
            r0 = reads.value()
            for seed in (1, 2, 3):
                other = Session.create(eng, seed=seed)
                for _ in range(2):
                    other.run(100)
                    other.flush()
            sess.run(100)
            sess.flush()
            assert _flush_compiles() == c0 + 1
            assert reads.value() == r0 + 7
        finally:
            jax.clear_caches()


class TestLaneScheduler:
    def _net(self):
        return build_synfire(SYNFIRE4_MINI, policy="fp16")

    def test_lane_equals_solo_session_bitwise(self):
        net = self._net()
        sched = LaneScheduler(net, capacity=3)
        sched.admit("a", key=jax.random.key(1))
        sched.admit("b", key=jax.random.key(2))
        for _ in range(3):
            sched.step(40)
        for sid, seed in (("a", 1), ("b", 2)):
            solo = Session.create(Engine(net), key=jax.random.key(seed))
            solo.run(120)
            lane_flush = sched.flush(sid)
            solo_flush = solo.flush()
            assert np.array_equal(lane_flush["spike_count"],
                                  solo_flush["spike_count"])
            assert lane_flush["spike_count"].sum() > 0

    def test_evict_resumes_bitwise_as_solo(self):
        net = self._net()
        sched = LaneScheduler(net, capacity=2)
        sched.admit("a", key=jax.random.key(7))
        sched.step(60)
        ev = sched.evict("a")
        assert sched.occupancy == 0
        # Evicted carries the stimulus key — resume needs no out-of-band
        # bookkeeping (and the key must be the admitted one).
        assert np.array_equal(jax.random.key_data(ev.gen_key),
                              jax.random.key_data(jax.random.key(7)))
        resumed = Session.create(Engine(net), key=ev.gen_key,
                                 state=ev.state)
        solo = Session.create(Engine(net), key=jax.random.key(7))
        solo.run(60)
        assert np.array_equal(resumed.spike_raster(60),
                              solo.spike_raster(60))

    def test_idle_lanes_are_silent(self):
        net = self._net()
        sched = LaneScheduler(net, capacity=4)
        sched.admit("only", key=jax.random.key(3))
        sched.step(50)
        # Idle lanes: generator draw suppressed => their SpikeCount
        # accumulators never move.
        tel = sched._tel[0]  # SpikeCount slot, [lanes, N]
        counts = np.asarray(tel)
        assert counts[0].sum() > 0  # the admitted lane fired
        assert counts[1:].sum() == 0  # idle lanes stayed silent

    def test_admit_evict_readmit_cycle(self):
        net = self._net()
        sched = LaneScheduler(net, capacity=2)
        a = sched.admit("a", seed=1)
        b = sched.admit("b", seed=2)
        assert {a, b} == {0, 1}
        with pytest.raises(RuntimeError, match="full"):
            sched.admit("c", seed=3)
        sched.evict("a")
        with pytest.raises(ValueError, match="already admitted"):
            sched.admit("b", seed=9)
        c = sched.admit("c", seed=3)
        assert c == a and sched.occupancy == 2
        with pytest.raises(KeyError):
            sched.flush("a")  # evicted — no longer addressable

    def test_ledger_registration_and_session_bytes(self):
        net = self._net()
        before = net.ledger.total_used
        sched = LaneScheduler(net, capacity=8)
        assert net.ledger.serve_bytes() > 0
        assert net.ledger.total_used > before
        assert sched.session_bytes * 8 == pytest.approx(
            net.ledger.serve_bytes(), rel=0.01)
        stages = net.ledger.stage_bytes()
        assert "8. Serve Lanes" in stages
        # a second scheduler over the same net replaces, not double-counts
        LaneScheduler(net, capacity=8)
        assert net.ledger.stage_bytes()["8. Serve Lanes"] == stages[
            "8. Serve Lanes"]

    @pytest.mark.parametrize("plastic", [False, True])
    def test_64_sessions_chunked_o1_host(self, plastic):
        """The acceptance-scale configuration: 64 concurrent mini tenants
        advanced in chunks with no [T, N] raster anywhere and per-session
        bytes reported. Per-lane plastic weights: each tenant's STDP
        evolves its own weights on its own stimulus."""
        net = build_synfire(SYNFIRE4_MINI, policy="fp16",
                            stdp_chain=CHAIN_STDP if plastic else None)
        sched = LaneScheduler(net, capacity=64)
        for i in range(64):
            sched.admit(f"t{i}", seed=i)
        sched.step(50)
        sched.step(50)
        assert sched.occupancy == 64
        assert sched.session_bytes > 0
        flushes = sched.flush_all()
        assert len(flushes) == 64
        fired = sum(f["spike_count"].sum() > 0 for f in flushes.values())
        assert fired == 64  # every tenant's pulse ignited its wave
        if plastic:
            # per-lane weights diverged tenant-to-tenant (independent
            # stimulus streams driving independent STDP)
            j = next(j for j, s in enumerate(net.static.projections)
                     if s.plastic)
            w = np.asarray(sched.states.weights[j].astype(jnp.float32))
            assert not np.array_equal(w[0], w[1])

    def test_monitors_required_for_default_record(self):
        net = build_synfire(SYNFIRE4_MINI, policy="fp16", monitors=None)
        with pytest.raises(ValueError, match="monitors"):
            LaneScheduler(net, capacity=2)
        sched = LaneScheduler(net, capacity=2, record="none")
        sched.admit("a", seed=0)
        sched.step(40)  # runs bare
        with pytest.raises(ValueError, match="record='none'"):
            sched.flush("a")

    def test_raster_record_rejected(self):
        with pytest.raises(ValueError, match="raster"):
            LaneScheduler(self._net(), capacity=2, record="raster")


class TestCheckpointRestore:
    def test_bit_exact_resume_with_telemetry(self, tmp_path):
        net = build_synfire(SYNFIRE4_MINI, policy="fp16")
        eng = Engine(net)
        sess = Session.create(eng, seed=5)
        sess.run(80)
        save_session(str(tmp_path), sess)
        restored = restore_session(str(tmp_path), eng)
        assert restored.ticks == sess.ticks == int(restored.state.t)
        cont = sess.spike_raster(80)
        res = restored.spike_raster(80)
        assert np.array_equal(cont, res)
        # telemetry accumulators carried through the checkpoint: flushes
        # agree bitwise after the post-restore chunk
        assert np.array_equal(sess.flush()["spike_count"],
                              restored.flush()["spike_count"])

    def test_restore_before_first_chunk(self, tmp_path):
        net = build_synfire(SYNFIRE4_MINI, policy="fp16")
        sess = Session.create(net, seed=9)
        save_session(str(tmp_path), sess)
        restored = restore_session(str(tmp_path), net)
        assert restored.ticks == 0
        assert np.array_equal(
            Session.create(net, seed=9).spike_raster(60),
            restored.spike_raster(60))

    def test_missing_dir_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            restore_session(str(tmp_path / "empty"),
                            build_synfire(SYNFIRE4_MINI, policy="fp16"))


def _tiny_ckpt_net(policy, plastic, homeo, seed):
    net = NetworkBuilder(seed=seed)
    net.add_spike_generator("g", 16, rate_hz=120.0)
    net.add_group("n", izh4(8, a=0.02, b=0.2, c=-65.0, d=8.0))
    net.connect(
        "g", "n", fanin=6, weight=2.0, delay_ms=2,
        stdp=STDPConfig(a_plus=0.01, a_minus=0.004, w_max=6.0)
        if plastic else None,
        homeostasis=HOMEO if (plastic and homeo) else None,
    )
    return net.compile(
        policy=policy, homeostasis_period=10 if (plastic and homeo) else 0)


def _check_ckpt_roundtrip(ckpt_dir, policy, plastic, homeo, seed, j, k):
    """save → restore → run(k) ≡ the never-interrupted session, bitwise —
    rasters, weights, and the concatenation equal to one run(j + k)."""
    net = _tiny_ckpt_net(policy, plastic, homeo, seed)
    eng = Engine(net)
    base = Session.create(eng, seed=seed)
    r1 = base.spike_raster(j)
    save_session(ckpt_dir, base)
    restored = restore_session(ckpt_dir, eng)
    r2_cont = base.spike_raster(k)
    r2_rest = restored.spike_raster(k)
    assert np.array_equal(r2_cont, r2_rest)
    for a, b in zip(_weights_f32(base.state), _weights_f32(restored.state)):
        assert np.array_equal(a, b)
    # and the chunked pair equals one uninterrupted run(j + k)
    _, whole = eng.run(j + k, gen_base=base.gen_key)
    assert np.array_equal(np.asarray(whole["spikes"]),
                          np.concatenate([r1, r2_rest], axis=0))


class TestCheckpointRoundtripMatrix:
    """Deterministic slice of the save→restore→run property (runs even
    without hypothesis): plastic and non-plastic, fp32 and fp16, with and
    without the slow timer."""

    @pytest.mark.parametrize("policy", ["fp32", "fp16"])
    @pytest.mark.parametrize("plastic,homeo",
                             [(False, False), (True, False), (True, True)])
    def test_roundtrip(self, tmp_path, policy, plastic, homeo):
        _check_ckpt_roundtrip(str(tmp_path), policy, plastic, homeo,
                              seed=3, j=30, k=40)


try:
    from hypothesis import given, settings, strategies as st
    _HAS_HYPOTHESIS = True
except ImportError:  # covered by the deterministic matrix above
    _HAS_HYPOTHESIS = False

if _HAS_HYPOTHESIS:

    class TestCheckpointProperties:
        """Hypothesis: save → restore → run(k) ≡ uninterrupted run(j + k)
        for plastic and non-plastic nets, fp32 and fp16 — over random
        split points and seeds (the satellite acceptance property)."""

        @given(
            policy=st.sampled_from(["fp32", "fp16"]),
            plastic=st.booleans(),
            homeo=st.booleans(),
            seed=st.integers(min_value=0, max_value=2 ** 16),
            j=st.integers(min_value=1, max_value=6),
            k=st.integers(min_value=1, max_value=6),
        )
        @settings(max_examples=12, deadline=None)
        def test_save_restore_run_bit_identical(self, tmp_path_factory,
                                                policy, plastic, homeo,
                                                seed, j, k):
            # homeostasis period 10 => keep chunks multiples of 10
            _check_ckpt_roundtrip(str(tmp_path_factory.mktemp("ck")),
                                  policy, plastic, homeo, seed,
                                  j * 10, k * 10)
