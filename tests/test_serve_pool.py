"""Elastic serving plane (`repro.serve.pool` + scheduler migration/mesh):
rung migration parity, admit/evict churn, sharded-lane parity, recycled
lanes, per-rung ledger bytes.

The load-bearing claim is that **elasticity is invisible to tenants**: a
session that rode the capacity ladder 1 → 8 → 64 lanes and back — or had
its lane axis sharded across a device mesh — produces bit-identical
state, weights, flushed telemetry, and subsequent generator stream to a
session that never moved. Everything here asserts equality, never
tolerance.
"""
import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.synfire4 import SYNFIRE4_MINI, CHAIN_STDP, build_synfire
from repro.core.plasticity import HomeostasisConfig
from repro.serve import (
    CapacityLadder,
    LaneScheduler,
    ServePool,
    Session,
    compile_fingerprint,
    restore_lane,
    save_lane,
)
from repro.telemetry import PlasticWeights, SpikeCount

from test_serve import _eager_drain, _plastic_mini

MODES = [("packed", "xla"), ("sparse", "xla"), ("auto", "xla"),
         ("packed", "fused"), ("sparse", "fused"), ("auto", "fused")]

HOMEO = HomeostasisConfig(target_hz=8.0, tau_avg_ms=500.0, beta=1.0)

# Sustained stimulus keeps every tenant spiking through the whole horizon,
# so plasticity/homeostasis state keeps moving — a migration bug can't
# hide behind a network at rest.
DRIVEN = dataclasses.replace(SYNFIRE4_MINI, stim_rate_hz=60.0)


def _mini(policy, prop, backend, *, plastic=False, homeo=False):
    return build_synfire(
        DRIVEN, policy=policy, propagation=prop, backend=backend,
        stdp_chain=CHAIN_STDP if plastic else None,
        homeo_chain=HOMEO if (plastic and homeo) else None,
        homeostasis_period=40 if (plastic and homeo) else 0,
    )


def _dekey(tree):
    """Typed PRNG key leaves -> raw uint32 data, for bitwise comparison."""
    return jax.tree.map(
        lambda x: jax.random.key_data(x)
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype,
                                                  jax.dtypes.prng_key)
        else x, tree)


def _assert_state_eq(a, b, what="state"):
    fa, fb = jax.tree.leaves(_dekey(a)), jax.tree.leaves(_dekey(b))
    assert len(fa) == len(fb)
    for i, (x, y) in enumerate(zip(fa, fb)):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes(), \
            f"{what}: leaf {i} differs"


def _assert_flush_eq(a, b):
    assert a.keys() == b.keys()
    for k in a:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), \
            f"flush value {k!r} differs"


def _seed_of(session_id: str) -> int:
    # admit()'s default stream seed
    return zlib.crc32(session_id.encode())


def _ladder_roundtrip_vs_solo(net, chunk=40):
    """Drive tenant "t" up the ladder 1 → 8 → 64 and back down to 1 (five
    chunks total), then compare against a solo session that never moved:
    full NetState, weights, flushed telemetry, and the next chunk's
    raster. Returns the ladder for extra assertions."""
    lad = CapacityLadder(net, rungs=(1, 8, 64), idle_after=1)
    lad.admit("t")                       # rung 1
    lad.step(chunk)
    for i in range(7):
        lad.admit(f"filler{i}")          # 8 tenants -> rung 8
    lad.step(chunk)
    for i in range(7, 9):
        lad.admit(f"filler{i}")          # 10 tenants -> rung 64
    lad.step(chunk)
    for i in range(9):
        lad.evict(f"filler{i}")
    lad.step(chunk)                      # occupancy 1 + idle_after=1 -> rung 1
    assert lad.rung == 1, "down-rung migration did not fire"
    lad.step(chunk)
    assert lad.migrations == 3           # 1->8, 8->64, 64->1

    solo = Session.create(net, seed=_seed_of("t"))
    for _ in range(5):
        solo.run(chunk)

    flush = lad.flush("t")
    _assert_flush_eq(flush, solo.flush())
    ev = lad.evict("t")
    _assert_state_eq(ev.state, solo.state, "post-ladder NetState")
    # the stream CONTINUES identically: next chunk's raster, bit for bit
    cont = Session.create(net, key=ev.gen_key, state=ev.state)
    assert np.array_equal(cont.spike_raster(chunk), solo.spike_raster(chunk))
    return lad


class TestRungMigrationParity:
    """Capacity-ladder migration (1 → 8 → 64 and back) is bit-invisible:
    the tenant's NetState, plastic weights, flushed telemetry, and
    subsequent generator stream equal an uninterrupted single-rung run."""

    def test_mini_rung_migration(self):
        """Fast-suite slice: one plastic+homeostatic fp16 config."""
        _ladder_roundtrip_vs_solo(
            _mini("fp16", "sparse", "xla", plastic=True, homeo=True))

    @pytest.mark.slow
    @pytest.mark.parametrize("prop,backend", MODES)
    @pytest.mark.parametrize("policy", ["fp32", "fp16"])
    def test_matrix_plastic_homeostatic(self, prop, backend, policy):
        """Full matrix: every propagation mode × xla/fused × fp32/fp16,
        STDP every tick + the slow timer firing mid-ladder."""
        _ladder_roundtrip_vs_solo(
            _mini(policy, prop, backend, plastic=True, homeo=True))

    @pytest.mark.slow
    @pytest.mark.parametrize("policy", ["fp32", "fp16"])
    def test_matrix_nonplastic(self, policy):
        _ladder_roundtrip_vs_solo(_mini(policy, "auto", "xla"))

    def test_migration_preserves_flush_accounting(self):
        """export/restore carries the telemetry accumulators raw: a flush
        AFTER a migration reports the counts since the tenant's last
        flush — not since the move."""
        net = _mini("fp32", "packed", "xla")
        lad = CapacityLadder(net, rungs=(1, 8))
        lad.admit("t")
        lad.step(50)
        for i in range(3):
            lad.admit(f"f{i}")           # forces 1 -> 8 migration
        lad.step(50)
        flush = lad.flush("t")
        assert flush["n_ticks"] == 100   # both chunks, across the move
        solo = Session.create(net, seed=_seed_of("t"))
        solo.run(50)
        solo.run(50)
        _assert_flush_eq(flush, solo.flush())

    def test_top_rung_overflow_raises(self):
        net = _mini("fp32", "packed", "xla")
        lad = CapacityLadder(net, rungs=(1, 8))
        for i in range(8):
            lad.admit(f"t{i}")
        with pytest.raises(RuntimeError, match="top rung"):
            lad.admit("t8")


class TestPoolRouting:
    """Cross-topology ServePool: fingerprint-keyed ladders, id routing."""

    def test_fingerprint_semantics(self):
        a1 = _mini("fp16", "packed", "xla")
        a2 = _mini("fp16", "packed", "xla")   # same config, fresh build
        b = _mini("fp16", "sparse", "xla")
        c = _mini("fp32", "packed", "xla")
        assert compile_fingerprint(a1) == compile_fingerprint(a2)
        assert compile_fingerprint(a1) != compile_fingerprint(b)
        assert compile_fingerprint(a1) != compile_fingerprint(c)

    def test_heterogeneous_tenants_route_and_match_solo(self):
        net_a = _mini("fp16", "packed", "xla", plastic=True)
        net_b = _mini("fp32", "sparse", "xla")
        pool = ServePool(rungs=(1, 8))
        fa = pool.admit(net_a, "a0")
        fb = pool.admit(net_b, "b0")
        assert fa != fb and set(pool.fingerprints) == {fa, fb}
        assert pool.admit(net_a, "a1") == fa   # same ladder
        pool.step(50)
        pool.step(50)
        for sid, net in [("a0", net_a), ("b0", net_b), ("a1", net_a)]:
            solo = Session.create(net, seed=_seed_of(sid))
            solo.run(50)
            solo.run(50)
            _assert_flush_eq(pool.flush(sid), solo.flush())
            _assert_state_eq(pool.evict(sid).state, solo.state, sid)

    def test_duplicate_session_id_rejected(self):
        net = _mini("fp32", "packed", "xla")
        pool = ServePool()
        pool.admit(net, "x")
        with pytest.raises(ValueError, match="already admitted"):
            pool.admit(net, "x")

    def test_export_checkpoint_restore_across_pools(self, tmp_path):
        """Cross-process migration: export → save_lane → restore_lane →
        restore into a DIFFERENT pool; the stream continues bit-exactly."""
        net = _mini("fp16", "auto", "xla", plastic=True)
        pool1 = ServePool(rungs=(1, 8))
        pool1.admit(net, "mig")
        pool1.step(50)
        save_lane(str(tmp_path), pool1.export("mig"))
        pool2 = ServePool(rungs=(1, 8))
        pool2.restore(net, restore_lane(str(tmp_path), net))
        pool2.step(50)
        solo = Session.create(net, seed=_seed_of("mig"))
        solo.run(50)
        solo.run(50)
        _assert_flush_eq(pool2.flush("mig"), solo.flush())
        _assert_state_eq(pool2.evict("mig").state, solo.state)


class TestRecycledLane:
    """Regression: a lane freed by evict OR export and re-admitted must
    hand the new tenant a fully zeroed slot — in particular the GroupRate
    filter *level*, which flush deliberately keeps in the lane and export
    leaves behind wholesale."""

    @pytest.mark.parametrize("leave", ["evict", "export"])
    def test_recycled_lane_is_pristine(self, leave):
        net = _mini("fp16", "packed", "xla", plastic=True, homeo=True)
        sched = LaneScheduler(net, 1)
        sched.admit("hot")
        sched.step(80)                   # builds rate-filter level + counts
        getattr(sched, leave)("hot")     # lane 0 freed, carry left behind
        sched.admit("fresh")
        sched.step(80)

        virgin = LaneScheduler(net, 1)
        virgin.admit("fresh")
        virgin.step(80)

        flush_r, flush_v = sched.flush("fresh"), virgin.flush("fresh")
        assert np.array_equal(np.asarray(flush_r["group_rate"]),
                              np.asarray(flush_v["group_rate"])), \
            "recycled lane leaked its predecessor's rate-filter level"
        _assert_flush_eq(flush_r, flush_v)
        _assert_state_eq(sched.evict("fresh").state,
                         virgin.evict("fresh").state, "recycled lane state")


class TestLaneFlush:
    """``LaneScheduler.flush`` is one compiled program over the donated
    fleet carry, the lane a traced index, and one host copy: it drains
    that lane alone."""

    @pytest.mark.parametrize("plastic", [False, True])
    def test_lane_flush_touches_only_its_lane(self, plastic):
        net = (_plastic_mini() if plastic
               else _mini("fp16", "packed", "xla"))
        monitors = net.static.monitors
        sched = LaneScheduler(net, 3)
        for sid in ("a", "b", "c"):
            sched.admit(sid)
        sched.step(1000 if plastic else 80)
        lane = sched.lane_of("b")
        before = jax.tree.map(np.asarray, sched._tel)
        got = sched.flush("b")
        after = jax.tree.map(np.asarray, sched._tel)

        want = _eager_drain(net.static,
                            jax.tree.map(lambda x: x[lane], before))
        for k, v in want.items():
            assert np.asarray(got[k]).tobytes() == np.asarray(v).tobytes(), k
        assert got["spike_count"].sum() > 0
        assert got.get("weight_updates", 1) == 1
        others = [i for i in range(3) if i != lane]
        for s, b, a in zip(monitors, before, after):
            # leaves that are windowed sums: SpikeCount, the update count
            zeroed = {SpikeCount: (0,), PlasticWeights: (1,)}.get(type(s), ())
            leaves = zip(jax.tree.leaves(b), jax.tree.leaves(a))
            for i, (x, y) in enumerate(leaves):
                assert x[others].tobytes() == y[others].tobytes()
                if i in zeroed:
                    assert not y[lane].any()
                else:  # levels are kept
                    assert x[lane].tobytes() == y[lane].tobytes()

    def test_one_program_and_one_copy_for_every_lane(self):
        """After the first lane flush, flushing the other lanes (and this
        lane again, and ``flush_all``) compiles nothing; every flush makes
        one host copy."""
        net = _mini("fp16", "packed", "xla")
        sched = LaneScheduler(net, 4)
        for i in range(4):
            sched.admit(f"t{i}")
        sched.step(40)
        reg = obs.registry()
        reads = reg.counter("repro_flush_host_reads_total")
        compiles = reg.counter("repro_compiles_total")
        jax.clear_caches()
        try:
            c0, r0 = compiles.value(site="flush"), reads.value()
            sched.flush("t0")
            assert compiles.value(site="flush") == c0 + 1
            for sid in ("t1", "t2", "t3", "t0"):
                sched.flush(sid)
            sched.step(40)
            assert len(sched.flush_all()) == 4
            assert compiles.value(site="flush") == c0 + 1
            assert reads.value() == r0 + 9
        finally:
            jax.clear_caches()


class TestLedgerRungBytes:
    def test_per_rung_bytes_track_the_occupied_rung(self):
        net = _mini("fp16", "packed", "xla")
        lad = CapacityLadder(net, rungs=(1, 8), ledger_prefix="p.")
        lad.admit("t")
        by_rung = net.ledger.serve_rung_bytes()
        assert set(by_rung) == {"p.rung1"} and by_rung["p.rung1"] > 0
        lane_bytes_1 = by_rung["p.rung1"]
        for i in range(3):
            lad.admit(f"f{i}")           # 1 -> 8 migration
        by_rung = net.ledger.serve_rung_bytes()
        assert set(by_rung) == {"p.rung8"}, "old rung must be released"
        assert by_rung["p.rung8"] == 8 * lane_bytes_1  # lanes scale linearly

    def test_unkeyed_scheduler_groups_under_empty_key(self):
        net = _mini("fp16", "packed", "xla")
        LaneScheduler(net, 2)
        assert net.ledger.serve_rung_bytes()[""] > 0
        assert net.ledger.serve_bytes() >= net.ledger.serve_rung_bytes()[""]


try:
    from hypothesis import given, settings, strategies as st
    _HAS_HYPOTHESIS = True
except ImportError:
    _HAS_HYPOTHESIS = False

if _HAS_HYPOTHESIS:

    _CHURN_NETS = {}

    def _churn_net(kind):
        if kind not in _CHURN_NETS:
            _CHURN_NETS[kind] = (
                _mini("fp16", "packed", "xla", plastic=True)
                if kind == "plastic" else _mini("fp32", "sparse", "xla"))
        return _CHURN_NETS[kind]

    class TestPoolChurnProperty:
        """Hypothesis: under a random admit/step/evict/flush/migrate
        schedule over a two-topology pool, every surviving tenant's final
        state equals its solo-run oracle (same stream seed, same number of
        chunks served while admitted). The falsifying ``sched_seed`` IS
        the deterministic regression seed — rebuilding the schedule from
        it replays the exact op sequence."""

        CHUNK = 25

        @given(sched_seed=st.integers(min_value=0, max_value=2 ** 31 - 1),
               n_ops=st.integers(min_value=4, max_value=14))
        @settings(max_examples=8, deadline=None, print_blob=True)
        def test_survivors_equal_solo_oracle(self, sched_seed, n_ops):
            rng = np.random.default_rng(sched_seed)
            pool = ServePool(rungs=(1, 8), idle_after=2)
            served = {}      # session id -> chunks stepped while admitted
            schedule = []    # replay log, shown on failure
            next_id = 0
            for _ in range(n_ops):
                live = pool.session_ids
                sid = "*"
                op = rng.choice(["admit", "step", "evict", "flush",
                                 "migrate"])
                if op == "admit" and len(live) < 8:
                    kind = rng.choice(["plastic", "simple"])
                    sid = f"{kind}-{next_id}"
                    next_id += 1
                    pool.admit(_churn_net(kind), sid)
                    served[sid] = 0
                elif op == "step":
                    pool.step(self.CHUNK)
                    for sid in pool.session_ids:
                        served[sid] += 1
                elif op == "evict" and live:
                    sid = live[int(rng.integers(len(live)))]
                    pool.evict(sid)
                    del served[sid]
                elif op == "flush" and live:
                    sid = live[int(rng.integers(len(live)))]
                    pool.flush(sid)
                elif op == "migrate" and live:
                    # out-and-back migration through a raw lane export
                    sid = live[int(rng.integers(len(live)))]
                    net = pool.network_of(sid)
                    pool.restore(net, pool.export(sid))
                else:
                    continue
                schedule.append((op, sid))

            for sid in pool.session_ids:
                kind = sid.split("-")[0]
                oracle = Session.create(_churn_net(kind),
                                        seed=_seed_of(sid))
                for _ in range(served[sid]):
                    oracle.run(self.CHUNK)
                _assert_state_eq(
                    pool.evict(sid).state, oracle.state,
                    f"survivor {sid} after schedule {schedule} "
                    f"(sched_seed={sched_seed})")


@pytest.mark.slow
class TestShardedLaneParity:
    """Mesh-sharded scheduler ≡ single-device scheduler, bitwise, on 4
    virtual host devices (subprocess — the parent must keep 1 device)."""

    def test_sharded_matches_single_device(self):
        import json
        import subprocess
        import sys
        import textwrap
        code = textwrap.dedent("""
        import os
        os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
        import dataclasses, json
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.configs.synfire4 import SYNFIRE4_MINI, CHAIN_STDP, \\
            build_synfire
        from repro.core.distributed import lane_mesh
        from repro.serve import LaneScheduler

        cfg = dataclasses.replace(SYNFIRE4_MINI, stim_rate_hz=60.0)
        net = build_synfire(cfg, policy="fp16", propagation="sparse",
                            stdp_chain=CHAIN_STDP)

        def drive(mesh):
            s = LaneScheduler(net, 8, mesh=mesh)
            for i in range(8):
                s.admit(f"t{i}")
            s.step(50)
            s.step(50)
            flush = s.flush_all()
            states = jax.tree.map(
                lambda x: np.asarray(jax.random.key_data(x))
                if jnp.issubdtype(x.dtype, jax.dtypes.prng_key)
                else np.asarray(x), s.states)
            return states, flush

        assert len(jax.devices()) == 4
        st_m, fl_m = drive(lane_mesh(4))
        st_1, fl_1 = drive(None)
        ok = all(np.asarray(a).tobytes() == np.asarray(b).tobytes()
                 for a, b in zip(jax.tree.leaves(st_m),
                                 jax.tree.leaves(st_1)))
        for sid in fl_1:
            for k in fl_1[sid]:
                ok = ok and np.array_equal(np.asarray(fl_m[sid][k]),
                                           np.asarray(fl_1[sid][k]))
        print(json.dumps({"ok": bool(ok)}))
        """)
        try:
            out = subprocess.run(
                [sys.executable, "-c", code], capture_output=True,
                text=True,
                env={**__import__("os").environ, "PYTHONPATH": "src"},
                timeout=900)
        except (OSError, subprocess.SubprocessError) as e:
            pytest.skip(f"cannot spawn subprocess here: {e}")
        assert out.returncode == 0, out.stderr[-3000:]
        res = json.loads(out.stdout.strip().splitlines()[-1])
        assert res["ok"], "sharded lanes diverged from single-device"


class TestAdmissionPolicy:
    """ServePool(policy=): first-fit default vs best-fit bin packing."""

    def test_bad_policy_rejected(self):
        with pytest.raises(ValueError, match="admission policy"):
            ServePool(policy="worst_fit")
        with pytest.raises(ValueError, match="bin_lanes"):
            ServePool(policy="best_fit", bin_lanes=0)

    def test_pinned_lane_must_be_free(self):
        net = _mini("fp32", "packed", "xla")
        sched = LaneScheduler(net, 2, record="monitors")
        assert sched.admit("a") == 0
        with pytest.raises(ValueError, match="not free"):
            sched.admit("b", lane=0)
        assert sched.admit("b", lane=1) == 1
        assert sched.lane_sessions == ["a", "b"]

    def test_default_first_fit_unchanged(self):
        """The default pool keeps the historical lane order: lowest free
        lane, regardless of bin occupancy."""
        net = _mini("fp32", "packed", "xla")
        pool = ServePool(rungs=(8,))
        for i in range(5):
            pool.admit(net, f"t{i}")
        pool.evict("t1")
        pool.admit(net, "t5")  # first free lane = 1
        sched = pool.ladder_of("t5").scheduler
        assert sched.lane_sessions[:6] == \
            ["t0", "t5", "t2", "t3", "t4", None]

    def test_best_fit_prefers_fullest_bin(self):
        """With bin0 nearly empty and bin1 nearly full, best-fit closes
        up bin1 (lane 7) where first-fit would take lane 1."""
        net = _mini("fp32", "packed", "xla")
        pool = ServePool(rungs=(8,), policy="best_fit", bin_lanes=4)
        for i in range(7):
            pool.admit(net, f"t{i}")   # best-fit on empty = lanes 0..6
        for sid in ("t1", "t2", "t3"):
            pool.evict(sid)            # bin0 = {t0}, bin1 = {t4, t5, t6}
        pool.admit(net, "t7")
        sched = pool.ladder_of("t7").scheduler
        assert sched.lane_sessions == \
            ["t0", None, None, None, "t4", "t5", "t6", "t7"]

    def test_best_fit_activity_tiebreak(self):
        """Equal occupancy: the bin with lower aggregate flush-reported
        activity wins, spreading hot tenants apart."""
        net = _mini("fp32", "packed", "xla")
        pool = ServePool(rungs=(8,), policy="best_fit", bin_lanes=4)
        for i in range(5):
            pool.admit(net, f"t{i}")
        for sid in ("t1", "t2", "t3"):
            pool.evict(sid)            # bin0 = {t0}, bin1 = {t4}
        pool._activity.update({"t0": 40.0, "t4": 2.0})
        pool.admit(net, "cool")        # tie on occupancy -> quieter bin1
        sched = pool.ladder_of("cool").scheduler
        assert sched.lane_sessions[5] == "cool"
        pool.evict("cool")             # back to a 1-vs-1 tie
        pool._activity.update({"t0": 2.0, "t4": 40.0})
        pool.admit(net, "hot")         # now bin0 is the quieter bin
        assert sched.lane_sessions[1] == "hot"

    def test_flush_feeds_activity_and_evict_clears_it(self):
        net = _mini("fp32", "packed", "xla")
        pool = ServePool(rungs=(8,), policy="best_fit")
        pool.admit(net, "t")
        pool.step(50)
        pool.flush("t")
        assert "t" in pool._activity
        assert np.isfinite(pool._activity["t"])
        assert pool._activity["t"] >= 0.0
        pool.evict("t")
        assert "t" not in pool._activity

    def test_best_fit_streams_match_solo(self):
        """Placement policy is routing only — every tenant's numerics are
        bit-identical to a solo session regardless of which lane it got."""
        net = _mini("fp16", "packed", "xla", plastic=True)
        pool = ServePool(rungs=(8,), policy="best_fit", bin_lanes=2)
        for i in range(5):
            pool.admit(net, f"s{i}")
        pool.evict("s1")
        pool.admit(net, "s5")          # lands by best-fit, not lane 1
        pool.step(40)
        pool.step(40)
        for sid in ("s0", "s2", "s3", "s4", "s5"):
            solo = Session.create(net, seed=_seed_of(sid))
            solo.run(40)
            solo.run(40)
            _assert_flush_eq(pool.flush(sid), solo.flush())
            _assert_state_eq(pool.evict(sid).state, solo.state, sid)
