"""``backend="fused"`` parity: the single-dispatch XLA tick AND the Pallas
megakernel tick must be bit-exact with ``backend="xla"``.

The fused backend re-expresses the packed bucket plan (per-bucket gating
with small [Q] cond payloads when event-gated, batched shape-class
contractions when not) and — where ``NetStatic.fused_kernel`` engages —
collapses the whole tick into one Pallas program.  Every restructuring is
bitwise neutral by construction (exact ±0 contributions, identical
expression trees, exactly-representable Synfire weights), so bitwise
equality is the correct assertion, not a tolerance.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.synfire4 import (
    CHAIN_STDP,
    SYNFIRE4,
    SYNFIRE4_MINI,
    build_synfire,
)
from repro.core import Engine
from repro.core import neurons as nrn
from repro.core.plasticity import HomeostasisConfig
from repro.kernels import ops as kops
from repro.kernels.ops import env_interpret
from repro.serve import Session

TICKS = 250
HOMEO = HomeostasisConfig(target_hz=8.0, tau_avg_ms=500.0, beta=1.0)


def _build(policy, backend, prop="packed", cfg=SYNFIRE4_MINI, **kw):
    return build_synfire(cfg, policy=policy, backend=backend,
                         propagation=prop, **kw)


def _run(net, ticks=TICKS):
    final, out = Engine(net).run(ticks)
    return final, np.asarray(out["spikes"])


def _assert_state_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        if jnp.issubdtype(getattr(x, "dtype", None), jax.dtypes.prng_key):
            x, y = jax.random.key_data(x), jax.random.key_data(y)
        assert np.array_equal(np.asarray(x), np.asarray(y))


class TestFusedParity:
    @pytest.mark.parametrize("prop", ["packed", "sparse", "auto"])
    @pytest.mark.parametrize("policy", ["fp32", "fp16"])
    def test_fused_matches_xla_bitwise(self, prop, policy):
        """Raster AND the full final NetState (neurons, ring, weights,
        traces) are bit-identical across the propagation matrix."""
        fx, rx = _run(_build(policy, "xla", prop))
        ff, rf = _run(_build(policy, "fused", prop))
        assert rx.sum() > 50, "wave never ignited — degenerate parity"
        assert np.array_equal(rx, rf), (
            f"{prop}/{policy}: rasters diverge at tick "
            f"{int(np.argwhere((rx != rf).any(axis=1))[0][0])}"
        )
        _assert_state_equal(fx, ff)

    @pytest.mark.parametrize("policy", ["fp32", "fp16"])
    def test_fused_plastic_with_homeostasis(self, policy):
        """STDP weight evolution + chunk-boundary homeostasis: fused and
        xla drive the exact same weight trajectory."""
        kw = dict(stdp_chain=CHAIN_STDP, homeo_chain=HOMEO,
                  homeostasis_period=50)
        fx, rx = _run(_build(policy, "xla", **kw))
        ff, rf = _run(_build(policy, "fused", **kw))
        assert np.array_equal(rx, rf)
        _assert_state_equal(fx, ff)
        # and plasticity actually moved the weights
        w0 = _build(policy, "fused", **kw).state0.weights
        moved = any(
            not np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(fx.weights, w0))
        assert moved, "no weight changed — plasticity parity is vacuous"

    def test_fused_chunked_serve_session(self):
        """A fused-backend session streamed in chunks reproduces the
        xla whole-run raster bitwise (call-split invariance rides the
        gen_base counter stream, which the fused tick consumes as-is)."""
        key = jax.random.key(11)
        net_x = _build("fp16", "xla")
        whole_final, whole = Engine(net_x).run(150, gen_base=key)
        sess = Session.create(Engine(_build("fp16", "fused")), key=key,
                              monitors=False)
        parts = [sess.spike_raster(30) for _ in range(5)]
        assert np.array_equal(np.asarray(whole["spikes"]),
                              np.concatenate(parts, axis=0))
        _assert_state_equal(whole_final, sess.state)

    def test_fused_run_batch_matches_xla(self):
        """Ungated (vmap) regime: the batched shape-class contractions
        must match the xla per-bucket matmuls bitwise."""
        _, ox = Engine(_build("fp16", "xla")).run_batch(TICKS, 4)
        _, of = Engine(_build("fp16", "fused")).run_batch(TICKS, 4)
        assert np.asarray(ox["spikes"]).sum() > 200
        assert np.array_equal(np.asarray(ox["spikes"]),
                              np.asarray(of["spikes"]))

    def test_fused_rejects_loop_propagation(self):
        with pytest.raises(ValueError, match="loop"):
            _build("fp32", "fused", prop="loop")


class TestFusedKernel:
    """The Pallas megakernel tick (``NetStatic.fused_kernel``), forced on
    via the compile-time flag (interpret execution on CPU)."""

    def _kernel_net(self, policy, prop, cfg=SYNFIRE4_MINI):
        net = _build(policy, "fused", prop, cfg=cfg)
        assert net.static.fused.kernel_ok
        static = dataclasses.replace(net.static, fused_kernel=True)
        return dataclasses.replace(net, static=static)

    @pytest.mark.parametrize("prop", ["packed", "sparse"])
    @pytest.mark.parametrize("policy", ["fp32", "fp16"])
    def test_kernel_tick_matches_xla_bitwise(self, prop, policy):
        fx, rx = _run(_build(policy, "xla", prop))
        ff, rf = _run(self._kernel_net(policy, prop))
        assert rx.sum() > 50
        assert np.array_equal(rx, rf), (
            f"{prop}/{policy}: megakernel raster diverges at tick "
            f"{int(np.argwhere((rx != rf).any(axis=1))[0][0])}"
        )
        _assert_state_equal(fx, ff)

    @pytest.mark.parametrize("policy", ["fp32", "fp16"])
    def test_kernel_windowed_csr_full_synfire4_matches_xla(self, policy):
        """Full Synfire4 in CSR rows (1,200 neurons, 14-chunk spike row):
        the CSR tiles gather over their buckets' source windows only, and
        raster and final state stay bitwise equal to the XLA path."""
        ticks = 100
        net_x = _build(policy, "xla", "sparse", cfg=SYNFIRE4)
        fx, rx = _run(net_x, ticks)
        ff, rf = _run(self._kernel_net(policy, "sparse", cfg=SYNFIRE4),
                      ticks)
        gen = np.asarray(net_x.params.neuron.model
                         == nrn.NeuronModel.GENERATOR)
        assert rx[:, ~gen].sum() > 1000, "no wave past the drive"
        assert np.array_equal(rx, rf)
        _assert_state_equal(fx, ff)

    def test_csr_chunks_gauge(self):
        """Assembling a sparse megakernel payload publishes its gather's
        chunk passes per tick, windowed and whole-row."""
        from repro import obs
        from repro.core import backend as be

        net = self._kernel_net("fp16", "sparse", cfg=SYNFIRE4)
        obs.configure(enabled=True, reset=True)
        kp = be.assemble_fused(net.static, net.state0.weights,
                               net.params).kernel
        g = obs.registry().get("repro_fused_csr_chunks")
        assert g.value(walk="window") == kp.csr_chunks == 30
        assert g.value(walk="row") == kp.csr_row_chunks == 182
        assert "repro_fused_csr_chunks{walk=\"window\"} 30" in (
            obs.registry().to_prometheus())

    def test_kernel_ineligible_when_plastic(self):
        net = _build("fp16", "fused", stdp_chain=CHAIN_STDP)
        assert not net.static.fused.kernel_ok
        assert not net.static.fused_kernel


class TestChipGuards:
    """What the chip path refuses: interpret-mode Pallas on a TPU backend
    (steered here by faking ``ops.on_tpu``), and a megakernel-ineligible
    net says why at compile time."""

    @pytest.fixture
    def fake_tpu(self, monkeypatch):
        monkeypatch.setattr(kops, "on_tpu", lambda: True)
        monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)

    def test_interpret_kwarg_raises_on_tpu(self, fake_tpu):
        with pytest.raises(kops.InterpretOnTPUError, match="TPU"):
            _build("fp32", "pallas", pallas_interpret=True)

    @pytest.mark.parametrize("backend", ["pallas", "fused"])
    def test_interpret_env_raises_on_tpu(self, fake_tpu, monkeypatch,
                                         backend):
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
        with pytest.raises(kops.InterpretOnTPUError):
            kops.resolve_interpret()
        with pytest.raises(kops.InterpretOnTPUError):
            _build("fp16", backend)

    def test_tpu_compiles_kernels_natively(self, fake_tpu):
        net = _build("fp16", "fused")
        assert net.static.pallas_interpret is False
        assert net.static.fused_kernel and net.static.fused.kernel_reason == ""

    def test_off_tpu_default_is_interpret(self, monkeypatch):
        monkeypatch.setattr(kops, "on_tpu", lambda: False)
        monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
        assert kops.resolve_interpret() is True
        assert kops.resolve_interpret(False) is False

    @pytest.mark.parametrize("kw,why", [
        (dict(stdp_chain=CHAIN_STDP), "plastic"),
        (dict(method="rk4"), "euler"),
    ])
    def test_ineligible_net_reports_reason(self, kw, why):
        net = _build("fp16", "fused", **kw)
        assert not net.static.fused.kernel_ok
        assert not net.static.fused_kernel
        assert why in net.static.fused.kernel_reason


class TestEnvInterpret:
    """``REPRO_PALLAS_INTERPRET`` tri-state parsing (satellite of the
    once-per-process ``_interpret()`` fix)."""

    @pytest.mark.parametrize("val,expect", [
        ("1", True), ("true", True), ("YES", True), ("on", True),
        ("0", False), ("false", False), ("off", False), ("", False),
    ])
    def test_parse(self, monkeypatch, val, expect):
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", val)
        assert env_interpret() is expect

    def test_unset_is_none(self, monkeypatch):
        monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
        assert env_interpret() is None


@pytest.mark.slow
class TestFullFusedMatrix:
    """Nightly matrix: full Synfire4, fused × {packed, sparse} ×
    {fp32, fp16}, 1,000 ticks, bitwise vs xla."""

    FULL_TICKS = 1000

    @pytest.mark.parametrize("prop", ["packed", "sparse"])
    @pytest.mark.parametrize("policy", ["fp32", "fp16"])
    def test_full_synfire_fused_bitwise(self, prop, policy):
        _, rx = _run(_build(policy, "xla", prop, cfg=SYNFIRE4),
                     self.FULL_TICKS)
        _, rf = _run(_build(policy, "fused", prop, cfg=SYNFIRE4),
                     self.FULL_TICKS)
        assert rx.sum() > 20_000
        assert np.array_equal(rx, rf)
