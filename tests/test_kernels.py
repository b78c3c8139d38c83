"""Per-kernel interpret-mode validation against the pure-jnp oracles.

Every Pallas kernel is swept over shapes/dtypes and asserted allclose
against ``repro.kernels.ref``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attn import flash_attention
from repro.kernels.izh_update import izh4_update
from repro.kernels.stdp_gather import stdp_gather
from repro.kernels.stdp_update import stdp_update
from repro.kernels.syn_gather import lane_take, syn_gather
from repro.kernels.syn_matmul import syn_matmul

I = True  # interpret mode (CPU container; kernels target TPU)


class TestIzh4Kernel:
    @pytest.mark.parametrize("n", [5, 128, 1000, 1200, 4096])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.float16])
    def test_matches_ref(self, n, dtype):
        k = jax.random.split(jax.random.key(0), 7)
        v = (jax.random.uniform(k[0], (n,)) * 40 - 80).astype(dtype)
        u = (jax.random.uniform(k[1], (n,)) * 10 - 15).astype(dtype)
        i_syn = jax.random.uniform(k[2], (n,)) * 20
        a = jnp.full((n,), 0.02)
        b = jnp.full((n,), 0.2)
        c = jnp.full((n,), -65.0)
        d = jnp.full((n,), 8.0)
        vo, uo, sp = izh4_update(v, u, i_syn, a, b, c, d, interpret=I)
        vr, ur, sr = ref.izh4_ref(v, u, i_syn, a, b, c, d)
        np.testing.assert_allclose(np.asarray(vo, np.float32),
                                   np.asarray(vr, np.float32), rtol=2e-3, atol=2e-2)
        np.testing.assert_allclose(np.asarray(uo, np.float32),
                                   np.asarray(ur, np.float32), rtol=2e-3, atol=2e-2)
        assert np.array_equal(np.asarray(sp), np.asarray(sr))

    @pytest.mark.parametrize("substeps,method_dt", [(1, 1.0), (2, 1.0), (4, 0.5)])
    def test_substep_sweep(self, substeps, method_dt):
        n = 300
        k = jax.random.split(jax.random.key(1), 3)
        v = jax.random.uniform(k[0], (n,)) * 40 - 80
        u = jax.random.uniform(k[1], (n,)) * 10 - 15
        i_syn = jax.random.uniform(k[2], (n,)) * 15
        a = jnp.full((n,), 0.1); b = jnp.full((n,), 0.2)
        c = jnp.full((n,), -65.0); d = jnp.full((n,), 2.0)
        vo, uo, sp = izh4_update(v, u, i_syn, a, b, c, d, dt=method_dt,
                                 substeps=substeps, interpret=I)
        vr, ur, sr = ref.izh4_ref(v, u, i_syn, a, b, c, d, dt=method_dt,
                                  substeps=substeps)
        np.testing.assert_allclose(np.asarray(vo), np.asarray(vr), rtol=1e-5, atol=1e-4)
        assert np.array_equal(np.asarray(sp), np.asarray(sr))


class TestSynMatmul:
    @pytest.mark.parametrize("shape", [(1, 200, 200), (8, 256, 512),
                                       (3, 1000, 50), (128, 384, 384)])
    @pytest.mark.parametrize("wdtype", [jnp.float16, jnp.bfloat16, jnp.float32])
    def test_matches_ref(self, shape, wdtype):
        m, k, n = shape
        kk = jax.random.split(jax.random.key(2), 2)
        x = jax.random.normal(kk[0], (m, k), jnp.float32)
        w = jax.random.normal(kk[1], (k, n), jnp.float32).astype(wdtype)
        out = syn_matmul(x, w, interpret=I)
        want = ref.syn_matmul_ref(x, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)

    def test_spike_propagation_semantics(self):
        # 0/1 spike vector times fp16 weights == exact sum of fan-in weights.
        rng = np.random.default_rng(0)
        spikes = (rng.random((1, 500)) < 0.2).astype(np.float32)
        w = (rng.random((500, 300)) < 0.3) * rng.normal(1.5, 0.1, (500, 300))
        w16 = jnp.asarray(w, jnp.float16)
        out = syn_matmul(jnp.asarray(spikes), w16, interpret=I)
        want = spikes @ np.asarray(w16, np.float32)
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6, atol=1e-5)


class TestSynGather:
    """CSR fan-in gather + segment-sum vs the jnp oracle (interpret mode)."""

    def _case(self, seed, p, q, f, wdtype, ragged=True):
        rng = np.random.default_rng(seed)
        idx = rng.integers(0, p, (q, f))
        w = rng.normal(0.0, 1.0, (q, f))
        if ragged:
            lens = rng.integers(0, f + 1, q)
            valid = np.arange(f)[None, :] < lens[:, None]
            idx = np.where(valid, idx, 0)
            w = np.where(valid, w, 0.0)
        spikes = jnp.asarray(rng.random(p) < 0.25, jnp.float32)
        return spikes, jnp.asarray(idx, jnp.int32), jnp.asarray(w, wdtype)

    @pytest.mark.parametrize("pqf", [
        (200, 200, 60),    # Synfire4-scale projection
        (2000, 2000, 60),  # Synfire4x10-scale (fanin << n_pre)
        (50, 300, 7),      # fan-in narrower than a lane
        (130, 257, 129),   # everything ragged vs the 128 padding
        (1000, 3, 1000),   # tall fan-in, tiny post group
    ])
    @pytest.mark.parametrize("wdtype", [jnp.float16, jnp.float32])
    def test_matches_ref(self, pqf, wdtype):
        p, q, f = pqf
        spikes, idx, w = self._case(0, p, q, f, wdtype)
        out = syn_gather(spikes, idx, w, interpret=I)
        want = ref.syn_gather_ref(spikes, idx, w)
        assert out.shape == (q,) and out.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("wdtype", [jnp.float16, jnp.float32])
    def test_ragged_last_row_and_padding_are_exact_zero(self, wdtype):
        # A row whose tail is padding (idx 0, w 0) must contribute exactly
        # the sum of its valid prefix, even when spikes[0] fires.
        spikes = jnp.ones((8,), jnp.float32)  # every source fires
        idx = jnp.asarray([[1, 3, 0, 0], [2, 0, 0, 0], [0, 0, 0, 0]], jnp.int32)
        w = jnp.asarray([[0.5, 1.5, 0.0, 0.0],
                         [2.0, 0.0, 0.0, 0.0],
                         [0.0, 0.0, 0.0, 0.0]], wdtype)
        out = np.asarray(syn_gather(spikes, idx, w, interpret=I))
        np.testing.assert_array_equal(out, np.asarray([2.0, 2.0, 0.0], np.float32))

    def test_golden_spike_semantics_bitwise_vs_dense(self):
        # 0/1 spikes with exactly-representable weights: the CSR reduction
        # must equal the dense matmul bit-for-bit (exact sums, any order).
        from repro.core.synapses import dense_to_csr
        rng = np.random.default_rng(3)
        mask = rng.random((400, 300)) < 0.05
        w = np.where(mask, rng.integers(1, 9, (400, 300)) * 0.25, 0.0)
        w = w.astype(np.float32)
        csr = dense_to_csr(mask, w)
        spikes = jnp.asarray(rng.random(400) < 0.2, jnp.float32)
        out = syn_gather(spikes, csr.idx, csr.weight, interpret=I)
        want = jnp.dot(spikes, jnp.asarray(w))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    def test_int16_indices_accepted(self):
        spikes, idx, w = self._case(5, 100, 64, 9, jnp.float16)
        out16 = syn_gather(spikes, idx.astype(jnp.int16), w, interpret=I)
        out32 = syn_gather(spikes, idx, w, interpret=I)
        np.testing.assert_array_equal(np.asarray(out16), np.asarray(out32))

    def test_empty_fanin_returns_zeros(self):
        out = syn_gather(jnp.ones((10,), jnp.float32),
                         jnp.zeros((4, 0), jnp.int32),
                         jnp.zeros((4, 0), jnp.float32), interpret=I)
        np.testing.assert_array_equal(np.asarray(out), np.zeros(4, np.float32))


class TestLaneTake:
    """``lane_take`` on its own, inside a Pallas program: the whole-row
    default and a window read from SMEM (traced loop bounds)."""

    N, R, F = 1024, 16, 256  # 8 chunks; two 128-column blocks of indices

    @staticmethod
    def _take(row, idx, window=None):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def kern(m_ref, row_ref, idx_ref, o_ref):
            if window is None:
                o_ref[...] = lane_take(row_ref, idx_ref[...])
            else:
                o_ref[...] = lane_take(row_ref, idx_ref[...],
                                       base=m_ref[0], n_chunks=m_ref[1])

        m = jnp.asarray(window or (0, 0), jnp.int32)
        whole = lambda i, m: (0, 0)
        return pl.pallas_call(
            kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(1,),
                in_specs=[pl.BlockSpec(row.shape, whole),
                          pl.BlockSpec(idx.shape, whole)],
                out_specs=pl.BlockSpec(idx.shape, whole)),
            out_shape=jax.ShapeDtypeStruct(idx.shape, jnp.float32),
            interpret=I)(m, row, idx)

    def _row(self, rng):
        # distinct, exactly representable, none zero: a read from the
        # wrong chunk or lane cannot go unnoticed
        return (np.arange(1, self.N + 1) * rng.choice([-1.0, 1.0], self.N)
                ).astype(np.float32)[None]

    def test_whole_row_is_take(self):
        rng = np.random.default_rng(0)
        row = self._row(rng)
        idx = rng.integers(0, self.N, (self.R, self.F)).astype(np.int32)
        got = np.asarray(self._take(jnp.asarray(row), jnp.asarray(idx)))
        np.testing.assert_array_equal(got, np.take(row[0], idx))

    @pytest.mark.parametrize("base,n_chunks", [(0, 1), (256, 3), (768, 2),
                                               (0, 8)])
    def test_window_is_take_inside_zero_outside(self, base, n_chunks):
        rng = np.random.default_rng(base + n_chunks)
        row = self._row(rng)
        hi = base + 128 * n_chunks
        # inside the window (off its lane boundaries too), and outside it
        idx = rng.integers(0, self.N, (self.R, self.F)).astype(np.int32)
        idx[:, :8] = rng.integers(base, hi, (self.R, 8))
        got = np.asarray(self._take(jnp.asarray(row), jnp.asarray(idx),
                                    (base, n_chunks)))
        inside = (idx >= base) & (idx < hi)
        assert inside.any() and (n_chunks == 8 or (~inside).any())
        np.testing.assert_array_equal(got[inside],
                                      np.take(row[0], idx[inside]))
        np.testing.assert_array_equal(got[~inside], 0.0)

    def test_window_pads_give_the_whole_row_products(self):
        """The megakernel's contract: live cells inside the window, pads
        ``idx 0`` / weight ``+0.0`` anywhere; every product and row sum
        is bitwise the whole row's, even with spike 0 firing."""
        rng = np.random.default_rng(5)
        base, n_chunks = 384, 3
        spikes = (rng.random(self.N) < 0.5).astype(np.float32)
        spikes[0] = 1.0
        live = rng.random((self.R, self.F)) < 0.4
        idx = np.where(live, rng.integers(base + 5, base + 128 * n_chunks,
                                          (self.R, self.F)), 0)
        idx = idx.astype(np.int32)
        w = np.where(live, rng.integers(1, 9, (self.R, self.F)) * 0.25,
                     0.0).astype(np.float32)
        row, idx_j = jnp.asarray(spikes[None]), jnp.asarray(idx)
        win = np.asarray(self._take(row, idx_j, (base, n_chunks))) * w
        full = np.asarray(self._take(row, idx_j)) * w
        np.testing.assert_array_equal(win.view(np.uint32),
                                      full.view(np.uint32))
        np.testing.assert_array_equal(win.sum(axis=1).view(np.uint32),
                                      full.sum(axis=1).view(np.uint32))
        assert not np.signbit(win).any()


class TestFlashAttention:
    @pytest.mark.parametrize("bhsd", [
        (1, 4, 128, 64),   # MHA
        (2, 8, 256, 64),   # GQA 8q over 2kv below
        (1, 2, 100, 32),   # ragged seq (padding path)
    ])
    def test_causal_mha(self, bhsd):
        b, h, s, d = bhsd
        k3 = jax.random.split(jax.random.key(3), 3)
        q = jax.random.normal(k3[0], (b, h, s, d), jnp.float32)
        k = jax.random.normal(k3[1], (b, h, s, d), jnp.float32)
        v = jax.random.normal(k3[2], (b, h, s, d), jnp.float32)
        out = flash_attention(q, k, v, causal=True, interpret=I)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("g", [2, 4])
    def test_gqa(self, g):
        b, hkv, s, d = 1, 2, 192, 64
        k3 = jax.random.split(jax.random.key(4), 3)
        q = jax.random.normal(k3[0], (b, hkv * g, s, d), jnp.float32)
        k = jax.random.normal(k3[1], (b, hkv, s, d), jnp.float32)
        v = jax.random.normal(k3[2], (b, hkv, s, d), jnp.float32)
        out = flash_attention(q, k, v, causal=True, interpret=I)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    def test_local_window(self):
        b, h, s, d = 1, 2, 256, 64
        k3 = jax.random.split(jax.random.key(5), 3)
        q = jax.random.normal(k3[0], (b, h, s, d), jnp.float32)
        k = jax.random.normal(k3[1], (b, h, s, d), jnp.float32)
        v = jax.random.normal(k3[2], (b, h, s, d), jnp.float32)
        out = flash_attention(q, k, v, causal=True, window=64, interpret=I)
        want = ref.flash_attention_ref(q, k, v, causal=True, window=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    def test_decode_alignment(self):
        # Sq=1 against a long KV (decode): query sits at the KV end.
        b, h, sk, d = 2, 4, 384, 64
        k3 = jax.random.split(jax.random.key(6), 3)
        q = jax.random.normal(k3[0], (b, h, 1, d), jnp.float32)
        k = jax.random.normal(k3[1], (b, h, sk, d), jnp.float32)
        v = jax.random.normal(k3[2], (b, h, sk, d), jnp.float32)
        out = flash_attention(q, k, v, causal=True, interpret=I)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    def test_fp16_kv(self):
        b, h, s, d = 1, 2, 128, 64
        k3 = jax.random.split(jax.random.key(7), 3)
        q = jax.random.normal(k3[0], (b, h, s, d), jnp.float32)
        k = jax.random.normal(k3[1], (b, h, s, d), jnp.float16)
        v = jax.random.normal(k3[2], (b, h, s, d), jnp.float16)
        out = flash_attention(q, k, v, causal=True, interpret=I)
        want = ref.flash_attention_ref(q, k.astype(jnp.float32),
                                       v.astype(jnp.float32), causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=5e-3, atol=5e-3)


class TestSTDPKernel:
    @pytest.mark.parametrize("pq", [(50, 60), (200, 200), (1000, 300)])
    @pytest.mark.parametrize("wdtype", [jnp.float16, jnp.float32])
    def test_matches_ref(self, pq, wdtype):
        p, q = pq
        rng = np.random.default_rng(1)
        mask = jnp.asarray(rng.random((p, q)) < 0.3)
        w = jnp.where(mask, 1.0, 0.0).astype(wdtype)
        pre_t = jnp.asarray(rng.random((p,)), jnp.float32)
        post_t = jnp.asarray(rng.random((q,)), jnp.float32)
        pre_s = jnp.asarray(rng.random((p,)) < 0.1)
        post_s = jnp.asarray(rng.random((q,)) < 0.1)
        kw = dict(a_plus=0.01, a_minus=0.012, w_min=0.0, w_max=5.0)
        out = stdp_update(w, mask, pre_t, post_t, pre_s, post_s, interpret=I, **kw)
        want = ref.stdp_update_ref(w, mask, pre_t, post_t, pre_s, post_s, **kw)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=1e-3, atol=1e-3)


class TestSTDPGatherKernel:
    """Fused CSR-row STDP vs the jnp oracle. Every op is elementwise per
    row cell (the gathers read, never reduce), so the kernel must match
    the oracle — and hence the dense STDP at the twin cells —
    **bit-for-bit**, not just allclose."""

    def _case(self, seed, p, q, f, wdtype, ragged=True):
        rng = np.random.default_rng(seed)
        idx = np.sort(rng.integers(0, p, (q, f)), axis=1)
        valid = np.ones((q, f), bool)
        if ragged:
            lens = rng.integers(0, f + 1, q)
            valid = np.arange(f)[None, :] < lens[:, None]
            idx = np.where(valid, idx, 0)
        w = np.where(valid, rng.normal(1.0, 0.4, (q, f)), 0.0)
        return (jnp.asarray(w, wdtype), jnp.asarray(idx, jnp.int32),
                jnp.asarray(valid),
                jnp.asarray(rng.random(p).astype(np.float32) * 2),
                jnp.asarray(rng.random(q).astype(np.float32) * 2),
                jnp.asarray((rng.random(p) < 0.2).astype(np.float32)),
                jnp.asarray((rng.random(q) < 0.2).astype(np.float32)))

    KW = dict(a_plus=0.01, a_minus=0.012, w_min=0.0, w_max=5.0)

    @pytest.mark.parametrize("pqf", [
        (200, 200, 60),    # Synfire4-scale plastic projection
        (2000, 2000, 90),  # Synfire4x10-scale (fanin << n_pre)
        (50, 300, 7),      # fan-in narrower than a lane
        (130, 257, 129),   # everything ragged vs the 128 padding
        (40, 10, 15),      # fan-in wider than the post group
    ])
    @pytest.mark.parametrize("wdtype", [jnp.float16, jnp.float32])
    def test_matches_ref_bitwise(self, pqf, wdtype):
        import functools
        p, q, f = pqf
        args = self._case(0, p, q, f, wdtype)
        out = stdp_gather(*args, interpret=I, **self.KW)
        # jit the oracle: the engine always runs it jitted, and XLA's FMA
        # contraction of mul+add differs between eager op-by-op dispatch
        # and a compiled program — jitted-vs-kernel is the real contract.
        want = jax.jit(functools.partial(ref.stdp_gather_ref,
                                         **self.KW))(*args)
        assert out.shape == (q, f) and out.dtype == wdtype
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(want, np.float32))

    @pytest.mark.parametrize("wdtype", [jnp.float16, jnp.float32])
    def test_padding_rows_stay_exact_zero(self, wdtype):
        # Padded cells (valid=False) gather pre_trace[0] for their Δw but
        # the validity mask must pin them at exact 0 — otherwise CSR rows
        # drift from their dense twins.
        w, idx, valid, pre_t, post_t, pre_s, post_s = self._case(
            3, 64, 32, 9, wdtype, ragged=True)
        pre_t = pre_t.at[0].set(7.5)  # make a leak visible
        post_s = jnp.ones_like(post_s)
        out = np.asarray(stdp_gather(w, idx, valid, pre_t, post_t, pre_s,
                                     post_s, interpret=I, **self.KW),
                         np.float32)
        assert np.all(out[~np.asarray(valid)] == 0.0)

    def test_matches_dense_stdp_kernel_at_twin_cells(self):
        # The same synapses through the dense outer-product kernel and the
        # CSR gather kernel end at identical weights.
        from repro.core.synapses import dense_to_csr
        rng = np.random.default_rng(5)
        mask = rng.random((120, 80)) < 0.2
        mask[0, :] = True
        w = np.where(mask, rng.normal(2.0, 0.3, (120, 80)), 0.0).astype(np.float32)
        csr = dense_to_csr(mask, w)
        pre_t = jnp.asarray(rng.random(120).astype(np.float32))
        post_t = jnp.asarray(rng.random(80).astype(np.float32))
        pre_s = jnp.asarray((rng.random(120) < 0.3).astype(np.float32))
        post_s = jnp.asarray((rng.random(80) < 0.3).astype(np.float32))
        dense = np.asarray(stdp_update(jnp.asarray(w), jnp.asarray(mask),
                                       pre_t, post_t, pre_s, post_s,
                                       interpret=I, **self.KW))
        rows = np.asarray(stdp_gather(csr.weight, csr.idx, csr.valid,
                                      pre_t, post_t, pre_s, post_s,
                                      interpret=I, **self.KW))
        idx = np.asarray(csr.idx)
        valid = np.asarray(csr.valid)
        cols = np.broadcast_to(np.arange(80)[:, None], idx.shape)
        np.testing.assert_array_equal(dense[idx[valid], cols[valid]],
                                      rows[valid])

    def test_empty_fanin_passthrough(self):
        w = jnp.zeros((4, 0), jnp.float16)
        out = stdp_gather(w, jnp.zeros((4, 0), jnp.int32),
                          jnp.zeros((4, 0), bool),
                          jnp.ones((10,), jnp.float32),
                          jnp.ones((4,), jnp.float32),
                          jnp.zeros((10,), jnp.float32),
                          jnp.zeros((4,), jnp.float32),
                          interpret=I, **self.KW)
        assert out.shape == (4, 0)


class TestFusedTickKernel:
    """Whole-tick megakernel vs the independent jnp oracle
    (``ref.fused_tick_ref``) on a network OFF the lane grid (Synfire4-mini,
    N=186 — not a multiple of the 128-lane block), fp32+fp16 storage,
    dense and CSR tile schedules, random (non-engine-trajectory) state.

    Bitwise, not allclose: the exactly-representable Synfire weight tables
    plus +0.0 tile padding make every accumulation order exact, so the
    kernel's lane padding / tile schedule / clamped DMAs must cancel out
    perfectly against the oracle's unpadded arithmetic."""

    def _net(self, policy, prop, cfg_name="SYNFIRE4_MINI"):
        import dataclasses

        from repro.configs import synfire4
        net = synfire4.build_synfire(getattr(synfire4, cfg_name),
                                     policy=policy, backend="fused",
                                     propagation=prop)
        static = dataclasses.replace(net.static, fused_kernel=True)
        return dataclasses.replace(net, static=static)

    @pytest.mark.parametrize("prop", ["packed", "sparse"])
    @pytest.mark.parametrize("policy", ["fp32", "fp16"])
    def test_matches_ref_bitwise(self, prop, policy):
        self._assert_matches_ref(self._net(policy, prop), prop)

    @pytest.mark.parametrize("policy", ["fp32", "fp16"])
    def test_full_synfire4_windowed_csr_matches_ref_bitwise(self, policy):
        """Full Synfire4 in CSR rows: every row tile gathers from a window
        of a few chunks of the ~14-chunk spike row, most of them starting
        off a lane boundary."""
        from repro.kernels import fused_tick as ftk

        net = self._net(policy, "sparse", "SYNFIRE4")
        kp = self._assert_matches_ref(net, "sparse")
        csr = np.asarray(kp.meta)[np.asarray(kp.meta)[:, ftk._KIND] == 1]
        assert (csr[:, ftk._NCH] < kp.n_pad // 128).all()
        assert kp.csr_chunks < kp.csr_row_chunks
        starts = [b.pre_start for b in net.static.buckets]
        assert any(s % 128 for s in starts)

    def _assert_matches_ref(self, net, prop):
        from repro.core import backend as be
        from repro.core import neurons as nrn
        from repro.kernels import fused_tick as ftk

        static, params = net.static, net.params
        assert static.n % 128 != 0  # off the lane grid on purpose
        payload = be.assemble_fused(static, net.state0.weights, params)
        kp = payload.kernel
        assert kp is not None and kp.n_steps > 1  # a real tile schedule

        rng = np.random.default_rng(7)
        n = static.n
        sdtype = net.state0.neurons.v.dtype
        v = jnp.asarray(rng.uniform(-80, -20, n), sdtype)
        u = jnp.asarray(rng.uniform(-15, 5, n), sdtype)
        # exactly-representable ring charge (multiples of 0.25) so the
        # bitwise contract holds for the i_syn read-back too
        ring = jnp.asarray(rng.integers(0, 64, (static.ring_len, n)) * 0.25,
                           net.state0.ring.dtype)
        gen_row = jnp.asarray(rng.random(n) < 0.3)
        p = params.neuron
        is_gen = p.model == nrn.NeuronModel.GENERATOR
        t = jnp.int32(137)  # deep into the run: ring slots wrap

        out = ftk.fused_tick(static, v, u, ring, gen_row, is_gen,
                             p.a, p.b, p.c, p.d, t, kp, interpret=True)

        buckets = static.buckets
        dense = [(b.pre_start, b.post_start, b.delay_ms, payload.packed[bi])
                 for bi, b in enumerate(buckets) if b.kind == "dense"]
        csr = [(b.post_start, b.delay_ms,
                params.bucket_csr_idx[bi].astype(jnp.int32) + b.pre_start,
                payload.packed[bi])
               for bi, b in enumerate(buckets) if b.kind == "sparse"]
        assert dense if prop == "packed" else csr
        # jit the oracle: eager op-by-op dispatch skips XLA's mul+add FMA
        # contraction and lands 1 ulp off the compiled kernel on fp32
        # membranes — jitted-vs-kernel is the real contract (same policy
        # as the stdp_gather golden).
        import functools
        want = jax.jit(functools.partial(
            ref.fused_tick_ref, dense=dense, csr=csr,
            ring_len=static.ring_len, dt=static.dt,
            substeps=static.substeps))(
                v, u, ring, gen_row, is_gen, p.a, p.b, p.c, p.d, t)
        for name, o, w in zip(("v", "u", "spikes", "ring", "i_syn"),
                              out, want):
            np.testing.assert_array_equal(
                np.asarray(o, np.float32), np.asarray(w, np.float32),
                err_msg=f"fused tick kernel diverges from oracle on {name}")
        return kp

    def test_x10_windows_cover_live_indices(self):
        """Synfire4×10's schedule: 46 CSR row tiles whose windows hold
        every live index, 605 chunk passes per tick against 4,508 for the
        whole 98-chunk row; a live index moved out of its window is
        refused."""
        import dataclasses

        from repro.configs.synfire4 import SYNFIRE4_X10, build_synfire
        from repro.core import backend as be
        from repro.kernels import fused_tick as ftk

        net = build_synfire(SYNFIRE4_X10, policy="fp16", backend="fused",
                            propagation="sparse", budget=None,
                            monitor_ms_hint=0)
        static = dataclasses.replace(net.static, fused_kernel=True)
        kp = be.assemble_fused(static, net.state0.weights,
                               net.params).kernel  # checks the windows
        assert (kp.n_pad, kp.n_steps) == (12544, 46)
        assert (kp.csr_chunks, kp.csr_row_chunks) == (605, 4508)

        meta = np.asarray(kp.meta)
        idx, w = np.asarray(kp.csr_idx), np.asarray(kp.csr_w)
        tile = np.repeat(np.arange(kp.n_steps), kp.tile_r)
        lo = meta[tile, ftk._PRE][:, None]
        hi = lo + 128 * meta[tile, ftk._NCH][:, None]
        live = w != 0
        assert live.sum() > 890_000  # of 900,000 synapses; a few weigh 0
        assert ((idx[live] >= np.broadcast_to(lo, idx.shape)[live])
                & (idx[live] < np.broadcast_to(hi, idx.shape)[live])).all()

        r, f = np.argwhere(live)[0]
        bad = idx.copy()
        bad[r, f] = hi[r, 0]  # the first lane past the window
        with pytest.raises(ValueError, match="outside its tile's gather"):
            ftk._check_windows(meta, bad, w, kp.tile_r)
        ftk._check_windows(meta, idx, w, kp.tile_r)


class TestFlashAttentionStress:
    @pytest.mark.parametrize("case", [
        # (b, hkv, g, sq, sk, d, window, kvdtype) — combined stress
        (2, 2, 4, 96, 320, 64, 128, jnp.float16),   # GQA+window+fp16+ragged
        (1, 1, 8, 64, 64, 32, -1, jnp.bfloat16),    # MQA g=8, bf16 kv
        (1, 4, 1, 1, 500, 128, 200, jnp.float16),   # decode + ring window
    ])
    def test_combined(self, case):
        b, hkv, g, sq, sk, d, window, kvd = case
        ks = jax.random.split(jax.random.key(11), 3)
        q = jax.random.normal(ks[0], (b, hkv * g, sq, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, hkv, sk, d), jnp.float32).astype(kvd)
        v = jax.random.normal(ks[2], (b, hkv, sk, d), jnp.float32).astype(kvd)
        out = flash_attention(q, k, v, causal=True, window=window, interpret=I)
        want = ref.flash_attention_ref(q, k.astype(jnp.float32),
                                       v.astype(jnp.float32),
                                       causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=6e-3, atol=6e-3)

    def test_xla_chunked_path_matches_kernel(self):
        """The model's XLA chunked attention == the Pallas kernel (same
        online-softmax algorithm, two implementations)."""
        from repro.models.attention import chunked_attention
        b, h, s, d = 1, 4, 256, 64
        ks = jax.random.split(jax.random.key(12), 3)
        q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(s), (b, s))
        xla = chunked_attention(q, k, v, pos, jnp.arange(s), causal=True,
                                block_k=64)
        pall = flash_attention(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                               jnp.moveaxis(v, 2, 1), causal=True, interpret=I)
        np.testing.assert_allclose(np.asarray(jnp.moveaxis(xla, 2, 1)),
                                   np.asarray(pall), rtol=2e-3, atol=2e-3)
