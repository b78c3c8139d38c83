"""Ahead-of-time compiles of the SNN path's Pallas kernels for TPU v5e.

Nothing runs here: each test lowers a kernel at real widths and compiles it
with the TPU compiler for a ``v5e:2x2`` topology described in-process. That
catches what interpret mode hides — tiling and alignment rules, VMEM
limits, dtypes and gathers Mosaic cannot lower. The topology is described
inside a module fixture (never at import), and every compile runs in the
test's own process; where no topology can be described the fixture skips.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.synfire4 import SYNFIRE4, SYNFIRE4_X10, build_synfire
from repro.core import backend as be
from repro.core import neurons as nrn
from repro.kernels import fused_tick as ftk
from repro.kernels.izh_update import izh4_update
from repro.kernels.stdp_gather import stdp_gather
from repro.kernels.stdp_update import stdp_update
from repro.kernels.syn_gather import syn_gather
from repro.kernels.syn_matmul import syn_matmul

STDP_KW = dict(a_plus=0.004, a_minus=0.0033, w_min=0.0, w_max=4.0)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    cache_on = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs on disk
        # a compile for a described chip cannot be read back from the
        # persistent cache; keep it out of the cache entirely
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu / no TPU compiler here
            jax.config.update("jax_enable_compilation_cache", cache_on)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", cache_on)


def _compile(fn, args, chip):
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
             for a in args]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel is there
    return compiled


_NETS = {"synfire4": SYNFIRE4, "synfire4_x10": SYNFIRE4_X10}


def _tick_fn(cfg_name, policy, prop):
    """The megakernel for a real net, as a function of its operands."""
    net = build_synfire(_NETS[cfg_name], policy=policy, backend="fused",
                        propagation=prop, budget=None, monitor_ms_hint=0)
    assert net.static.fused.kernel_ok, net.static.fused.kernel_reason
    static = dataclasses.replace(net.static, fused_kernel=True)
    kp = be.assemble_fused(static, net.state0.weights, net.params).kernel
    st, p = net.state0, net.params.neuron
    args = (st.neurons.v, st.neurons.u, st.ring[:, :, 0],
            jnp.zeros((static.n,), bool),
            p.model == nrn.NeuronModel.GENERATOR, p.a, p.b, p.c, p.d,
            jnp.int32(0), kp.meta, kp.w_stack, kp.csr_idx, kp.csr_w)

    def tick(v, u, ring, gen_row, is_gen, a, b, c, d, t, meta, w_stack,
             csr_idx, csr_w):
        payload = kp._replace(meta=meta, w_stack=w_stack, csr_idx=csr_idx,
                              csr_w=csr_w)
        return ftk.fused_tick(static, v, u, ring, gen_row, is_gen, a, b, c,
                              d, t, payload)
    return tick, args


@pytest.mark.parametrize("cfg_name,policy,prop", [
    ("synfire4", "fp32", "packed"),
    ("synfire4", "fp16", "packed"),
    ("synfire4_x10", "fp16", "sparse"),
])
def test_fused_tick_compiles(one_chip, cfg_name, policy, prop):
    tick, args = _tick_fn(cfg_name, policy, prop)
    _compile(tick, args, one_chip)


def test_fused_tick_compiles_under_vmap(one_chip):
    """The lane scheduler's shape: per-lane state and tick counter,
    shared weights and schedule."""
    tick, args = _tick_fn("synfire4", "fp16", "packed")
    lanes = 64
    lane_axes = (0, 0, 0, 0, None, None, None, None, None, 0,
                 None, None, None, None)
    batched = [jnp.broadcast_to(a, (lanes,) + a.shape) if ax == 0 else a
               for a, ax in zip(args, lane_axes)]
    _compile(jax.vmap(tick, in_axes=lane_axes), batched, one_chip)


# Synfire4×10 widths: 2,000-neuron exc populations, Table II fan-in 60
# (the Bernoulli draw pads rows to ~90), 12,000 neurons in all.
X10_PRE, X10_POST, X10_FANIN, X10_N = 2000, 2000, 90, 12000


def test_syn_gather_compiles_x10(one_chip):
    args = (jnp.zeros((X10_PRE,), jnp.float32),
            jnp.zeros((X10_POST, X10_FANIN), jnp.int16),
            jnp.zeros((X10_POST, X10_FANIN), jnp.float32))
    _compile(syn_gather, args, one_chip)


@pytest.mark.parametrize("wdtype", [jnp.float16, jnp.float32])
def test_stdp_gather_compiles_x10(one_chip, wdtype):
    args = (jnp.zeros((X10_POST, X10_FANIN), wdtype),
            jnp.zeros((X10_POST, X10_FANIN), jnp.int16),
            jnp.zeros((X10_POST, X10_FANIN), bool),
            jnp.zeros((X10_PRE,)), jnp.zeros((X10_POST,)),
            jnp.zeros((X10_PRE,)), jnp.zeros((X10_POST,)))
    _compile(lambda *a: stdp_gather(*a, **STDP_KW), args, one_chip)


@pytest.mark.parametrize("sdtype", [jnp.float16, jnp.float32])
def test_izh4_update_compiles_x10(one_chip, sdtype):
    args = (jnp.zeros((X10_N,), sdtype), jnp.zeros((X10_N,), sdtype)) + tuple(
        jnp.zeros((X10_N,)) for _ in range(5))
    _compile(izh4_update, args, one_chip)


def test_syn_matmul_compiles_x10(one_chip):
    args = (jnp.zeros((1, X10_PRE)), jnp.zeros((X10_PRE, X10_POST)))
    _compile(lambda x, w: syn_matmul(x, w, block_k=4096), args, one_chip)


def test_stdp_update_compiles_x10(one_chip):
    args = (jnp.zeros((X10_PRE, X10_POST), jnp.float16),
            jnp.zeros((X10_PRE, X10_POST), bool),
            jnp.zeros((X10_PRE,)), jnp.zeros((X10_POST,)),
            jnp.zeros((X10_PRE,)), jnp.zeros((X10_POST,)))
    _compile(lambda *a: stdp_update(*a, **STDP_KW), args, one_chip)
