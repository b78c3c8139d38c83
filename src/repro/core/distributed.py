"""Pod-scale SNN engine: neuron-sharded ``shard_map`` with spike all-gather.

The paper's future work is engaging the RP2350's second core; CARLsim's
lineage is multi-GPU partitioning by neuron. The TPU-native version shards
neurons across the ``model`` mesh axis. Each device owns:

  * its neurons' state (v, u) and delay-ring slice
  * the **incoming** synapses of its neurons in sparse fan-in form:
    ``idx[int32, n_local, fanin]`` + ``w[fp16, n_local, fanin]``

Per tick, devices all-gather the global spike bitmap (N bool — the only
collective; 1 M neurons ≈ 125 KB/step), then gather+reduce their fan-in:
``I_local[i] = Σ_k w[i,k] · spikes[idx[i,k]]``. Delay handled per-synapse via
a delay bucket per ring slot offset.

The dense single-device engine (`repro.core.engine`) remains the reference;
this module is the scale-out path used by the SNN dry-run and the sizing
analysis. fp16 weights here are exactly the paper's storage technique at
pod scale.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import neurons as nrn
from repro.core.network import CompiledNetwork

__all__ = ["ShardedSNN", "build_sharded", "sharded_from_network", "lane_mesh",
           "core_mesh"]


def lane_mesh(n: int | None = None, *, axis: str = "lanes") -> Mesh:
    """A 1-D device mesh for serving-lane sharding (``LaneScheduler(mesh=...)``).

    Uses ``n`` devices (default: all visible). The lane axis is the only
    sharded dimension in the serving plane — lanes never interact, so this
    mesh carries zero collectives. On a 1-device CPU host, spawn virtual
    devices via ``XLA_FLAGS=--xla_force_host_platform_device_count=K``
    (set before jax import — see ``tests/test_distributed.py``).
    """
    devices = jax.devices()
    if n is None:
        n = len(devices)
    if n > len(devices):
        raise ValueError(
            f"requested {n} mesh devices but only {len(devices)} visible — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count before "
            "jax import to fake more on CPU")
    return Mesh(np.array(devices[:n]), (axis,))


def core_mesh(n: int | None = None, *, axis: str = "cores") -> Mesh:
    """A 1-D device mesh for core-grid partitioning
    (``run_partitioned_mesh``): one device per partition core, spike
    exchange via a per-tick ``all_gather`` over ``axis``. Same device
    semantics as :func:`lane_mesh`."""
    return lane_mesh(n, axis=axis)


class ShardedParams(NamedTuple):
    # Neuron dynamics parameters, sharded on the neuron axis.
    a: jax.Array
    b: jax.Array
    c: jax.Array
    d: jax.Array
    is_gen: jax.Array  # bool
    gen_rate: jax.Array  # f32 Hz (pulse)
    gen_until: jax.Array
    gen_rate_after: jax.Array
    # Sparse in-edges: [N, fanin] target-local synapses.
    idx: jax.Array  # int32 global pre index
    w: jax.Array  # storage dtype (fp16 policy)
    delay: jax.Array  # int32 per-synapse delay in ticks


class ShardedState(NamedTuple):
    t: jax.Array
    key: jax.Array  # per-device key (shard_map splits)
    v: jax.Array
    u: jax.Array
    ring: jax.Array  # [D, N]


@dataclasses.dataclass
class ShardedSNN:
    mesh: Mesh
    axis: str
    n: int  # global neuron count (padded to shard multiple)
    fanin: int
    ring_len: int
    dt: float
    params: ShardedParams
    state: ShardedState

    def step_fn(self):
        return make_step(self.mesh, self.axis, self.ring_len, self.dt)

    def run(self, n_steps: int):
        step = self.step_fn()

        @jax.jit
        def scan_run(params, state):
            def body(carry, _):
                st, out = step(params, carry)
                return st, out.sum()  # spike count per tick

            return jax.lax.scan(body, state, None, length=n_steps)

        return scan_run(self.params, self.state)


def make_step(mesh: Mesh, axis: str, ring_len: int, dt: float):
    """Build the sharded step. Inside shard_map all arrays are local shards."""

    def _step(params: ShardedParams, state: ShardedState):
        f32 = jnp.float32
        t = state.t
        key, k_gen = jax.random.split(state.key)
        slot = jnp.mod(t, ring_len)

        # 1. deliver currents for this tick
        i_syn = jax.lax.dynamic_index_in_dim(state.ring, slot, 0, keepdims=False)
        i_syn = i_syn.astype(f32)
        ring = jax.lax.dynamic_update_index_in_dim(
            state.ring, jnp.zeros_like(i_syn, state.ring.dtype), slot, 0
        )

        # 2. IZH4 dynamics (2 × 0.5 ms Euler, CARLsim default)
        v = state.v.astype(f32)
        u = state.u.astype(f32)
        for _ in range(2):
            v = v + 0.5 * dt * (0.04 * v * v + 5.0 * v + 140.0 - u + i_syn)
            u = u + 0.5 * dt * params.a * (params.b * v - u)
        spiked = (v >= 30.0) & ~params.is_gen
        v = jnp.where(spiked, params.c, v)
        u = jnp.where(spiked, u + params.d, u)

        # 3. Poisson generators (per-device key stream via axis index)
        k_gen = jax.random.fold_in(k_gen, jax.lax.axis_index(axis))
        in_pulse = (t.astype(f32) * dt) < params.gen_until
        rate = jnp.where(in_pulse, params.gen_rate, params.gen_rate_after)
        gen_sp = jax.random.uniform(k_gen, v.shape, dtype=f32) < rate * (dt / 1000.0)
        spikes = jnp.where(params.is_gen, gen_sp, spiked)

        # 4. THE collective: all-gather the global spike bitmap.
        spikes_global = jax.lax.all_gather(spikes, axis).reshape(-1)

        # 5. sparse fan-in accumulation, fp16 weights -> f32 math
        pre = spikes_global[params.idx].astype(f32)  # [n_local, fanin]
        contrib = pre * params.w.astype(f32)  # [n_local, fanin]
        # scatter into ring slots (t + delay) mod D, per synapse delay
        dslot = jnp.mod(t + params.delay, ring_len)  # [n_local, fanin]
        n_local = contrib.shape[0]
        rows = jnp.broadcast_to(jnp.arange(n_local)[:, None], contrib.shape)
        ring = ring.at[dslot, rows].add(contrib.astype(ring.dtype))

        new_state = ShardedState(
            t=t + 1, key=key,
            v=v.astype(state.v.dtype), u=u.astype(state.u.dtype), ring=ring,
        )
        return new_state, spikes

    pspec_params = ShardedParams(
        a=P(axis), b=P(axis), c=P(axis), d=P(axis), is_gen=P(axis),
        gen_rate=P(axis), gen_until=P(axis), gen_rate_after=P(axis),
        idx=P(axis), w=P(axis), delay=P(axis),
    )
    pspec_state = ShardedState(t=P(), key=P(), v=P(axis), u=P(axis), ring=P(None, axis))

    return jax.shard_map(
        _step, mesh=mesh,
        in_specs=(pspec_params, pspec_state),
        out_specs=(pspec_state, P(axis)),
        check_vma=False,
    )


def build_sharded(
    mesh: Mesh,
    axis: str,
    *,
    n_neurons: int,
    fanin: int,
    max_delay: int,
    seed: int = 0,
    exc_frac: float = 0.8,
    w_exc: float = 1.0,
    w_inh: float = -2.0,
    weight_dtype=jnp.float16,
    state_dtype=jnp.float16,
    stim_frac: float = 0.05,
    stim_rate_hz: float = 300.0,
    stim_ms: float = 15.0,
    as_specs: bool = False,
) -> ShardedSNN:
    """Random balanced network at pod scale (synfire-like statistics).

    With ``as_specs=True`` all arrays are ShapeDtypeStructs — used by the
    dry-run to lower/compile without allocating (1M+ neuron networks).
    """
    k = mesh.shape[axis]
    n = ((n_neurons + k - 1) // k) * k  # pad to shard multiple
    ring_len = max_delay + 1

    def arr(shape, dtype, fill=None):
        if as_specs:
            return jax.ShapeDtypeStruct(shape, dtype)
        if fill is None:
            return jnp.zeros(shape, dtype)
        return jnp.full(shape, fill, dtype)

    if as_specs:
        idx = jax.ShapeDtypeStruct((n, fanin), jnp.int32)
        w = jax.ShapeDtypeStruct((n, fanin), weight_dtype)
        delay = jax.ShapeDtypeStruct((n, fanin), jnp.int32)
        is_gen = jax.ShapeDtypeStruct((n,), jnp.bool_)
        a = b = c = d = gr = gu = ga = jax.ShapeDtypeStruct((n,), jnp.float32)
        key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
        t = jax.ShapeDtypeStruct((), jnp.int32)
    else:
        rng = np.random.default_rng(seed)
        idx = jnp.asarray(rng.integers(0, n, size=(n, fanin)), jnp.int32)
        sign = rng.random((n, fanin)) < exc_frac
        w = jnp.asarray(np.where(sign, w_exc, w_inh), weight_dtype)
        delay = jnp.asarray(rng.integers(1, max_delay + 1, size=(n, fanin)), jnp.int32)
        gen_mask = np.zeros((n,), bool)
        gen_mask[: int(n * stim_frac)] = True
        is_gen = jnp.asarray(gen_mask)
        # RS for exc-ish population, FS for the rest (statistics only)
        fs = rng.random((n,)) > exc_frac
        a = jnp.asarray(np.where(fs, 0.1, 0.02), jnp.float32)
        b = jnp.full((n,), 0.2, jnp.float32)
        c = jnp.full((n,), -65.0, jnp.float32)
        d = jnp.asarray(np.where(fs, 2.0, 8.0), jnp.float32)
        gr = jnp.asarray(np.where(gen_mask, stim_rate_hz, 0.0), jnp.float32)
        gu = jnp.full((n,), stim_ms, jnp.float32)
        ga = jnp.zeros((n,), jnp.float32)
        key = jax.random.key(seed)
        t = jnp.int32(0)

    params = ShardedParams(
        a=a, b=b, c=c, d=d, is_gen=is_gen, gen_rate=gr, gen_until=gu,
        gen_rate_after=ga, idx=idx, w=w, delay=delay,
    )
    if as_specs:
        v = u = jax.ShapeDtypeStruct((n,), state_dtype)
        ring = jax.ShapeDtypeStruct((ring_len, n), state_dtype)
    else:
        v = jnp.full((n,), -65.0, state_dtype)
        u = (jnp.full((n,), -65.0, jnp.float32) * 0.2).astype(state_dtype)
        ring = jnp.zeros((ring_len, n), state_dtype)
    state = ShardedState(t=t, key=key, v=v, u=u, ring=ring)

    return ShardedSNN(mesh=mesh, axis=axis, n=n, fanin=fanin, ring_len=ring_len,
                      dt=1.0, params=params, state=state)
