"""Izhikevich's polychronization network (Neural Computation 18(2):245-282,
2006, DOI 10.1162/089976606775093882; its simulation code ``spnet.cpp``).

1,000 IZH4 neurons: 800 regular-spiking excitatory (a 0.02, b 0.2, c -65,
d 8) and 200 fast-spiking inhibitory (a 0.1, b 0.2, c -65, d 2). Each
neuron has exactly M = 100 outgoing synapses to distinct targets, never
itself: an excitatory neuron's drawn from all N, an inhibitory neuron's
from the excitatory neurons only. Excitatory synapse ``j`` has axonal delay
``j // 5 + 1`` ms (5 synapses per delay from 1 to 20), weight 6 and learns
under :data:`SPNET_STDP` within [0, 10]; inhibitory synapses have delay 1,
weight -5 and stay fixed. Each ms one neuron drawn from all N gets an
input of 20.

:data:`IZH2006_X10` scales N ×10 (8,000 + 2,000 neurons, 1,000,000
synapses, 800,000 plastic) at the published M, D, weights and STDP
constants, with N/1000 = 10 kicks per ms so each neuron keeps its 1 Hz
kick rate.
"""
from __future__ import annotations

import dataclasses

from repro.core.network import CompiledNetwork, NetworkBuilder
from repro.core.neurons import izh4
from repro.core.plasticity import DelaySTDPConfig
from repro.memory import MemoryLedger
from repro.telemetry import GroupRate, PlasticWeights, SpikeCount

__all__ = ["Izhikevich2006Config", "IZH2006", "IZH2006_X10", "SPNET_STDP",
           "build_izhikevich2006", "spnet_delays"]


@dataclasses.dataclass(frozen=True)
class Izhikevich2006Config:
    name: str
    n_exc: int = 800
    n_inh: int = 200
    fanout: int = 100  # M: outgoing synapses per neuron
    max_delay: int = 20  # D: excitatory delays 1..D ms
    w_exc: float = 6.0
    w_inh: float = -5.0
    kicks_per_tick: int = 1  # N / 1000
    kick_amp: float = 20.0


IZH2006 = Izhikevich2006Config(name="izhikevich2006")
IZH2006_X10 = dataclasses.replace(IZH2006, name="izhikevich2006_x10",
                                  n_exc=8000, n_inh=2000, kicks_per_tick=10)

# spnet's rule: LTP 0.1 / LTD 0.12 nearest-spike traces decaying by 0.95 a
# ms, and once a second w <- clip(w + 0.01 + sd, 0, 10), sd <- 0.9 sd.
SPNET_STDP = DelaySTDPConfig(ltp_amp=0.1, ltd_amp=0.12, decay=0.95,
                             update_every=1000, bias=0.01, sd_decay=0.9,
                             w_min=0.0, w_max=10.0)

MONITORS = (SpikeCount(), GroupRate(), PlasticWeights())


def spnet_delays(fanout: int, max_delay: int) -> tuple[int, ...]:
    """Delay (ms) of each outgoing excitatory synapse slot: spnet gives slot
    ``j`` the delay index ``j // (M / D)``, i.e. ``j // (M / D) + 1`` ms."""
    if fanout % max_delay:
        raise ValueError(f"fanout {fanout} is not a multiple of {max_delay}")
    per = fanout // max_delay
    return tuple(j // per + 1 for j in range(fanout))


def build_izhikevich2006(
    cfg: Izhikevich2006Config = IZH2006,
    *,
    policy: str = "fp16",
    seed: int = 42,
    backend: str = "xla",
    monitors: str | tuple | None = MONITORS,
    watches: str | tuple | None = None,
    pallas_interpret: bool | None = None,
) -> CompiledNetwork:
    """The network under a precision policy (``fp16``: ``v``, ``u``, ``w``
    and ``sd`` stored in binary16, computed in float32; traces float32),
    connectivity drawn from ``seed``; CSR fan-in rows (the per-synapse
    delays ride them), the multi-op path of ``backend`` (``xla`` or
    ``pallas``)."""
    net = NetworkBuilder(seed=seed)
    net.add_group("exc", izh4(cfg.n_exc, a=0.02, b=0.2, c=-65.0, d=8.0))
    net.add_group("inh", izh4(cfg.n_inh, a=0.1, b=0.2, c=-65.0, d=2.0))
    net.connect("exc", ("exc", "inh"), mode="fanout", fanout=cfg.fanout,
                weight=cfg.w_exc,
                delay_ms=spnet_delays(cfg.fanout, cfg.max_delay),
                stdp=SPNET_STDP)
    net.connect("inh", "exc", mode="fanout", fanout=cfg.fanout,
                weight=cfg.w_inh, delay_ms=1)
    net.add_kicks(cfg.kicks_per_tick, cfg.kick_amp)
    ledger = MemoryLedger(budget=None, name=f"{cfg.name}/{policy}")
    return net.compile(policy=policy, ledger=ledger, monitors=monitors,
                       watches=watches, backend=backend,
                       propagation="sparse",
                       pallas_interpret=pallas_interpret)
