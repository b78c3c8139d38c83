"""The least work of the polychronization network's plasticity per tick,
added to ``bench/work.py``'s (synaptic events and neuron updates) for the
whole-tick share of the plastic cells.

Counted from the configuration's sizes alone, never from how the simulator
lays the synapses out, so it is a floor that any correct implementation of
the rule (Izhikevich 2006, ``spnet``) must do:

* Traces: every tick each excitatory neuron's LTP and every neuron's LTD
  trace is multiplied by its decay or set on a spike (:data:`TRACE_OPS`
  operations), read and written in float32.
* The once-a-period weight update, amortized over its period of
  :data:`PERIOD_TICKS`: every plastic synapse reads and writes ``w`` and
  ``sd`` in the storage dtype and computes ``clip((w + 0.01) + sd)`` and
  ``0.9 sd`` (:data:`UPDATE_OPS` operations).

Left out: the spike-driven LTP and LTD terms (their number depends on the
spikes, which the metric's context does not carry per pre and post
neuron), and any pass a layout makes over synapses that did not fire.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

from bench.work import Work

PERIOD_TICKS = 1000  # spnet: one weight update per second of model time
TRACE_OPS = 2  # one multiply, one select
UPDATE_OPS = 5  # two adds, two clip bounds, one multiply
TRACE_BYTES = 8  # a float32 read and write

CONFIGS = Path(__file__).with_name("configs")


def network_of(config: str) -> dict:
    """The ``network`` block of ``configs/<config>.json``."""
    return json.loads((CONFIGS / f"{config}.json").read_text())["network"]


def per_tick(network: dict) -> tuple[float, float]:
    """(operations, bytes) of the plasticity floor per tick."""
    n_exc, n = network["n_exc"], network["n_exc"] + network["n_inh"]
    plastic = n_exc * network["fanout"]
    item = np.dtype(network["storage_dtype"]).itemsize
    traces = n_exc + n
    ops = TRACE_OPS * traces + UPDATE_OPS * plastic / PERIOD_TICKS
    nbytes = TRACE_BYTES * traces + 4 * item * plastic / PERIOD_TICKS
    return float(ops), float(nbytes)


def with_plasticity(base: Work, network: dict, ticks: int,
                    peak: dict) -> Work:
    """``base`` (``bench/work.py``'s work of the traced ticks) plus the
    plasticity floor of ``ticks`` ticks, with its least time at ``peak``."""
    ops, nbytes = per_tick(network)
    ops = base.ops + ops * ticks
    nbytes = base.bytes + nbytes * ticks
    t_ops = ops / peak["flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return dataclasses.replace(
        base, ops=ops, bytes=nbytes, least_s=max(t_ops, t_mem),
        bound="compute" if t_ops >= t_mem else "memory")
