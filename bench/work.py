"""The least work a tick needs, and the least time a chip could take for it.

Counted from the network as the reference builds it and from the spikes the
window reported, never from how the simulator lays the network out: a
dense image, a CSR table, the XLA path or the megakernel all get the same
number, and it is a floor for any correct implementation.

* Operations: 2 per synaptic event delivered (a spike times its neuron's
  out-degree: one multiply, one add), plus :data:`IZH4_OPS` per IZH4 neuron
  per tick (eqs. 1-3 with two Euler half steps).
* Bytes, per program call (one chunk of every lane): the synapse table
  read once, as (source index, weight) pairs in the narrowest integer width
  that holds the presynaptic group and the storage dtype; and each lane's
  state (``v``, ``u`` and ``max_delay`` ticks of pending input per neuron,
  in the storage dtype) read once and written once.
* Least time: the larger of operations over peak FLOP/s and bytes over peak
  bytes/s; ``bound`` says which.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

# Per half step: dv (6), du (3), the two Euler updates (4); then the
# threshold compare, two selects and the u + d of the reset.
IZH4_OPS = 2 * 13 + 4

PEAKS = Path(__file__).with_name("peaks.json")


@dataclasses.dataclass(frozen=True)
class Work:
    ops: float
    bytes: float
    least_s: float
    bound: str  # "compute" or "memory"


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The peak row for ``device_kind``; a kind the table lacks is an error."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def _index_bytes(n_pre: int) -> int:
    return 1 if n_pre <= 2**8 else 2 if n_pre <= 2**16 else 4


def chunk_work(net, group_spikes, lane_ticks: int, calls: int, lanes: int,
               peak: dict) -> Work:
    """Work of ``calls`` program calls that advanced ``lanes`` lanes by
    ``lane_ticks`` lane-ticks in all, during which the groups fired
    ``group_spikes`` spikes ([groups], summed over lanes).

    ``net`` is the reference's network description (sizes, synapse masks,
    storage dtype)."""
    spikes = np.asarray(group_spikes, np.float64)
    events = float(spikes @ net.out_degree())
    n_izh = net.n - net.n_gen
    ops = 2.0 * events + IZH4_OPS * n_izh * float(lane_ticks)
    item = np.dtype(net.storage).itemsize
    table = sum(float(p.mask.sum()) * (_index_bytes(p.mask.shape[0]) + item)
                for p in net.projections)
    state = (2 * n_izh + net.max_delay * net.n) * item
    nbytes = float(calls) * (table + 2.0 * lanes * state)
    t_ops = ops / peak["flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return Work(ops=ops, bytes=nbytes, least_s=max(t_ops, t_mem),
                bound="compute" if t_ops >= t_mem else "memory")
