"""Pod-scale SNN: the paper's simulator sharded across devices.

Runs a 16k-neuron random balanced network (synfire-like statistics, fp16
synapses) neuron-sharded with shard_map over every visible device (8
virtual ones on a CPU host, the real chips on an accelerator host) — the spike
bitmap all-gather is the only collective, exactly the CARLsim multi-device
partitioning mapped to a TPU mesh. The same engine dry-runs at 1M+ neurons
on the production mesh (see EXPERIMENTS.md §Dry-run SNN row).

  PYTHONPATH=src python examples/snn_pod_scale.py
"""
import os
# Eight virtual devices on a CPU host. The flag touches only the host CPU
# platform: on an accelerator host the mesh spans the real chips.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import time

import jax
import numpy as np

from repro.core.distributed import build_sharded


def main() -> None:
    mesh = jax.make_mesh((len(jax.devices()),), ("model",))
    snn = build_sharded(mesh, "model", n_neurons=16384, fanin=64,
                        max_delay=10, seed=7)
    print(f"{snn.n} neurons / {snn.n * snn.fanin} synapses "
          f"sharded over {mesh.devices.size} devices "
          f"(fp16 weights: {snn.params.w.nbytes / 2**20:.1f} MiB)")
    t0 = time.time()
    state, counts = snn.run(500)
    counts.block_until_ready()
    wall = time.time() - t0
    c = np.asarray(counts)
    print(f"500 ms model time in {wall:.2f} s wall "
          f"({0.5 / wall:.2f}x real-time on {os.cpu_count()} host core)")
    print(f"spikes: {int(c.sum())}, peak tick {int(c.max())}, "
          f"mean rate {c.sum() / snn.n / 0.5:.1f} Hz")


if __name__ == "__main__":
    main()
