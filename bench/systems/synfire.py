"""The system under test for Synfire configurations: the simulator's own
``build_synfire``, given the configuration's network and build blocks."""
from __future__ import annotations

import numpy as np


def build(config: dict, seed: int):
    """The simulator's ``CompiledNetwork`` for ``config``, connectivity drawn
    from ``seed``, with its default streamed telemetry (spike counts and
    filtered rates per group) and no raster budget."""
    from repro.configs.synfire4 import SynfireConfig, build_synfire

    network = dict(config["network"])
    storage = network.pop("storage_dtype")
    net = build_synfire(SynfireConfig(name=config["name"], **network),
                        seed=seed, budget=None, monitor_ms_hint=0,
                        **config["build"])
    got = np.dtype(net.policy.state_storage).name
    if got != storage:
        raise ValueError(f"{config['name']}: policy {config['build']['policy']!r}"
                         f" stores {got}, the configuration states {storage}")
    return net


def kernel_engaged(net) -> bool:
    """Whether the tick runs as the Pallas megakernel."""
    return bool(net.static.fused_kernel)
