"""The system under test for the polychronization network: the simulator's
own ``build_izhikevich2006``, given the configuration's network and build
blocks."""
from __future__ import annotations

import numpy as np


def build(config: dict, seed: int):
    """The simulator's ``CompiledNetwork`` for ``config``, connectivity drawn
    from ``seed``, with its default streamed telemetry (spike counts and
    filtered rates per group, the plastic weights)."""
    from repro.configs.izhikevich2006 import (
        Izhikevich2006Config,
        build_izhikevich2006,
    )

    network = dict(config["network"])
    storage = network.pop("storage_dtype")
    build_block = dict(config["build"])
    if build_block.pop("propagation") != "sparse":
        raise ValueError(f"{config['name']}: the network builds CSR rows; "
                         "propagation must be 'sparse'")
    net = build_izhikevich2006(Izhikevich2006Config(name=config["name"],
                                                    **network),
                               seed=seed, **build_block)
    got = np.dtype(net.policy.state_storage).name
    if got != storage:
        raise ValueError(f"{config['name']}: policy {build_block['policy']!r}"
                         f" stores {got}, the configuration states {storage}")
    return net


def kernel_engaged(net) -> bool:
    """Whether Pallas kernels run in the tick (``backend="pallas"``: the
    IZH4 update and the inhibitory projection's CSR gather, ``syn_gather``;
    the plastic projection is plain XLA on either backend)."""
    return net.static.backend == "pallas" and not net.static.pallas_interpret
