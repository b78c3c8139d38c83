"""Streaming telemetry: in-scan monitors + the paper's metrics layer.

``repro.telemetry.monitors`` compiles declarative monitor specs into
accumulators that ride the engine's ``lax.scan`` carry (constant-memory
runs, ``Engine.run(n, record="monitors")``); ``repro.telemetry.metrics``
turns monitor output + a ``HardwareSpec`` into the paper's accuracy /
real-time / energy numbers (driven by ``benchmarks/report.py``).
"""
from repro.telemetry.monitors import (
    CUMULATIVE,
    DEFAULT_MONITORS,
    GroupRate,
    MonitorSpec,
    PlasticWeights,
    SpikeCount,
    VoltageProbe,
    WeightNorm,
    carry_struct,
    chunk_carry,
    collect,
    flush_carry,
    init_carry,
    resolve,
    summarize,
    update,
)
from repro.telemetry import metrics

__all__ = [
    "CUMULATIVE",
    "DEFAULT_MONITORS",
    "GroupRate",
    "MonitorSpec",
    "PlasticWeights",
    "SpikeCount",
    "VoltageProbe",
    "WeightNorm",
    "carry_struct",
    "chunk_carry",
    "collect",
    "flush_carry",
    "init_carry",
    "metrics",
    "resolve",
    "summarize",
    "update",
]
