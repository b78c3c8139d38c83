"""The comparison that decides ``correct``.

What the timed path produced is the flushed spike count of every group in
every chunk, per stimulus stream (a trial or a tenant). A sample of streams
drawn from the seed, the longest among them, is simulated again by the
configuration's plain reference from rest, and the counts are compared
exactly: with the configured storage dtype the reference reproduces the
simulator tick for tick, while computing in the next precision down
(bfloat16 storage, the simulator's ``bf16`` policy) changes counts within
the first chunks (``PERF.md`` gives the readings).
"""
from __future__ import annotations

import numpy as np

# Number compared -> limit. An exact comparison has the limit 0.
LIMITS = {"count_mismatches": 0}


def sample(streams: list, n: int, seed: int) -> list:
    """Up to ``n`` streams drawn from ``seed``, always with the longest."""
    if len(streams) <= n:
        return list(streams)
    longest = max(range(len(streams)), key=lambda i: len(streams[i][1]))
    rest = [i for i in range(len(streams)) if i != longest]
    pick = np.random.default_rng([seed, 3]).choice(rest, n - 1, replace=False)
    return [streams[i] for i in sorted([longest, *pick.tolist()])]


def compare(program: list, reference: np.ndarray) -> dict:
    """``program``: ``(seed, counts [chunks, groups])`` per stream;
    ``reference``: ``[streams, chunks >= each stream's, groups]``.

    Returns the numbers compared (each with its value and limit), the
    diagnostics printed beside them, and ``failed``: the compared chunks
    with any count that differs."""
    mism = 0
    failed = 0
    gap = 0
    total = 0
    chunks = 0
    for (_, got), want in zip(program, reference):
        want = want[:len(got)]
        mism += int((got != want).sum())
        failed += int((got != want).any(axis=1).sum())
        gap += int(np.abs(got - want).sum())
        total += int(want.sum())
        chunks += len(got)
    numbers = {"count_mismatches": {"value": mism,
                                    "limit": LIMITS["count_mismatches"]}}
    diag = {"streams": len(program), "chunks": chunks,
            "reference_spikes": total,
            "count_gap": gap / max(total, 1)}
    return {"numbers": numbers, "diagnostics": diag, "failed": failed,
            "correct": all(v["value"] <= v["limit"] for v in numbers.values())}
