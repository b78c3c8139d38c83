#!/usr/bin/env python3
"""Readings that set the limits of ``check.py``: the program as configured,
and the control (the program with its ``bf16`` policy switched on, the
precision below the configured fp16), each compared with the reference at
the configured precision.

    python3 bench/control.py --workload synfire4.sim --seeds 1 2 3 --chunks 20
    python3 bench/control.py --workload synfire4.sim --seeds 1 2 3 --chunks 20 --control

Each seed builds the cell's network and traffic as a run does, drives
``--chunks`` chunks through the cell's driver (no timing), and prints one
JSON line with the numbers ``check.compare`` gives. The benchmark's own runs
never run this. Needs a TPU.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The control's policy and the storage dtype it states.
CONTROL_POLICY, CONTROL_STORAGE = "bf16", "bfloat16"


def readings(cell, seeds, chunks: int, control: bool = False,
             bench=None) -> list[dict]:
    """Per seed: ``check.compare`` of ``chunks`` chunks of the cell's driver
    against the reference, the program built as configured or, with
    ``control``, under the control's policy."""
    from bench import check, harness

    bench = bench or harness.BENCH
    program_config = copy.deepcopy(cell.config)
    if control:
        program_config["build"]["policy"] = CONTROL_POLICY
        program_config["network"]["storage_dtype"] = CONTROL_STORAGE
    system = harness.load_module(bench / "systems" /
                                 f"{cell.config['system']}.py")
    drivers = harness.load_module(bench / "drivers" /
                                  f"{cell.traffic['driver']}.py")
    ref_mod = harness.load_module(bench / "references" /
                                  f"{cell.config['reference']}.py")
    out = []
    for seed in seeds:
        driver = drivers.Driver(system.build(program_config, seed),
                                cell.traffic, seed)
        for _ in range(chunks):
            driver.step()
        picked = check.sample(driver.streams(),
                              int(cell.traffic["check_streams"]), seed)
        driver.close()
        del driver
        gc.collect()
        ref_net = ref_mod.build(cell.config["network"], seed)
        ref = ref_mod.simulate(ref_net, [s for s, _ in picked],
                               max(len(c) for _, c in picked),
                               cell.traffic["chunk_ticks"])
        verdict = check.compare(picked, ref)
        out.append({"seed": seed,
                    "policy": program_config["build"]["policy"],
                    "correct": verdict["correct"],
                    **{k: v["value"] for k, v in verdict["numbers"].items()},
                    **verdict["diagnostics"]})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--chunks", type=int, required=True)
    ap.add_argument("--control", action="store_true",
                    help=f"build the program under the {CONTROL_POLICY} policy")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from bench import harness

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = harness.resolve(args.workload)
    for r in readings(cell, args.seeds, args.chunks, args.control):
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
