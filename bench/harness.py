"""Runs one benchmark cell once: set-up, a measured window, the check.

Everything that belongs to one configuration, traffic mix or metric is
found by name from ``BENCHMARK.json``:

* ``configs/<config>.json`` names the system adapter
  (``systems/<system>.py``: builds the simulator's network) and the plain
  reference (``references/<reference>.py``), and holds the sizes;
* ``traffic/<traffic>.json`` names a driver (``drivers/<driver>.py``) and
  its parameters;
* ``metrics/<metric>.py`` reads one metric from the run (``read(ctx)``,
  ``None`` when it finds nothing to read).

:func:`run_cell` does not look for a chip; ``run.py`` does that first.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from bench import check, trace_reduce, work

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]


def load_module(path: Path):
    """Import a file by path (names may hold dots, as metric names do)."""
    path = Path(path)
    if not path.is_file():
        raise FileNotFoundError(path)
    name = "bench_" + "_".join(path.relative_to(path.parents[1]).with_suffix("")
                               .parts).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _reports(metric: dict, cell: str, e2e_names: set[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def resolve(name: str, bench: Path = BENCH) -> Cell:
    """The cell ``name`` of ``<bench>/../BENCHMARK.json``, with its
    configuration and traffic files read."""
    root = bench.parent
    spec = json.loads((root / "BENCHMARK.json").read_text())
    work = next((w for w in spec["workloads"] if w["name"] == name), None)
    if work is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == work["config"])
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((bench / "traffic" / f"{work['traffic']}.json")
                         .read_text())
    e2e = tuple(m for m in spec["end_to_end"] if _reports(m, name, set()))
    names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in spec["per_layer"]
                      if _reports(m, name, names))
    return Cell(name=name, config=config, traffic=traffic,
                chips=int(work["chips"]), end_to_end=e2e, per_layer=per_layer)


class _CompileCounter:
    """Counts backend compilations and persistent-cache hits and misses."""

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.hits = 0
        self.misses = 0

        def on_duration(event, _secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)


def _log(**kw) -> None:
    print(json.dumps(kw), file=sys.stderr, flush=True)


def _read_metrics(metrics, ctx, bench: Path) -> dict:
    out = {}
    for m in metrics:
        value = load_module(bench / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, bench: Path = BENCH) -> dict:
    """Set up, warm, measure for ``seconds``, check; returns the result."""
    import jax

    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = _CompileCounter()
    dev = jax.devices()[0]
    t_init = time.perf_counter()

    system = load_module(bench / "systems" / f"{cell.config['system']}.py")
    net = system.build(cell.config, seed)
    kernel = system.kernel_engaged(net)
    t_build = time.perf_counter()

    drivers = load_module(bench / "drivers" / f"{cell.traffic['driver']}.py")
    driver = drivers.Driver(net, cell.traffic, seed)
    t_admit = time.perf_counter()
    driver.step()  # compiles, or loads from the cache, every program it runs
    t_warm = time.perf_counter()
    setup_s = t_warm - t_start
    setup_compiles = counter.compiles

    chunk_s: list[float] = []
    flush_s: list[float] = []
    n_traced = int(cell.traffic["trace_chunks"]) if trace else 0
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    traced_span = None
    c0 = counter.compiles
    # Set-up's objects (JAX's and the network's) move out of the collector's
    # reach, so a full collection inside the window scans only what the
    # window itself allocated; the collector stays on.
    gc.collect()
    gc.freeze()
    gc0 = [g["collections"] for g in gc.get_stats()]
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while True:
        if len(chunk_s) == 0 and n_traced:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # host spans without every call
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            traced_span = jax.profiler.TraceAnnotation("traced")
            traced_span.__enter__()
        a = time.perf_counter()
        flush_s.append(driver.step())
        b = time.perf_counter()
        chunk_s.append(b - a)
        if traced_span is not None and len(chunk_s) == n_traced:
            traced_span.__exit__(None, None, None)
            traced_span = None
            jax.profiler.stop_trace()
        if b >= deadline and len(chunk_s) >= n_traced:
            break
    window_s = b - t0
    window_compiles = counter.compiles - c0
    window_gcs = [g["collections"] - c for g, c in zip(gc.get_stats(), gc0)]
    gc.unfreeze()
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    n_chunks = len(chunk_s)
    chunk = driver.chunk
    lanes = driver.lanes
    streams = driver.streams()
    _log(setup_split={
        "import_and_init_s": t_init - t_start, "build_s": t_build - t_init,
        "admit_s": t_admit - t_build,
        "compile_or_cache_load_s": (t_warm - t_admit) - statistics.median(chunk_s),
        "warm_chunk_s": statistics.median(chunk_s), "setup_s": setup_s},
        compiles_in_setup=setup_compiles, cache_hits=counter.hits,
        cache_misses=counter.misses, compiles_in_window=window_compiles,
        gc_collections_in_window=window_gcs,
        cache_dir=cache_dir, megakernel=kernel)
    ms = sorted(1e3 * c for c in chunk_s)
    _log(chunks=n_chunks, chunk_ticks=chunk, lanes=lanes,
         chunk_ms_median=statistics.median(ms),
         chunk_ms_p95=float(np.percentile(ms, 95)), chunk_ms_max=ms[-1],
         slowest_chunk=int(np.argmax(chunk_s)),
         share_within_model_time=sum(m <= chunk for m in ms) / n_chunks,
         flush_ms_median=1e3 * statistics.median(flush_s))

    ref_mod = load_module(bench / "references" /
                          f"{cell.config['reference']}.py")
    ref_net = ref_mod.build(cell.config["network"], seed)
    ctx = SimpleNamespace(
        kind=drivers.KIND, setup_s=setup_s, window_s=window_s,
        chunk_s=chunk_s, flush_s=flush_s, ticks=n_chunks * chunk,
        lane_ticks=n_chunks * chunk * lanes, trace=None, traced=None,
        work=None)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace:
        summary = trace_reduce.summarize(trace_dir, window="traced")
        shutil.rmtree(trace_dir, ignore_errors=True)
        spikes = _traced_spikes(streams, n_traced, drivers.KIND)
        ctx.trace = summary
        ctx.traced = SimpleNamespace(chunks=n_traced, ticks=n_traced * chunk)
        ctx.work = work.chunk_work(ref_net, spikes, n_traced * chunk * lanes,
                                   n_traced, lanes, work.peaks(dev.device_kind))
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = {"device_ops": summary.top_ops(10),
                     "idle_gaps": summary.top_gaps(10)}
        _log(work=dataclasses.asdict(ctx.work), busy_s=summary.busy_s,
             window_s=summary.window_s)
    metrics = _read_metrics(cell.per_layer if trace else cell.end_to_end,
                            ctx, bench)

    # The check runs once the program's state is freed.
    picked = check.sample(streams, int(cell.traffic["check_streams"]), seed)
    driver.close()
    del driver, net
    gc.collect()
    longest = max(len(c) for _, c in picked)
    t_ref = time.perf_counter()
    ref = ref_mod.simulate(ref_net, [s for s, _ in picked], longest, chunk)
    verdict = check.compare(picked, ref)
    _log(check=verdict["diagnostics"], reference_s=time.perf_counter() - t_ref)
    result = {"correct": bool(verdict["correct"]),
              "attempted": n_chunks * lanes, "failed": verdict["failed"],
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = verdict["numbers"]
    return result


def _traced_spikes(streams, n_traced: int, kind: str):
    """Spikes per group in the window's first ``n_traced`` chunks, summed
    over streams. The run's first chunk (of the first trial, or of every
    tenant) was the warm-up."""
    if kind == "serve":
        return sum(c[1:1 + n_traced].sum(axis=0) for _, c in streams)
    flat = np.concatenate([c for _, c in streams])
    return flat[1:1 + n_traced].sum(axis=0)


def emit(result: dict) -> None:
    """The numbers compared as the last lines of standard error, and the
    result as the last line of standard output."""
    for name, v in result["checks"].items():
        print(f"check {name} {v['value']} limit {v['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
