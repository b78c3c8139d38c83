"""Observability plane (`repro.obs`): tracing, metrics, health, exporters —
and the contract everything else rests on: obs on/off is **bitwise
invisible** to device results.

The instrumentation wraps jit *dispatch* and host bookkeeping, never traced
computation, so rasters, weights, final state, and flushed telemetry must
be byte-identical with obs enabled or disabled (fast single-cell check in
tier 1; the full propagation × backend × dtype matrix under ``-m slow``).
The rest of the file pins the exporters' formats (Chrome-trace JSON shape,
Prometheus text escaping + cumulative buckets), the ring-buffer bound, the
compile/cache-hit classification, the SLO health verdicts against the
paper's budgets, and the typed checkpoint-failure surface.
"""
import json
import os
import time

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs.synfire4 import SYNFIRE4_MINI, build_synfire
from repro.core import Engine
from repro.memory import MemoryLedger
from repro.obs.metrics import Histogram, MetricsRegistry, escape_label_value
from repro.obs.trace import Tracer
from repro.serve import (
    CheckpointError,
    LaneScheduler,
    Session,
    restore_lane,
    restore_session,
    save_session,
)


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Every test starts with an empty, enabled obs plane and leaves the
    process-global state reset for whoever runs next."""
    obs.configure(enabled=True, reset=True)
    yield
    obs.configure(enabled=True, reset=True)


def _mini(policy="fp16", prop="packed", backend="xla"):
    return build_synfire(SYNFIRE4_MINI, policy=policy, propagation=prop,
                         backend=backend)


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------
class TestTracer:
    def test_nested_spans_record_depth_and_duration(self):
        tr = Tracer()
        with tr.span("admit", rung="cap4"):
            with tr.span("step", n_ticks=10):
                pass
        inner, outer = tr.snapshot()  # inner exits (and records) first
        assert (outer.name, outer.depth) == ("admit", 0)
        assert (inner.name, inner.depth) == ("step", 1)
        assert outer.dur_us >= inner.dur_us >= 0.0
        assert outer.cat == inner.cat == "runtime"
        assert outer.args == {"rung": "cap4"}

    def test_span_exposes_dur_s_for_metric_reuse(self):
        tr = Tracer()
        with tr.span("flush") as sp:
            pass
        assert sp.dur_s == tr.snapshot()[0].dur_us / 1e6

    def test_ring_overflow_counts_dropped(self):
        tr = Tracer(capacity=4)
        for i in range(10):
            tr.event("e", i=i)
        assert len(tr) == 4
        assert tr.dropped == 6
        # oldest fell off the back: the retained window is the newest 4
        assert [e.args["i"] for e in tr.snapshot()] == [6, 7, 8, 9]

    def test_span_records_error_tag_on_exception(self):
        tr = Tracer()
        with pytest.raises(ValueError):
            with tr.span("evict"):
                raise ValueError("boom")
        (ev,) = tr.snapshot()
        assert ev.args["error"] == "ValueError"

    def test_jsonl_export(self, tmp_path):
        tr = Tracer(capacity=8)
        tr.event("admit", session="a")
        with tr.span("step"):
            pass
        path = tmp_path / "trace.jsonl"
        tr.to_jsonl(str(path))
        lines = [json.loads(line) for line in path.read_text().splitlines()]
        assert lines[0]["meta"]["retained"] == 2
        assert lines[0]["meta"]["capacity"] == 8
        assert [ln["name"] for ln in lines[1:]] == ["admit", "step"]
        assert lines[1]["ph"] == "i" and lines[2]["ph"] == "X"

    def test_chrome_export_is_loadable_trace_json(self, tmp_path):
        tr = Tracer()
        tr.event("route", fingerprint="abc")
        with tr.span("rung_migrate", from_rung=8, to_rung=64):
            pass
        path = tmp_path / "trace.chrome.json"
        tr.to_chrome(str(path))
        doc = json.loads(path.read_text())
        events = doc["traceEvents"]
        assert events[0]["ph"] == "M"  # process_name metadata first
        by_name = {e["name"]: e for e in events[1:]}
        assert by_name["route"]["ph"] == "i"
        assert by_name["route"]["s"] == "t"
        assert by_name["rung_migrate"]["ph"] == "X"
        assert by_name["rung_migrate"]["dur"] >= 0
        assert all({"ts", "pid", "tid"} <= set(e) for e in events[1:])


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
class TestMetrics:
    def test_histogram_bucketing_le_semantics(self):
        h = Histogram("h", buckets=(1.0, 2.0, 5.0))
        for v in (0.5, 1.0, 1.5, 7.0):
            h.observe(v)
        (counts, total_sum, total) = h.series()[()]
        # 0.5 and 1.0 land in le=1; 1.5 in le=2; 7.0 in +Inf
        assert counts == [2, 1, 0, 1]
        assert total == 4 and total_sum == pytest.approx(10.0)
        assert h.quantile(0.5) == pytest.approx(1.0)
        assert h.quantile(1.0) == pytest.approx(5.0)  # +Inf -> last edge
        assert Histogram("e", buckets=(1.0,)).quantile(0.5) is None

    def test_histogram_merged_quantile_across_series(self):
        h = Histogram("h", buckets=(1.0, 10.0))
        h.observe(0.5, rung="a")
        h.observe(9.0, rung="b")
        assert h.quantile(1.0, {"rung": "a"}) == pytest.approx(0.5)
        assert h.quantile(1.0) == pytest.approx(9.0)  # labels=None merges

    def test_prometheus_cumulative_buckets_and_headers(self):
        reg = MetricsRegistry()
        h = reg.histogram("repro_serve_chunk_latency_ms")
        for v in (0.4, 3.0, 9999.0):
            h.observe(v, rung="cap4")
        text = reg.to_prometheus()
        assert "# HELP repro_serve_chunk_latency_ms " in text
        assert "# TYPE repro_serve_chunk_latency_ms histogram" in text
        assert ('repro_serve_chunk_latency_ms_bucket'
                '{rung="cap4",le="0.5"} 1') in text
        assert ('repro_serve_chunk_latency_ms_bucket'
                '{rung="cap4",le="5"} 2') in text
        assert ('repro_serve_chunk_latency_ms_bucket'
                '{rung="cap4",le="+Inf"} 3') in text
        assert 'repro_serve_chunk_latency_ms_count{rung="cap4"} 3' in text

    def test_prometheus_label_value_escaping(self):
        assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
        reg = MetricsRegistry()
        reg.counter("c_total").inc(path='tmp\\x "y"\nz')
        line = next(ln for ln in reg.to_prometheus().splitlines()
                    if ln.startswith("c_total{"))
        assert line == 'c_total{path="tmp\\\\x \\"y\\"\\nz"} 1'

    def test_counter_rejects_negative_and_kind_clash(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.counter("c").inc(-1.0)
        with pytest.raises(ValueError):
            reg.gauge("c")  # name already registered as a counter

    def test_gauge_clear_where_subset(self):
        g = MetricsRegistry().gauge("g")
        g.set(1.0, ledger="a", rung="r1")
        g.set(2.0, ledger="a", rung="r2")
        g.set(3.0, ledger="b", rung="r1")
        g.clear_where(ledger="a")
        assert list(g.series().values()) == [3.0]

    def test_snapshot_is_json_safe_with_quantiles(self):
        reg = MetricsRegistry()
        reg.histogram("repro_serve_us_per_tick").observe(30.0, rung="x")
        snap = json.loads(reg.to_json())
        (series,) = snap["repro_serve_us_per_tick"]["series"]
        assert series["count"] == 1
        assert 25.0 <= series["p95"] <= 50.0


# ---------------------------------------------------------------------------
# facade: enable/disable, dispatch classification
# ---------------------------------------------------------------------------
class TestFacade:
    def test_disabled_records_nothing(self):
        obs.configure(enabled=False)
        with obs.span("step") as sp:
            assert sp is None
        obs.event("admit")
        obs.inc("repro_serve_admits_total")
        obs.observe("repro_serve_us_per_tick", 1.0)
        obs.gauge("repro_serve_lane_occupancy", 1.0)
        assert len(obs.tracer()) == 0
        assert obs.registry().get("repro_serve_admits_total") is None

    def test_compile_then_cache_hit_classification(self):
        jax.clear_caches()
        eng = Engine(_mini())
        eng.run(17)  # unusual static tick count -> fresh compile
        reg = obs.registry()
        compiles = reg.counter("repro_compiles_total").value(site="dispatch")
        assert compiles >= 1
        eng.run(17)  # same entry -> served from the jit cache
        assert reg.counter("repro_compiles_total").value(
            site="dispatch") == compiles
        events = [e for e in obs.tracer().snapshot() if e.name == "compile"]
        assert sum(e.args["site"] == "dispatch" for e in events) == compiles
        assert all(e.args["secs"] > 0 for e in events)
        assert reg.get("repro_jit_cache_hits_total") is None
        assert reg.counter("repro_engine_ticks_total").value() == 34.0

    def test_forced_compile_is_filed_under_the_enclosing_span(self):
        x = np.arange(5, dtype=np.float32)
        with obs.span("dispatch"):
            with obs.span("custom_site"):
                jax.jit(lambda v: v * 3.0 - 1.0)(x).block_until_ready()
        (ev,) = [e for e in obs.tracer().snapshot() if e.name == "compile"]
        assert ev.args["site"] == "custom_site"
        assert obs.registry().counter("repro_compiles_total").value(
            site="custom_site") == 1.0
        obs.configure(enabled=False)
        jax.jit(lambda v: v * 5.0)(x).block_until_ready()
        assert obs.registry().counter("repro_compiles_total").value(
            site="none") == 0.0

    def test_span_lands_on_the_profiler_host_plane(self, tmp_path):
        from jax.profiler import ProfileData

        with jax.profiler.trace(str(tmp_path)):
            with obs.span("flush", rung="cap4"):
                with obs.span("read"):
                    jax.numpy.ones(8).block_until_ready()
        (path,) = tmp_path.glob("**/*.xplane.pb")
        host = [e for p in ProfileData.from_file(str(path)).planes
                if p.name.startswith("/host:")
                for line in p.lines for e in line.events]
        by_name = {e.name: e for e in host}
        assert {"repro.flush", "repro.read"} <= set(by_name)
        assert dict(by_name["repro.flush"].stats)["rung"] == "cap4"
        flush, read = by_name["repro.flush"], by_name["repro.read"]
        assert flush.start_ns <= read.start_ns
        assert (read.start_ns + read.duration_ns
                <= flush.start_ns + flush.duration_ns)
        # Program spans never take a bare name a caller's annotation uses.
        assert "flush" not in by_name and "read" not in by_name
        # The ring recorded both spans as well.
        assert [e.name for e in obs.tracer().snapshot()
                if e.ph == "X"] == ["read", "flush"]

    def test_env_var_default(self, monkeypatch):
        from repro.obs import _env_enabled
        monkeypatch.delenv("REPRO_OBS", raising=False)
        assert _env_enabled()
        monkeypatch.setenv("REPRO_OBS", "0")
        assert not _env_enabled()
        monkeypatch.setenv("REPRO_OBS", "off")
        assert not _env_enabled()


# ---------------------------------------------------------------------------
# bitwise parity: obs on/off must not touch device results
# ---------------------------------------------------------------------------
def _leaf_bytes(tree):
    out = []
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "dtype") and jax.numpy.issubdtype(
                leaf.dtype, jax.dtypes.prng_key):
            leaf = jax.random.key_data(leaf)
        out.append(np.asarray(leaf).tobytes())
    return out


def _cell_outputs(policy, prop, backend, enabled):
    """Raster + final state + flushed serve telemetry of one fixed
    workload under the given obs setting, everything reduced to bytes."""
    obs.configure(enabled=enabled, reset=True)
    net = build_synfire(SYNFIRE4_MINI, policy=policy, propagation=prop,
                        backend=backend)
    eng = Engine(net)
    final, out = eng.run(120, gen_base=jax.random.key(5), record="both")
    sched = LaneScheduler(net, 2)
    sched.admit("a", seed=1)
    sched.admit("b", seed=2)
    sched.step(40)
    sched.step(40)
    flushed = sched.flush_all()
    sched.close()
    return {
        "raster": np.asarray(out["spikes"]).tobytes(),
        "telemetry": {k: np.asarray(v).tobytes()
                      for k, v in out["telemetry"].items()},
        "state": _leaf_bytes(final),
        "weights": _leaf_bytes(final.weights),
        "flushed": {sid: {k: np.asarray(v).tobytes()
                          for k, v in f.items()}
                    for sid, f in flushed.items()},
    }


def _assert_parity(policy, prop, backend):
    on = _cell_outputs(policy, prop, backend, enabled=True)
    off = _cell_outputs(policy, prop, backend, enabled=False)
    assert on == off, (
        f"obs on/off changed device results for "
        f"({prop}/{backend}/{policy})")


class TestBitwiseParity:
    def test_mini_cell_fast(self):
        _assert_parity("fp16", "packed", "xla")

    @pytest.mark.slow
    @pytest.mark.parametrize("prop", ["packed", "sparse", "auto"])
    @pytest.mark.parametrize("backend", ["xla", "fused"])
    @pytest.mark.parametrize("policy", ["fp32", "fp16"])
    def test_full_matrix(self, prop, backend, policy):
        _assert_parity(policy, prop, backend)


# ---------------------------------------------------------------------------
# serve instrumentation lands in the registry/trace
# ---------------------------------------------------------------------------
class TestServeInstrumentation:
    def test_scheduler_emits_counters_gauges_histograms(self):
        net = _mini()
        sched = LaneScheduler(net, 4)
        sched.admit("a", seed=1)
        sched.admit("b", seed=2)
        sched.step(40)
        sched.evict("a")
        reg = obs.registry()
        assert reg.counter("repro_serve_admits_total").value(
            rung="cap4") == 2.0
        assert reg.counter("repro_serve_evicts_total").value(
            rung="cap4") == 1.0
        assert reg.gauge("repro_serve_lane_occupancy").value(
            rung="cap4") == 1.0
        assert reg.gauge("repro_serve_lane_capacity").value(
            rung="cap4") == 4.0
        assert reg.counter("repro_serve_ticks_total").value(
            rung="cap4") == 80.0  # 40 ticks x 2 occupied lanes
        h = reg.histogram("repro_serve_us_per_tick")
        assert h.count(scope="scheduler", rung="cap4") == 1
        names = [e.name for e in obs.tracer().snapshot()]
        for expected in ("admit", "step", "evict"):
            assert expected in names
        sched.close()
        # close() drops the rung's occupancy/capacity gauge series
        assert reg.gauge("repro_serve_lane_occupancy").value(
            rung="cap4") is None

    def test_rung_bytes_gauge_tracks_ledger(self):
        net = _mini()
        rungs = net.ledger.serve_rung_bytes()
        sched = LaneScheduler(net, 2, ledger_key="rungtest")
        g = obs.registry().gauge("repro_serve_rung_bytes")
        live = net.ledger.serve_rung_bytes()["rungtest"]
        assert g.value(ledger=net.ledger.name, rung="rungtest") == live
        sched.close()
        assert g.value(ledger=net.ledger.name, rung="rungtest") is None
        assert net.ledger.serve_rung_bytes() == rungs

    def test_pool_migration_spans_and_counter(self):
        from repro.serve.pool import ServePool

        net = _mini()
        pool = ServePool(rungs=(2, 4))
        for i in range(3):  # third admit overflows rung 2 -> migrate up
            pool.admit(net, f"s{i}", seed=i)
        reg = obs.registry()
        assert reg.counter("repro_rung_migrations_total").value(
            direction="up") == 1.0
        assert reg.counter("repro_pool_routes_total").series()
        names = [e.name for e in obs.tracer().snapshot()]
        for expected in ("route", "rung_build", "rung_migrate",
                         "export", "restore"):
            assert expected in names, f"missing {expected} in trace"

    def test_session_chunk_histogram(self):
        sess = Session.create(_mini(), seed=3)
        sess.run(40)
        h = obs.registry().histogram("repro_serve_chunk_latency_ms")
        assert h.count(scope="session", rung="solo") == 0  # not yet flushed
        sess.flush()
        assert h.count(scope="session", rung="solo") == 1
        sess.run(40)
        sess.run(40)  # the unflushed chunk before it goes untimed
        sess.flush()
        assert h.count(scope="session", rung="solo") == 2

    def test_chunk_ends_once_outputs_are_ready(self):
        net = _mini()
        sched = LaneScheduler(net, 2)
        sched.admit("a", seed=1)
        sched.step(40)  # compiles: its chunk would pass every bucket edge
        sched.flush("a")
        obs.configure(reset=True)
        sched.step(40)
        jax.block_until_ready(sched.states)
        ready_us = obs.tracer().now_us()
        sched.flush("a")
        sched.flush("a")  # the chunk is closed once
        events = obs.tracer().snapshot()
        (chunk,) = [e for e in events if e.name == "chunk"]
        (step,) = [e for e in events if e.name == "step"]
        (dispatch,) = [e for e in events if e.name == "dispatch"]
        assert chunk.args == {"n_ticks": 40, "scope": "scheduler",
                              "rung": "cap2"}
        assert chunk.ts_us <= step.ts_us <= dispatch.ts_us
        assert chunk.ts_us + chunk.dur_us >= ready_us
        assert "ready" not in [e.name for e in events]  # it was ready
        h = obs.registry().histogram("repro_serve_us_per_tick")
        assert h.count(scope="scheduler", rung="cap2") == 1
        check = obs.health.measured_serve_check(obs.registry())
        assert check.value == pytest.approx(chunk.dur_us / 40, rel=1e-3)
        assert "completed serve chunk" in check.detail
        sched.close()

    def test_chunk_waits_inside_ready_when_outputs_are_late(self):
        class Late:  # an output the device finishes 20 ms after the flush
            ready_us = None

            def is_ready(self):
                return False

            def block_until_ready(self):
                time.sleep(0.02)
                Late.ready_us = obs.tracer().now_us()
                return self

        timer = obs.ChunkTimer(scope="session", rung="solo")
        timer.start(10)
        timer.dispatched(Late())
        with obs.span("flush"):
            timer.close()
        events = {e.name: e for e in obs.tracer().snapshot()}
        chunk, ready = events["chunk"], events["ready"]
        assert chunk.ts_us + chunk.dur_us >= Late.ready_us
        assert ready.dur_us >= 20_000 * 0.9
        assert ready.ts_us >= chunk.ts_us
        assert obs.registry().histogram("repro_serve_us_per_tick").count(
            scope="session", rung="solo") == 1

    def test_flush_counts_two_host_reads_with_default_monitors(self):
        sess = Session.create(_mini(), seed=4)
        for _ in range(3):
            sess.run(20)
            sess.flush()
        reads = obs.registry().counter("repro_flush_host_reads_total")
        assert reads.value() == 6.0
        events = obs.tracer().snapshot()
        flushes = [e for e in events if e.name == "flush"]
        inside = [e for e in events if e.name == "read"
                  and any(f.ts_us <= e.ts_us and e.ts_us + e.dur_us
                          <= f.ts_us + f.dur_us for f in flushes)]
        assert len(flushes) == 3 and len(inside) == 6

# ---------------------------------------------------------------------------
# health snapshots vs the paper's budgets
# ---------------------------------------------------------------------------
class TestHealth:
    def test_mini_realtime_passes_on_m33(self):
        snap = obs.health.health_snapshot(_mini())
        by_name = {c["name"]: c for c in snap["checks"]}
        rt = by_name["realtime_vs_rp2350_m33"]
        assert rt["status"] == "pass" and rt["value"] >= 1.0
        assert by_name["ledger_budget"]["status"] == "pass"
        assert snap["status"] == "pass"
        assert snap["hardware"] == "rp2350_m33"

    def test_synfire4_misses_realtime_on_m33(self):
        from repro.obs.health import realtime_check

        # 1200 neurons at Synfire4's fan-in cannot hit the 1 ms tick on
        # the M33 roofline — the paper's point about the mini config.
        check = realtime_check(n_neurons=1200, fanin=120.0)
        assert check.status == "fail" and check.value < 1.0

    def test_oversized_rung_fails_mcu_budget(self):
        ledger = MemoryLedger(budget=None, name="test")
        big = jax.ShapeDtypeStruct((9 * 1024 * 1024 // 4 + 1024, 2),
                                   jax.numpy.float32)  # ~9 MB > 8.477 MB
        ledger.register("serve.lanes.rungbig", big)
        snap = obs.health.health_snapshot(ledger=ledger)
        by_name = {c["name"]: c for c in snap["checks"]}
        assert by_name["rung_bytes[rungbig]"]["status"] == "fail"
        assert snap["status"] == "fail"
        assert snap["mcu_budget_bytes"] == int(8.477 * 1024**2)

    def test_measured_serve_check_from_live_histogram(self):
        h = obs.registry().histogram("repro_serve_us_per_tick")
        for _ in range(20):
            h.observe(40.0, scope="scheduler", rung="cap4")
        snap = obs.health.health_snapshot()
        by_name = {c["name"]: c for c in snap["checks"]}
        assert by_name["serve_realtime_measured"]["status"] == "pass"
        for _ in range(3):  # push >5% of observations past the bar
            h.observe(50_000.0, scope="scheduler", rung="cap4")
        snap = obs.health.health_snapshot()
        by_name = {c["name"]: c for c in snap["checks"]}
        assert by_name["serve_realtime_measured"]["status"] == "fail"

    def test_registry_rung_gauges_feed_health_without_a_net(self):
        obs.gauge("repro_serve_rung_bytes", 9_500_000.0,
                  ledger="x", rung="rung512")
        snap = obs.health.health_snapshot()
        by_name = {c["name"]: c for c in snap["checks"]}
        assert by_name["rung_bytes[rung512]"]["status"] == "fail"


# ---------------------------------------------------------------------------
# typed checkpoint failures
# ---------------------------------------------------------------------------
class TestCheckpointErrors:
    def _session(self):
        sess = Session.create(_mini(), seed=9)
        sess.run(40)
        return sess

    def test_roundtrip_still_works_and_counts(self, tmp_path):
        sess = self._session()
        save_session(str(tmp_path), sess)
        restored = restore_session(str(tmp_path), sess.engine)
        assert restored.ticks == sess.ticks
        reg = obs.registry()
        assert reg.counter("repro_checkpoint_saves_total").value(
            kind="session") == 1.0
        assert reg.counter("repro_checkpoint_restores_total").value(
            status="ok") == 1.0

    def test_truncated_file_raises_typed_error(self, tmp_path):
        sess = self._session()
        path = save_session(str(tmp_path), sess)
        with open(path, "wb") as f:
            f.write(b"definitely not an npz archive")
        with pytest.raises(CheckpointError) as ei:
            restore_session(str(tmp_path), sess.engine)
        assert ei.value.path == path
        assert "corrupt or truncated" in str(ei.value)
        errs = [e for e in obs.tracer().snapshot()
                if e.name == "checkpoint_restore"
                and e.args.get("status") == "error"]
        assert errs and errs[0].args["path"] == path
        assert obs.registry().counter(
            "repro_checkpoint_restores_total").value(status="error") == 1.0

    def test_unstamped_checkpoint_rejected(self, tmp_path):
        from repro.checkpoint import ckpt

        sess = self._session()
        ckpt.save(str(tmp_path), 7, {"gen_key": np.zeros(1, np.uint32)})
        with pytest.raises(CheckpointError) as ei:
            restore_session(str(tmp_path), sess.engine, step=7)
        assert ei.value.key == "fmt"
        assert "format stamp" in str(ei.value)

    def test_wrong_format_version_rejected(self, tmp_path):
        from repro.checkpoint import ckpt

        sess = self._session()
        ckpt.save(str(tmp_path), 7, {"fmt": np.int32(99)})
        with pytest.raises(CheckpointError) as ei:
            restore_lane(str(tmp_path), sess.engine, step=7)
        assert ei.value.key == "fmt"
        assert "format 99" in str(ei.value)

    def test_missing_payload_key_is_named(self, tmp_path):
        from repro.checkpoint import ckpt

        sess = self._session()
        ckpt.save(str(tmp_path), 7, {"fmt": np.int32(1),
                                     "ticks": np.int32(0)})
        with pytest.raises(CheckpointError) as ei:
            restore_session(str(tmp_path), sess.engine, step=7)
        assert "missing payload key" in str(ei.value)
        assert ei.value.key  # names the first absent leaf

    def test_missing_file_stays_file_not_found(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            restore_session(str(tmp_path), Engine(_mini()))
