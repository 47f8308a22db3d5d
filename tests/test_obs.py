"""Observability layer: span bus, exporters, metrics registry, drift.

The load-bearing assertions: (1) one served slice captured with tracing
on yields a single Chrome trace tying the serve lifecycle (submit → wave
→ done) to the core spans underneath it (plan, compile, per-mode solves
with solver/backend/rank attrs); (2) a deliberately mis-calibrated
CostModel is flagged STALE by the drift monitor with a ``repro.tune``
repair recommendation; (3) the serve TraceWriter raises after ``close()``
instead of silently reopening its file.
"""

import json
import threading
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import TuckerConfig
from repro.core.api import _SWEEP_CACHE, plan as make_plan
from repro.core.cost_model import CostModel
from repro.obs import drift as drift_mod
from repro.obs import export as export_mod
from repro.obs import metrics as obs_metrics
from repro.obs.__main__ import main as obs_cli
from repro.obs.drift import DriftMonitor, MemoryWatch
from repro.serve import BucketPolicy, TuckerService
from repro.serve.metrics import LatencyWindow, TraceWriter

SHAPE = (16, 18, 20)
RANKS = (4, 4, 4)


@pytest.fixture(autouse=True)
def _obs_disabled_after():
    """Tracing must never leak into other test modules."""
    yield
    obs.disable()


def _x(shape=SHAPE, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape), jnp.float32)


# ---------------------------------------------------------------------------
# span bus
# ---------------------------------------------------------------------------

class TestTraceBus:
    def test_disabled_is_default_and_free(self):
        assert not obs.enabled()
        buf = obs.EventBuffer()
        obs.add_sink(buf)
        try:
            obs.event("cache", status="hit")
            with obs.span("execute", backend="matfree"):
                pass
            assert len(buf) == 0
        finally:
            obs.remove_sink(buf)

    def test_event_shape_and_span_nesting(self):
        with obs.capture() as buf:
            with obs.span("outer", a=1) as sp:
                obs.event("cache", status="miss")
                with obs.span("inner"):
                    pass
                sp.set(late=True)
        evs = buf.events()
        kinds = [(e["kind"], e.get("name")) for e in evs]
        # inner span exits first, point event lands before both
        assert kinds == [("cache", None), ("span", "inner"),
                         ("span", "outer")]
        cache, inner, outer = evs
        for e in evs:
            assert {"t", "kind", "pid", "tid"} <= e.keys()
        assert cache["parent"] == outer["span"]
        assert inner["parent"] == outer["span"]
        assert outer["parent"] is None
        assert outer["late"] is True and outer["a"] == 1
        assert outer["dur_s"] >= inner["dur_s"] >= 0.0

    def test_span_records_exception_and_unwinds(self):
        with obs.capture() as buf:
            with pytest.raises(RuntimeError):
                with obs.span("boom"):
                    raise RuntimeError("solver exploded")
            with obs.span("after"):
                pass
        boom, after = buf.events()
        assert "solver exploded" in boom["error"]
        assert after["parent"] is None  # contextvar fully unwound

    def test_capture_restores_enabled_state(self):
        assert not obs.enabled()
        with obs.capture():
            assert obs.enabled()
            with obs.capture():    # nested: inner exit must not disable
                pass
            assert obs.enabled()
        assert not obs.enabled()

    def test_broken_sink_warns_and_event_survives(self):
        def bad(evt):
            raise RuntimeError("sink down")
        with obs.capture() as buf:
            obs.add_sink(bad)
            try:
                with pytest.warns(RuntimeWarning, match="sink"):
                    obs.event("submit", rid=1)
            finally:
                obs.remove_sink(bad)
        assert [e["kind"] for e in buf.events()] == ["submit"]

    def test_event_buffer_is_a_ring(self):
        buf = obs.EventBuffer(maxlen=3)
        for i in range(5):
            buf({"kind": "e", "i": i})
        assert [e["i"] for e in buf.events()] == [2, 3, 4]
        buf.clear()
        assert len(buf) == 0


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

class TestExport:
    EVENTS = [
        {"t": 10.0, "kind": "span", "name": "solve", "dur_s": 0.5,
         "span": 1, "parent": None, "pid": 7, "tid": 9, "mode": 0,
         "solver": "eig"},
        {"t": 12.0, "kind": "wave", "wall_s": 2.0, "bucket": "16x16x16",
         "n": 4},
        {"t": 13.0, "kind": "submit", "rid": 3},
    ]

    def test_to_chrome_phases(self):
        doc = export_mod.to_chrome(self.EVENTS)
        assert doc["displayTimeUnit"] == "ms"
        sp, wave, sub = doc["traceEvents"]
        assert sp == {"name": "solve", "cat": "atucker", "ph": "X",
                      "ts": 10.0e6, "dur": 0.5e6, "pid": 7, "tid": 9,
                      "args": {"span": 1, "parent": None, "mode": 0,
                               "solver": "eig"}}
        # wave slices are rewound by wall_s so they sit where work ran
        assert wave["ph"] == "X" and wave["ts"] == 10.0e6 \
            and wave["dur"] == 2.0e6 and wave["name"] == "wave 16x16x16"
        assert sub["ph"] == "i" and sub["cat"] == "serve"

    def test_jsonl_round_trip_with_repr_fallback(self, tmp_path):
        events = [*self.EVENTS,
                  {"t": 14.0, "kind": "done", "shape": (16, 16)}]
        path = tmp_path / "ev.jsonl"
        assert export_mod.write_jsonl(events, path) == 4
        path.write_text(path.read_text() + "not json\n\n")
        back = export_mod.read_jsonl(path)
        assert len(back) == 4  # malformed + blank lines skipped
        assert back[0]["name"] == "solve"
        assert back[3]["shape"] == [16, 16] or \
            isinstance(back[3]["shape"], str)

    def test_chrome_args_jsonable(self):
        doc = export_mod.to_chrome(
            [{"t": 1.0, "kind": "span", "name": "s", "dur_s": 0.1,
              "weird": object()}])
        json.dumps(doc)  # must not raise


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_counter_gauge_histogram_render(self):
        reg = obs_metrics.MetricsRegistry()
        c = reg.counter("atucker_requests_total", "requests")
        c.inc(service="t")
        c.inc(2, service="t")
        with pytest.raises(ValueError):
            c.inc(-1, service="t")
        g = reg.gauge("atucker_queue_depth")
        g.set(5, bucket="a")
        g.inc(bucket="a")
        h = reg.histogram("atucker_latency_s", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v, arm="svc")
        text = reg.render()
        assert "# TYPE atucker_requests_total counter" in text
        assert 'atucker_requests_total{service="t"} 3' in text
        assert 'atucker_queue_depth{bucket="a"} 6' in text
        assert '# TYPE atucker_latency_s histogram' in text
        assert 'atucker_latency_s_bucket{arm="svc",le="0.1"} 1' in text
        assert 'atucker_latency_s_bucket{arm="svc",le="1"} 2' in text
        assert 'atucker_latency_s_bucket{arm="svc",le="+Inf"} 3' in text
        assert 'atucker_latency_s_count{arm="svc"} 3' in text

    def test_registry_idempotent_and_type_guarded(self):
        reg = obs_metrics.MetricsRegistry()
        assert reg.counter("a") is reg.counter("a")
        with pytest.raises(ValueError):
            reg.gauge("a")

    def test_quantile_from_histogram(self):
        reg = obs_metrics.MetricsRegistry()
        h = reg.histogram("lat", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 1.6, 3.0):
            h.observe(v)
        q = obs_metrics.quantile_from_histogram(h, 50.0)
        assert 1.0 <= q <= 2.0

    def test_absorb_service_stats(self):
        svc = TuckerService(policy=BucketPolicy(grid=8, wave_slots=2))
        cfg = TuckerConfig(ranks=RANKS, methods="eig")
        svc.submit(_x(), cfg)
        svc.drain()
        stats = svc.stats()
        svc.stop()
        reg = obs_metrics.MetricsRegistry()
        obs_metrics.absorb_service_stats(stats, reg)
        text = reg.render()
        assert 'atucker_serve_submitted{service="tucker"} 1' in text
        assert "atucker_serve_latency_ms" in text
        assert "atucker_bucket_completed" in text


# ---------------------------------------------------------------------------
# drift monitor
# ---------------------------------------------------------------------------

class TestDrift:
    def test_centered_cell_is_not_stale(self):
        m = DriftMonitor(min_samples=5)
        rng = np.random.default_rng(0)
        for _ in range(50):
            actual = 0.01 * float(np.exp(rng.normal(0.0, 0.05)))
            m.observe(platform="cpu", backend="matfree", solver="eig",
                      predicted_s=0.01, actual_s=actual)
        rep = m.report()
        assert len(rep["cells"]) == 1
        assert not rep["cells"][0]["stale"]
        assert rep["recommendations"] == []

    def test_consistent_drift_is_stale_with_tune_recommendation(self):
        m = DriftMonitor(min_samples=5)
        rng = np.random.default_rng(1)
        for _ in range(20):   # ~3x slower than predicted, modest noise
            actual = 0.03 * float(np.exp(rng.normal(0.0, 0.1)))
            m.observe(platform="cpu", backend="matfree", solver="eig",
                      predicted_s=0.01, actual_s=actual)
        rep = m.report()
        (cell,) = rep["cells"]
        assert cell["stale"] and cell["ratio"] == pytest.approx(3.0, rel=0.3)
        cmds = [r["command"] for r in rep["recommendations"]]
        assert any("repro.tune calibrate --platform cpu "
                   "--backend matfree" in c for c in cmds)
        assert any("repro.tune train" in c for c in cmds)

    def test_small_consistent_bias_tolerated(self):
        # hugely significant z but inside the tolerance band: not stale
        m = DriftMonitor(min_samples=5, tolerance=1.5)
        rng = np.random.default_rng(2)
        for _ in range(100):
            actual = 0.012 * float(np.exp(rng.normal(0.0, 0.01)))
            m.observe(platform="cpu", backend="matfree", solver="eig",
                      predicted_s=0.01, actual_s=actual)
        (cell,) = m.report()["cells"]
        assert abs(cell["z"]) > m.z_threshold
        assert not cell["stale"]

    def test_nonpositive_pairs_ignored_and_z_clamped(self):
        m = DriftMonitor()
        m.observe(platform="cpu", backend="matfree", solver="eig",
                  predicted_s=0.0, actual_s=1.0)
        m.observe(platform="cpu", backend="matfree", solver="eig",
                  predicted_s=1.0, actual_s=0.0)
        assert m.report()["cells"] == []
        for _ in range(10):  # identical ratios: zero variance, clamped z
            m.observe(platform="cpu", backend="matfree", solver="eig",
                      predicted_s=0.01, actual_s=0.1)
        (cell,) = m.report()["cells"]
        assert cell["z"] == 99.0 and cell["stale"]

    def test_observe_traces_skips_fused_steps(self):
        class T:
            def __init__(self, s):
                self.method, self.predicted_s, self.seconds = "eig", 0.01, s
        m = DriftMonitor()
        n = m.observe_traces([T(0.02), T(0.0)], platform="cpu",
                             backend="matfree")
        assert n == 1

    def test_memory_drift_recommendation(self):
        m = DriftMonitor(tolerance=1.5)
        m.observe_memory(backend="matfree", modeled_bytes=100,
                         observed_bytes=400)
        rep = m.report()
        assert rep["memory"]["matfree"]["ratio"] == pytest.approx(4.0)
        assert any(r["cell"][0] == "memory"
                   for r in rep["recommendations"])

    def test_summary_shape(self):
        m = DriftMonitor()
        m.observe(platform="cpu", backend="matfree", solver="eig",
                  predicted_s=0.01, actual_s=0.02)
        s = m.summary()
        assert s["cells"] == 1 and s["observations"] == 1
        assert s["stale"] == []

    def test_memory_watch_sees_allocations(self):
        with MemoryWatch(interval_s=0.001) as mw:
            arrs = [jnp.zeros((128, 128), jnp.float32) for _ in range(4)]
            jax.block_until_ready(arrs[-1])
            time.sleep(0.05)
        assert mw.high_water >= 4 * 128 * 128 * 4


class TestMiscalibratedCostModel:
    def test_execute_flags_bogus_calibration(self):
        """A deliberately absurd calibrated CostModel (1 second per FLOP)
        stamps absurd predicted_s on the plan; a handful of recorded
        executes must flag the (platform, backend, eig) cell stale and
        recommend a repro.tune recalibration."""
        class BogusSelector:
            cost_model = CostModel(eig_scale=1.0, source="calibrated")

        drift_mod.MONITOR.reset()
        try:
            cfg = TuckerConfig(ranks=RANKS, methods="eig")
            p = make_plan(SHAPE, jnp.float32, cfg,
                          selector=BogusSelector())
            assert p.total_predicted_s > 1e3   # absurd by construction
            x = _x()
            for _ in range(drift_mod.MONITOR.min_samples):
                p.execute(x, record=True)
            rep = drift_mod.MONITOR.report()
            platform = jax.default_backend()
            stale = {(c["platform"], c["backend"], c["solver"])
                     for c in rep["stale"]}
            assert (platform, "matfree", "eig") in stale
            assert any("repro.tune calibrate" in r["command"]
                       for r in rep["recommendations"])
            (cell,) = [c for c in rep["cells"]
                       if c["solver"] == "eig"]
            assert cell["ratio"] < 1e-3   # wildly over-predicted
            assert cell["sources"].get("execute", 0) >= \
                drift_mod.MONITOR.min_samples
        finally:
            drift_mod.MONITOR.reset()


# ---------------------------------------------------------------------------
# core instrumentation
# ---------------------------------------------------------------------------

class TestCoreSpans:
    def test_plan_and_execute_spans(self):
        cfg = TuckerConfig(ranks=RANKS, methods="eig")
        x = _x()
        with obs.capture() as buf:
            _SWEEP_CACHE.clear()
            p = make_plan(SHAPE, jnp.float32, cfg)
            p.execute(x)
            p.execute(x)
        spans = {e["name"]: e for e in obs.iter_spans(buf.events())}
        assert {"plan", "compile", "execute"} <= spans.keys()
        assert spans["plan"]["n_steps"] == 3
        assert spans["plan"]["backend"] == "matfree"
        assert spans["execute"]["shape"] == list(SHAPE)
        cache = [e for e in buf.events() if e["kind"] == "cache"]
        assert [e["status"] for e in cache] == ["miss"]

    def test_recorded_execute_emits_solve_spans_with_attrs(self):
        cfg = TuckerConfig(ranks=RANKS, methods="eig")
        p = make_plan(SHAPE, jnp.float32, cfg)
        with obs.capture() as buf:
            p.execute(_x(), record=True)
        solves = [e for e in obs.iter_spans(buf.events())
                  if e["name"] == "solve"]
        assert [e["mode"] for e in solves] == [0, 1, 2]
        for e in solves:
            assert e["solver"] == "eig" and e["backend"] == "matfree"
            assert e["rank"] == 4 and e["dur_s"] > 0.0
            assert e["platform"] == jax.default_backend()

    def test_disabled_span_is_the_shared_null_span(self):
        from repro.obs import trace as trace_mod
        assert not obs.enabled()
        assert obs.span("sketch", mode=0) is trace_mod._NULL_SPAN
        with obs.capture():
            assert obs.span("sketch") is not trace_mod._NULL_SPAN
        assert obs.span("sketch") is trace_mod._NULL_SPAN

    def test_importing_obs_and_enabling_it_loads_no_jax(self):
        """The bus is stdlib only at import; jax arrives with the first
        enabled span (its profiler annotation), not before."""
        import subprocess
        import sys
        code = ("import sys; from repro import obs; "
                "assert 'jax' not in sys.modules, 'jax at import'; "
                "obs.enable(); obs.event('x'); "
                "assert 'jax' not in sys.modules, 'jax on an event'; "
                "sp = obs.span('s'); "
                "assert 'jax' not in sys.modules, 'jax on a span object'")
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr

    def test_every_span_is_live(self):
        """No span is written after the fact: each carries its own id, so
        the DP searches, solves and sketches can hold children."""
        cfg = TuckerConfig(ranks=RANKS, methods="eig", mode_order="opt")
        with obs.capture() as buf:
            p = make_plan(SHAPE, jnp.float32, cfg)
            p.execute(_x(), record=True)
        spans = list(obs.iter_spans(buf.events()))
        names = {e["name"] for e in spans}
        assert {"plan", "plan.dp_search", "execute", "solve"} <= names
        assert all(isinstance(e.get("span"), int) for e in spans)
        by_id = {e["span"]: e for e in spans}
        (search,) = [e for e in spans if e["name"] == "plan.dp_search"]
        assert by_id[search["parent"]]["name"] == "plan"
        assert search["order"] and search["n_states"] > 0

    def test_adaptive_execute_nests_readbacks_and_marks_the_refine_plan(
            self):
        cfg = TuckerConfig(error_target=0.5)
        x = _x()
        make_plan(SHAPE, jnp.float32, cfg).execute(x)
        with obs.capture() as buf:
            make_plan(SHAPE, jnp.float32, cfg).execute(x)
        spans = list(obs.iter_spans(buf.events()))
        by_id = {e["span"]: e for e in spans}
        sketches = [e for e in spans if e["name"] == "sketch"]
        readbacks = [e for e in spans if e["name"] == "sketch.readback"]
        # one read per width tried, and no other wait
        assert len(readbacks) == sum(e["widths"] for e in sketches)
        for e in readbacks:
            parent = by_id[e["parent"]]
            assert parent["name"] == "sketch"
            assert parent["mode"] == e["mode"]
        for e in sketches:
            kids = [r["dur_s"] for r in readbacks if r["parent"] == e["span"]]
            assert sum(kids) <= e["dur_s"]
        plans = [e for e in spans if e["name"] == "plan"]
        front = [e for e in plans if e["parent"] is None]
        refine = [e for e in plans if e["refine"]]
        assert len(front) == 1 and not front[0]["refine"]
        assert len(refine) == 1
        assert by_id[refine[0]["parent"]]["name"] == "execute"

    def test_fixed_rank_sweep_hlo_carries_step_scopes(self):
        """The compiled sweep names each schedule step and the backend call
        inside it, so a device profile's op name stack says which mode's
        Gram, TTM or solve an op belongs to."""
        from repro.core.api import _make_sweep
        cfg = TuckerConfig(ranks=RANKS, methods="eig")
        p = make_plan(SHAPE, jnp.float32, cfg)
        hlo = _make_sweep(p, batched=False).lower(
            jax.ShapeDtypeStruct(SHAPE, jnp.float32)).compile().as_text()
        for m in range(3):
            assert f"mode{m}.eig/" in hlo
        assert "mode0.eig/jit(eig_solve)/gram/" in hlo
        assert "/ttm/" in hlo and "/solve/" in hlo

    def test_adaptive_execute_emits_sketch_spans(self):
        cfg = TuckerConfig(error_target=0.5)
        p = make_plan(SHAPE, jnp.float32, cfg)
        with obs.capture() as buf:
            p.execute(_x())
        sketches = [e for e in obs.iter_spans(buf.events())
                    if e["name"] == "sketch"]
        assert len(sketches) == 3
        for e in sketches:
            assert e["solver"] == "rand" and e["rank"] >= 1
            assert 0.0 <= e["tail_err"] <= 1.0


# ---------------------------------------------------------------------------
# serve: TraceWriter, LatencyWindow, service wiring
# ---------------------------------------------------------------------------

class TestTraceWriter:
    def test_event_after_close_raises(self, tmp_path):
        path = tmp_path / "trace.jsonl"
        w = TraceWriter(path)
        w.event("submit", rid=1)
        w.close()
        with pytest.raises(ValueError, match="closed"):
            w.event("submit", rid=2)
        # the file did NOT silently reopen/grow
        lines = path.read_text().splitlines()
        assert len(lines) == 1
        w.close()  # idempotent

    def test_close_before_first_event_raises_without_creating_file(
            self, tmp_path):
        path = tmp_path / "never.jsonl"
        w = TraceWriter(path)
        w.close()
        with pytest.raises(ValueError):
            w.event("submit")
        assert not path.exists()

    def test_handle_as_bus_sink(self, tmp_path):
        path = tmp_path / "bus.jsonl"
        w = TraceWriter(path)
        obs.add_sink(w.handle)
        try:
            obs.enable()
            with obs.span("execute", backend="matfree"):
                obs.event("cache", status="miss")
        finally:
            obs.disable()
            obs.remove_sink(w.handle)
            w.close()
        evs = export_mod.read_jsonl(path)
        assert [e["kind"] for e in evs] == ["cache", "span"]
        assert evs[1]["name"] == "execute"


class TestLatencyWindow:
    def test_snapshot_percentiles_and_window_mean(self):
        w = LatencyWindow(maxlen=4)
        for s in (0.010, 0.020, 0.030, 0.040, 0.100):
            w.add(s)           # 0.010 evicted from the window
        snap = w.snapshot_ms()
        assert snap["p50_ms"] == pytest.approx(35.0)
        assert snap["p95_ms"] == pytest.approx(91.0)
        # lifetime mean over all 5; window mean over the surviving 4
        assert snap["mean_ms"] == pytest.approx(40.0)
        assert snap["window_mean_ms"] == pytest.approx(47.5)
        assert w.percentile(50.0) == pytest.approx(0.035)

    def test_empty_window(self):
        snap = LatencyWindow().snapshot_ms()
        assert snap == {"p50_ms": 0.0, "p95_ms": 0.0, "p99_ms": 0.0,
                        "mean_ms": 0.0, "window_mean_ms": 0.0}


class TestServiceObservability:
    def test_stats_exposes_sweep_cache_and_drift(self):
        svc = TuckerService()
        try:
            stats = svc.stats()
            assert {"builds", "hits"} <= stats["sweep_cache"].keys()
            assert {"cells", "observations", "stale"} \
                <= stats["drift"].keys()
        finally:
            svc.stop()

    def test_served_run_spans_share_request_ids(self):
        """Each request's admission and each wave's dispatch and finish are
        spans carrying the request ids, and every completion stamps its own
        queue wait."""
        cfg = TuckerConfig(ranks=RANKS, methods="eig")
        policy = BucketPolicy(grid=8, wave_slots=2)
        with obs.capture() as buf:
            with TuckerService(policy=policy) as svc:
                tickets = [svc.submit(_x(seed=k), cfg) for k in range(3)]
                svc.drain()
        events = buf.events()
        spans = list(obs.iter_spans(events))
        by_id = {e["span"]: e for e in spans}

        def named(name):
            return [e for e in spans if e["name"] == name]

        rids = [t.rid for t in tickets]
        submits = named("serve.submit")
        assert sorted(e["rid"] for e in submits) == rids
        for name in ("serve.validate", "serve.admit"):
            kids = named(name)
            assert len(kids) == 3
            for e in kids:
                parent = by_id[e["parent"]]
                assert parent["name"] == "serve.submit"
                assert parent["rid"] == e["rid"]
        waves = named("serve.wave")
        assert sorted(r for w in waves for r in w["rids"]) == rids
        assert {(w["lanes"], w["filled"], w["route"]) for w in waves} == \
            {(2, 2, "fused"), (1, 1, "fused")}
        for e in named("serve.wave.dispatch"):
            assert by_id[e["parent"]]["name"] == "serve.wave"
            assert by_id[e["parent"]]["rids"] == e["rids"]
        assert sorted(tuple(e["rids"]) for e in named("serve.wave.finish")) \
            == sorted(tuple(w["rids"]) for w in waves)
        done = [e for e in events if e["kind"] == "done"]
        assert sorted(e["rid"] for e in done) == rids
        assert all(0.0 <= e["queue_s"] <= e["latency_s"] for e in done)
        # the JSONL schema operators read keeps its wave events
        assert len([e for e in events if e["kind"] == "wave"]) == 2

    def test_stats_totals_sum_the_bucket_counters(self):
        cfg = TuckerConfig(ranks=RANKS, methods="eig")
        with TuckerService(policy=BucketPolicy(grid=8, wave_slots=2)) as svc:
            for k, shape in enumerate([(16, 16, 16), (16, 16, 16),
                                       (16, 16, 13)]):
                svc.submit(_x(shape, seed=k), cfg)
            svc.drain()
            stats = svc.stats()
        tot = stats["totals"]
        assert set(tot) == {"waves", "lanes", "lanes_filled", "true_elems",
                            "slot_elems", "completed", "padded", "failed"}
        assert tot["completed"] == 3 and tot["failed"] == 0
        assert tot["padded"] == 1 and tot["lanes_filled"] == 3
        assert tot["waves"] == sum(b["waves"]
                                   for b in stats["buckets"].values())
        assert tot["true_elems"] == 16 * 16 * (16 + 16 + 13)
        assert tot["slot_elems"] == 3 * 16 ** 3
        assert stats["pad_waste"] == pytest.approx(
            1 - tot["true_elems"] / tot["slot_elems"], abs=1e-6)

    def test_serve_slice_yields_one_perfetto_trace(self, tmp_path):
        """One traced serve slice ties the whole story together: submit →
        wave → done around plan/compile/execute, with per-mode solve spans
        from a recorded wave — all in a single loadable Chrome trace."""
        cfg = TuckerConfig(ranks=RANKS, methods="eig")
        policy = BucketPolicy(grid=8, wave_slots=2, pad_mode="mask")
        with obs.capture() as buf:
            _SWEEP_CACHE.clear()
            for record in (False, True):
                with TuckerService(policy=policy, record=record) as svc:
                    for seed in range(2):
                        svc.submit(_x(seed=seed), cfg)
                    svc.drain()
        path = tmp_path / "trace.json"
        doc = export_mod.write_chrome(buf.events(), path)
        names = {e["name"].split(" ")[0] for e in doc["traceEvents"]}
        assert {"submit", "wave", "solve", "compile", "plan",
                "execute", "done", "serve.submit", "serve.wave",
                "serve.wave.dispatch", "serve.wave.finish"} <= names
        json.loads(path.read_text())   # loadable
        solves = [e for e in doc["traceEvents"] if e["name"] == "solve"]
        assert all(e["args"]["solver"] == "eig" and "rank" in e["args"]
                   for e in solves)

    def test_concurrent_submit_and_stats(self):
        """Hammer submit() and stats() from threads: no torn reads, no
        exceptions, and the final counters balance exactly."""
        cfg = TuckerConfig(ranks=RANKS, methods="eig")
        svc = TuckerService(policy=BucketPolicy(grid=8, wave_slots=4),
                            max_queue=None)
        svc.start()
        n_threads, per_thread = 4, 8
        errors = []
        snapshots = []
        stop = threading.Event()

        def submitter(tid):
            try:
                for i in range(per_thread):
                    svc.submit(_x(seed=tid * 100 + i), cfg)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        def reader():
            while not stop.is_set():
                s = svc.stats()
                c = s["counters"] if "counters" in s else s
                assert c["submitted"] >= c["requests"] >= 0
                assert c["failed"] == 0 and c["rejected"] == 0
                snapshots.append(c["submitted"])
                time.sleep(0.001)

        readers = [threading.Thread(target=reader) for _ in range(2)]
        writers = [threading.Thread(target=submitter, args=(t,))
                   for t in range(n_threads)]
        for th in readers + writers:
            th.start()
        for th in writers:
            th.join()
        svc.drain()
        stop.set()
        for th in readers:
            th.join()
        stats = svc.stats()
        svc.stop()
        assert not errors
        assert stats["submitted"] == n_threads * per_thread
        assert stats["requests"] == n_threads * per_thread
        assert stats["failed"] == 0
        # monotone non-decreasing submitted counter across reader snapshots
        assert all(a <= b for a, b in zip(snapshots, snapshots[1:]))


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

class TestCli:
    def _events_file(self, tmp_path):
        events = [
            {"t": 1.0 + i, "kind": "span", "name": "solve", "dur_s": 0.03,
             "mode": i % 3, "solver": "eig", "backend": "matfree",
             "platform": "cpu", "predicted_s": 0.01}
            for i in range(6)
        ]
        events.append({"t": 9.0, "kind": "submit", "rid": 1})
        path = tmp_path / "events.jsonl"
        export_mod.write_jsonl(events, path)
        return path

    def test_report_from_events_json(self, tmp_path, capsys):
        path = self._events_file(tmp_path)
        assert obs_cli(["report", str(path), "--json"]) == 0
        out = capsys.readouterr().out
        rep = json.loads(out[out.index("{"):])
        (cell,) = rep["cells"]
        assert (cell["platform"], cell["backend"], cell["solver"]) == \
            ("cpu", "matfree", "eig")
        assert cell["n"] == 6 and cell["stale"]
        assert rep["recommendations"]

    def test_report_text_flags_stale(self, tmp_path, capsys):
        path = self._events_file(tmp_path)
        assert obs_cli(["report", str(path)]) == 0
        out = capsys.readouterr().out
        assert "STALE" in out and "repro.tune calibrate" in out

    def test_export_to_chrome(self, tmp_path, capsys):
        path = self._events_file(tmp_path)
        to = tmp_path / "trace.json"
        assert obs_cli(["export", str(path), "--to", str(to)]) == 0
        doc = json.loads(to.read_text())
        assert len(doc["traceEvents"]) == 7
        assert {e["ph"] for e in doc["traceEvents"]} == {"X", "i"}
