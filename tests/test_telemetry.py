"""Telemetry subsystem: span recorder/trace export, metrics registry,
prometheus rendering, the feedback controller (synthetic clocks), and
the service integration contracts — Gantt span sums vs busy clocks,
mid-batch snapshot flushing, pool grow/shrink token conservation."""
import json
import threading
import time

import numpy as np
import pytest

from repro.api import DesignRequest, DesignSession, Requirements
from repro.serve.design_service import DesignService
from repro.telemetry import (DEFAULT_LATENCY_BUCKETS, METRICS_SCHEMA,
                             TRACE_SCHEMA, ControllerConfig,
                             FeedbackController, Histogram, MetricsRegistry,
                             SpanRecorder, Telemetry, TraceExport,
                             atomic_write_json, load_snapshot, percentile,
                             render_prometheus, write_metrics_json)

pytestmark = pytest.mark.timeout(900)

POP, GENS = 48, 10
REQS = Requirements(min_tops=0.5, min_snr_db=10.0)


def _request(array_size=4096, seed=0, **kw):
    kw.setdefault("pop_size", POP)
    kw.setdefault("generations", GENS)
    return DesignRequest(array_size=array_size, seed=seed, **kw)


class _Clock:
    """Deterministic monotonic clock for recorder/controller tests."""

    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


# -- percentile (the shared quantile math) --------------------------------

class TestPercentile:
    def test_matches_numpy_linear_interpolation(self):
        rng = np.random.default_rng(7)
        for n in (1, 2, 3, 10, 101):
            xs = rng.uniform(-50, 50, size=n).tolist()
            for q in (0, 1, 25, 50, 75, 95, 99, 100):
                assert percentile(xs, q) == pytest.approx(
                    float(np.percentile(xs, q)), abs=1e-12)

    def test_edge_contracts(self):
        with pytest.raises(ValueError, match="empty"):
            percentile([], 50)
        with pytest.raises(ValueError, match="outside"):
            percentile([1.0], 101)
        assert percentile([3.0], 95) == 3.0


# -- metrics registry -----------------------------------------------------

class TestMetrics:
    def test_counter_gauge_fn_proxy_wins(self):
        reg = MetricsRegistry()
        box = {"n": 0}
        c = reg.counter("widgets_total", "w", fn=lambda: box["n"])
        box["n"] = 7
        assert c.value == 7.0
        g = reg.gauge("depth", fn=lambda: 3)
        assert g.value == 3.0
        # re-registration returns the same object; kind mismatch raises
        assert reg.counter("widgets_total") is c
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("widgets_total")

    def test_labels_key_separate_series(self):
        reg = MetricsRegistry()
        a = reg.counter("served", labels={"tier": "cache"})
        b = reg.counter("served", labels={"tier": "explorer"})
        assert a is not b
        a.inc(2)
        snap = reg.snapshot()
        assert snap["schema"] == METRICS_SCHEMA
        assert len(snap["metrics"]["served"]) == 2

    def test_histogram_buckets_and_summary(self):
        h = Histogram("lat", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.1, 0.5, 5.0, 50.0):
            h.observe(v)
        d = h.to_dict()
        # le is inclusive: 0.1 lands in the first bucket
        assert [c for _, c in d["buckets"]] == [2, 1, 1]
        assert d["inf_count"] == 1
        assert d["count"] == 5
        s = h.summary()
        assert s["p50"] == pytest.approx(
            float(np.percentile([0.05, 0.1, 0.5, 5.0, 50.0], 50)))
        with pytest.raises(ValueError, match="ascending"):
            Histogram("bad", buckets=(1.0, 1.0))

    def test_default_buckets_are_log_spaced_and_fixed(self):
        assert DEFAULT_LATENCY_BUCKETS[0] == pytest.approx(0.001)
        ratios = {b2 / b1 for b1, b2 in zip(DEFAULT_LATENCY_BUCKETS,
                                            DEFAULT_LATENCY_BUCKETS[1:])}
        assert ratios == {2.0}

    def test_prometheus_render_and_json_roundtrip(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("jobs_total", "jobs", labels={"kind": "a"}).inc(3)
        reg.gauge("depth", fn=lambda: 2)
        h = reg.histogram("lat_seconds", buckets=(0.5, 1.0))
        h.observe(0.25)
        h.observe(2.0)
        snap = reg.snapshot()
        text = render_prometheus(snap)
        assert '# TYPE jobs_total counter' in text
        assert 'jobs_total{kind="a"} 3' in text
        assert 'lat_seconds_bucket{le="0.5"} 1' in text
        assert 'lat_seconds_bucket{le="+Inf"} 2' in text
        assert 'lat_seconds_count 2' in text
        path = tmp_path / "m.json"
        write_metrics_json(snap, path)
        assert load_snapshot(path)["metrics"]["depth"][0]["value"] == 2
        with pytest.raises(ValueError, match="schema"):
            render_prometheus({"schema": 0, "metrics": {}})
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"schema": 999}))
        with pytest.raises(ValueError, match="schema"):
            load_snapshot(bad)

    def test_atomic_write_never_leaves_partials(self, tmp_path):
        path = tmp_path / "x.json"
        atomic_write_json({"ok": 1}, path)
        assert json.loads(path.read_text()) == {"ok": 1}
        assert list(tmp_path.glob("*.tmp")) == []


# -- span recorder + trace export -----------------------------------------

class TestSpans:
    def test_span_lifecycle_and_export(self):
        clk = _Clock()
        rec = SpanRecorder(clock=clk)
        s = rec.begin("explore", cat="stage", batch=0, at=clk.t)
        clk.advance(2.0)
        rec.end(s, at=clk.t)
        rec.instant("admit", cat="pump", batch=1)
        clk.advance(1.0)
        rec.begin("layout", cat="stage", batch=0, bucket=(8, 8))
        exp = rec.export()                       # flushes the open span
        assert exp.schema == TRACE_SCHEMA
        names = [sp.name for sp in exp.spans]
        assert names == ["explore", "admit", "layout"]
        open_span = exp.spans[-1]
        assert open_span.args["open"] is True
        assert open_span.duration_s == pytest.approx(0.0)   # flushed at now
        assert open_span.bucket == "(8, 8)"      # stringified tag
        assert exp.stage_totals() == pytest.approx({"explore": 2.0,
                                                    "layout": 0.0})

    def test_chrome_trace_events_and_roundtrip(self, tmp_path):
        clk = _Clock()
        rec = SpanRecorder(clock=clk)
        with rec.span("distill", cat="stage", batch=3,
                      worker="distill", requests=4):
            clk.advance(0.5)
        rec.instant("shed", cat="fault", bucket="(4, 4)")
        exp = rec.export()
        evs = exp.to_events()
        assert evs[0]["ph"] == "X" and evs[0]["dur"] == pytest.approx(5e5)
        assert evs[0]["args"] == {"requests": 4, "batch": 3}
        assert evs[1]["ph"] == "i"
        path = tmp_path / "trace.json"
        exp.to_json(path)
        back = TraceExport.from_json(path)
        assert [s.name for s in back.spans] == ["distill", "shed"]
        assert back.stage_totals() == pytest.approx({"distill": 0.5})
        bad = dict(json.loads(path.read_text()), schema=0)
        with pytest.raises(ValueError, match="schema"):
            TraceExport.from_dict(bad)

    def test_gantt_groups_by_batch(self):
        clk = _Clock()
        rec = SpanRecorder(clock=clk)
        with rec.span("explore", cat="stage", batch=0):
            clk.advance(1.0)
        with rec.span("explore", cat="stage", batch=1):
            clk.advance(1.0)
        rec.instant("control", cat="control", window_s=0.1)
        g = rec.export().gantt()
        assert g["schema"] == TRACE_SCHEMA
        assert set(g["batches"]) == {0, 1, -1}
        row = g["batches"][0][0]
        assert row["t1_s"] - row["t0_s"] == pytest.approx(1.0)

    def test_threaded_recording_is_complete(self):
        rec = SpanRecorder()

        def work(i):
            for k in range(50):
                with rec.span("unit", cat="stage", batch=i, k=k):
                    pass
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(rec) == 200


# -- feedback controller (synthetic clock) --------------------------------

def _tick(c, clk, **kw):
    kw.setdefault("queue_depth", 0)
    kw.setdefault("layout_backlog", 0)
    kw.setdefault("inflight_buckets", 0)
    kw.setdefault("layout_workers", 1)
    return c.tick(clk.t, **kw)


class TestFeedbackController:
    def test_burst_widens_idle_narrows_window(self):
        cfg = ControllerConfig(min_window_s=0.01, max_window_s=0.5,
                               target_batch=8, window_smoothing=0.0,
                               rate_decay=0.0, tick_interval_s=0.05)
        c = FeedbackController(cfg)
        clk = _Clock()
        assert _tick(c, clk, arrivals_total=0, window_s=0.01) is None
        clk.advance(1.0)         # 40 arrivals/s: ideal window 8/40 = 0.2
        d = _tick(c, clk, arrivals_total=40, window_s=0.01)
        assert d is not None and d.window_s == pytest.approx(0.2)
        clk.advance(1.0)         # idle: back to the latency floor
        d = _tick(c, clk, arrivals_total=40, window_s=d.window_s)
        assert d is not None and d.window_s == pytest.approx(0.01)

    def test_window_clamped_to_bounds(self):
        cfg = ControllerConfig(min_window_s=0.02, max_window_s=0.1,
                               target_batch=100, window_smoothing=0.0,
                               rate_decay=0.0, tick_interval_s=0.05)
        c = FeedbackController(cfg)
        clk = _Clock()
        _tick(c, clk, arrivals_total=0, window_s=0.05)
        clk.advance(1.0)         # 1/s -> desired 100s, clamped to max
        d = _tick(c, clk, arrivals_total=1, window_s=0.05)
        assert d.window_s == pytest.approx(0.1)

    def test_sub_interval_ticks_are_ignored(self):
        cfg = ControllerConfig(tick_interval_s=0.05, target_batch=4)
        c = FeedbackController(cfg)
        clk = _Clock()
        _tick(c, clk, arrivals_total=0, window_s=0.05)
        clk.advance(0.01)
        assert _tick(c, clk, arrivals_total=99, window_s=0.05) is None
        # the delayed tick still sees every arrival (monotonic counter)
        clk.advance(0.05)
        d = _tick(c, clk, arrivals_total=99, window_s=0.05)
        assert c.arrival_rate > 0

    def test_pool_scaling_needs_hysteresis(self):
        cfg = ControllerConfig(min_workers=1, max_workers=3,
                               scale_up_backlog=2.0, hysteresis_ticks=3,
                               target_batch=4, tick_interval_s=0.05)
        c = FeedbackController(cfg, recorder=SpanRecorder())
        clk = _Clock()
        _tick(c, clk, arrivals_total=0, window_s=0.05)
        grew = []
        for _ in range(6):
            clk.advance(0.1)
            d = _tick(c, clk, arrivals_total=0, window_s=0.05,
                      layout_backlog=8, layout_workers=1,
                      inflight_buckets=1)
            if d is not None and d.workers != 1:
                grew.append(d)
        # exactly every hysteresis_ticks'th pressured tick grows by one
        assert [d.workers for d in grew] == [2, 2]
        # decisions are recorded as control spans
        cats = {s.cat for s in c.recorder.export().spans}
        assert cats == {"control"}

    def test_single_idle_tick_does_not_shrink(self):
        cfg = ControllerConfig(min_workers=1, max_workers=3,
                               hysteresis_ticks=3, target_batch=4,
                               tick_interval_s=0.05)
        c = FeedbackController(cfg)
        clk = _Clock()
        _tick(c, clk, arrivals_total=0, window_s=0.05)
        clk.advance(0.1)        # one idle observation: no actuation
        d = _tick(c, clk, arrivals_total=0, window_s=0.05,
                  layout_workers=2)
        assert d is None or d.workers == 2
        clk.advance(0.1)        # pressure resets the down counter
        _tick(c, clk, arrivals_total=0, window_s=0.05, layout_workers=2,
              layout_backlog=8, inflight_buckets=2)
        for _ in range(2):
            clk.advance(0.1)
            d = _tick(c, clk, arrivals_total=0, window_s=0.05,
                      layout_workers=2)
            assert d is None or d.workers == 2   # counter restarted

    def test_config_validation(self):
        with pytest.raises(ValueError, match="min_window_s"):
            ControllerConfig(min_window_s=0.0)
        with pytest.raises(ValueError, match="min_workers"):
            ControllerConfig(min_workers=2, max_workers=1)
        with pytest.raises(ValueError, match="hysteresis"):
            ControllerConfig(hysteresis_ticks=0)


# -- service integration --------------------------------------------------

class TestServiceTelemetry:
    def test_metrics_work_without_telemetry_opt_in(self):
        svc = DesignService()
        assert svc.trace() is None
        snap = svc.metrics()
        assert snap["schema"] == METRICS_SCHEMA
        gauges = {s["labels"].get("stage"): s["value"]
                  for s in snap["metrics"]["design_stage_busy_seconds"]}
        assert set(gauges) == {"explore", "distill", "layout", "finalize"}

    def test_mid_batch_snapshot_flushes_open_clocks(self):
        # the satellite-2 contract: an OPEN stage clock is flushed into
        # both stats() and the metrics gauges — a mid-batch snapshot
        # reports in-progress stage time, never a stale closed total
        svc = DesignService(telemetry=True)
        t0 = time.monotonic() - 1.0
        with svc._lock:
            svc._mark("explore", busy=True, now=t0)
        open_span = svc.recorder.begin("explore", cat="stage", at=t0)
        try:
            st = svc.stats()
            assert st["stage_busy_s"]["explore"] >= 1.0
            assert st["stage_busy"]["explore"] is True
            snap = svc.metrics()
            busy = {s["labels"]["stage"]: s["value"] for s in
                    snap["metrics"]["design_stage_busy_seconds"]}
            assert busy["explore"] >= 1.0
            trace = svc.trace()              # open span flushed too
            assert trace.stage_totals()["explore"] >= 1.0
        finally:
            with svc._lock:
                svc._mark("explore", busy=False)
            svc.recorder.end(open_span)

    def test_gantt_totals_agree_with_busy_clocks_k1(self):
        # acceptance: with single-occupant stages (K=1) the span edges
        # share the busy clocks' exact monotonic reads, so per-stage
        # span sums equal the busy clocks to float precision
        svc = DesignService(max_coalesce=1, layout_workers=1,
                            telemetry=True)
        with svc.serve():
            tickets = [svc.submit(_request(seed=sd, requirements=REQS,
                                           layout=True))
                       for sd in (0, 1)]
            arts = [svc.collect(t, timeout=600) for t in tickets]
        assert all(a.ok for a in arts)
        totals = svc.trace().stage_totals()
        busy = svc.stats()["stage_busy_s"]
        for stage in ("explore", "distill", "layout", "finalize"):
            assert totals[stage] == pytest.approx(busy[stage], abs=1e-9)
        # the Gantt carries every batch, each with all four stages
        g = svc.trace().gantt()
        for seq in (0, 1):
            names = {r["name"] for r in g["batches"][seq]
                     if r["cat"] == "stage"}
            assert names == {"explore", "distill", "layout", "finalize"}

    def test_metrics_latency_histogram_and_tiers(self, tmp_path):
        ses = DesignSession(artifact_cache=tmp_path)
        svc = DesignService(ses, telemetry=True)
        req = _request(seed=0, requirements=REQS, layout=True)
        with svc.serve():
            a1 = svc.collect(svc.submit(req), timeout=600)
        svc2 = DesignService(DesignSession(artifact_cache=tmp_path))
        with svc2.serve():
            a2 = svc2.collect(svc2.submit(req), timeout=600)
        assert a1.summary() == a2.summary()
        for s, expect_tier in ((svc, "explorer"), (svc2, "artifact_cache")):
            snap = s.metrics()
            lat = snap["metrics"]["design_ticket_latency_seconds"][0]
            assert lat["count"] == 1
            assert lat["summary"]["p50"] > 0
            tiers = {t["labels"]["tier"]: t["value"] for t in
                     snap["metrics"]["design_tickets_served_total"]}
            assert tiers[expect_tier] == 1.0
        text = render_prometheus(svc.metrics())
        assert "design_ticket_latency_seconds_bucket" in text
        assert 'design_tickets_served_total{tier="explorer"} 1' in text

    def test_pool_grow_shrink_conserves_sentinels(self):
        # the deadlock-prone path: grow the pool mid-serve, shrink it
        # back (shrink tokens pending in the layout queue), then close
        # with work still queued — every ticket must land and close()
        # must join every worker (finalize sentinel fired exactly once)
        svc = DesignService(max_coalesce=1, layout_workers=1,
                            telemetry=True)
        with svc.serve():
            with svc._lock:
                svc._grow_pool()
                svc._grow_pool()
            tickets = [svc.submit(_request(seed=sd, requirements=REQS,
                                           layout=True))
                       for sd in (0, 1)]
            with svc._lock:
                svc._shrink_pool()
            arts = [svc.collect(t, timeout=600) for t in tickets]
        assert all(a.ok for a in arts)
        st = svc.stats()
        assert st["pool_scale_ups"] == 2
        assert st["pool_scale_downs"] == 1
        assert svc.layout_workers == 2
        assert not any(t.is_alive() for t in svc._stage_threads)

    def test_adaptive_window_moves_under_load(self):
        cfg = ControllerConfig(min_window_s=0.01, max_window_s=0.3,
                               target_batch=4, tick_interval_s=0.02,
                               window_smoothing=0.0)
        svc = DesignService(max_coalesce=4, coalesce_window_s=0.01,
                            telemetry=True, controller=cfg)
        assert svc.controller.config.target_batch == 4
        with svc.serve():
            tickets = [svc.submit(_request(seed=sd, requirements=REQS,
                                           layout=False))
                       for sd in (0, 1, 2)]
            arts = [svc.collect(t, timeout=600) for t in tickets]
        assert all(a.ok for a in arts)
        st = svc.stats()
        assert st["control_window_updates"] == len(
            [d for d in svc.controller.decisions]) >= 1
        cfg = svc.controller.config
        assert cfg.min_window_s <= svc.coalesce_window_s <= cfg.max_window_s
        # every actuation is auditable as a control span
        control = [s for s in svc.trace().spans if s.cat == "control"]
        assert len(control) >= len(svc.controller.decisions)

    def test_adaptive_window_artifacts_equal_fixed_window(self, tmp_path):
        # the controller moves when batches dispatch, never what they
        # compute; the adaptive run's trace, Gantt and metrics export
        # with their schema stamps
        reqs = [_request(seed=sd, requirements=REQS, layout=False)
                for sd in (0, 1, 2)]
        fixed = DesignService(max_coalesce=4, coalesce_window_s=0.01)
        with fixed.serve():
            tickets = [fixed.submit(r) for r in reqs]
            ref = [fixed.collect(t, timeout=600) for t in tickets]
        cfg = ControllerConfig(min_window_s=0.01, max_window_s=0.3,
                               target_batch=4, tick_interval_s=0.02,
                               window_smoothing=0.0)
        svc = DesignService(max_coalesce=4, coalesce_window_s=0.01,
                            telemetry=True, controller=cfg)
        with svc.serve():
            tickets = [svc.submit(r) for r in reqs]
            arts = [svc.collect(t, timeout=600) for t in tickets]
        assert [a.summary() for a in arts] == [a.summary() for a in ref]
        updates = svc.stats()["control_window_updates"]
        assert len(svc.controller.decisions) >= updates >= 1
        trace = svc.trace()
        assert trace.spans
        trace.to_json(tmp_path / "trace.json")
        back = TraceExport.from_json(tmp_path / "trace.json")
        assert back.schema == TRACE_SCHEMA and back.to_events()
        gantt = trace.gantt()
        assert gantt["schema"] == TRACE_SCHEMA and gantt["batches"]
        write_metrics_json(svc.metrics(), tmp_path / "metrics.json")
        snap = load_snapshot(tmp_path / "metrics.json")
        assert snap["schema"] == METRICS_SCHEMA
        assert {"design_ticket_latency_seconds",
                "design_tickets_served_total",
                "design_coalesce_window_s"} <= set(snap["metrics"])

    def test_telemetry_bundle_shares_recorder_with_session(self):
        tel = Telemetry()
        svc = DesignService(telemetry=tel)
        assert svc.session.recorder is tel.recorder
        assert svc.recorder is tel.recorder
        assert svc.registry is tel.metrics
