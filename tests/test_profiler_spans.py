"""Program spans on the profiler's clock (`repro.telemetry.spans.trace_span`)
and the queue-wait counters.

Every span of the service, the session and the explore/layout library
is a `design.<cat>.<name>` `jax.profiler.TraceAnnotation`, with or
without a `SpanRecorder`; library spans inherit `batch`/`bucket` from
the enclosing span on their thread.  A recorder, when attached, records
exactly what it recorded before (names, categories, args).  The
`admit_wait_s`/`explore_wait_s` counters in `stats()` sum the
per-ticket waits stamped into provenance; `explore_host_s` sums each
explore dispatch's host seconds outside its fetch."""
import glob
import os
import textwrap
import threading

import jax
import pytest

from repro.analysis import trace_purity
from repro.analysis.core import parse_file
from repro.api import DesignRequest, Requirements
from repro.serve.design_service import DesignService
from repro.telemetry import SpanRecorder, trace_span

pytestmark = pytest.mark.timeout(900)

POP, GENS = 48, 10
REQS = Requirements(min_tops=0.5, min_snr_db=10.0)

EXPLORE_SPANS = {
    "design.pump.admit", "design.stage.explore",
    "design.session.explore_dispatch", "design.explore.launch",
    "design.explore.fetch", "design.explore.postprocess",
    "design.stage.distill", "design.stage.finalize"}
LAYOUT_SPANS = {
    "design.stage.layout", "design.session.layout_bucket",
    "design.layout.prepare", "design.layout.place_drc_nets",
    "design.layout.route", "design.layout.netlist_stats",
    "design.layout.rows"}


def _request(array_size=4096, seed=0, **kw):
    kw.setdefault("pop_size", POP)
    kw.setdefault("generations", GENS)
    return DesignRequest(array_size=array_size, seed=seed, **kw)


def _capture(path, fn):
    """Run `fn` under `jax.profiler`; returns (its result, the host
    `design.*` events as dicts with name, stats, thread, start, end)."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (xplane,) = glob.glob(os.path.join(str(path), "**", "*.xplane.pb"),
                          recursive=True)
    events = []
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for thread, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("design."):
                    events.append({"name": e.name, "stats": dict(e.stats),
                                   "thread": thread, "start": e.start_ns,
                                   "end": e.start_ns + e.duration_ns})
    return out, events


def _serve_two(svc):
    """An explore-only request, then a laid-out one: two batches."""
    with svc.serve():
        a = svc.collect(svc.submit(_request(seed=0, layout=False)),
                        timeout=600)
        b = svc.collect(svc.submit(_request(seed=1, requirements=REQS,
                                            layout=True)), timeout=600)
    assert a.ok and b.ok
    return a, b


def _enclosing(events, child, name):
    """The `name` span on `child`'s thread whose interval holds it."""
    return [e for e in events if e["name"] == name
            and e["thread"] == child["thread"]
            and e["start"] <= child["start"] and child["end"] <= e["end"]]


class TestServiceSpansWithoutRecorder:
    def test_every_named_span_with_its_tags(self, tmp_path):
        svc = DesignService(max_coalesce=2)
        _, events = _capture(tmp_path, lambda: _serve_two(svc))
        assert svc.trace() is None           # no recorder: profiler only
        names = {e["name"] for e in events}
        assert EXPLORE_SPANS | LAYOUT_SPANS <= names
        by = {n: [e for e in events if e["name"] == n] for n in names}
        assert {e["stats"]["batch"] for e in by["design.stage.explore"]} \
            == {0, 1}
        assert all(e["stats"]["requests"] == 1
                   for e in by["design.pump.admit"])
        # library spans run on the stage's thread, inside its span, and
        # carry its batch
        for n in ("design.explore.launch", "design.explore.fetch",
                  "design.explore.postprocess",
                  "design.session.explore_dispatch"):
            for e in by[n]:
                (stage,) = _enclosing(events, e, "design.stage.explore")
                assert e["stats"]["batch"] == stage["stats"]["batch"]
        assert all(e["stats"]["cells"] == 1
                   for e in by["design.explore.launch"])
        for n in LAYOUT_SPANS - {"design.stage.layout"}:
            for e in by[n]:
                (stage,) = _enclosing(events, e, "design.stage.layout")
                assert e["stats"]["batch"] == 1
                assert e["stats"]["bucket"] == stage["stats"]["bucket"]
                assert e["stats"]["specs"] >= 1
        assert all(e["stats"]["worker"] == "layout-0"
                   for e in by["design.layout.route"])


class TestRecorderExportUnchanged:
    def test_recorded_spans_are_the_service_and_session_spans(
            self, tmp_path):
        svc = DesignService(max_coalesce=2, layout_workers=1,
                            telemetry=True)
        _, events = _capture(tmp_path, lambda: _serve_two(svc))
        assert EXPLORE_SPANS | LAYOUT_SPANS <= {e["name"] for e in events}
        exp = svc.trace()
        kinds = {(s.cat, s.name) for s in exp.spans}
        assert kinds == {("pump", "admit"), ("stage", "explore"),
                         ("stage", "distill"), ("stage", "layout"),
                         ("stage", "finalize"),
                         ("session", "explore_dispatch"),
                         ("session", "layout_bucket")}
        admits = [s for s in exp.spans if s.name == "admit"]
        assert all(s.end_s == s.start_s for s in admits)      # instants
        assert all(set(s.args) == {"requests", "oldest_wait_s", "window_s"}
                   for s in admits)
        dispatch = [s for s in exp.spans if s.name == "explore_dispatch"]
        assert all(set(s.args) == {"cells", "coalesced"} and s.batch is None
                   for s in dispatch)
        buckets = [s for s in exp.spans if s.name == "layout_bucket"]
        assert buckets and all(set(s.args) == {"specs"} and s.bucket
                               for s in buckets)
        busy = svc.stats()["stage_busy_s"]
        for stage, total in exp.stage_totals().items():
            assert total == pytest.approx(busy[stage], abs=1e-9)


class TestQueueWaitCounters:
    def test_stats_equal_the_provenance_sums(self):
        reqs = [_request(seed=s, layout=False) for s in (0, 1, 2)]
        reqs.append(reqs[0])                 # a repeat, its own ticket
        svc = DesignService(max_coalesce=2, coalesce_window_s=0.02)
        with svc.serve():
            tickets = [svc.submit(r) for r in reqs]
            arts = [svc.collect(t, timeout=600) for t in tickets]
        stats = svc.stats()
        admit = [a.provenance.admit_wait_s for a in arts]
        explore = [a.provenance.explore_wait_s for a in arts]
        assert all(w > 0 for w in admit)
        assert stats["admit_wait_s"] == pytest.approx(sum(admit), abs=1e-9)
        assert stats["explore_wait_s"] == pytest.approx(sum(explore),
                                                        abs=1e-9)

    def test_explore_host_seconds_per_dispatch(self):
        """`explore_host_s` adds each dispatch's host seconds outside the
        fetch: above 0, below the dispatches' whole time."""
        reqs = [_request(seed=s, layout=False) for s in (0, 1, 2)]
        svc = DesignService(max_coalesce=2, coalesce_window_s=0.02)
        with svc.serve():
            arts = [svc.collect(t, timeout=600)
                    for t in [svc.submit(r) for r in reqs]]
        stats = svc.stats()
        assert stats["explorer_dispatches"] >= 2
        whole = sum(a.provenance.explore_s for a in arts)
        assert 0.0 < stats["explore_host_s"] < whole

    def test_explore_host_reader(self):
        import importlib.util
        import pathlib
        from types import SimpleNamespace

        path = (pathlib.Path(__file__).resolve().parents[1] / "bench" /
                "layer_metrics" / "explore_host_ms_per_dispatch.py")
        spec = importlib.util.spec_from_file_location("lm_explore_host",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)

        def ctx(stats, traced=None):
            win = SimpleNamespace(stats=stats, traced_stats=traced)
            return {"window": win}

        stats = {"explorer_dispatches": 4, "explore_host_s": 0.02}
        assert mod.read(ctx(stats)) == pytest.approx(5.0)
        traced = {"explorer_dispatches": 2, "explore_host_s": 0.004}
        assert mod.read(ctx(stats, traced)) == pytest.approx(2.0)
        # a service without the counter, or no dispatch: nothing
        assert mod.read(ctx({"explorer_dispatches": 4})) is None
        assert mod.read(ctx({**stats, "explorer_dispatches": 0})) is None

    def test_sequential_drivers_stamp_no_wait(self):
        svc = DesignService()
        t = svc.submit(_request(seed=0, layout=False))
        art = svc.run()[t]
        assert art.provenance.admit_wait_s == 0.0
        assert svc.stats()["admit_wait_s"] == 0


def _load_reader(name):
    import importlib.util
    import pathlib

    path = (pathlib.Path(__file__).resolve().parents[1] / "bench" /
            "layer_metrics" / f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"lm_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class TestRouteCounters:
    """`layout_stage` adds the Pallas wavefront's counters: sweeps run
    (`route_wavefront_iters`), grid-slots with a live net
    (`route_wavefronts`), wavefronts stopped on their targets
    (`route_goal_stops`) and backtrace steps walked in the kernel
    (`route_walk_steps`); nothing where no kernel routed."""

    @pytest.fixture(scope="class")
    def distilled(self):
        from repro.api import DesignSession

        session = DesignSession()
        req = _request(seed=3, layout=True, requirements=REQS)
        batch = session.distill_stage(session.explore_stage([req]))
        return session, batch

    def test_host_engine_adds_no_route_counters(self, distilled):
        session, batch = distilled
        session.layout_stage(batch.buckets[0])
        assert session.stats["layout_dispatches"] >= 1
        assert "route_wavefront_iters" not in session.stats
        assert "route_wavefronts" not in session.stats
        assert "route_walk_steps" not in session.stats

    def test_kernel_route_counters(self, distilled, monkeypatch):
        from repro.eda import batched_flow

        session, batch = distilled
        seen = []

        def layout(specs, *, coarse=64, capacity=4, engine=None):
            res = batched_flow.generate_layouts(
                specs, coarse=coarse, capacity=capacity, use_kernel=True)
            seen.append(res.routing)
            return res

        monkeypatch.setattr(session, "layout", layout)
        for bucket in batch.buckets:
            session.layout_stage(bucket)
        stats = session.stats
        live = sum(int((r.routed + r.failed).sum()) for r in seen)
        assert stats["route_wavefronts"] == live > 0
        assert stats["route_wavefront_iters"] == sum(
            int(r.sweeps.sum()) for r in seen)
        assert stats["route_goal_stops"] == sum(
            int(r.goal_stops.sum()) for r in seen)
        assert 0 < stats["route_goal_stops"] <= live
        assert stats["route_walk_steps"] == sum(
            int(r.walk_steps.sum()) for r in seen) > 0
        read = _load_reader("route_iters_per_wavefront")
        ctx = {"window": type("W", (), {"stats": dict(stats),
                                        "traced_stats": None})()}
        assert read(ctx) == pytest.approx(
            stats["route_wavefront_iters"] / live)
        read = _load_reader("route_walk_steps_per_wavefront")
        assert read(ctx) == pytest.approx(stats["route_walk_steps"] / live)

    def test_route_iters_reader(self):
        from types import SimpleNamespace

        read = _load_reader("route_iters_per_wavefront")

        def ctx(stats, traced=None):
            return {"window": SimpleNamespace(stats=stats,
                                              traced_stats=traced)}

        stats = {"route_wavefronts": 40, "route_wavefront_iters": 6000}
        assert read(ctx(stats)) == pytest.approx(150.0)
        traced = {"route_wavefronts": 10, "route_wavefront_iters": 1200}
        assert read(ctx(stats, traced)) == pytest.approx(120.0)
        # a program without the counters, or no wavefront: nothing
        assert read(ctx({"layout_dispatches": 3})) is None
        assert read(ctx({**stats, "route_wavefronts": 0})) is None

    def test_route_walk_steps_reader(self):
        from types import SimpleNamespace

        read = _load_reader("route_walk_steps_per_wavefront")

        def ctx(stats, traced=None):
            return {"window": SimpleNamespace(stats=stats,
                                              traced_stats=traced)}

        stats = {"route_wavefronts": 40, "route_walk_steps": 4000}
        assert read(ctx(stats)) == pytest.approx(100.0)
        traced = {"route_wavefronts": 10, "route_walk_steps": 900}
        assert read(ctx(stats, traced)) == pytest.approx(90.0)
        # a program without the counter (the walk outside the kernel),
        # or no wavefront: nothing
        assert read(ctx({"route_wavefronts": 40,
                         "route_wavefront_iters": 6000})) is None
        assert read(ctx({**stats, "route_wavefronts": 0})) is None


class TestTraceSpan:
    def test_recorded_edges_and_delegation(self):
        rec = SpanRecorder()
        with trace_span("unit", cat="stage", recorder=rec, at=1.0,
                        batch=4, requests=2) as sp:
            sp.end_at = 3.5
        assert (sp.span.start_s, sp.span.end_s) == (1.0, 3.5)
        assert sp.span.batch == 4 and sp.span.args == {"requests": 2}
        with rec.span("other", cat="session", specs=3):
            pass
        assert [s.name for s in rec.export().spans] == ["unit", "other"]
        with trace_span("free", cat="explore") as bare:
            pass
        assert bare.span is None

    def test_tags_are_inherited_on_the_same_thread_only(self, tmp_path):
        def elsewhere():
            with trace_span("elsewhere", cat="explore"):
                pass

        def body():
            with trace_span("outer", cat="stage", batch=7, bucket=(1, 2)):
                with trace_span("inner", cat="explore", cells=2):
                    pass
                t = threading.Thread(target=elsewhere)
                t.start()
                t.join()
            with trace_span("after", cat="explore"):
                pass

        _, events = _capture(tmp_path, body)
        by = {e["name"]: e["stats"] for e in events}
        assert by["design.explore.inner"] == {"batch": 7,
                                              "bucket": "(1, 2)",
                                              "cells": 2}
        assert by["design.explore.elsewhere"] == {}
        assert by["design.explore.after"] == {}

    def test_lint_flags_a_span_in_traced_code(self, tmp_path):
        (tmp_path / "m.py").write_text(textwrap.dedent('''
            import jax
            from repro.telemetry.spans import trace_span

            @jax.jit
            def step(x):
                with trace_span("bad", cat="explore"):
                    return x + 1

            def host(x):
                with trace_span("fine", cat="explore"):
                    return step(x)
            '''))
        m = parse_file(tmp_path / "m.py", root=tmp_path)
        found = trace_purity.run({m.name: m})
        assert [f.rule for f in found] == ["host-call"]
        assert "trace_span" in found[0].message and "m.step" in found[0].message
