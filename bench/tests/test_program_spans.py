"""The program's `design.*` spans read from a trace, and the
queue-wait reader."""
import threading

import jax
import jax.numpy as jnp
import pytest

import loadgen
import program_spans as ps
import trace_reduce as tr

MS = 1_000_000   # ns


def _trace():
    """One device busy [2,4] and [20,22] ms of a 30 ms window.  The
    client waits in `bench.collect` [1,25] while a worker thread runs a
    layout stage [5,19] (prepare [5,7], route [7,18], rows [18,19]), an
    explore launch [19,20] and a post-processing that outlasts the
    window [28,35]."""
    dev = tr.Device(modules=[("jit__route_program", 2 * MS, 4 * MS),
                             ("jit_sweep_program", 20 * MS, 22 * MS)],
                    ops=[("fusion.1", 2 * MS, 4 * MS, None),
                         ("fusion.2", 20 * MS, 22 * MS, None)])
    bench = [("bench.traced_window", 0, 30 * MS),
             ("bench.collect", 1 * MS, 25 * MS)]
    worker = (0, 3)
    design = [("design.stage.layout", 5 * MS, 19 * MS, worker),
              ("design.layout.prepare", 5 * MS, 7 * MS, worker),
              ("design.layout.route", 7 * MS, 18 * MS, worker),
              ("design.layout.rows", 18 * MS, 19 * MS, worker),
              ("design.explore.launch", 19 * MS, 20 * MS, worker),
              ("design.explore.postprocess", 28 * MS, 35 * MS, worker)]
    return {0: dev}, bench, design


def test_self_time_counts_and_the_window_clip():
    red = ps.reduce(*_trace())
    s = red["span_s"]
    assert s["design.stage.layout"] == pytest.approx(0.0)   # all children
    assert s["design.layout.route"] == pytest.approx(0.011)
    assert s["design.explore.postprocess"] == pytest.approx(0.002)
    assert red["span_n"]["design.layout.prepare"] == 1


def test_idle_is_named_by_the_innermost_span():
    red = ps.reduce(*_trace())
    idle = red["idle_by_span"]
    # idle: [0,2] [4,20] [22,30]
    assert idle[ps.OUTSIDE] == pytest.approx(0.001 + 0.003)  # [0,1] [25,28]
    assert idle["bench.collect"] == pytest.approx(0.001 + 0.001 + 0.003)
    assert idle["design.layout.prepare"] == pytest.approx(0.002)
    assert idle["design.layout.route"] == pytest.approx(0.011)
    assert idle["design.layout.rows"] == pytest.approx(0.001)
    assert idle["design.explore.launch"] == pytest.approx(0.001)
    assert idle["design.explore.postprocess"] == pytest.approx(0.002)
    assert sum(idle.values()) == pytest.approx(0.026)
    assert red["idle_gaps"][0] == ["design.layout.route",
                                   pytest.approx(0.016)]
    assert red["layout_host_idle_share"] == pytest.approx(
        100 * 0.014 / 0.030)
    # one explore program run; launch 1 ms + postprocess 2 ms in window
    assert red["explore_host_ms_per_dispatch"] == pytest.approx(3.0)


def test_the_device_numbers_are_trace_reduce_s():
    devices, bench, design = _trace()
    red = ps.reduce(devices, bench, design)
    base = tr.reduce(devices, bench)
    assert (red["window_s"], red["busy_s"]) == (base["window_s"],
                                                 base["busy_s"])


def test_a_program_without_spans_gives_no_program_numbers():
    devices, bench, _ = _trace()
    red = ps.reduce(devices, bench, [])
    assert red["span_s"] == {}
    assert "layout_host_idle_share" not in red
    assert "explore_host_ms_per_dispatch" not in red
    assert set(red["idle_by_span"]) == {ps.OUTSIDE, "bench.collect"}


def test_a_cpu_trace_finds_worker_thread_spans(tmp_path):
    from repro.telemetry import trace_span

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()

    def worker():
        with trace_span("layout", cat="stage", batch=0, bucket=(64, 4)):
            with trace_span("route", cat="layout", specs=2):
                f(x).block_until_ready()

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.traced_window"):
        with jax.profiler.TraceAnnotation("bench.collect"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
    jax.profiler.stop_trace()
    red = ps.reduce_dir(str(tmp_path))
    assert red["span_n"] == {"design.stage.layout": 1,
                             "design.layout.route": 1}
    assert red["span_s"]["design.layout.route"] > 0
    base = tr.reduce_dir(str(tmp_path))
    assert (red["window_s"], red["busy_s"]) == (base["window_s"],
                                                 base["busy_s"])
    assert all(not g[0].startswith("design.")
               for g in base["breakdown"]["idle_gaps"])


def _ctx(stats, traced=None):
    win = loadgen.WindowResult(requests=[], artifacts=[], latency_s=[],
                               seconds=1.0, sessions=[], stats=stats,
                               traced_stats=traced)
    return {"window": win}


def test_queue_wait_reader():
    read = loadgen.load_module(ps.BENCH / "layer_metrics" /
                               "queue_wait_ms.py", "lm_queue_wait_ms").read
    stats = {"service_batch_requests": 4, "admit_wait_s": 0.12,
             "explore_wait_s": 0.08}
    assert read(_ctx(stats)) == pytest.approx(50.0)
    traced = {**stats, "service_batch_requests": 2}
    assert read(_ctx(stats, traced)) == pytest.approx(100.0)
    # a service without the counters (or no request done): nothing
    assert read(_ctx({"service_batch_requests": 4})) is None
    assert read(_ctx({**stats, "service_batch_requests": 0})) is None
