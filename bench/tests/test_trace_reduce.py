"""The reduction from a profiler trace to the benchmark's device numbers."""
import jax
import jax.numpy as jnp
import pytest

import trace_reduce as tr

MS = 1_000_000   # ns


def _tpu_like():
    """Two devices in the TPU layout: program executions and their ops."""
    d0 = tr.Device(
        modules=[("jit__route_program", 0, 10 * MS),
                 ("jit_sweep_program", 20 * MS, 30 * MS)],
        ops=[("fusion.1", 1 * MS, 4 * MS, None),
             ("fusion.2", 3 * MS, 6 * MS, None),      # overlaps fusion.1
             ("collective-permute-start.3", 22 * MS, 24 * MS, None),
             ("fusion.4", 24 * MS, 29 * MS, None)])
    d1 = tr.Device(
        modules=[("jit_sweep_program", 20 * MS, 30 * MS)],
        ops=[("all-reduce.1", 21 * MS, 23 * MS, None),
             ("fusion.5", 23 * MS, 25 * MS, None)])
    spans = [("bench.traced_window", 0, 40 * MS),
             ("bench.collect", 5 * MS, 40 * MS),
             ("bench.submit", 8 * MS, 12 * MS)]
    return {0: d0, 1: d1}, spans


def test_busy_modules_and_collectives_are_exact():
    red = tr.reduce(*_tpu_like())
    assert red["devices"] == 2
    assert red["window_s"] == pytest.approx(0.040)
    # device 0: [1,6] + [22,29] = 12 ms; device 1: [21,25] = 4 ms
    assert red["busy_s"] == pytest.approx((0.012 + 0.004) / 2)
    # route ops union 5 ms on device 0 only; sweep 7 ms and 4 ms
    assert red["module_s"]["jit__route_program"] == pytest.approx(0.005 / 2)
    assert red["module_s"]["jit_sweep_program"] == pytest.approx(0.011 / 2)
    assert red["module_n"]["jit_sweep_program"] == pytest.approx(1.0)
    assert red["collective_s"] == pytest.approx((0.002 + 0.002) / 2)
    assert red["collective_modules"] == pytest.approx(1.0)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    red = tr.reduce(*_tpu_like())
    gaps = red["breakdown"]["idle_gaps"]
    # device 0 idle: [0,1], [6,22], [29,40]
    assert [g[1] for g in gaps] == pytest.approx([0.016, 0.011, 0.001])
    assert gaps[0][0] == "bench.collect"
    assert gaps[2][0] == "host: outside the harness spans"
    assert len(red["breakdown"]["device_ops"]) <= 10


def test_a_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.traced_window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.submit"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    red = tr.reduce_dir(str(tmp_path))
    assert red["devices"] >= 1
    assert 0 < red["busy_s"] <= red["window_s"]
    assert any("lambda" in k for k in red["module_s"])
    assert all(isinstance(g[0], str) and g[1] >= 0
               for g in red["breakdown"]["idle_gaps"])
