"""Fault tolerance: restart-exactness, preemption, injected failures,
straggler monitoring, elastic re-mesh restore."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.configs import registry as creg
from repro.launch.mesh import make_mesh
from repro.runtime.fault_tolerance import (FailureInjector, PreemptionGuard,
                                           RESTART_EXIT_CODE,
                                           SimulatedNodeFailure,
                                           StragglerMonitor, run_supervised)
from repro.train.trainer import TrainerConfig, train


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


def _tcfg(tmp_path, steps=12, ckpt_every=4):
    return TrainerConfig(seq=32, global_batch=4, total_steps=steps,
                         ckpt_every=ckpt_every, ckpt_dir=str(tmp_path),
                         log_every=0)


class TestRestartExactness:
    def test_resume_is_bitwise_identical(self, tmp_path):
        cfg = creg.reduced("qwen2_5_3b")
        mesh = _mesh()
        # uninterrupted reference
        ref = train(cfg, mesh, _tcfg(tmp_path / "ref"))
        assert ref.exit_code == 0

        # interrupted at step 6 via preemption guard
        guard = PreemptionGuard()
        seen = []

        def on_step(step, metrics):
            seen.append(step)
            if step == 5:
                guard.request()

        r1 = train(cfg, mesh, _tcfg(tmp_path / "int"), guard=guard,
                   on_step=on_step)
        assert r1.exit_code == RESTART_EXIT_CODE
        # resume
        r2 = train(cfg, mesh, _tcfg(tmp_path / "int"))
        assert r2.exit_code == 0
        combined = r1.losses + r2.losses
        np.testing.assert_array_equal(np.asarray(combined),
                                      np.asarray(ref.losses))

    def test_injected_node_failure_supervised(self, tmp_path):
        cfg = creg.reduced("qwen3_8b")
        mesh = _mesh()
        injector = FailureInjector(fail_at_steps=(5,))
        calls = []

        def run_once():
            calls.append(1)
            inj = injector if len(calls) == 1 else None
            return train(cfg, mesh, _tcfg(tmp_path), injector=inj).exit_code

        code = run_supervised(run_once, max_restarts=2)
        assert code == 0
        assert len(calls) == 2   # failed once, restarted once

    def test_failure_without_supervisor_raises(self, tmp_path):
        cfg = creg.reduced("qwen3_8b")
        with pytest.raises(SimulatedNodeFailure):
            train(cfg, _mesh(), _tcfg(tmp_path),
                  injector=FailureInjector(fail_at_steps=(2,)))


class TestStraggler:
    def test_monitor_flags_outliers(self):
        mon = StragglerMonitor(threshold=2.0)
        for step in range(10):
            mon.observe(step, 0.1)
        assert mon.observe(10, 0.5)         # 5x EMA -> straggler
        assert not mon.observe(11, 0.11)
        assert len(mon.events) == 1
        # straggler did not poison the EMA
        assert mon.ema == pytest.approx(0.1, rel=0.2)

    def test_mitigation_drains_slow_host(self):
        mon = StragglerMonitor()
        plan = mon.mitigation_plan(n_hosts=4, slow_host=2)
        assert plan[2] != 2 and len(plan) == 4

    def test_stuck_judges_inflight_without_mutating(self):
        mon = StragglerMonitor(threshold=2.0)
        assert not mon.stuck(1000.0)   # no EMA yet: no baseline to judge
        for step in range(5):
            mon.observe(step, 0.1)
        ema = mon.ema
        assert mon.stuck(0.5)          # 5x EMA, still in flight
        assert not mon.stuck(0.15)
        # unlike observe(), stuck() records nothing and moves nothing
        assert mon.ema == ema and mon.events == []


class TestElasticRemesh:
    def test_restore_under_different_sharding(self, tmp_path):
        """Elastic restore: same checkpoint, different target sharding."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        ckpt.save(tmp_path, 1, tree)
        mesh = make_mesh((1,), ("x",))
        sh = {"w": NamedSharding(mesh, P("x", None))}
        out = ckpt.restore(tmp_path, 1, jax.eval_shape(lambda: tree), sh)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(tree["w"]))
        assert out["w"].sharding == sh["w"]
