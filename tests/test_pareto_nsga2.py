"""Pareto utilities + NSGA-II: deterministic checks and ground-truth front
recovery against exhaustive enumeration.

Hypothesis property tests live in `test_pareto_properties.py` (skipped
cleanly when hypothesis is not installed)."""
import jax.numpy as jnp
import numpy as np

from repro.api import DesignRequest, DesignSession
from repro.core import explorer, nsga2, pareto


def _front(array_size, **kw):
    """The undistilled front one explore-only `DesignRequest` returns."""
    return DesignSession().run(DesignRequest(
        array_size=array_size, layout=False, **kw)).pareto


class TestDominance:
    def test_crowding_boundaries_infinite(self):
        f = jnp.asarray(np.array([[0., 5.], [1., 4.], [2., 3.], [3., 2.]],
                                 np.float32))
        ranks = pareto.non_dominated_rank(f)
        crowd = np.asarray(pareto.crowding_distance(f, ranks))
        assert crowd[0] > 1e20 and crowd[-1] > 1e20
        assert np.all(crowd[1:-1] < 1e20)

    def test_constrained_dominance_feasible_beats_infeasible(self):
        f = jnp.asarray(np.array([[5., 5.], [0., 0.]], np.float32))
        cv = jnp.asarray(np.array([0.0, 2.0], np.float32))
        d = np.asarray(pareto.constrained_dominance_matrix(f, cv))
        assert d[0, 1] and not d[1, 0]


class TestNSGA2:
    def test_recovers_true_front_16kb(self):
        genes, objs_all = explorer.full_design_space(16384)
        true_front_mask = np.asarray(pareto.non_dominated_mask(objs_all))
        true_front = {tuple(g) for g, m in
                      zip(np.asarray(genes), true_front_mask) if m}
        res = _front(16384, pop_size=192, generations=60, seed=3)
        found = {(int(np.log2(s.h)), int(np.log2(s.l)), s.b_adc)
                 for s in res.specs}
        # every found point is truly non-dominated...
        assert found <= true_front
        # ...and covers most of the true front
        assert len(found) >= 0.6 * len(true_front)

    def test_population_always_feasible(self):
        cfg = nsga2.NSGA2Config(array_size=16384, pop_size=64, generations=10)
        pop = nsga2.run(cfg)
        cv = np.asarray(nsga2.constraint_violation(pop.genes, cfg))
        assert (cv == 0).all()
        g = np.asarray(pop.genes)
        h_lo, h_hi = cfg.h_exp_bounds
        assert (g[:, 0] >= h_lo).all() and (g[:, 0] <= h_hi).all()
        assert (g[:, 2] >= 1).all() and (g[:, 2] <= (g[:, 0] - g[:, 1])).all()

    def test_repair_projects_into_feasible_set(self):
        cfg = nsga2.NSGA2Config(array_size=16384)
        bad = jnp.asarray(np.array([[20, 9, 9], [4, 7, 8], [6, 1, 0]], np.int32))
        fixed = np.asarray(nsga2.repair(bad, cfg))
        cv = np.asarray(nsga2.constraint_violation(jnp.asarray(fixed), cfg))
        assert (cv == 0).all()
        assert (fixed[:, 2] >= 1).all()

    def test_agile_filter(self):
        res = _front(16384, pop_size=96, generations=25, seed=5)
        filt = res.filter(min_tops=0.5)
        assert all(m >= 0.5 for m in filt.metrics["tops"])
        assert len(filt) <= len(res)

    def test_legacy_generation_step_shapes(self):
        cfg = nsga2.NSGA2Config(array_size=16384, pop_size=32)
        import jax

        key = jax.random.key(0)
        genes = nsga2.init_population(key, cfg)
        objs = nsga2.evaluate(genes, cfg)
        g2, o2 = nsga2.generation_step(key, genes, objs, cfg)
        assert g2.shape == genes.shape and o2.shape == objs.shape
        cv = np.asarray(nsga2.constraint_violation(g2, cfg))
        assert (cv == 0).all()
