"""Batched one-compile explorer: equivalence with the sequential path,
single-trace contract, ground-truth front recovery, fused rank oracles,
and the front program against the eager front path it replaced."""
import time

import jax
import numpy as np
import pytest

import front_oracle
from repro.core import batched_explorer, explorer, nsga2, pareto
from repro.core.batched_explorer import explore_batch, explore_cells

SIZES = (4096, 16384, 65536)
ORACLE_CELLS = ((4096, 0), (4096, 3), (16384, 1), (16384, 2 ** 31 - 2),
                (65536, 0), (65536, 5))


def _front_set(res: explorer.ParetoResult):
    return {(s.h, s.w, s.l, s.b_adc) for s in res.specs}


def _true_front(array_size: int):
    genes, objs = explorer.full_design_space(array_size)
    mask = np.asarray(pareto.non_dominated_mask(objs))
    return {tuple(g) for g, m in zip(np.asarray(genes), mask) if m}


class TestExploreBatch:
    def test_single_trace_and_sequential_equivalence(self):
        """3 sizes x 2 seeds: exactly one trace of the generation program,
        and per-cell fronts identical to the sequential `nsga2.run` path."""
        seeds = (0, 1)
        pop, gens = 56, 10
        jax.clear_caches()   # order-independent: force a fresh compile
        before = nsga2.TRACE_COUNTS["run_cell"]
        out = explore_batch(SIZES, seeds, pop_size=pop, generations=gens)
        assert nsga2.TRACE_COUNTS["run_cell"] - before == 1
        assert set(out) == {(s, sd) for s in SIZES for sd in seeds}
        # warm re-dispatch: no new trace
        explore_batch(SIZES, seeds, pop_size=pop, generations=gens)
        assert nsga2.TRACE_COUNTS["run_cell"] - before == 1
        for s in SIZES:
            for sd in seeds:
                cfg = nsga2.NSGA2Config(array_size=s, pop_size=pop,
                                        generations=gens, seed=sd)
                popu = nsga2.run(cfg)
                ref = explorer.pareto_result_from_population(
                    s, popu.genes, popu.objs)
                assert _front_set(out[(s, sd)]) == _front_set(ref), (s, sd)

    def test_recovers_ground_truth_front_all_sizes(self):
        """At the default exploration budget the batched sweep recovers the
        exhaustive-enumeration Pareto set exactly, per size."""
        out = explore_batch(SIZES, (0,), pop_size=256, generations=80)
        for s in SIZES:
            found = {(int(np.log2(sp.h)), int(np.log2(sp.l)), sp.b_adc)
                     for sp in out[(s, 0)].specs}
            assert found == _true_front(s), s

    def test_explore_sizes_wrapper_matches_batch(self):
        by_size = explorer.explore_sizes(SIZES[:2], seed=4, pop_size=48,
                                         generations=6)
        out = explore_batch(SIZES[:2], (4,), pop_size=48, generations=6)
        for s in SIZES[:2]:
            assert _front_set(by_size[s]) == _front_set(out[(s, 4)])

    def test_operand_traced_sequential_path_single_trace(self):
        """Sweeping array sizes sequentially also compiles once: the size
        is an operand, not a static."""
        pop, gens = 40, 5
        jax.clear_caches()   # order-independent: force a fresh compile
        before = nsga2.TRACE_COUNTS["run_cell"]
        for s in SIZES:
            nsga2.run(nsga2.NSGA2Config(array_size=s, pop_size=pop,
                                        generations=gens))
        assert nsga2.TRACE_COUNTS["run_cell"] - before == 1


class TestFusedRankPath:
    """The Pallas rank path (interpret mode off-TPU) against jnp oracles."""

    @pytest.mark.parametrize("p,m,seed", [(64, 4, 0), (200, 4, 1),
                                          (256, 3, 2), (400, 2, 3)])
    def test_rank_and_crowd_agree_with_oracles(self, p, m, seed):
        from repro.kernels.pareto_dom import ops as dom_ops

        f = jax.random.normal(jax.random.key(seed), (p, m))
        ranks, crowd = dom_ops.rank_and_crowd(f)
        ranks_ref = pareto.non_dominated_rank(f)
        np.testing.assert_array_equal(np.asarray(ranks), np.asarray(ranks_ref))
        np.testing.assert_allclose(
            np.asarray(crowd),
            np.asarray(pareto.crowding_distance(f, ranks_ref)))

    def test_explore_with_pallas_rank_matches_default(self):
        a = explorer.explore(16384, pop_size=64, generations=8, seed=2)
        b = explorer.explore(16384, pop_size=64, generations=8, seed=2,
                             use_pallas_rank=True)
        assert _front_set(a) == _front_set(b)


@pytest.fixture(scope="module")
def coalesced():
    """One dispatch of `ORACLE_CELLS`, with what each cell handed
    `pareto_result_from_population`."""
    with pytest.MonkeyPatch.context() as mp:
        rec = front_oracle.Recorder(mp)
        out = explore_cells(ORACLE_CELLS, pop_size=64, generations=10)
    assert [c["array_size"] for c in rec.calls] == [s for s, _ in
                                                    ORACLE_CELLS]
    return out, dict(zip(ORACLE_CELLS, rec.calls))


class TestFrontProgram:
    """`explorer.front_program` against the eager path it replaced: the
    same specs in the same order, float32 metrics within 1e-5."""

    @pytest.mark.parametrize("cell", ORACLE_CELLS,
                             ids=[f"{s}-{sd}" for s, sd in ORACLE_CELLS])
    def test_coalesced_front_matches_eager_oracle(self, coalesced, cell):
        out, calls = coalesced
        call = calls[cell]
        assert set(call["kw"]) == {"mask", "report"}   # batch-computed
        assert call["result"] is out[cell]
        front_oracle.assert_matches(out[cell], cell[0], call["genes"],
                                    call["objs"])

    @pytest.mark.parametrize("size", SIZES)
    def test_duplicate_genes_match_eager_oracle(self, size):
        """A population of repeated genes, shuffled: each distinct gene
        once, in sorted order, whichever of its rows came first."""
        genes, _ = explorer.full_design_space(size)
        genes = np.asarray(genes)
        rng = np.random.default_rng(size)
        pop = genes[rng.integers(0, len(genes), size=3 * len(genes))]
        objs = np.asarray(nsga2.evaluate(
            pop, nsga2.NSGA2Config(array_size=size)))
        assert len(np.unique(pop, axis=0)) < len(pop)
        res = explorer.pareto_result_from_population(size, pop, objs)
        front_oracle.assert_matches(res, size, pop, objs)

    def test_dispatch_is_shape_stable(self):
        """After one dispatch of B cells, dispatches of B cells with
        other seeds (other front sizes) build no program."""
        cells = lambda k: [(s, 100 * k + i) for i, s in enumerate(SIZES)]
        kw = dict(pop_size=48, generations=6)
        first = explore_cells(cells(0), **kw)
        built = []

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                built.append(event)

        jax.monitoring.register_event_duration_secs_listener(listen)
        try:
            later = [explore_cells(cells(k), **kw) for k in (1, 2, 3)]
        finally:
            jax.monitoring.unregister_event_duration_listener(listen)
        assert built == []
        sizes = {tuple(len(f) for f in out.values())
                 for out in [first, *later]}
        assert len(sizes) > 1          # the front sizes did change

    def test_launch_keys_are_jax_random_keys(self):
        seeds = [0, 1, 2 ** 31 - 2]
        got = batched_explorer.seed_keys(seeds)
        want = np.stack([jax.random.key_data(jax.random.key(sd))
                         for sd in seeds])
        assert got.dtype == jax.random.key(0).dtype
        np.testing.assert_array_equal(jax.random.key_data(got), want)

    def test_host_seconds_leave_out_the_fetch(self, monkeypatch):
        """`timings["host_s"]` counts launch and post-processing: a fetch
        made slow on purpose does not show in it."""
        cells = [(4096, 0), (16384, 1)]
        kw = dict(pop_size=48, generations=6)
        explore_cells(cells, **kw)                 # built
        get = jax.device_get

        def slow_get(x):
            time.sleep(0.5)
            return get(x)

        monkeypatch.setattr(jax, "device_get", slow_get)
        timings = {}
        t0 = time.perf_counter()
        explore_cells(cells, timings=timings, **kw)
        wall = time.perf_counter() - t0
        assert 0.0 < timings["host_s"] < wall - 0.5
