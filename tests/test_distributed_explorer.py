"""Mesh explore engine: sharded-cells bit-equality with the single-device
explorer, island-model determinism/device-count independence (in-process
and under 8 forced host devices), and true-front recovery."""
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

import front_oracle
from repro.core import explorer, nsga2, pareto
from repro.core.batched_explorer import explore_cells
from repro.parallel import distributed_explorer as dx

REPO = pathlib.Path(__file__).resolve().parents[1]
CELLS = ((4096, 0), (16384, 1), (65536, 0))


def _rows(res):
    return res.to_rows()


def _true_front(array_size: int):
    genes, objs = explorer.full_design_space(array_size)
    mask = np.asarray(pareto.non_dominated_mask(objs))
    return {tuple(g) for g, m in zip(np.asarray(genes), mask) if m}


class TestShardedCells:
    def test_bit_equal_to_single_device_engine(self):
        """islands=1 mesh mode is the acceptance contract: per-cell fronts
        (including metrics) identical to `explore_cells` for the same
        request — so mesh on/off never invalidates a cache tier."""
        pop, gens = 48, 8
        ref = explore_cells(CELLS, pop_size=pop, generations=gens)
        out, facts = dx.explore_cells_mesh(CELLS, pop_size=pop,
                                           generations=gens)
        assert facts["migration_topology"] == "sharded"
        assert facts["islands"] == 1 and facts["migration_rounds"] == 0
        assert facts["mesh_devices"] == jax.device_count()
        assert set(out) == set(ref)
        for cell in CELLS:
            assert _rows(out[cell]) == _rows(ref[cell]), cell

    def test_single_trace_of_run_cell(self):
        jax.clear_caches()
        dx._PROGRAMS.clear()
        before = nsga2.TRACE_COUNTS["run_cell"]
        dx.explore_cells_mesh(CELLS, pop_size=40, generations=5)
        assert nsga2.TRACE_COUNTS["run_cell"] - before == 1
        # warm re-dispatch: program cache hit, no new trace
        dx.explore_cells_mesh(CELLS, pop_size=40, generations=5)
        assert nsga2.TRACE_COUNTS["run_cell"] - before == 1


@pytest.mark.parametrize("islands", [1, 2])
def test_mesh_front_matches_eager_oracle(islands, monkeypatch):
    """The mesh engine's front program against the eager front path, on
    the population each cell hands `pareto_result_from_population` (the
    island union, island by island, when `islands` > 1)."""
    pop = 40
    rec = front_oracle.Recorder(monkeypatch)
    out, _ = dx.explore_cells_mesh(CELLS, islands=islands, migrate_every=4,
                                   pop_size=pop, generations=8)
    assert len(rec.calls) == len(CELLS)
    for cell, call in zip(CELLS, rec.calls):
        assert call["result"] is out[cell]
        assert set(call["kw"]) == {"mask", "report"}
        assert np.asarray(call["genes"]).shape == (islands * pop, 3)
        front_oracle.assert_matches(out[cell], cell[0], call["genes"],
                                    call["objs"])


class TestIslands:
    def test_deterministic_and_facts(self):
        pop, gens = 48, 20
        out1, facts = dx.explore_cells_mesh(
            CELLS[:2], islands=4, migrate_every=10,
            pop_size=pop, generations=gens)
        out2, _ = dx.explore_cells_mesh(
            CELLS[:2], islands=4, migrate_every=10,
            pop_size=pop, generations=gens)
        assert facts == {"mesh_devices": dx.devices_for_islands(
                             dx.default_mesh(), 4),
                         "islands": 4, "migration_topology": "ring",
                         "migration_rounds": 1}
        for cell in CELLS[:2]:
            assert _rows(out1[cell]) == _rows(out2[cell]), cell

    def test_explicit_one_device_submesh_matches_default(self):
        """Forcing the 1-device submesh reproduces the default-mesh result:
        the key schedule is a function of global island ids only."""
        kw = dict(islands=4, migrate_every=8, pop_size=40, generations=16)
        base, _ = dx.explore_cells_mesh(CELLS[:1], **kw)
        one, _ = dx.explore_cells_mesh(
            CELLS[:1], mesh=dx.default_mesh(max_devices=1), **kw)
        assert _rows(base[CELLS[0]]) == _rows(one[CELLS[0]])

    def test_round_schedule_and_divisors(self):
        assert dx._round_schedule(80, 20) == (20, 20, 20, 20)
        assert dx._round_schedule(50, 20) == (20, 20, 10)
        assert dx._round_schedule(5, 20) == (5,)
        with pytest.raises(ValueError):
            dx._round_schedule(10, 0)
        mesh = dx.default_mesh()
        assert dx.devices_for_islands(mesh, 1) == 1
        n = dx.mesh_size(mesh)
        assert dx.devices_for_islands(mesh, n * 6) == n
        with pytest.raises(ValueError):
            dx.explore_cells_mesh(CELLS[:1], islands=0)


@pytest.mark.slow
def test_islands_device_count_independent_and_recover_front():
    """8 forced host devices: the islands=8 run is bit-identical to the
    1-device run of the same request, and the merged union front recovers
    the exhaustive ground-truth Pareto set."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json, sys
        import jax, numpy as np
        from repro.core import explorer, pareto
        from repro.parallel import distributed_explorer as dx

        assert jax.device_count() == 8
        kw = dict(islands=8, migrate_every=10, pop_size=96, generations=60)
        on8, facts8 = dx.explore_cells_mesh([(16384, 0)], **kw)
        assert facts8["mesh_devices"] == 8 and \\
            facts8["migration_topology"] == "ring", facts8
        on1, facts1 = dx.explore_cells_mesh(
            [(16384, 0)], mesh=dx.default_mesh(max_devices=1), **kw)
        assert facts1["mesh_devices"] == 1
        assert on8[(16384, 0)].to_rows() == on1[(16384, 0)].to_rows()

        genes, objs = explorer.full_design_space(16384)
        mask = np.asarray(pareto.non_dominated_mask(objs))
        truth = {tuple(g) for g, m in zip(np.asarray(genes), mask) if m}
        found = {(int(np.log2(s.h)), int(np.log2(s.l)), s.b_adc)
                 for s in on8[(16384, 0)].specs}
        assert found <= truth, sorted(found - truth)
        assert len(found) >= 0.8 * len(truth), (len(found), len(truth))
        print("OK", len(found), "/", len(truth), "front points, 8dev == 1dev")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": str(REPO / "src")})
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
