"""The unified `repro.api` front-end: request round-trips, session
caching (zero-retrace contract), service coalescing, and artifact
equality with the pre-API call sequence."""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.api.session import ARTIFACT_SCHEMA
from repro.api import (DesignArtifact, DesignRequest, DesignSession,
                       Requirements)
from repro.core import explorer, nsga2
from repro.core.batched_explorer import explore_batch
from repro.eda.batched_flow import generate_layouts
from repro.serve.design_service import DesignService

# Small budget shared by most tests: fast, and known (from the batched
# flow tests) to leave >= 2 DRC-clean survivors at 4 kb under REQS.
POP, GENS = 48, 10
REQS = Requirements(min_tops=0.5, min_snr_db=10.0)


def _request(array_size=4096, seed=0, **kw):
    kw.setdefault("pop_size", POP)
    kw.setdefault("generations", GENS)
    return DesignRequest(array_size=array_size, seed=seed, **kw)


def _legacy(req: DesignRequest):
    """The pre-API call sequence: explore -> filter -> generate_layouts."""
    front = explore_batch((req.array_size,), (req.seed,),
                          pop_size=req.pop_size,
                          generations=req.generations,
                          cal=req.cal)[req.cell]
    distilled = (front if req.requirements.is_noop
                 else front.filter(**req.requirements.as_filter_kwargs()))
    rows = None
    if req.layout:
        rows = generate_layouts(distilled.specs, coarse=req.coarse,
                                capacity=req.capacity).metrics_rows()
    return distilled, rows


class TestDesignRequest:
    def test_frozen_hashable_json_roundtrip(self):
        req = _request(requirements=REQS, layout=True)
        again = DesignRequest.from_json(req.to_json())
        assert again == req
        assert hash(again) == hash(req)
        assert again.sha() == req.sha()
        with pytest.raises(dataclasses.FrozenInstanceError):
            req.array_size = 8192

    def test_infinite_thresholds_survive_strict_json(self):
        req = _request(requirements=Requirements(min_tops=1.0))
        d = json.loads(req.to_json())  # default thresholds are +/-inf
        assert d["requirements"]["min_snr_db"] == "-inf"
        assert DesignRequest.from_json(json.dumps(d)) == req
        # an exclude-everything threshold must NOT collapse to a default
        hard = _request(requirements=Requirements(min_tops=float("inf")))
        back = DesignRequest.from_json(hard.to_json())
        assert back == hard and back.sha() != req.sha()

    def test_validation(self):
        with pytest.raises(ValueError):
            DesignRequest(array_size=3000)       # not a power of two
        with pytest.raises(ValueError):
            _request(pop_size=0)

    def test_shape_signature_ignores_operands(self):
        a, b = _request(4096, seed=0), _request(16384, seed=3)
        assert a.shape_signature() == b.shape_signature()
        assert a.explore_key() != b.explore_key()
        assert _request(pop_size=POP + 8).shape_signature() \
            != a.shape_signature()


class TestParetoResultJson:
    def test_from_json_roundtrip(self, tmp_path):
        res = explore_batch((4096,), (0,), pop_size=POP,
                            generations=GENS)[(4096, 0)]
        path = tmp_path / "pareto.json"
        res.to_json(path)
        back = explorer.ParetoResult.from_json(path)
        assert back.array_size == res.array_size
        assert back.specs == res.specs
        assert set(back.metrics) == set(res.metrics)
        for k in res.metrics:
            np.testing.assert_array_equal(back.metrics[k], res.metrics[k])

    def test_empty_frontier_raises_clearly(self):
        res = explore_batch((4096,), (0,), pop_size=POP,
                            generations=GENS)[(4096, 0)]
        empty = res.filter(min_tops=1e9)
        with pytest.raises(ValueError, match="empty Pareto frontier"):
            empty.best("tops")
        with pytest.raises(ValueError, match="empty Pareto frontier"):
            empty.filter(min_tops=1.0)


class TestDesignSession:
    def test_artifact_equals_legacy_path(self):
        req = _request(requirements=REQS, layout=True)
        art = DesignSession().run(req)
        distilled, rows = _legacy(req)
        assert [s.as_tuple() for s in art.pareto.specs] \
            == [s.as_tuple() for s in distilled.specs]
        assert art.pareto.to_rows() == distilled.to_rows()
        assert list(art.layout_rows) == rows
        assert art.layouts is not None and len(art.layouts) == len(distilled)

    def test_zero_retrace_for_repeat_and_shape_compatible_requests(self):
        jax.clear_caches()   # order-independent: force a fresh compile
        ses = DesignSession()
        before = nsga2.TRACE_COUNTS["run_cell"]
        req = _request(4096, layout=False)
        a1 = ses.run(req)
        assert nsga2.TRACE_COUNTS["run_cell"] - before == 1
        assert a1.provenance.new_traces == 1
        # repeat request: front cache, no dispatch, no trace
        a2 = ses.run(req)
        assert nsga2.TRACE_COUNTS["run_cell"] - before == 1
        assert a2.provenance.front_cache_hit
        assert a2.provenance.explorer_dispatches == 0
        # shape-signature-compatible variants (size and seed are traced
        # operands): new dispatches, ZERO new traces
        a3 = ses.run(dataclasses.replace(req, array_size=16384))
        a4 = ses.run(dataclasses.replace(req, seed=7))
        assert nsga2.TRACE_COUNTS["run_cell"] - before == 1
        assert a3.provenance.new_traces == 0
        assert a4.provenance.explorer_dispatches == 1
        assert ses.stats["program_cache_hits"] >= 2
        assert a1.pareto.to_rows() == a2.pareto.to_rows()

    def test_requirements_removing_everything(self):
        ses = DesignSession()
        req = _request(requirements=Requirements(min_tops=1e9), layout=True)
        with pytest.raises(ValueError, match="removed every Pareto point"):
            ses.run(req)
        # without layout the empty distilled front is a valid answer
        art = ses.run(dataclasses.replace(req, layout=False))
        assert len(art.pareto) == 0 and art.layout_rows is None

    def test_artifact_json_roundtrip(self, tmp_path):
        req = _request(requirements=REQS, layout=True)
        art = DesignSession().run(req)
        path = tmp_path / "artifact.json"
        art.to_json(path)
        back = DesignArtifact.from_json(path)
        assert back.request == req
        assert back.summary() == art.summary()
        assert back.provenance == art.provenance
        assert back.layouts is None   # tensors are not serialized

    def test_route_provenance_columns(self):
        req = _request(requirements=REQS, layout=True)
        art = DesignSession().run(req)
        p = art.provenance
        # the auto engine choice is recorded: conflict-aware concurrent
        # scheduler off-TPU, scanned per-slot wavefronts on TPU
        expected = "scan" if jax.default_backend() == "tpu" else "concurrent"
        assert p.route_engine == expected
        assert p.route_rounds > 0 and p.route_collisions >= 0
        d = art.to_dict()
        assert d["schema"] == ARTIFACT_SCHEMA >= 4
        for k in ("route_engine", "route_rounds", "route_collisions"):
            assert k in d["provenance"]

    def test_mesh_provenance_columns(self):
        req = _request(requirements=REQS, layout=True, islands=2,
                       migrate_every=5)
        session = DesignSession()
        art = session.run(req)
        p = art.provenance
        assert p.served_from == "explorer"
        assert p.islands == 2 and p.migration_topology == "ring"
        assert p.mesh_devices >= 1 and p.migration_rounds == 1
        assert session.stats["mesh_dispatches"] == 1
        d = art.to_dict()
        for k in ("mesh_devices", "islands", "migration_topology",
                  "migration_rounds"):
            assert k in d["provenance"]
        # islands=1 requests never touch the mesh engine by default
        plain = session.run(_request(seed=3))
        assert plain.provenance.migration_topology == ""
        assert session.stats["mesh_dispatches"] == 1


class TestDesignService:
    def test_coalesces_concurrent_requests_into_one_dispatch(self):
        reqs = [_request(4096, seed=0, requirements=REQS, layout=True),
                _request(4096, seed=1, requirements=REQS, layout=True)]
        svc = DesignService()
        tickets = [svc.submit(r) for r in reqs]
        done = svc.run()
        assert svc.stats()["explorer_dispatches"] == 1
        for r, t in zip(reqs, tickets):
            art = done[t]
            assert art.provenance.coalesced == 2
            # grid-shape buckets never exceed the distinct shapes of the
            # request's own surviving specs
            assert 1 <= art.provenance.layout_dispatches <= len(art.pareto)
            distilled, rows = _legacy(r)
            assert art.pareto.to_rows() == distilled.to_rows()
            assert list(art.layout_rows) == rows

    def test_bucketing_bounded_by_distinct_grid_shapes(self):
        from repro.api.session import _bucket_key, _grid_sig

        reqs = [_request(4096, seed=0, requirements=REQS, layout=True),
                _request(4096, seed=1, requirements=REQS, layout=True)]
        svc = DesignService()
        for r in reqs:
            svc.submit(r)
        done = svc.run()
        buckets = {_bucket_key(s, art.request.coarse, art.request.capacity)
                   for art in done.values() for s in art.pareto.specs}
        exact = {(art.request.coarse, art.request.capacity)
                 + _grid_sig(s, art.request.coarse)
                 for art in done.values() for s in art.pareto.specs}
        assert svc.stats()["layout_dispatches"] == len(buckets)
        # quantization merges exact shapes, never splits them
        assert len(buckets) <= len(exact) <= sum(
            len(a.pareto) for a in done.values())

    def test_max_coalesce_splits_batches(self):
        svc = DesignService(max_coalesce=1)
        for sd in range(2):
            svc.submit(_request(4096, seed=sd, layout=False))
        svc.run()
        assert svc.stats()["explorer_dispatches"] == 2

    def test_poison_request_cannot_starve_the_batch(self):
        svc = DesignService()
        bad = svc.submit(_request(
            4096, requirements=Requirements(min_tops=1e9), layout=True))
        good = svc.submit(_request(4096, seed=1, requirements=REQS,
                                   layout=True))
        done = svc.run()
        assert len(svc) == 0
        assert not done[bad].ok
        assert "removed every Pareto point" in done[bad].error
        assert done[bad].layout_rows is None and len(done[bad].pareto) == 0
        assert done[good].ok and len(done[good].layout_rows) >= 2

    def test_tickets_demux_to_their_own_requests(self):
        svc = DesignService()
        ra = _request(4096, seed=0, layout=False)
        rb = _request(16384, seed=0, layout=False)
        ta, tb = svc.submit(ra), svc.submit(rb)
        done = svc.run()
        assert done[ta].pareto.array_size == 4096
        assert done[tb].pareto.array_size == 16384
        assert svc.collect(ta) is done[ta]
