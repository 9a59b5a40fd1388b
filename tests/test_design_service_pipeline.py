"""Staged pipeline executor (`DesignService.serve()`):
ticket-for-ticket equality with the sequential stages, bucket
streaming / overlap gauges, drain-on-close, per-stage failure
isolation (error artifacts instead of a dead pipeline), preemption
drain vs collect/close races, and the `stats()` snapshot contract.
The full fault-injection matrix lives in `tests/test_service_faults.py`."""
import dataclasses
import threading
import time

import pytest

from repro.api import DesignRequest, DesignSession, Requirements
from repro.serve.design_service import DesignService

# every test here runs threads; a pipeline bug deadlocks rather than
# fails, so each test carries a hard deadline (pytest-timeout in CI,
# the conftest watchdog otherwise)
pytestmark = pytest.mark.timeout(600)

# Same small budget as tests/test_design_api.py: these ride the shared
# process-wide jit cache instead of paying fresh compiles.
POP, GENS = 48, 10
REQS = Requirements(min_tops=0.5, min_snr_db=10.0)


def _request(array_size=4096, seed=0, **kw):
    kw.setdefault("pop_size", POP)
    kw.setdefault("generations", GENS)
    return DesignRequest(array_size=array_size, seed=seed, **kw)


# -- pipelined == sequential ---------------------------------------------

class TestPipelinedEquality:
    def test_pipelined_equals_sequential_stages(self):
        # a mixed batch: laid-out tenants, a front-only tenant, and a
        # poison tenant whose requirements remove everything
        reqs = [_request(seed=0, requirements=REQS, layout=True),
                _request(seed=1, requirements=REQS, layout=True),
                _request(array_size=16384, layout=False),
                _request(seed=2, requirements=Requirements(min_tops=1e9),
                         layout=True)]
        seq = DesignSession().run_many(reqs, strict=False)

        svc = DesignService(coalesce_window_s=0.25)
        with svc.serve():
            tickets = [svc.submit(r) for r in reqs]
            arts = [svc.collect(t, timeout=600) for t in tickets]
        for r, a in zip(reqs, arts):
            assert a.summary() == seq[r].summary()
            assert a.ok == seq[r].ok
            assert a.provenance.pipelined
        assert not arts[3].ok and "removed every Pareto point" in arts[3].error

    def test_concurrent_submits_multi_batch(self):
        # max_coalesce=2 forces several batches in flight concurrently;
        # every tenant must get its own request's artifact back
        svc = DesignService(max_coalesce=2, coalesce_window_s=0.05)
        seeds = list(range(6))
        results, errors = {}, []

        def tenant(sd):
            try:
                t = svc.submit(_request(seed=sd, requirements=REQS,
                                        layout=True))
                results[sd] = svc.collect(t, timeout=600)
            except Exception as e:   # surfaced below
                errors.append(e)

        with svc.serve():
            threads = [threading.Thread(target=tenant, args=(sd,))
                       for sd in seeds]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        assert not errors
        assert sorted(results) == seeds
        assert {results[sd].request.seed for sd in seeds} == set(seeds)
        seq = DesignSession().run_many(
            [_request(seed=sd, requirements=REQS, layout=True)
             for sd in seeds], strict=False)
        for sd in seeds:
            assert results[sd].summary() == seq[results[sd].request].summary()

    def test_multi_batch_overlap_and_waits(self):
        # one request per batch: batch N+1's explore overlaps batch N's
        # layout, which the occupancy clocks must witness
        svc = DesignService(max_coalesce=1)
        with svc.serve():
            tickets = [svc.submit(_request(seed=sd, requirements=REQS,
                                           layout=True))
                       for sd in (0, 1, 2)]
            arts = [svc.collect(t, timeout=600) for t in tickets]
            stats = svc.stats()
        assert stats["service_batches"] == 3
        busy = stats["stage_busy_s"]
        assert busy["explore"] > 0 and busy["layout"] > 0
        assert busy["distill"] >= 0 and busy["finalize"] > 0
        assert stats["pipeline_overlap_s"] > 0
        assert 0 < stats["pipeline_overlap_fraction"] <= 1.0
        for a in arts:
            assert a.provenance.pipelined
            assert a.provenance.explore_wait_s >= 0.0
            assert a.provenance.layout_wait_s >= 0.0
        # later batches waited on the explore queue behind earlier ones
        assert arts[-1].provenance.explore_wait_s > 0.0

    def test_sequential_driver_reports_not_pipelined(self):
        art = DesignSession().run(_request(requirements=REQS, layout=True))
        assert not art.provenance.pipelined
        assert art.provenance.explore_wait_s == 0.0
        assert art.provenance.layout_wait_s == 0.0


# -- lifecycle ------------------------------------------------------------

class TestPipelineLifecycle:
    def test_mid_pipeline_close_drains_all_tickets(self):
        # close() immediately after a burst of submissions: the final
        # drain must push every admitted AND still-queued batch through
        # all four stages — no ticket lost
        svc = DesignService(max_coalesce=1)
        svc.serve()
        tickets = [svc.submit(_request(seed=sd, layout=False))
                   for sd in range(4)]
        svc.close()
        for t in tickets:
            art = svc.poll(t)
            assert art is not None and art.ok
        assert len(svc) == 0

    def test_front_only_requests_flow_through(self):
        # zero layout buckets: the batch must still traverse the layout
        # stage (as a no-op) and finalize in order
        svc = DesignService(coalesce_window_s=0.05)
        with svc.serve():
            t = svc.submit(_request(layout=False))
            art = svc.collect(t, timeout=600)
        assert art.ok and art.layout_rows is None
        assert art.provenance.layout_dispatches == 0
        assert art.provenance.pipelined

    def test_artifact_cache_hits_flow_through_pipeline(self, tmp_path):
        req = _request(requirements=REQS, layout=True)
        DesignSession(artifact_cache=tmp_path).run(req)   # fill the cache
        svc = DesignService(DesignSession(artifact_cache=tmp_path))
        with svc.serve():
            t = svc.submit(req)
            art = svc.collect(t, timeout=600)
        assert art.provenance.served_from == "artifact_cache"
        assert art.provenance.explorer_dispatches == 0
        assert art.provenance.pipelined

    def test_pipeline_depth_validation(self):
        with pytest.raises(ValueError, match="pipeline_depth"):
            DesignService(pipeline_depth=0)

    def test_serve_refused_while_sync_drain_active(self):
        # the converse of run()-refused-under-pump: a mid-flight
        # run()/step() drain owns the session (simulated the same way
        # test_submit_and_serve_refused_while_closing simulates close)
        svc = DesignService()
        svc._sync_dispatchers = 1
        with pytest.raises(RuntimeError, match="run\\(\\)/step\\(\\) drain"):
            svc.serve()
        svc._sync_dispatchers = 0
        with svc.serve():
            pass


# -- failure isolation ----------------------------------------------------

class TestStageFailureIsolation:
    @pytest.mark.parametrize("stage", ["explore_stage", "distill_stage",
                                       "finalize_stage"])
    def test_batch_stage_failure_isolates_to_error_artifacts(
            self, stage, monkeypatch):
        # an always-failing batch stage no longer kills the pipeline:
        # after the retry budget the batch's tickets complete with
        # error artifacts, and the pump stays alive for the next batch
        svc = DesignService(coalesce_window_s=0.02, max_retries=0,
                            retry_backoff_s=0.001)
        real = getattr(svc.session, stage)

        def boom(*a, **kw):
            raise RuntimeError(f"injected {stage} failure")

        monkeypatch.setattr(svc.session, stage, boom)
        svc.serve()
        tickets = [svc.submit(_request(seed=sd, requirements=REQS,
                                       layout=True))
                   for sd in (0, 1)]
        arts = [svc.collect(t, timeout=600) for t in tickets]
        for a in arts:
            assert not a.ok
            assert f"injected {stage} failure" in a.error
            assert a.provenance.served_from == "error"
        # the pipeline survived: restore the stage, next batch is clean
        monkeypatch.setattr(svc.session, stage, real)
        t2 = svc.submit(_request(seed=2, requirements=REQS, layout=True))
        assert svc.collect(t2, timeout=600).ok
        svc.close()   # clean close: no restore, no re-raise
        assert len(svc) == 0

    def test_layout_failure_isolates_per_bucket(self, monkeypatch):
        # layout failures are finer-grained still: only tickets touching
        # the dead bucket(s) error out (here: all buckets die, so the
        # laid-out tenant errors while its front survives on the artifact)
        svc = DesignService(coalesce_window_s=0.02, max_retries=0,
                            retry_backoff_s=0.001)

        def boom(*a, **kw):
            raise RuntimeError("injected layout failure")

        monkeypatch.setattr(svc.session, "layout_stage", boom)
        svc.serve()
        ticket = svc.submit(_request(requirements=REQS, layout=True))
        art = svc.collect(ticket, timeout=600)
        assert not art.ok and "layout bucket" in art.error
        assert art.pareto.specs          # distilled front still attached
        assert art.layout_rows is None
        assert svc.stats()["bucket_failures"] >= 1
        svc.close()

    def test_blocked_collector_woken_by_error_artifact(self, monkeypatch):
        # the window is long, so the collector blocks BEFORE the batch
        # dispatches; the isolated failure must wake it with the error
        # artifact (not strand it waiting for a dead pipeline)
        svc = DesignService(max_coalesce=2, coalesce_window_s=30.0,
                            max_retries=0, retry_backoff_s=0.001)

        def boom(*a, **kw):
            raise RuntimeError("injected explore failure")

        monkeypatch.setattr(svc.session, "explore_stage", boom)
        svc.serve()
        ticket = svc.submit(_request(layout=False))
        got: list = []

        def collector():
            got.append(svc.collect(ticket, timeout=600))

        th = threading.Thread(target=collector)
        th.start()
        time.sleep(0.2)            # collector is parked on the ticket
        svc.submit(_request(seed=1, layout=False))   # fills the batch
        th.join(timeout=60)
        assert not th.is_alive()
        assert got and not got[0].ok
        assert "injected explore failure" in got[0].error
        svc.close()


# -- preemption drain vs collect()/poll()/close() races --------------------

class TestPreemptDrainRaces:
    def test_collect_and_poll_raced_against_close_during_drain(
            self, tmp_path):
        # a preemption drain is in flight (slow explore keeps batches
        # in the pipeline); close() races blocked collect(timeout=...)
        # callers and a poll() spinner.  Contract: no deadlock, and
        # every ticket resolves exactly one way — an artifact (drained)
        # or PendingTicket (journaled for replay)
        from repro.runtime.fault_tolerance import PreemptionGuard
        from repro.serve.design_service import PendingTicket

        guard = PreemptionGuard()
        svc = DesignService(max_coalesce=1, pipeline_depth=1,
                            coalesce_window_s=0.01, guard=guard,
                            journal=tmp_path / "journal.jsonl")
        real_explore = svc.session.explore_stage

        def slow_explore(reqs):
            time.sleep(0.3)        # hold batches in the pipeline
            return real_explore(reqs)

        svc.session.explore_stage = slow_explore
        svc.serve()
        tickets = [svc.submit(_request(seed=sd, layout=False))
                   for sd in range(4)]
        outcomes: dict[int, str] = {}
        errors: list = []

        def collector(t):
            try:
                svc.collect(t, timeout=120, keep_done=True)
                outcomes[t] = "drained"
            except PendingTicket:
                outcomes[t] = "journaled"
            except Exception as e:
                errors.append((t, e))

        def poller(t):
            try:
                while True:
                    if svc.poll(t) is not None:
                        outcomes[t] = "drained"
                        return
                    time.sleep(0.02)
            except PendingTicket:
                outcomes[t] = "journaled"
            except Exception as e:
                errors.append((t, e))

        threads = [threading.Thread(target=collector, args=(t,))
                   for t in tickets[:-1]]
        threads.append(threading.Thread(target=poller,
                                        args=(tickets[-1],)))
        for th in threads:
            th.start()
        time.sleep(0.1)            # batch 0 is mid-explore
        guard.request()            # preemption drain begins...
        svc.close()                # ...and close() races it
        for th in threads:
            th.join(timeout=120)
            assert not th.is_alive(), "collector/poller deadlocked"
        assert not errors, errors
        assert sorted(outcomes) == sorted(tickets)   # no ticket lost
        assert "drained" in outcomes.values()        # batch 0 made it
        drained = [t for t, o in outcomes.items() if o == "drained"]
        for t in drained:          # drained artifacts are real and ok
            assert svc.done[t].ok
        journaled = [t for t, o in outcomes.items() if o == "journaled"]
        # the WAL holds exactly the tickets that did not drain locally
        # (plus any that drained after being journaled mid-flight)
        assert len(svc.journal) >= len(journaled)
        assert svc.stats()["preemptions"] == 1


# -- stats() snapshot -----------------------------------------------------

class TestStatsSnapshot:
    def test_snapshot_is_isolated_and_gauged(self):
        svc = DesignService()
        t0 = svc.submit(_request(seed=0, layout=False))
        svc.submit(_request(seed=1, layout=False))
        before = svc.stats()
        assert before["queue_depth"] == 2
        assert before["done_count"] == 0
        assert not before["pump_alive"]
        # mutating the snapshot must not corrupt the service
        before["explorer_dispatches"] = 10 ** 9
        before["stage_busy_s"]["explore"] = -1.0
        svc.run()
        after = svc.stats()
        assert after["queue_depth"] == 0
        assert after["done_count"] == 2
        assert after["explorer_dispatches"] < 10 ** 9
        assert after["stage_busy_s"]["explore"] >= 0.0
        assert set(after["stage_queue_depth"]) == {"explore", "distill",
                                                   "layout", "finalize"}
        svc.collect(t0)
        assert svc.stats()["done_count"] == 1

    def test_inflight_gauge_returns_to_zero(self):
        svc = DesignService(coalesce_window_s=0.02)
        with svc.serve():
            t = svc.submit(_request(layout=False))
            svc.collect(t, timeout=600)
        stats = svc.stats()
        assert stats["inflight_batches"] == 0
        assert all(d == 0 for d in stats["stage_queue_depth"].values())


# -- provenance schema ----------------------------------------------------

class TestPipelineProvenance:
    def test_waits_round_trip_through_json(self, tmp_path):
        svc = DesignService(max_coalesce=1)
        with svc.serve():
            tickets = [svc.submit(_request(seed=sd, requirements=REQS,
                                           layout=True))
                       for sd in (0, 1)]
            art = svc.collect(tickets[1], timeout=600)
            svc.collect(tickets[0], timeout=600)
        path = tmp_path / "artifact.json"
        art.to_json(path)
        from repro.api import DesignArtifact

        back = DesignArtifact.from_json(path)
        assert back.provenance == art.provenance
        assert back.provenance.pipelined
        assert back.provenance.explore_wait_s == art.provenance.explore_wait_s

    def test_coalesced_batch_shares_explore_wait(self):
        svc = DesignService(coalesce_window_s=0.2)
        with svc.serve():
            ta = svc.submit(_request(seed=0, layout=False))
            tb = svc.submit(_request(seed=1, layout=False))
            a = svc.collect(ta, timeout=600)
            b = svc.collect(tb, timeout=600)
        assert a.provenance.coalesced == b.provenance.coalesced == 2
        # one batch -> one explore-queue wait, stamped on both tenants
        assert a.provenance.explore_wait_s == b.provenance.explore_wait_s
