"""Fault-tolerant design fleet: per-bucket retry + isolation in the
layout pool, supervised stage workers, preemption journal + replay,
straggler shedding, and the `TicketJournal` / `PreemptionGuard` /
`run_supervised` primitives they are built on.

Every fault here is injected deterministically (`FailureInjector`
schedules, monkeypatched stage functions, injectable `sleep`) — no real
signals, no flaky timing assumptions beyond generous deadlines."""
import threading
import time

import pytest

from repro.api import DesignRequest, DesignSession, Requirements, TicketJournal
from repro.runtime.fault_tolerance import (FailureInjector, PreemptionGuard,
                                           SimulatedNodeFailure,
                                           StragglerMonitor, capped_backoff,
                                           run_supervised)
from repro.serve.design_service import DesignService, PendingTicket

# threaded pipeline tests deadlock rather than fail when broken
pytestmark = pytest.mark.timeout(900)

POP, GENS = 16, 4
REQS = Requirements(min_tops=0.5, min_snr_db=10.0)


def _request(array_size=4096, seed=0, **kw):
    kw.setdefault("pop_size", POP)
    kw.setdefault("generations", GENS)
    return DesignRequest(array_size=array_size, seed=seed, **kw)


def _fast_svc(**kw):
    """A service with sub-millisecond retry backoff (tests should not
    wait out real backoff) and a short coalescing window."""
    kw.setdefault("coalesce_window_s", 0.02)
    kw.setdefault("retry_backoff_s", 0.001)
    kw.setdefault("retry_backoff_cap_s", 0.002)
    return DesignService(**kw)


# -- primitives: backoff, guard, supervisor, injector ----------------------

class TestCappedBackoff:
    def test_exponential_then_capped(self):
        delays = [capped_backoff(n, base_s=0.1, cap_s=0.5)
                  for n in (1, 2, 3, 4, 5)]
        assert delays == [0.1, 0.2, 0.4, 0.5, 0.5]

    def test_jitter_bounded_and_attempt_validated(self):
        import random
        rng = random.Random(7)
        for _ in range(50):
            d = capped_backoff(3, base_s=0.1, cap_s=10.0,
                               jitter_frac=0.25, rng=rng)
            assert 0.4 <= d <= 0.4 * 1.25
        with pytest.raises(ValueError, match="1-based"):
            capped_backoff(0, base_s=0.1, cap_s=1.0)


class TestPreemptionGuard:
    def test_double_install_raises_and_uninstall_restores_once(self):
        import signal
        before = signal.getsignal(signal.SIGTERM)
        guard = PreemptionGuard()
        assert not guard.installed
        guard.install()
        assert guard.installed
        with pytest.raises(RuntimeError, match="install\\(\\) called twice"):
            guard.install()
        guard.uninstall()
        assert not guard.installed
        assert signal.getsignal(signal.SIGTERM) is before
        # idempotent: a second uninstall must not re-restore stale
        # handlers over someone else's
        other = PreemptionGuard().install()
        guard.uninstall()   # no-op, NOT a restore of `before`
        assert signal.getsignal(signal.SIGTERM) == other._handler
        other.uninstall()
        assert signal.getsignal(signal.SIGTERM) is before

    def test_context_manager_and_request_without_install(self):
        import signal
        before = signal.getsignal(signal.SIGTERM)
        with PreemptionGuard() as guard:
            assert guard.installed
            assert not guard.preempted
            guard.request()
            assert guard.preempted
        assert not guard.installed
        assert signal.getsignal(signal.SIGTERM) is before
        # request() never needs install() (the test path)
        g = PreemptionGuard()
        g.request()
        assert g.preempted and not g.installed


class TestRunSupervised:
    def test_backoff_spacing_between_restarts(self):
        slept, calls = [], []

        def crashy():
            calls.append(1)
            if len(calls) < 4:
                raise SimulatedNodeFailure("boom")
            return 0

        code = run_supervised(crashy, max_restarts=5, backoff_s=0.1,
                              backoff_cap_s=0.25, sleep=slept.append)
        assert code == 0 and len(calls) == 4
        assert slept == [0.1, 0.2, 0.25]   # capped exponential

    def test_budget_exhausted_raises(self):
        slept = []

        def always():
            raise SimulatedNodeFailure("boom")

        with pytest.raises(RuntimeError, match="restart budget exhausted"):
            run_supervised(always, max_restarts=2, backoff_s=0.05,
                           sleep=slept.append)
        assert len(slept) == 2   # no sleep after the final give-up

    def test_restart_on_filters_exception_types(self):
        def raises_value_error():
            raise ValueError("not restartable by default")

        with pytest.raises(ValueError):
            run_supervised(raises_value_error, backoff_s=0.0)
        calls = []

        def once():
            calls.append(1)
            if len(calls) == 1:
                raise ValueError("restartable here")
            return 0

        assert run_supervised(once, restart_on=(Exception,),
                              backoff_s=0.0) == 0

    def test_on_restart_callback_counts(self):
        seen, calls = [], []

        def twice():
            calls.append(1)
            if len(calls) < 3:
                raise SimulatedNodeFailure("boom")
            return 0

        run_supervised(twice, backoff_s=0.0, on_restart=seen.append)
        assert seen == [1, 2]


class TestFailureInjector:
    def test_stage_schedule_fires_once_per_unit(self):
        inj = FailureInjector(fail_at={"layout": [2]})
        inj.fire("layout", 0)
        inj.fire("layout", 1)
        with pytest.raises(SimulatedNodeFailure, match="layout .* unit 2"):
            inj.fire("layout", 2)
        inj.fire("layout", 3)    # a retried unit gets a new index: no fire
        inj.fire("explore", 2)   # other stages unaffected
        assert inj.fired == [("layout", 2, "node")]

    def test_per_entry_kind_override_and_preempt(self):
        guard = PreemptionGuard()
        inj = FailureInjector(fail_at={"admit": [(1, "preempt")],
                                       "layout": [0]}, guard=guard)
        inj.fire("admit", 0)
        assert not guard.preempted
        inj.fire("admit", 1)
        assert guard.preempted
        with pytest.raises(SimulatedNodeFailure):
            inj.fire("layout", 0)

    def test_preempt_without_guard_and_unknown_kind(self):
        with pytest.raises(ValueError, match="PreemptionGuard"):
            FailureInjector(fail_at={"layout": [(0, "preempt")]}) \
                .fire("layout", 0)
        with pytest.raises(ValueError, match="unknown failure kind"):
            FailureInjector(fail_at={"layout": [(0, "meteor")]}) \
                .fire("layout", 0)

    def test_slow_kind_sleeps(self, monkeypatch):
        import repro.runtime.fault_tolerance as ft
        slept = []
        monkeypatch.setattr(ft.time, "sleep", slept.append)
        FailureInjector(kind="slow", slow_seconds=3.0,
                        fail_at={"layout": [0]}).fire("layout", 0)
        assert slept == [3.0]

    def test_legacy_train_step_shape_still_works(self):
        inj = FailureInjector(fail_at_steps=(5,))
        inj.maybe_fail(4)
        with pytest.raises(SimulatedNodeFailure):
            inj.maybe_fail(5)


# -- ticket journal (the preemption WAL) -----------------------------------

class TestTicketJournal:
    def test_write_replay_roundtrip_preserves_order(self, tmp_path):
        j = TicketJournal(tmp_path / "wal" / "journal.jsonl")
        reqs = [_request(seed=sd, layout=False) for sd in (3, 1, 2)]
        assert j.write(reqs) == 3
        assert len(j) == 3
        assert j.replay() == reqs        # admission order, not seed order
        assert j.replay() == reqs        # replay does NOT clear
        j.clear()
        assert j.replay() == [] and len(j) == 0

    def test_write_is_full_rewrite_and_empty_clears(self, tmp_path):
        j = TicketJournal(tmp_path / "journal.jsonl")
        j.write([_request(seed=1, layout=False)])
        j.write([_request(seed=2, layout=False)])
        assert [r.seed for r in j.replay()] == [2]   # replaced, not appended
        j.write([])
        assert not j.path.exists()

    def test_corrupt_line_skipped_and_counted(self, tmp_path):
        j = TicketJournal(tmp_path / "journal.jsonl")
        good = _request(seed=9, layout=False)
        j.write([good])
        j.path.write_text("this is not json\n" + good.to_json() + "\n")
        assert j.replay() == [good]
        assert j.stats["rejects"] == 1

    def test_beside_cache_colocation(self, tmp_path):
        from repro.api import ArtifactCache
        from repro.api.artifact_cache import JOURNAL_NAME
        cache = ArtifactCache(tmp_path / "cache")
        j = TicketJournal.beside(cache)
        assert j.path == cache.root / JOURNAL_NAME


# -- per-bucket retry + isolation in the layout pool -----------------------

class TestBucketIsolation:
    def test_killed_bucket_retries_then_succeeds(self):
        # the first layout dispatch (unit 0) dies; the retry is a NEW
        # unit index, so the injection fires exactly once and the
        # bucket completes on attempt 2
        inj = FailureInjector(fail_at={"layout": [0]})
        svc = _fast_svc(injector=inj, max_retries=2)
        ref = DesignSession().run_many(
            [_request(requirements=REQS, layout=True)], strict=False)
        with svc.serve():
            t = svc.submit(_request(requirements=REQS, layout=True))
            art = svc.collect(t, timeout=600)
        assert art.ok and art.error is None
        (ref_art,) = ref.values()
        assert art.summary() == ref_art.summary()
        assert art.provenance.retried_buckets == 1
        assert art.provenance.attempts >= 2
        stats = svc.stats()
        assert stats["bucket_retries"] == 1
        assert stats["bucket_failures"] == 0
        assert inj.fired == [("layout", 0, "node")]

    def test_exhausted_bucket_isolates_only_touching_tickets(self):
        # two coalesced tenants with DISJOINT bucket sets (different
        # array sizes quantize to different grid shapes); every dispatch
        # of tenant A's first bucket dies and the budget is zero — A
        # completes with artifact.error, B finalizes untouched
        inj = FailureInjector(fail_at={"layout": [0]})
        svc = _fast_svc(max_coalesce=2, coalesce_window_s=0.3,
                        injector=inj, max_retries=0)
        ra = _request(array_size=4096, seed=0, requirements=REQS, layout=True)
        rb = _request(array_size=16384, seed=1, requirements=REQS,
                      layout=True)
        ref = DesignSession().run_many([ra, rb], strict=False)
        with svc.serve():
            ta = svc.submit(ra)
            tb = svc.submit(rb)
            aa = svc.collect(ta, timeout=600)
            ab = svc.collect(tb, timeout=600)
        assert not aa.ok
        assert "layout bucket" in aa.error and "failed" in aa.error
        assert aa.pareto.specs        # the distilled front still rides along
        assert aa.layout_rows is None
        assert ab.ok and ab.error is None
        assert ab.summary() == ref[rb].summary()
        stats = svc.stats()
        assert stats["bucket_failures"] == 1
        assert stats["bucket_retries"] == 0
        assert stats["service_batches"] == 1   # one batch, two fates

    def test_batch_stage_failure_yields_error_artifacts(self, monkeypatch):
        # a whole-batch stage (explore) that fails through its retry
        # budget turns into per-ticket error artifacts — the pipeline
        # survives and serves the next batch
        svc = _fast_svc(max_retries=1)
        calls = []

        def boom(*a, **kw):
            calls.append(1)
            raise RuntimeError("injected explore failure")

        real = svc.session.explore_stage
        monkeypatch.setattr(svc.session, "explore_stage", boom)
        with svc.serve():
            t = svc.submit(_request(layout=False))
            art = svc.collect(t, timeout=600)
            assert not art.ok
            assert "explore stage failed after 2 attempt(s)" in art.error
            assert art.provenance.served_from == "error"
            assert len(calls) == 2            # initial + one retry
            # the pipeline is still alive: the next batch serves fine
            monkeypatch.setattr(svc.session, "explore_stage", real)
            t2 = svc.submit(_request(seed=1, layout=False))
            assert svc.collect(t2, timeout=600).ok
        stats = svc.stats()
        assert stats["explore_stage_retries"] == 1
        assert stats["explore_stage_failures"] == 1


# -- supervised stage workers ----------------------------------------------

class TestSupervisedWorkers:
    def test_worker_crash_restarts_in_process_and_unit_survives(self):
        svc = _fast_svc()
        real = svc._process_explore
        crashes = []

        def flaky(batch):
            if not crashes:
                crashes.append(1)
                raise RuntimeError("worker loop crash")
            real(batch)

        svc._process_explore = flaky
        with svc.serve():
            t = svc.submit(_request(layout=False))
            art = svc.collect(t, timeout=600)
        assert art.ok    # the in-hand batch was re-queued, not lost
        assert svc.stats()["stage_worker_restarts"] == 1

    def test_restart_budget_exhaustion_is_terminal_and_restores(self):
        svc = _fast_svc(worker_restarts=1)

        def always(batch):
            raise RuntimeError("hopeless worker")

        svc._process_explore = always
        svc.serve()
        ticket = svc.submit(_request(layout=False))
        with pytest.raises(RuntimeError, match="pump failed"):
            svc.collect(ticket, timeout=600)
        with pytest.raises(RuntimeError, match="restored"):
            svc.close()
        assert svc.stats()["stage_worker_restarts"] == 1
        # the ticket is back in the queue — the synchronous drain path
        # (run_many, untouched by the patch) still serves it
        assert svc.poll(ticket) is None
        assert svc.run()[ticket].ok


# -- preemption: drain, journal, replay ------------------------------------

class TestPreemptionReplay:
    def _drain_pump(self, svc, timeout=600.0):
        deadline = time.monotonic() + timeout
        while svc._pump is not None and svc._pump.is_alive():
            assert time.monotonic() < deadline, "preempted pump never exited"
            time.sleep(0.02)

    def test_preempt_journals_then_fresh_service_replays(self, tmp_path):
        reqs = [_request(seed=sd, layout=False) for sd in range(4)]
        ref = DesignSession().run_many(reqs, strict=False)
        guard = PreemptionGuard()
        svc = _fast_svc(session=DesignSession(artifact_cache=tmp_path),
                        max_coalesce=1, pipeline_depth=1, guard=guard)
        assert svc.journal is not None
        assert svc.journal.path.parent == svc.session.artifact_cache.root
        svc.serve()
        tickets = [svc.submit(r) for r in reqs]
        guard.request()              # simulated SIGTERM
        self._drain_pump(svc)
        svc.close()

        drained, journaled = {}, []
        for t, r in zip(tickets, reqs):
            try:
                art = svc.poll(t)
            except PendingTicket:
                journaled.append((t, r))
                continue
            assert art is not None, "drain finished with an unset ticket"
            drained[r] = art
        stats = svc.stats()
        assert stats["preemptions"] == 1
        assert stats["preempted"]
        assert stats["journaled_tickets"] == len(journaled) > 0
        assert [r.seed for r in svc.journal.replay()] \
            == [r.seed for _, r in journaled]   # admission order preserved
        with pytest.raises(RuntimeError, match="preempted"):
            svc.submit(_request(seed=99, layout=False))

        # a fresh service over the same cache root replays the journal
        svc2 = _fast_svc(session=DesignSession(artifact_cache=tmp_path),
                         max_coalesce=1)
        svc2.serve()
        replayed = svc2.stats()["replayed_tickets"]
        assert replayed == len(journaled)
        assert len(svc2.journal) == 0    # cleared once resubmitted
        arts2 = [svc2.collect(t, timeout=600)
                 for t in range(replayed)]
        svc2.close()
        for (orig_t, r), art in zip(journaled, arts2):
            assert art.provenance.served_from == "journal_replay"
            assert art.summary() == ref[r].summary()
        # drained tickets match the uninterrupted reference too
        for r, art in drained.items():
            assert art.summary() == ref[r].summary()

    def test_injector_preempt_kind_drives_the_same_path(self, tmp_path):
        # kind="preempt" on the admit schedule: the SECOND admitted
        # batch requests preemption mid-run — no real signal involved
        guard = PreemptionGuard()
        inj = FailureInjector(fail_at={"admit": [(1, "preempt")]},
                              guard=guard)
        svc = _fast_svc(max_coalesce=1, guard=guard, injector=inj,
                        journal=tmp_path / "journal.jsonl")
        svc.serve()
        tickets = [svc.submit(_request(seed=sd, layout=False))
                   for sd in range(3)]
        self._drain_pump(svc)
        svc.close()
        assert guard.preempted
        assert ("admit", 1, "preempt") in inj.fired
        resolved, unresolved = [], []
        for t in tickets:
            try:
                (resolved if svc.poll(t) is not None
                 else unresolved).append(t)
            except PendingTicket:
                unresolved.append(t)
        # the WAL covers everything unfinished at drain time — every
        # unresolved ticket for sure, plus in-flight tickets that then
        # drained locally (if the drain had died, replay still recovers
        # them; the artifact cache de-duplicates on replay)
        journaled_shas = {r.sha() for r in svc.journal.replay()}
        by_ticket = dict(zip(tickets, range(3)))
        for t in unresolved:
            assert _request(seed=by_ticket[t],
                            layout=False).sha() in journaled_shas
        assert len(unresolved) >= 1
        assert resolved   # the first admitted batch drained to an artifact

    def test_serve_refused_with_already_preempted_guard(self):
        guard = PreemptionGuard()
        guard.request()
        svc = _fast_svc(guard=guard)
        with pytest.raises(RuntimeError, match="fresh guard"):
            svc.serve()

    def test_explicit_replay_journal_for_sync_drains(self, tmp_path):
        j = TicketJournal(tmp_path / "journal.jsonl")
        reqs = [_request(seed=sd, layout=False) for sd in (5, 6)]
        j.write(reqs)
        svc = _fast_svc(journal=j)
        tickets = svc.replay_journal()
        assert len(tickets) == 2 and len(j) == 0
        done = svc.run()
        for t, r in zip(tickets, reqs):
            assert done[t].request == r
            assert done[t].provenance.served_from == "journal_replay"


@pytest.fixture(scope="module")
def chaos(tmp_path_factory):
    """A layout-bucket kill and a preemption in one run, then a restart.
    Phase 1: a guarded service whose first layout bucket dies and whose
    second admission requests preemption drains what it admitted and
    journals the rest.  Phase 2: a fresh service over the same cache
    root replays the journal."""
    reqs = [_request(seed=sd, requirements=REQS, layout=True)
            for sd in range(3)]
    ref = DesignSession().run_many(reqs, strict=False)
    cache = tmp_path_factory.mktemp("chaos-cache")
    guard = PreemptionGuard()
    inj = FailureInjector(guard=guard, fail_at={
        "layout": [0], "admit": [(1, "preempt")]})
    svc1 = _fast_svc(session=DesignSession(artifact_cache=cache),
                     max_coalesce=1, layout_workers=2, max_retries=2,
                     guard=guard, injector=inj)
    drained = {}
    with svc1.serve():
        tickets = [svc1.submit(r) for r in reqs]
        for r, t in zip(reqs, tickets):
            try:
                drained[r] = svc1.collect(t, timeout=600)
            except PendingTicket:
                pass            # journaled: the replaying service owns it
    svc2 = _fast_svc(session=DesignSession(artifact_cache=cache),
                     max_coalesce=1, layout_workers=2)
    pending = svc2.journal.replay()    # peek; replay() does not clear
    tickets2 = svc2.replay_journal()
    with svc2.serve():
        replayed = {r: svc2.collect(t, timeout=600)
                    for r, t in zip(pending, tickets2)}
    return {"reqs": reqs, "ref": ref, "drained": drained,
            "replayed": replayed, "stats1": svc1.stats(),
            "n_replayed": len(tickets2)}


class TestChaos:
    def test_every_ticket_resolves_equal_to_fault_free(self, chaos):
        # in-flight tickets are journaled too (at-least-once WAL), so a
        # request can resolve in both phases; both copies must be right
        for r in chaos["reqs"]:
            arts = [a for a in (chaos["drained"].get(r),
                                chaos["replayed"].get(r)) if a is not None]
            assert arts, f"seed {r.seed} resolved in neither phase"
            for a in arts:
                assert a.ok and a.summary() == chaos["ref"][r].summary()

    def test_kill_retried_and_journal_replayed_in_full(self, chaos):
        s1 = chaos["stats1"]
        assert s1["preemptions"] == 1
        assert s1["bucket_retries"] >= 1
        assert s1["bucket_failures"] == 0
        assert chaos["n_replayed"] == s1["journaled_tickets"] >= 1
        assert chaos["drained"]   # the first admitted batch drained


# -- straggler shedding in the layout pool ---------------------------------

class TestStragglerShed:
    def test_stuck_bucket_shed_to_peer_first_completion_wins(self):
        # the first layout dispatch is held by a slow fault far past
        # threshold x EMA; the watchdog re-queues it, the peer worker
        # completes it, and the stuck incarnation is cancelled-on-observe
        mon = StragglerMonitor(threshold=2.0, ema=3.0)   # stuck past 6s
        inj = FailureInjector(slow_seconds=20.0,
                              fail_at={"layout": [(0, "slow")]})
        svc = _fast_svc(layout_workers=2, straggler=mon, injector=inj)
        with svc.serve():
            t = svc.submit(_request(requirements=REQS, layout=True))
            art = svc.collect(t, timeout=600)
            stats_live = svc.stats()
        assert art.ok and art.error is None
        assert art.provenance.shed_buckets >= 1
        assert stats_live["shed_buckets"] >= 1
        assert any(ev[0] == "shed" for ev in mon.events)
        # the ticket completed long before the 20s fault released: the
        # shed actually rescued it rather than waiting the fault out
        stats = svc.stats()   # post-close: the loser was observed
        assert stats["shed_losses"] + stats["bucket_cancellations"] >= 1

    def test_single_worker_pool_never_sheds(self):
        # shedding requires a peer; K=1 must not re-queue to itself
        mon = StragglerMonitor(threshold=2.0, ema=0.001)
        svc = _fast_svc(layout_workers=1, straggler=mon)
        with svc.serve():
            t = svc.submit(_request(requirements=REQS, layout=True))
            art = svc.collect(t, timeout=600)
        assert art.ok
        assert svc.stats()["shed_buckets"] == 0
        assert not any(ev[0] == "shed" for ev in mon.events)


# -- layout pool: equality + knobs -----------------------------------------

class TestLayoutPool:
    def test_pool_artifacts_equal_sequential(self):
        reqs = [_request(array_size=4096, seed=0, requirements=REQS,
                         layout=True),
                _request(array_size=16384, seed=1, requirements=REQS,
                         layout=True)]
        ref = DesignSession().run_many(reqs, strict=False)
        svc = _fast_svc(max_coalesce=2, coalesce_window_s=0.3,
                        layout_workers=4)
        with svc.serve():
            tickets = [svc.submit(r) for r in reqs]
            arts = [svc.collect(t, timeout=600) for t in tickets]
        for r, a in zip(reqs, arts):
            assert a.summary() == ref[r].summary()
            assert a.ok
            assert a.provenance.worker_id.startswith("layout-")
        assert svc.stats()["layout_workers"] == 4

    def test_pool_retries_killed_bucket_and_matches_sequential(self):
        # the K-wide pool under a node fault on its first bucket: the
        # bucket is retried in place, nothing fails, and the artifacts
        # equal the fault-free sequential run
        reqs = [_request(array_size=4096, seed=0, requirements=REQS,
                         layout=True),
                _request(array_size=16384, seed=1, requirements=REQS,
                         layout=True)]
        ref = DesignSession().run_many(reqs, strict=False)
        inj = FailureInjector(fail_at={"layout": [0]})
        svc = _fast_svc(max_coalesce=2, coalesce_window_s=0.3,
                        layout_workers=4, injector=inj, max_retries=2)
        with svc.serve():
            tickets = [svc.submit(r) for r in reqs]
            arts = [svc.collect(t, timeout=600) for t in tickets]
        for r, a in zip(reqs, arts):
            assert a.ok and a.summary() == ref[r].summary()
        stats = svc.stats()
        assert stats["bucket_retries"] >= 1
        assert stats["bucket_failures"] == 0
        assert inj.fired == [("layout", 0, "node")]

    def test_knob_validation(self):
        with pytest.raises(ValueError, match="layout_workers"):
            DesignService(layout_workers=0)
        with pytest.raises(ValueError, match="max_retries"):
            DesignService(max_retries=-1)
