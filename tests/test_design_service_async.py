"""Async `DesignService` (thread-pumped serve() loop, deadline
coalescing, ticket lifecycle, failure/restore) and the persistent
`ArtifactCache` (atomic writes, schema stamp, cross-process round
trip)."""
import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import threading
import time

import pytest

from repro.api import (ArtifactCache, DesignArtifact, DesignRequest,
                       DesignSession, Requirements)
from repro.api.session import ARTIFACT_SCHEMA, _grid_sig
from repro.serve.design_service import (DesignService, PendingTicket,
                                        UnknownTicket)

REPO = pathlib.Path(__file__).resolve().parents[1]

# threaded serve()-loop tests deadlock rather than fail when broken;
# bound each test (pytest-timeout in CI, the conftest watchdog otherwise)
pytestmark = pytest.mark.timeout(900)

# Same small budget as tests/test_design_api.py: the compiled sweep and
# layout programs are shared process-wide, so these tests ride its jit
# cache (and vice versa) instead of paying a fresh compile each.
POP, GENS = 48, 10
REQS = Requirements(min_tops=0.5, min_snr_db=10.0)


def _request(array_size=4096, seed=0, **kw):
    kw.setdefault("pop_size", POP)
    kw.setdefault("generations", GENS)
    return DesignRequest(array_size=array_size, seed=seed, **kw)


@pytest.fixture(scope="module")
def laid_artifact():
    """One real, laid-out artifact (built once per module)."""
    art = DesignSession().run(_request(requirements=REQS, layout=True))
    assert art.ok and art.layout_rows
    return art


# -- async serve loop ----------------------------------------------------

class TestServeLoop:
    def test_async_artifacts_equal_sync_drain(self):
        reqs = [_request(seed=sd, requirements=REQS, layout=True)
                for sd in (0, 1)]
        sync = DesignService()
        tickets = [sync.submit(r) for r in reqs]
        done = sync.run()
        sync_arts = {r: done[t] for r, t in zip(reqs, tickets)}

        svc = DesignService(coalesce_window_s=0.25)
        with svc.serve():
            tickets = [svc.submit(r) for r in reqs]
            arts = [svc.collect(t, timeout=600) for t in tickets]
        for r, a in zip(reqs, arts):
            assert a.summary() == sync_arts[r].summary()
        # the window actually merged the concurrent submissions
        stats = svc.stats()
        assert stats["service_batches"] == 1
        assert stats["service_batch_requests"] == 2
        assert arts[0].provenance.coalesced == 2

    def test_window_deadline_dispatches_partial_batch(self):
        # max_coalesce is far above the submission count, so only the
        # deadline of the oldest queued request can trigger the dispatch
        svc = DesignService(max_coalesce=64, coalesce_window_s=0.2)
        with svc.serve():
            t = svc.submit(_request(layout=False))
            art = svc.collect(t, timeout=600)
        assert art.ok
        assert svc.stats()["service_batches"] == 1

    def test_full_batch_dispatches_before_window(self):
        # window is huge; hitting max_coalesce must dispatch immediately
        svc = DesignService(max_coalesce=2, coalesce_window_s=3600.0)
        with svc.serve():
            tickets = [svc.submit(_request(seed=sd, layout=False))
                       for sd in (0, 1)]
            t0 = time.monotonic()
            arts = [svc.collect(t, timeout=600) for t in tickets]
            assert time.monotonic() - t0 < 600
        assert all(a.ok for a in arts)
        assert svc.stats()["service_batches"] == 1

    def test_concurrent_submit_during_active_pump(self):
        svc = DesignService(max_coalesce=8, coalesce_window_s=0.1)
        seeds = list(range(6))
        results: dict[int, DesignArtifact] = {}
        errors: list[Exception] = []

        def tenant(sd):
            try:
                t = svc.submit(_request(seed=sd, layout=False))
                results[sd] = svc.collect(t, timeout=600)
            except Exception as e:
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
        assert all(results[sd].ok for sd in seeds)
        # every tenant's artifact demuxed to its own request
        assert {results[sd].request.seed for sd in seeds} == set(seeds)
        assert len(svc) == 0 and not svc.done   # collected == popped

    def test_serve_idempotent_and_close_reusable(self):
        svc = DesignService(coalesce_window_s=0.05)
        assert svc.serve() is svc.serve()
        svc.close()
        svc.close()   # idempotent
        # service still usable synchronously after close
        t = svc.submit(_request(layout=False))
        assert svc.run()[t].ok
        # and serve() can be restarted
        with svc.serve():
            t2 = svc.submit(_request(seed=1, layout=False))
            assert svc.collect(t2, timeout=600).ok

    def test_run_and_step_refused_while_pump_active(self):
        # only one dispatcher may drive the (non-thread-safe) session
        svc = DesignService()
        with svc.serve():
            with pytest.raises(RuntimeError, match="serve\\(\\) pump"):
                svc.run()
            with pytest.raises(RuntimeError, match="serve\\(\\) pump"):
                svc.step()

    def test_submit_and_serve_refused_while_closing(self):
        svc = DesignService()
        svc._closing = True   # simulate the mid-close window
        with pytest.raises(RuntimeError, match="closing"):
            svc.submit(_request(layout=False))
        with pytest.raises(RuntimeError, match="close\\(\\) is in progress"):
            svc.serve()


# -- failure / restore ---------------------------------------------------

class TestFailureRestore:
    def test_step_restores_batch_in_order(self, monkeypatch):
        svc = DesignService(max_coalesce=2)
        tickets = [svc.submit(_request(seed=sd, layout=False))
                   for sd in range(3)]

        def boom(*a, **kw):
            raise RuntimeError("injected dispatch failure")

        monkeypatch.setattr(svc.session, "run_many", boom)
        with pytest.raises(RuntimeError, match="injected"):
            svc.step()
        # nothing lost, nothing reordered, nothing marked done
        assert [t for t, _, _ in svc._queue] == tickets
        assert svc.poll(tickets[0]) is None
        monkeypatch.undo()
        done = svc.run()
        assert [done[t].request.seed for t in tickets] == [0, 1, 2]

    def test_pump_failure_surfaces_and_tickets_survive(self, monkeypatch):
        svc = DesignService(coalesce_window_s=0.02)

        def boom(*a, **kw):
            raise RuntimeError("injected pump failure")

        # the admission pump itself fails (a stage exception is isolated
        # into error artifacts instead — see tests/test_service_faults.py)
        monkeypatch.setattr(svc, "_admit_batch", boom)
        svc.serve()
        ticket = svc.submit(_request(layout=False))
        with pytest.raises(RuntimeError, match="pump failed"):
            svc.collect(ticket, timeout=600)
        with pytest.raises(RuntimeError, match="pump failed"):
            svc.poll(ticket)   # a poll-only consumer must not spin forever
        with pytest.raises(RuntimeError, match="pump failed"):
            svc.submit(_request(seed=9, layout=False))   # dead-pump refusal
        with pytest.raises(RuntimeError, match="restored"):
            svc.close()
        # the ticket is back in the queue, pending — not lost
        assert svc.poll(ticket) is None
        monkeypatch.undo()
        assert svc.run()[ticket].ok


# -- ticket lifecycle ----------------------------------------------------

class TestTicketLifecycle:
    def test_unknown_vs_pending_vs_collected(self):
        svc = DesignService()
        with pytest.raises(UnknownTicket, match="never issued"):
            svc.poll(0)
        ticket = svc.submit(_request(layout=False))
        assert svc.poll(ticket) is None   # pending, not an error
        with pytest.raises(PendingTicket, match="still pending"):
            svc.collect(ticket)           # no pump, no timeout: clear error
        svc.run()
        art = svc.collect(ticket)
        assert art.ok
        with pytest.raises(UnknownTicket, match="already collected"):
            svc.collect(ticket)
        with pytest.raises(UnknownTicket, match="never issued"):
            svc.collect(ticket + 1)

    def test_collect_timeout_raises_pending(self):
        svc = DesignService()
        ticket = svc.submit(_request(layout=False))
        t0 = time.monotonic()
        with pytest.raises(PendingTicket, match="after 0.2"):
            svc.collect(ticket, timeout=0.2)
        assert 0.1 < time.monotonic() - t0 < 10.0

    def test_done_bounded_by_pop_on_collect(self):
        svc = DesignService()
        tickets = [svc.submit(_request(seed=sd, layout=False))
                   for sd in (0, 1)]
        svc.run()
        assert len(svc.done) == 2
        kept = svc.collect(tickets[0], keep_done=True)
        assert svc.collect(tickets[0]) is kept   # escape hatch kept it
        svc.collect(tickets[1])
        assert not svc.done


# -- persistent artifact cache -------------------------------------------

class TestArtifactCache:
    def test_put_get_roundtrip(self, tmp_path, laid_artifact):
        cache = ArtifactCache(tmp_path / "cache")
        req = laid_artifact.request
        assert cache.get(req) is None and cache.stats["misses"] == 1
        path = cache.put(laid_artifact)
        assert path.name == f"{req.sha()}.json"
        assert req in cache and len(cache) == 1
        back = cache.get(req)
        assert back.summary() == laid_artifact.summary()
        assert cache.stats["hits"] == 1
        assert cache.clear() == 1 and len(cache) == 0

    def test_corrupt_entry_is_counted_miss(self, tmp_path, laid_artifact):
        cache = ArtifactCache(tmp_path)
        path = cache.put(laid_artifact)
        path.write_text(path.read_text()[: len(path.read_text()) // 2])
        assert cache.get(laid_artifact.request) is None
        assert cache.stats["rejects"] == 1

    def test_schema_skew_is_counted_miss(self, tmp_path, laid_artifact):
        cache = ArtifactCache(tmp_path)
        path = cache.put(laid_artifact)
        d = json.loads(path.read_text())
        assert d["schema"] == ARTIFACT_SCHEMA
        d["schema"] = 999
        path.write_text(json.dumps(d))
        assert cache.get(laid_artifact.request) is None
        assert cache.stats["rejects"] == 1
        with pytest.raises(ValueError, match="schema 999"):
            DesignArtifact.from_dict(d)

    def test_key_collision_guard(self, tmp_path, laid_artifact):
        # an entry parked under another request's sha must not be served
        cache = ArtifactCache(tmp_path)
        other = dataclasses.replace(laid_artifact.request, seed=123)
        cache.put(laid_artifact)
        os.replace(cache.path_for(laid_artifact.request),
                   cache.path_for(other))
        assert cache.get(other) is None
        assert cache.stats["rejects"] == 1

    def test_newer_request_schema_rejected_clearly(self, laid_artifact):
        d = laid_artifact.request.to_dict()
        d["hyperdrive"] = True
        with pytest.raises(ValueError, match="unknown DesignRequest field"):
            DesignRequest.from_dict(d)

    def test_atomic_write_preserves_previous_file(self, tmp_path,
                                                  laid_artifact):
        path = tmp_path / "artifact.json"
        laid_artifact.to_json(path)
        good = path.read_text()
        bad = dataclasses.replace(laid_artifact,
                                  layout_rows=(object(),))
        with pytest.raises(TypeError):
            bad.to_json(path)
        assert path.read_text() == good            # target never truncated
        assert list(tmp_path.iterdir()) == [path]  # no temp litter
        assert DesignArtifact.from_json(path).summary() \
            == laid_artifact.summary()

    def test_session_serves_repeat_from_disk(self, tmp_path):
        req = _request(requirements=REQS, layout=True)
        s1 = DesignSession(artifact_cache=tmp_path)
        a1 = s1.run(req)
        assert a1.provenance.served_from in ("explorer", "front_cache")
        assert s1.stats["artifact_cache_writes"] == 1
        # a FRESH session (fresh in-memory caches) hits the disk tier
        s2 = DesignSession(artifact_cache=ArtifactCache(tmp_path))
        a2 = s2.run(req)
        assert a2.provenance.served_from == "artifact_cache"
        assert a2.provenance.explorer_dispatches == 0
        assert s2.stats["explorer_dispatches"] == 0
        assert s2.stats["layout_dispatches"] == 0
        assert a2.summary() == a1.summary()
        # the service path uses the same tier
        svc = DesignService(session=DesignSession(artifact_cache=tmp_path))
        t = svc.submit(req)
        assert svc.run()[t].provenance.served_from == "artifact_cache"

    def test_error_artifacts_are_not_cached(self, tmp_path):
        ses = DesignSession(artifact_cache=tmp_path)
        bad = _request(requirements=Requirements(min_tops=1e9), layout=True)
        art = ses.run_many([bad], strict=False)[bad]
        assert not art.ok
        assert ses.stats["artifact_cache_writes"] == 0
        assert len(ses.artifact_cache) == 0


# -- cache eviction (long-lived fleets) ------------------------------------

def _variants(artifact, n):
    """Distinct cache entries: same content under fresh request keys."""
    return [dataclasses.replace(
        artifact, request=dataclasses.replace(artifact.request,
                                              seed=1000 + k))
            for k in range(n)]


class TestArtifactCacheEviction:
    def test_max_entries_prunes_lru_on_put(self, tmp_path, laid_artifact):
        cache = ArtifactCache(tmp_path, max_entries=2)
        v = _variants(laid_artifact, 3)
        for art in v:
            cache.put(art)
            time.sleep(0.02)   # distinct mtimes
        assert len(cache) == 2
        assert cache.stats["lru_evictions"] == 1
        assert cache.stats["prunes"] == 3
        # the oldest entry went; the newer two survive
        assert cache.get(v[0].request) is None
        assert cache.get(v[1].request) is not None
        assert cache.get(v[2].request) is not None

    def test_get_refreshes_lru_recency(self, tmp_path, laid_artifact):
        cache = ArtifactCache(tmp_path, max_entries=2)
        v = _variants(laid_artifact, 3)
        cache.put(v[0])
        time.sleep(0.02)
        cache.put(v[1])
        time.sleep(0.02)
        assert cache.get(v[0].request) is not None   # touch: v[1] is now LRU
        time.sleep(0.02)
        cache.put(v[2])                              # prune drops v[1]
        assert cache.get(v[1].request) is None
        assert cache.get(v[0].request) is not None

    def test_ttl_expires_old_entries(self, tmp_path, laid_artifact):
        cache = ArtifactCache(tmp_path, ttl_s=60.0)
        v = _variants(laid_artifact, 2)
        path = cache.put(v[0])
        stale = time.time() - 120.0
        os.utime(path, (stale, stale))
        cache.put(v[1])
        assert cache.stats["ttl_evictions"] == 1
        assert cache.get(v[0].request) is None
        assert cache.get(v[1].request) is not None
        assert len(cache) == 1

    def test_fresh_put_never_self_evicts(self, tmp_path, laid_artifact):
        cache = ArtifactCache(tmp_path, max_entries=1, ttl_s=3600.0)
        v = _variants(laid_artifact, 2)
        cache.put(v[0])
        time.sleep(0.02)
        cache.put(v[1])
        assert cache.get(v[1].request) is not None
        assert len(cache) == 1

    def test_entry_aged_exactly_ttl_survives(self, tmp_path, laid_artifact,
                                             monkeypatch):
        # the TTL bound is strict (`age > ttl_s` evicts): an entry aged
        # EXACTLY ttl_s is still valid.  Pin the prune's clock so the
        # boundary is exact, not within-jitter.
        from repro.api import artifact_cache as ac_mod
        cache = ArtifactCache(tmp_path, ttl_s=60.0)
        v = _variants(laid_artifact, 2)
        at_bound = cache.put(v[0])
        beyond = cache.put(v[1])
        t0 = time.time() + 1000.0

        class _FrozenTime:
            @staticmethod
            def time():
                return t0
        os.utime(at_bound, (t0 - 60.0, t0 - 60.0))       # age == ttl
        os.utime(beyond, (t0 - 60.0 - 1e-3, t0 - 60.0 - 1e-3))
        monkeypatch.setattr(ac_mod, "time", _FrozenTime)
        cache._prune()
        assert cache.get(v[0].request) is not None        # survives
        assert cache.get(v[1].request) is None            # past the bound
        assert cache.stats["ttl_evictions"] == 1

    def test_max_entries_bound_holds_under_concurrent_puts(
            self, tmp_path, laid_artifact):
        # max_entries=3 -> every put prunes (cadence max(1, 3//8) == 1);
        # concurrent putters race their prunes against each other's
        # unlinks, which must neither raise nor leave the bound broken
        cache = ArtifactCache(tmp_path, max_entries=3)
        v = _variants(laid_artifact, 12)
        errors = []

        def putter(arts):
            try:
                for a in arts:
                    cache.put(a)
            except Exception as e:     # pragma: no cover - the failure
                errors.append(e)
        threads = [threading.Thread(target=putter, args=(v[i::4],))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        cache._prune()
        assert len(cache) <= 3
        assert cache.stats["lru_evictions"] >= len(v) - 3

    def test_get_refreshed_mtime_survives_ttl_prune(self, tmp_path,
                                                    laid_artifact):
        # a hit refreshes the entry's mtime (os.utime in get), so a
        # recently-read entry must survive a TTL prune that evicts its
        # untouched sibling of the same age
        cache = ArtifactCache(tmp_path, ttl_s=50.0)
        v = _variants(laid_artifact, 2)
        touched = cache.put(v[0])
        untouched = cache.put(v[1])
        stale = time.time() - 100.0                       # both long expired
        os.utime(touched, (stale, stale))
        os.utime(untouched, (stale, stale))
        assert cache.get(v[0].request) is not None        # refresh mtime
        cache._prune()
        assert cache.get(v[0].request) is not None        # read kept it hot
        assert cache.get(v[1].request) is None
        assert cache.stats["ttl_evictions"] == 1

    def test_knob_validation(self, tmp_path):
        with pytest.raises(ValueError, match="max_entries"):
            ArtifactCache(tmp_path, max_entries=0)
        with pytest.raises(ValueError, match="ttl_s"):
            ArtifactCache(tmp_path, ttl_s=0)

    def test_unbounded_cache_never_prunes(self, tmp_path, laid_artifact):
        cache = ArtifactCache(tmp_path)
        for art in _variants(laid_artifact, 3):
            cache.put(art)
        assert len(cache) == 3
        assert cache.stats["prunes"] == 0


# -- bounded grid-sig cache ----------------------------------------------

class TestGridSigCache:
    def test_stat_counters_attributed_to_the_calling_session(
            self, laid_artifact):
        ses = DesignSession()
        req = laid_artifact.request
        ses.run_many([req])   # bucketed layout path exercises _grid_sig
        assert ses.stats["grid_sig_hits"] + ses.stats["grid_sig_misses"] \
            >= len(laid_artifact.pareto)
        # a second session's lookups land on ITS counter, not the first's
        before = dict(ses.stats)
        other = DesignSession()
        other.run_many([req])
        assert other.stats["grid_sig_hits"] >= len(laid_artifact.pareto)
        assert ses.stats["grid_sig_hits"] == before["grid_sig_hits"]

    def test_memo_bounded_by_lru_eviction(self, laid_artifact, monkeypatch):
        from repro.api import session as session_mod

        spec = laid_artifact.pareto.specs[0]
        monkeypatch.setattr(session_mod, "GRID_SIG_CACHE_SIZE", 2)
        for coarse in (61, 62, 63, 64):   # 4 distinct keys, bound of 2
            _grid_sig(spec, coarse)
        assert len(session_mod._GRID_SIG_MEMO) <= 2


# -- cross-process persistence -------------------------------------------

@pytest.mark.slow
def test_cross_process_cache_roundtrip(tmp_path):
    """A warm second *process* serves the repeat request entirely from
    the disk cache: zero explorer dispatches, provenance marks the
    cache tier, content equal to the first process's artifact."""
    cache_dir = tmp_path / "cache"
    req = _request(requirements=REQS, layout=True)
    parent = DesignSession(artifact_cache=cache_dir)
    art = parent.run(req)
    assert art.ok and parent.stats["artifact_cache_writes"] == 1

    r = subprocess.run(
        [sys.executable, str(REPO / "tests" / "cache_roundtrip_helper.py"),
         str(cache_dir), req.to_json()],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": str(REPO / "src"),
             "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    report = json.loads(r.stdout)
    assert report["ok"]
    assert report["explorer_dispatches"] == 0
    assert report["layout_dispatches"] == 0
    assert report["artifact_cache_hits"] == 1
    assert report["served_from"] == "artifact_cache"
    # tuples became JSON lists on the wire; compare in JSON space
    assert report["summary"] == json.loads(json.dumps(art.summary()))


@pytest.mark.slow
def test_cross_process_l2_sharing(tmp_path):
    """Two fleet workers (separate processes) with private L1s and one
    shared remote tier: the second worker serves the first worker's
    artifact with zero explorer dispatches, `served_from ==
    "artifact_cache_l2"`, and promotes it into its own L1."""
    remote = f"file://{tmp_path}/shared-l2"
    req = _request(requirements=REQS, layout=True, islands=2,
                   migrate_every=5)

    def worker(name):
        r = subprocess.run(
            [sys.executable,
             str(REPO / "tests" / "cache_roundtrip_helper.py"),
             str(tmp_path / name), req.to_json(), "--remote", remote],
            capture_output=True, text=True, timeout=600,
            env={**os.environ, "PYTHONPATH": str(REPO / "src"),
                 "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stderr[-3000:]
        return json.loads(r.stdout)

    first = worker("w1")
    assert first["ok"] and first["explorer_dispatches"] == 1
    assert first["served_from"] == "explorer"
    assert first["tier_stats"]["artifact_cache_l2_writes"] == 1
    assert first["mesh"]["islands"] == 2
    assert first["mesh"]["migration_topology"] == "ring"

    second = worker("w2")
    assert second["ok"]
    assert second["explorer_dispatches"] == 0
    assert second["layout_dispatches"] == 0
    assert second["served_from"] == "artifact_cache_l2"
    assert second["tier_stats"]["artifact_cache_l2_hits"] == 1
    assert second["tier_stats"]["artifact_cache_promotions"] == 1
    assert second["summary"] == first["summary"]
    # the promoted copy lives in w2's L1 now
    assert any((tmp_path / "w2").glob("*.json"))
