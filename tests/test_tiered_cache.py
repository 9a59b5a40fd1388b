"""Two-tier artifact cache: FileRemoteStore contract, L1/L2 cascade with
promotion, validation guards on the remote tier, per-tier session
provenance, and island-request cache keys."""
import json

import jax
import pytest

from repro.api import (ArtifactCache, DesignRequest, DesignSession,
                       FileRemoteStore, TieredArtifactCache)

POP, GENS = 48, 10


def _request(array_size=4096, seed=0, **kw):
    kw.setdefault("pop_size", POP)
    kw.setdefault("generations", GENS)
    kw.setdefault("layout", False)
    return DesignRequest(array_size=array_size, seed=seed, **kw)


class TestFileRemoteStore:
    def test_uri_and_roundtrip(self, tmp_path):
        store = FileRemoteStore(f"file://{tmp_path}/l2")
        assert store.uri == f"file://{tmp_path}/l2"
        assert store.get("a.json") is None
        store.put("a.json", b"{}")
        assert store.get("a.json") == b"{}"
        assert store.list() == ["a.json"]
        assert store.size_bytes() == 2
        assert store.delete("a.json") and not store.delete("a.json")
        assert store.list() == []

    def test_plain_path_accepted(self, tmp_path):
        store = FileRemoteStore(tmp_path / "plain")
        store.put("x.json", b"1")
        assert FileRemoteStore(f"file://{tmp_path}/plain").get("x.json") == b"1"

    def test_invalid_keys_rejected(self, tmp_path):
        store = FileRemoteStore(tmp_path)
        for key in ("", ".", "..", "a/b.json"):
            with pytest.raises(ValueError):
                store.put(key, b"x")


class TestTieredArtifactCache:
    def test_cascade_promotion_and_counters(self, tmp_path):
        req = _request()
        art = DesignSession().run(req)
        writer = TieredArtifactCache(tmp_path / "w1", tmp_path / "l2")
        writer.put(art)
        assert writer.lengths() == {"l1": 1, "l2": 1}
        assert writer.stats["l2_writes"] == 1
        assert req in writer

        # fresh worker, cold L1, same L2: served from l2 then promoted
        reader = TieredArtifactCache(tmp_path / "w2", tmp_path / "l2")
        got, tier = reader.get_with_tier(req)
        assert tier == "l2" and got.summary() == art.summary()
        assert reader.stats["promotions"] == 1
        assert reader.lengths()["l1"] == 1
        got, tier = reader.get_with_tier(req)
        assert tier == "l1"
        assert reader.stats == {"l1_misses": 1, "l2_hits": 1,
                                "promotions": 1, "l1_hits": 1}

    def test_l2_guards_mirror_l1(self, tmp_path):
        req = _request()
        cache = TieredArtifactCache(tmp_path / "l1", tmp_path / "l2")
        key = cache.key_for(req)
        # corrupt object -> counted reject, no promotion
        cache.remote.put(key, b"not json")
        assert cache.get_with_tier(req) == (None, None)
        assert cache.stats["l2_rejects"] == 1
        # wrong schema stamp -> reject
        cache.remote.put(key, json.dumps(
            {"schema": -1, "request": req.to_dict()}).encode())
        assert cache.get(req) is None
        assert cache.stats["l2_rejects"] == 2
        assert cache.lengths()["l1"] == 0

    def test_clear_and_prune_by_tier(self, tmp_path):
        reqs = [_request(seed=s) for s in range(3)]
        session = DesignSession()
        cache = TieredArtifactCache(tmp_path / "l1", tmp_path / "l2")
        for r in reqs:
            cache.put(session.run(r))
        assert cache.lengths() == {"l1": 3, "l2": 3}
        assert cache.prune(tier="l2", max_entries=2) == 1
        assert cache.lengths() == {"l1": 3, "l2": 2}
        assert cache.stats["l2_evictions"] == 1
        assert cache.clear(tier="l1") == 3
        assert cache.lengths() == {"l1": 0, "l2": 2}
        assert cache.clear() == 2
        assert cache.lengths() == {"l1": 0, "l2": 0}

    def test_session_stamps_tiers(self, tmp_path):
        """The end-to-end tier contract: explorer -> l2 (cold L1 worker)
        -> l1, with the session mirroring per-tier counters."""
        req = _request(seed=7)
        w1 = DesignSession(
            artifact_cache=TieredArtifactCache(tmp_path / "w1",
                                               tmp_path / "shared"))
        a1 = w1.run(req)
        assert a1.provenance.served_from == "explorer"
        assert w1.stats["artifact_cache_l2_writes"] == 1

        w2 = DesignSession(
            artifact_cache=TieredArtifactCache(tmp_path / "w2",
                                               tmp_path / "shared"))
        a2 = w2.run(req)
        assert a2.provenance.served_from == "artifact_cache_l2"
        assert w2.stats["explorer_dispatches"] == 0
        assert w2.stats["artifact_cache_promotions"] == 1
        assert a2.summary() == a1.summary()
        a3 = w2.run(req)   # artifact cache is consulted before the memo
        assert a3.provenance.served_from == "artifact_cache_l1"
        assert w2.stats["artifact_cache_l1_hits"] == 1

    def test_warm_fleet_serves_island_front_from_l2(self, tmp_path):
        """Fleet workers with private L1s over one shared L2: the cold
        worker explores an island request on the mesh (ring migration)
        and writes the shared tier; every warm worker serves it from L2
        with zero explorer dispatches; every front equals the same
        request explored on one device."""
        req = _request(seed=3, islands=2, migrate_every=5)
        baseline = DesignSession(mesh=1).run(req)
        workers = [DesignSession(artifact_cache=TieredArtifactCache(
            tmp_path / f"w{i}", tmp_path / "shared")) for i in range(3)]
        arts = [w.run(req) for w in workers]
        assert arts[0].provenance.served_from == "explorer"
        assert arts[0].provenance.migration_topology == "ring"
        assert 1 <= arts[0].provenance.mesh_devices <= jax.device_count()
        assert workers[0].stats["artifact_cache_l2_writes"] == 1
        for w, art in zip(workers[1:], arts[1:]):
            assert art.provenance.served_from == "artifact_cache_l2"
            assert w.stats["explorer_dispatches"] == 0
            assert w.stats["artifact_cache_l2_hits"] == 1
        for art in arts:
            assert art.summary() == baseline.summary()

    def test_legacy_single_tier_stamp_unchanged(self, tmp_path):
        req = _request(seed=9)
        cache = ArtifactCache(tmp_path / "flat")
        DesignSession(artifact_cache=cache).run(req)
        again = DesignSession(artifact_cache=cache).run(req)
        assert again.provenance.served_from == "artifact_cache"
