"""Subprocess half of the cross-process artifact-cache round trips.

Run as `python tests/cache_roundtrip_helper.py <cache_dir> <request_json>
[--remote URI]` (with `PYTHONPATH=src`): opens a *fresh*
`DesignSession` over the given persistent cache — a plain
`ArtifactCache` on `<cache_dir>`, or, with `--remote`, a two-tier
`TieredArtifactCache` (`<cache_dir>` is the worker-local L1, the URI
the shared L2) — runs the request, and prints a JSON report the parent
asserts on.  Single-tier round trip
(`tests/test_design_service_async.py`, CI smoke): a repeat request is
served entirely from disk (`explorer_dispatches == 0`,
`served_from == "artifact_cache"`).  Fleet round trip (same test
file): a second worker process with a cold L1 but the first worker's
L2 serves with zero explorer dispatches and
`served_from == "artifact_cache_l2"`.

The report carries the session's cache/dispatch counters, the
artifact's mesh provenance (device count, migration topology/rounds),
and the
provenance-free content summary for cross-process equality checks.
"""
import argparse
import json
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("cache_dir")
    ap.add_argument("request_json")
    ap.add_argument("--remote", default=None,
                    help="shared L2 URI: run over a TieredArtifactCache")
    args = ap.parse_args()
    from repro.api import DesignRequest, DesignSession, TieredArtifactCache

    cache = (args.cache_dir if args.remote is None
             else TieredArtifactCache(args.cache_dir, args.remote))
    session = DesignSession(artifact_cache=cache)
    artifact = session.run(DesignRequest.from_json(args.request_json))
    prov = artifact.provenance
    json.dump({
        "explorer_dispatches": int(session.stats["explorer_dispatches"]),
        "layout_dispatches": int(session.stats["layout_dispatches"]),
        "artifact_cache_hits": int(session.stats["artifact_cache_hits"]),
        "served_from": prov.served_from,
        "ok": artifact.ok,
        "summary": artifact.summary(),
        "tier_stats": {k: int(session.stats[k]) for k in (
            "artifact_cache_l1_hits", "artifact_cache_l1_misses",
            "artifact_cache_l2_hits", "artifact_cache_l2_misses",
            "artifact_cache_promotions", "artifact_cache_l2_writes")},
        "mesh": {"mesh_devices": prov.mesh_devices,
                 "islands": prov.islands,
                 "migration_topology": prov.migration_topology,
                 "migration_rounds": prov.migration_rounds,
                 "n_devices": __import__("jax").device_count()},
    }, sys.stdout)


if __name__ == "__main__":
    main()
