"""`repro.api` — the unified front-end for the EasyACIM flow.

One declarative request type, one long-lived session, one service:

    from repro.api import DesignRequest, DesignSession, Requirements

    req = DesignRequest(array_size=16384,
                        requirements=Requirements(min_tops=1.0))
    artifact = DesignSession().run(req)
    artifact.to_json("artifact.json")

`DesignRequest` captures the whole query (MOGA budget, calibration,
backend knobs, application requirements, layout options);
`DesignSession` owns the compiled-program and Pareto-front caches and
optionally a persistent, cross-process `ArtifactCache`
(`DesignSession(artifact_cache="/path")`);
`repro.serve.design_service.DesignService` adds the queue-backed
multi-tenant layer (request coalescing, grid-shape layout bucketing,
and the thread-pumped `serve()` loop with latency-bounded coalescing
windows).
"""
from repro.api.request import DesignRequest, Requirements
from repro.api.session import (BucketResult, DesignArtifact, DesignSession,
                               DistilledBatch, ExploredBatch, LayoutBucket,
                               Provenance)
from repro.api.artifact_cache import (ArtifactCache, FileRemoteStore,
                                      RemoteStore, TicketJournal,
                                      TieredArtifactCache)

_DEFAULT_SESSION: DesignSession | None = None


def default_session() -> DesignSession:
    """Process-wide session backing the legacy shims."""
    global _DEFAULT_SESSION
    if _DEFAULT_SESSION is None:
        _DEFAULT_SESSION = DesignSession()
    return _DEFAULT_SESSION


__all__ = ["DesignRequest", "Requirements", "DesignArtifact",
           "DesignSession", "Provenance", "ArtifactCache",
           "TieredArtifactCache", "RemoteStore", "FileRemoteStore",
           "TicketJournal", "ExploredBatch", "DistilledBatch",
           "LayoutBucket", "BucketResult", "default_session"]
