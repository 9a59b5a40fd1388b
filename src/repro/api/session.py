"""Compiled-program sessions: long-lived, cache-owning request execution.

`DesignSession` is the single supported way to run a `DesignRequest`
end to end.  It owns two caches:

  * a *program cache* keyed by `DesignRequest.shape_signature()` — one
    entry per compiled sweep program.  Array size, seed, and calibration
    are traced operands (`repro.core.nsga2.SpaceOperands`), so a repeat
    request or a signature-compatible variant request dispatches the
    cached program with **zero new traces** (observable through the
    `repro.core.nsga2.TRACE_COUNTS` probe, recorded per run in the
    artifact provenance);
  * a *front cache* keyed by `DesignRequest.explore_key()` — the
    distillation-independent Pareto front, so a repeat query (or the
    same exploration under different application requirements) costs no
    device dispatch at all;
  * optionally a third, *persistent* tier: an
    `repro.api.artifact_cache.ArtifactCache` (disk store keyed by
    `DesignRequest.sha()`), consulted before exploring and written
    after each run, so a fleet of processes shares exploration results
    across restarts — served artifacts carry
    `provenance.served_from == "artifact_cache"`.

Execution is factored into four first-class **stages** with explicit
inter-stage payload types, so the sequential drivers and the staged
pipeline executor (`repro.serve.design_service`) run the *same* code
and cannot diverge:

  * `explore_stage(requests)` — dedupe, consult the persistent
    artifact cache, and fold every cache-miss request in the same
    `explore_group()` into ONE `explore_cells` dispatch
    (-> `ExploredBatch`);
  * `distill_stage(batch)` — apply each request's requirements and
    form the layout buckets: under `bucket_layouts=True` the union of
    surviving specs is bucketed by quantized routing-grid shape
    (shapes quantized to powers of two so bucketing cannot degenerate
    into per-spec dispatches — heterogeneous Pareto sets no longer pay
    padded-batch waste for the biggest member); otherwise one
    whole-request bucket per request (-> `DistilledBatch`, whose
    `buckets` list is the streamable unit of layout work);
  * `layout_stage(bucket)` — one `LayoutBucket` through the batched
    flow (`eda.batched_flow.iter_layout_buckets`), independently
    dispatchable per bucket (-> `BucketResult`);
  * `finalize_stage(batch, bucket_results)` — demux per-request
    artifacts, stamp provenance, fill the persistent cache.

`run()` and `run_many()` are thin sequential drivers over these
stages; the service's pipeline executor drives the same stage
functions from per-stage workers so batch N+1's exploration overlaps
batch N's layout and buckets stream as they are formed.

Timing lives here, in the artifact provenance, not in the library flow
modules: `repro.eda.batched_flow` is pure compute.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import json
import os
import tempfile
import threading
import time
from typing import Iterable

from repro.core import nsga2
from repro.runtime.lock_sanitizer import make_lock
from repro.core.batched_explorer import explore_cells, sweep_program
from repro.core.explorer import ParetoResult
from repro.api.request import DesignRequest
from repro.core.acim_spec import MacroSpec
from repro.eda.batched_flow import BatchedLayoutResult, iter_layout_buckets
from repro.telemetry.spans import trace_span


# Stamped into every serialized artifact; `repro.api.artifact_cache`
# refuses entries whose stamp differs, so a fleet upgrade cannot feed a
# new reader stale-layout JSON.  Bump on any to_dict/from_dict change.
# 2: provenance gained the staged-pipeline fields (explore_wait_s,
#    layout_wait_s, pipelined).
# 3: provenance gained the fault-tolerance fields (attempts,
#    retried_buckets, shed_buckets, worker_id).
# 4: provenance gained the routing-engine fields (route_engine,
#    route_rounds, route_collisions).
# 5: provenance gained the mesh-exploration fields (mesh_devices,
#    islands, migration_topology, migration_rounds) and the tiered-
#    cache `served_from` values ("artifact_cache_l1"/"_l2"); requests
#    gained the islands/migrate_every genes.
# 6: provenance gained `admit_wait_s` (submit -> admission).
ARTIFACT_SCHEMA = 6


@dataclasses.dataclass(frozen=True)
class Provenance:
    """How an artifact was produced (the session's receipt).

    Wall-clock fields are this request's *fair share* of the shared
    work (an explorer dispatch split over the requests it coalesced, a
    layout bucket split over the specs it laid out), so summing
    `total_s` across a batch's artifacts approximates the real cost
    instead of multiply-counting it.  Count fields are dispatch-scoped:
    coalesced requests served by the same dispatch all report its
    trace/dispatch counts (dedupe by dispatch — e.g. keep one artifact
    per `coalesced` group — before summing them)."""

    request_sha: str
    explore_s: float            # fair share of the exploration dispatch
    layout_s: float             # fair share of the layout buckets touched
    total_s: float
    new_traces: int             # run_cell traces of the serving dispatch
    explorer_dispatches: int    # 0 when served from the front cache
    layout_dispatches: int      # grid-shape buckets this request touched
    front_cache_hit: bool
    coalesced: int              # requests sharing the exploration (>= 1)
    # which tier produced the artifact's content: "explorer" (a device
    # dispatch), "front_cache" (this process's in-memory front cache), or
    # "artifact_cache" (the persistent cross-process store)
    served_from: str = "explorer"
    # staged-pipeline facts (zero on the sequential drivers): how long
    # the request waited from submission to admission into a batch,
    # then in inter-stage queues before its explore batch was
    # picked up / before its layout buckets dispatched (mean over the
    # buckets the request touched), and whether the artifact was
    # produced by the staged pipeline executor at all
    admit_wait_s: float = 0.0
    explore_wait_s: float = 0.0
    layout_wait_s: float = 0.0
    pipelined: bool = False
    # fault-tolerance facts (schema 3): total layout attempts across the
    # buckets this request touched (>= bucket count when anything was
    # retried; 0 for cache-served / front-only requests), how many of
    # those buckets needed a retry, how many were shed to a peer layout
    # worker by the straggler policy, and which layout worker completed
    # the request's first bucket ("" outside the pipelined worker pool)
    attempts: int = 0
    retried_buckets: int = 0
    shed_buckets: int = 0
    worker_id: str = ""
    # routing-engine facts (schema 4), aggregated over the layout
    # buckets this request touched: which wavefront scheduler routed
    # them ("concurrent" = conflict-aware frontier batching, "scan" =
    # one lax.scan dispatch per net slot; "" for cache-served /
    # front-only requests), how many wavefront dispatch rounds they
    # took in total, and how many buffered routes a capacity crossing
    # invalidated and re-routed (the collision-retry count)
    route_engine: str = ""
    route_rounds: int = 0
    route_collisions: int = 0
    # mesh-exploration facts (schema 5), dispatch-scoped like the rest:
    # how many mesh devices the serving explore dispatch ran on (0 for
    # the single-device vmap engine and for cache-served artifacts),
    # the island count it evolved, the migration topology ("ring" for
    # island evolution, "sharded" for mesh-sharded cells, "" off-mesh),
    # and how many elite migrations fired
    mesh_devices: int = 0
    islands: int = 1
    migration_topology: str = ""
    migration_rounds: int = 0


@dataclasses.dataclass(frozen=True, eq=False)
class DesignArtifact:
    """The uniform result of one request: distilled front + layouts +
    provenance.

    `layout_rows` is the serializable layout product (one metrics row
    per spec, aligned with `pareto.specs`); `layouts` additionally holds
    the in-memory `BatchedLayoutResult` tensors when the request was
    laid out as a single batch (it is dropped by JSON round-trips and
    by the bucketed multi-tenant path).  `error` is set instead of
    raising on the non-strict (multi-tenant) path when the request's
    requirements removed every Pareto point.
    """

    request: DesignRequest
    pareto: ParetoResult                      # distilled frontier
    layout_rows: tuple[dict, ...] | None      # aligned with pareto.specs
    provenance: Provenance
    layouts: BatchedLayoutResult | None = dataclasses.field(
        default=None, repr=False)
    error: str | None = None

    @property
    def ok(self) -> bool:
        return self.error is None

    def summary(self) -> dict:
        """Provenance-free content view, for equality checks."""
        return {"array_size": self.pareto.array_size,
                "specs": [s.as_tuple() for s in self.pareto.specs],
                "front": self.pareto.to_rows(),
                "layout": (None if self.layout_rows is None
                           else list(self.layout_rows))}

    def to_dict(self) -> dict:
        return {"schema": ARTIFACT_SCHEMA,
                "request": self.request.to_dict(),
                "pareto": {"array_size": self.pareto.array_size,
                           "points": self.pareto.to_rows()},
                "layout_rows": (None if self.layout_rows is None
                                else list(self.layout_rows)),
                "provenance": dataclasses.asdict(self.provenance),
                "error": self.error}

    def to_json(self, path) -> None:
        """Atomic dump: a crash mid-write can never leave a truncated file
        at `path` (the persistent artifact cache depends on this)."""
        _atomic_dump(self.to_dict(), path)

    @classmethod
    def from_dict(cls, d: dict) -> "DesignArtifact":
        schema = d.get("schema", ARTIFACT_SCHEMA)   # pre-stamp files pass
        if schema != ARTIFACT_SCHEMA:
            raise ValueError(f"artifact schema {schema} != supported "
                             f"{ARTIFACT_SCHEMA}; re-run the request")
        rows = d["layout_rows"]
        return cls(request=DesignRequest.from_dict(d["request"]),
                   pareto=ParetoResult.from_rows(d["pareto"]["array_size"],
                                                 d["pareto"]["points"]),
                   layout_rows=None if rows is None else tuple(rows),
                   provenance=Provenance(**d["provenance"]),
                   error=d.get("error"))

    @classmethod
    def from_json(cls, path) -> "DesignArtifact":
        with open(path) as f:
            return cls.from_dict(json.load(f))


def _atomic_dump(payload: dict, path) -> None:
    """Temp-file + `os.replace` JSON write: readers only ever see either
    the previous complete file or the new complete file.  The temp file
    lives in the target's directory so the replace stays on one
    filesystem (rename atomicity)."""
    path = os.fspath(path)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                               prefix=os.path.basename(path) + ".",
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(payload, f, indent=1)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


# Bounded: a long-lived service sees an unbounded stream of distinct
# (spec, coarse) pairs, and an unbounded memo keyed by MacroSpec grows
# with it forever.  4096 entries cover hundreds of concurrent Pareto
# sets.  Hand-rolled (not lru_cache) so a hit/miss can be attributed to
# the *calling* session's stats Counter exactly — several sessions in
# one process share the memo without cross-counting each other.
GRID_SIG_CACHE_SIZE = 4096
_GRID_SIG_LOCK = make_lock("api.session._GRID_SIG_LOCK")
_GRID_SIG_MEMO: collections.OrderedDict = collections.OrderedDict()


def _grid_sig(spec: MacroSpec, coarse: int,
              stats: collections.Counter | None = None) -> tuple[int, int]:
    """Routing-grid shape of a spec's macro, without placing it.
    Memoized process-wide with an LRU bound; pass a session's `stats`
    to count the lookup as that session's "grid_sig_hits"/"_misses"."""
    key = (spec, coarse)
    with _GRID_SIG_LOCK:
        val = _GRID_SIG_MEMO.get(key)
        if val is not None:
            _GRID_SIG_MEMO.move_to_end(key)
            if stats is not None:
                stats["grid_sig_hits"] += 1
            return val
    from repro.eda.placer import geometry, layout_operands
    from repro.eda.router import grid_shape

    ops = layout_operands(spec, geometry())
    val = grid_shape(int(ops.width), int(ops.height), coarse)
    with _GRID_SIG_LOCK:
        if stats is not None:
            stats["grid_sig_misses"] += 1
        _GRID_SIG_MEMO[key] = val
        _GRID_SIG_MEMO.move_to_end(key)
        while len(_GRID_SIG_MEMO) > GRID_SIG_CACHE_SIZE:
            _GRID_SIG_MEMO.popitem(last=False)
    return val


def _bucket_key(spec: MacroSpec, coarse: int, capacity: int,
                stats: collections.Counter | None = None) -> tuple:
    """Layout-bucket key: the routing-grid shape quantized to the next
    power of two per axis.  Exact-shape buckets would degenerate to one
    dispatch (and one compile) per distinct spec on heterogeneous
    fronts; quantizing bounds the padded-cell waste at <2x per axis
    while keeping the bucket count logarithmic in the shape spread."""
    gh, gw = _grid_sig(spec, coarse, stats)
    return (coarse, capacity,
            1 << (gh - 1).bit_length(), 1 << (gw - 1).bit_length())


# ----------------------------------------------------------------------
# Inter-stage payload types: the explicit contracts between the four
# stages.  The sequential drivers (`run`/`run_many`) and the staged
# pipeline executor (`repro.serve.design_service`) both move exactly
# these values between exactly these stage functions.
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class LayoutBucket:
    """One streamable unit of layout work: the specs sharing a quantized
    routing-grid shape (shared bucket, `request is None`) or one
    request's whole distilled set (`request` set — the single-request
    path, which keeps the in-memory layout tensors)."""

    key: tuple
    coarse: int
    capacity: int
    specs: tuple[MacroSpec, ...]
    request: DesignRequest | None = None


@dataclasses.dataclass
class BucketResult:
    """`layout_stage`'s product for one bucket."""

    bucket: LayoutBucket
    rows: dict                        # MacroSpec -> metrics row
    elapsed_s: float
    result: BatchedLayoutResult | None = None   # whole-request buckets only
    queue_wait_s: float = 0.0         # stamped by the pipelined executor
    # fault-tolerance facts, stamped by the pipelined worker pool: which
    # layout attempt produced this result (1 = first try), whether the
    # bucket was shed to a peer worker mid-flight, and which worker
    # completed it first
    attempts: int = 1
    shed: bool = False
    worker_id: str = ""
    # routing facts from the bucket's `BatchedRouting`: which engine
    # routed it and what it cost (rounds; collision-retries)
    engine: str = ""
    rounds: int = 0
    collisions: int = 0


@dataclasses.dataclass
class ExploredBatch:
    """`explore_stage` -> `distill_stage` payload."""

    requests: list                    # deduped cache-miss remainder, in order
    served: dict                      # DesignRequest -> DesignArtifact
    fronts: dict                      # DesignRequest -> ParetoResult
    info: dict                        # DesignRequest -> explore-info dict


@dataclasses.dataclass
class DistilledBatch:
    """`distill_stage` -> `layout_stage`/`finalize_stage` payload.

    `buckets` is ordered (first-seen) and each entry is independently
    dispatchable — the pipeline executor submits every bucket to the
    layout worker as soon as `distill_stage` returns, instead of
    blocking on the whole union.  `spec_keys[r]` aligns with
    `distilled[r].specs`, naming the bucket each spec landed in (the
    demux map `finalize_stage` uses)."""

    explored: ExploredBatch
    distilled: dict                   # DesignRequest -> ParetoResult
    errors: dict                      # DesignRequest -> message
    buckets: list                     # [LayoutBucket], formation order
    spec_keys: dict                   # DesignRequest -> tuple[bucket key, ...]


class _SweepProgram:
    """One program-cache entry: the compiled sweep for a shape signature."""

    def __init__(self, request: DesignRequest):
        self.statics = nsga2.EvolveStatics(
            pop_size=request.pop_size,
            crossover_prob=request.crossover_prob,
            mutation_prob=request.mutation_prob,
            use_pallas_dominance=request.use_pallas_dominance,
            use_pallas_rank=request.use_pallas_rank)
        self.n_gens = request.generations
        self.fn = functools.partial(sweep_program, statics=self.statics,
                                    n_gens=self.n_gens)
        self.dispatches = 0


class DesignSession:
    """Long-lived request executor owning the program and front caches,
    optionally backed by a persistent cross-process artifact cache."""

    def __init__(self, *, artifact_cache=None, recorder=None, mesh=None):
        """`artifact_cache` is an `repro.api.artifact_cache.ArtifactCache`
        (or anything with its `get(request)`/`put(artifact)` shape —
        e.g. a two-tier `TieredArtifactCache`, whose hits are stamped
        `served_from="artifact_cache_l1"` / `"artifact_cache_l2"`), a
        directory path to open one at, or `None` for in-memory caches
        only.  With a cache, `run`/`run_many` consult it *before*
        exploring — a warm repeat request is served with zero explorer
        dispatches and `provenance.served_from == "artifact_cache"` —
        and write every successful artifact back after the run.

        `mesh` opts the explore stage onto the device-mesh engine
        (`repro.parallel.distributed_explorer.explore_cells_mesh`): a
        `jax.sharding.Mesh`, an int device cap for the auto-built 1-D
        mesh, or `True` for all local devices.  Island requests
        (`DesignRequest.islands > 1`) use the mesh engine even when
        `mesh` is None (auto mesh) — fronts are bit-identical for any
        device count, so the knob is pure throughput.

        `recorder` is an optional `repro.telemetry.spans.SpanRecorder`:
        with one attached, the stage functions record `cat="session"`
        spans (one per coalesced explore dispatch, distillation, layout
        bucket, finalize pass) — the sequential drivers' side of the
        stage Gantt.  A `DesignService` built with telemetry attaches
        its recorder here automatically.  With or without one, each of
        these spans is a `design.session.<name>` profiler annotation."""
        self._programs: dict[tuple, _SweepProgram] = {}
        self._fronts: dict[tuple, ParetoResult] = {}
        self.recorder = recorder
        self.stats: collections.Counter = collections.Counter()
        # Counter increments are read-modify-write and the counters are
        # written from every service thread (stage workers, the layout
        # pool, the pump) as well as the session's own stages, so ALL
        # mutations go through bump() and all snapshots copy under this
        # lock — a lock-free insert of a new key can otherwise race a
        # concurrent `Counter(self.stats)` copy mid-iteration.
        self.stats_lock = make_lock("DesignSession.stats_lock")
        if artifact_cache is not None and not hasattr(artifact_cache, "put"):
            from repro.api.artifact_cache import ArtifactCache
            artifact_cache = ArtifactCache(artifact_cache)
        self.artifact_cache = artifact_cache
        self.mesh = mesh
        self._resolved_mesh = None

    def _mesh_for_dispatch(self):
        """The resolved `jax.sharding.Mesh` for mesh dispatches (built
        lazily so sessions that never touch the mesh engine never
        inspect devices)."""
        if self._resolved_mesh is None:
            from jax.sharding import Mesh

            from repro.parallel import distributed_explorer as dx
            if isinstance(self.mesh, Mesh):
                self._resolved_mesh = self.mesh
            else:
                cap = (self.mesh if isinstance(self.mesh, int)
                       and not isinstance(self.mesh, bool) else None)
                self._resolved_mesh = dx.default_mesh(max_devices=cap)
        return self._resolved_mesh

    def bump(self, key: str, n: int = 1) -> None:
        """Increment a stats counter under `stats_lock`.  The single
        mutation path for `self.stats`: session stages and every
        service thread serialize here, so increments never lose updates
        and snapshot copies never see a dict mid-resize."""
        with self.stats_lock:
            self.stats[key] += n

    def _span(self, name: str, **tags) -> trace_span:
        """A `cat="session"` span: a `design.session.<name>` profiler
        annotation, recorded too when a recorder is attached."""
        return trace_span(name, cat="session", recorder=self.recorder,
                          **tags)

    # -- program cache ---------------------------------------------------
    def program_for(self, request: DesignRequest) -> _SweepProgram:
        sig = request.shape_signature()
        prog = self._programs.get(sig)
        if prog is None:
            prog = self._programs[sig] = _SweepProgram(request)
            self.bump("program_cache_misses")
        else:
            self.bump("program_cache_hits")
        return prog

    # -- exploration (coalesced across requests) -------------------------
    def _fronts_for(self, requests: list[DesignRequest]):
        """Resolve every request's (undistilled) front; missing fronts of
        the same explore group fold into one dispatch.  Returns
        (fronts, per-request explore info)."""
        info = {r: {"explore_s": 0.0, "new_traces": 0, "dispatches": 0,
                    "cache_hit": True, "coalesced": 1} for r in requests}
        pending: dict[tuple, list[DesignRequest]] = {}
        for r in requests:
            if r.explore_key() in self._fronts:
                self.bump("front_cache_hits")
            else:
                pending.setdefault(r.explore_group(), []).append(r)
        for group in pending.values():
            r0 = group[0]
            cells = list(dict.fromkeys(r.cell for r in group))
            on_mesh = r0.islands > 1 or self.mesh is not None
            n0 = nsga2.TRACE_COUNTS["run_cell"]
            t0 = time.perf_counter()
            facts: dict = {}
            timings: dict = {}
            if on_mesh:
                from repro.parallel import distributed_explorer as dx
                mesh = self._mesh_for_dispatch()
                with self._span("explore_dispatch", cells=len(cells),
                                coalesced=len(group), engine="mesh",
                                islands=r0.islands):
                    fronts, facts = dx.explore_cells_mesh(
                        cells, mesh=mesh, islands=r0.islands,
                        migrate_every=r0.migrate_every,
                        pop_size=r0.pop_size, generations=r0.generations,
                        crossover_prob=r0.crossover_prob,
                        mutation_prob=r0.mutation_prob, cal=r0.cal,
                        use_pallas_dominance=r0.use_pallas_dominance,
                        use_pallas_rank=r0.use_pallas_rank,
                        timings=timings)
                self.bump("mesh_dispatches")
            else:
                prog = self.program_for(r0)
                with self._span("explore_dispatch", cells=len(cells),
                                coalesced=len(group)):
                    fronts = explore_cells(
                        cells, pop_size=r0.pop_size,
                        generations=r0.generations,
                        crossover_prob=r0.crossover_prob,
                        mutation_prob=r0.mutation_prob, cal=r0.cal,
                        use_pallas_dominance=r0.use_pallas_dominance,
                        use_pallas_rank=r0.use_pallas_rank,
                        program=prog.fn, timings=timings)
                prog.dispatches += 1
            dt = time.perf_counter() - t0
            traces = nsga2.TRACE_COUNTS["run_cell"] - n0
            self.bump("explorer_dispatches")
            self.bump("explore_host_s", timings.get("host_s", 0.0))
            self.bump("run_cell_traces", traces)
            for cell, front in fronts.items():
                key = r0.explore_group() + cell
                self._fronts[key] = front
            for r in group:
                info[r] = {"explore_s": dt / len(group), "new_traces": traces,
                           "dispatches": 1, "cache_hit": False,
                           "coalesced": len(group), **facts}
        return {r: self._fronts[r.explore_key()] for r in requests}, info

    def fronts_for(self, requests: Iterable[DesignRequest]
                   ) -> dict[DesignRequest, ParetoResult]:
        """Coalesced exploration only (no distillation, no layout)."""
        fronts, _ = self._fronts_for(list(requests))
        return fronts

    # -- layout ----------------------------------------------------------
    def layout(self, specs, *, coarse: int = 64, capacity: int = 4,
               engine: str | None = None) -> BatchedLayoutResult:
        """One batched layout dispatch chain for a spec set.  Safe to
        call from several layout-pool workers concurrently (the batched
        flow is pure compute; the stats counter is locked).

        `engine` passes through to `eda.batched_flow.batched_route`
        ("concurrent" / "scan" / None for the backend auto choice); the
        choice is recorded in the artifact provenance either way."""
        self.bump("layout_dispatches")
        (res,) = iter_layout_buckets([(tuple(specs), coarse, capacity)],
                                     engine=engine)
        return res

    # -- the four stages --------------------------------------------------
    def explore_stage(self, requests: Iterable[DesignRequest]
                      ) -> ExploredBatch:
        """Stage 1 — dedupe, consult the persistent artifact cache, and
        fold every cache-miss request in the same explore group into one
        `explore_cells` dispatch.

        Requests found in the artifact cache land in `.served` with
        provenance re-stamped (`served_from="artifact_cache"`, zero
        dispatches); the remainder carries its fronts + explore info."""
        all_requests = list(dict.fromkeys(requests))
        served: dict[DesignRequest, DesignArtifact] = {}
        if self.artifact_cache is not None:
            tiered = hasattr(self.artifact_cache, "get_with_tier")
            for r in all_requests:
                t0 = time.perf_counter()
                if tiered:
                    hit, tier = self.artifact_cache.get_with_tier(r)
                else:
                    hit, tier = self.artifact_cache.get(r), None
                if hit is None:
                    self.bump("artifact_cache_misses")
                    if tiered:
                        self.bump("artifact_cache_l1_misses")
                        self.bump("artifact_cache_l2_misses")
                    continue
                self.bump("artifact_cache_hits")
                source = "artifact_cache"
                if tier is not None:
                    source = f"artifact_cache_{tier}"
                    self.bump(f"artifact_cache_{tier}_hits")
                    if tier == "l2":
                        self.bump("artifact_cache_l1_misses")
                        self.bump("artifact_cache_promotions")
                prov = dataclasses.replace(
                    hit.provenance, explore_s=0.0, layout_s=0.0,
                    total_s=time.perf_counter() - t0, new_traces=0,
                    explorer_dispatches=0, layout_dispatches=0,
                    front_cache_hit=False, coalesced=1,
                    admit_wait_s=0.0, explore_wait_s=0.0,
                    layout_wait_s=0.0, pipelined=False,
                    attempts=0, retried_buckets=0, shed_buckets=0,
                    worker_id="", route_engine="", route_rounds=0,
                    route_collisions=0, mesh_devices=0,
                    migration_topology="", migration_rounds=0,
                    served_from=source)
                served[r] = dataclasses.replace(hit, provenance=prov)
        remainder = [r for r in all_requests if r not in served]
        fronts, info = (self._fronts_for(remainder) if remainder
                        else ({}, {}))
        return ExploredBatch(requests=remainder, served=served,
                             fronts=fronts, info=info)

    def distill_stage(self, explored: ExploredBatch, *,
                      strict: bool = True, bucket_layouts: bool = True
                      ) -> DistilledBatch:
        """Stage 2 — apply each request's requirements and form the
        layout buckets.

        A request whose requirements remove every Pareto point raises
        `ValueError` under `strict=True`; under `strict=False` (the
        multi-tenant path) it is recorded in `.errors` and the rest of
        the batch proceeds.  Buckets are the quantized grid-shape union
        (`bucket_layouts=True`) or one whole-request bucket each."""
        distilled: dict[DesignRequest, ParetoResult] = {}
        errors: dict[DesignRequest, str] = {}
        for r in explored.requests:
            d = (explored.fronts[r] if r.requirements.is_noop
                 else explored.fronts[r].filter(
                     **r.requirements.as_filter_kwargs()))
            if r.layout and not len(d):
                msg = (f"requirements {r.requirements} removed every Pareto "
                       f"point for request {r.sha()} "
                       f"(array_size={r.array_size}); relax them or set "
                       f"layout=False")
                if strict:
                    raise ValueError(msg)
                errors[r] = msg
            distilled[r] = d

        laid = [r for r in explored.requests
                if r.layout and r not in errors]
        buckets: list[LayoutBucket] = []
        spec_keys: dict[DesignRequest, tuple] = {}
        if bucket_layouts:
            members: dict[tuple, dict] = {}   # key -> ordered spec set
            for r in laid:
                keys = []
                for spec in distilled[r].specs:
                    key = _bucket_key(spec, r.coarse, r.capacity, self.stats)
                    members.setdefault(key, {})[spec] = None
                    keys.append(key)
                spec_keys[r] = tuple(keys)
            buckets = [LayoutBucket(key=k, coarse=k[0], capacity=k[1],
                                    specs=tuple(specs))
                       for k, specs in members.items()]
        else:
            for r in laid:
                key = ("request", r.sha())
                buckets.append(LayoutBucket(key=key, coarse=r.coarse,
                                            capacity=r.capacity,
                                            specs=distilled[r].specs,
                                            request=r))
                spec_keys[r] = tuple(key for _ in distilled[r].specs)
        return DistilledBatch(explored=explored, distilled=distilled,
                              errors=errors, buckets=buckets,
                              spec_keys=spec_keys)

    def layout_stage(self, bucket: LayoutBucket) -> BucketResult:
        """Stage 3 — one bucket through the batched flow: a single
        `generate_layouts` dispatch chain, independent of every other
        bucket (what lets the pipeline executor stream them)."""
        t0 = time.perf_counter()
        with self._span("layout_bucket", bucket=bucket.key,
                        specs=len(bucket.specs)):
            res = self.layout(bucket.specs, coarse=bucket.coarse,
                              capacity=bucket.capacity)
        dt = time.perf_counter() - t0
        routing = res.routing
        if routing.sweeps is not None:
            self.bump("route_wavefront_iters", int(routing.sweeps.sum()))
            self.bump("route_wavefronts",
                      int((routing.routed + routing.failed).sum()))
            self.bump("route_goal_stops", int(routing.goal_stops.sum()))
            self.bump("route_walk_steps", int(routing.walk_steps.sum()))
        with trace_span("rows", cat="layout", bucket=bucket.key,
                        specs=len(bucket.specs)):
            rows = dict(zip(res.specs, res.metrics_rows()))
        return BucketResult(bucket=bucket, rows=rows, elapsed_s=dt,
                            result=(res if bucket.request is not None
                                    else None),
                            engine=res.routing.engine,
                            rounds=int(res.routing.rounds),
                            collisions=int(res.routing.collisions))

    def finalize_stage(self, batch: DistilledBatch,
                       bucket_results: Iterable[BucketResult], *,
                       waits: dict | None = None, pipelined: bool = False,
                       failed: dict | None = None
                       ) -> dict[DesignRequest, DesignArtifact]:
        """Stage 4 — demux bucket rows back to per-request artifacts,
        stamp provenance (fair-share wall-clock, queue waits), and fill
        the persistent artifact cache.

        `waits` optionally maps request -> explore-queue wait seconds
        (the pipelined executor's measurement); layout queue waits ride
        in on each `BucketResult.queue_wait_s`.

        `failed` maps bucket key -> `(message, attempts)` for buckets
        whose layout exhausted the retry budget (the pipelined
        executor's per-bucket isolation).  A request touching a failed
        bucket completes with `artifact.error` set (its distilled front
        is still attached; `layout_rows` is None) — batch-mates whose
        buckets all succeeded finalize normally, and error artifacts
        are never written to the persistent cache."""
        explored = batch.explored
        results = {br.bucket.key: br for br in bucket_results}
        waits = waits or {}
        failed = failed or {}
        out: dict[DesignRequest, DesignArtifact] = {}
        for r, art in explored.served.items():
            if pipelined:
                prov = dataclasses.replace(
                    art.provenance, pipelined=True,
                    explore_wait_s=waits.get(r, 0.0))
                art = dataclasses.replace(art, provenance=prov)
            out[r] = art
        for r in explored.requests:
            i = explored.info[r]
            keys = batch.spec_keys.get(r, ())
            uniq = list(dict.fromkeys(keys))
            bad = [k for k in uniq if k in failed]
            touched = [results[k] for k in uniq if k in results]
            layout_s = sum(results[k].elapsed_s / len(results[k].bucket.specs)
                           for k in keys if k in results)
            layout_wait = (sum(br.queue_wait_s for br in touched)
                           / len(touched) if touched else 0.0)
            rows_for = (tuple(results[k].rows[s] for k, s
                              in zip(keys, batch.distilled[r].specs))
                        if keys and not bad else None)
            layouts = next((br.result for br in touched
                            if br.bucket.request is r), None)
            error = batch.errors.get(r)
            if bad and error is None:
                error = (f"{len(bad)} of {len(uniq)} layout bucket(s) "
                         f"failed for request {r.sha()}: "
                         + "; ".join(failed[k][0] for k in bad))
            attempts = (sum(br.attempts for br in touched)
                        + sum(failed[k][1] for k in bad))
            retried = (sum(1 for br in touched if br.attempts > 1)
                       + sum(1 for k in bad if failed[k][1] > 1))
            prov = Provenance(
                request_sha=r.sha(), explore_s=i["explore_s"],
                layout_s=layout_s,
                total_s=i["explore_s"] + layout_s,
                new_traces=i["new_traces"],
                explorer_dispatches=i["dispatches"],
                layout_dispatches=len(touched),
                front_cache_hit=i["cache_hit"], coalesced=i["coalesced"],
                served_from=("front_cache" if i["cache_hit"]
                             else "explorer"),
                explore_wait_s=waits.get(r, 0.0),
                layout_wait_s=layout_wait, pipelined=pipelined,
                attempts=attempts, retried_buckets=retried,
                shed_buckets=sum(1 for br in touched if br.shed),
                worker_id=(touched[0].worker_id if touched else ""),
                route_engine="/".join(sorted({br.engine for br in touched
                                              if br.engine})),
                route_rounds=sum(br.rounds for br in touched),
                route_collisions=sum(br.collisions for br in touched),
                mesh_devices=i.get("mesh_devices", 0),
                islands=i.get("islands", r.islands),
                migration_topology=i.get("migration_topology", ""),
                migration_rounds=i.get("migration_rounds", 0))
            art = DesignArtifact(request=r, pareto=batch.distilled[r],
                                 layout_rows=rows_for,
                                 provenance=prov, layouts=layouts,
                                 error=error)
            if self.artifact_cache is not None and art.ok:
                self.artifact_cache.put(art)
                self.bump("artifact_cache_writes")
                if hasattr(self.artifact_cache, "get_with_tier"):
                    self.bump("artifact_cache_l2_writes")
            out[r] = art
        self.bump("requests_served", len(out))
        return out

    def error_artifact(self, request: DesignRequest, message: str, *,
                       pipelined: bool = False,
                       explore_wait_s: float = 0.0) -> DesignArtifact:
        """A terminal failure artifact: an empty frontier, no layouts,
        `error` set, `provenance.served_from == "error"`.  The pipelined
        executor produces these when a whole batch stage (explore /
        distill / finalize) exhausts its retry budget — the batch's
        tickets complete with a diagnosis instead of poisoning the
        pipeline.  Never written to the persistent cache (`art.ok` is
        False)."""
        prov = Provenance(
            request_sha=request.sha(), explore_s=0.0, layout_s=0.0,
            total_s=0.0, new_traces=0, explorer_dispatches=0,
            layout_dispatches=0, front_cache_hit=False, coalesced=1,
            served_from="error", explore_wait_s=explore_wait_s,
            pipelined=pipelined)
        return DesignArtifact(
            request=request,
            pareto=ParetoResult.from_rows(request.array_size, []),
            layout_rows=None, provenance=prov, error=message)

    # -- the end-to-end drivers -------------------------------------------
    def run_many(self, requests: Iterable[DesignRequest], *,
                 bucket_layouts: bool = True, strict: bool = True
                 ) -> dict[DesignRequest, DesignArtifact]:
        """Execute a request batch sequentially through the four stages:
        one coalesced exploration dispatch per explore group, then
        grid-shape-bucketed (or per-request) layout, demuxed into
        per-request artifacts.

        This is the same stage code the pipelined
        `repro.serve.design_service.DesignService` executor drives from
        per-stage workers — the sequential and pipelined paths cannot
        diverge because there is only one implementation of each stage.

        A request whose requirements remove every Pareto point raises
        `ValueError` under `strict=True`; under `strict=False` (the
        multi-tenant path) it gets an artifact with `error` set and the
        rest of the batch is served normally.

        With a persistent `artifact_cache`, requests found there are
        served directly (zero explorer/layout dispatches, provenance
        re-stamped `served_from="artifact_cache"`); the remainder runs
        the normal coalesced pipeline and is written back."""
        explored = self.explore_stage(requests)
        with self._span("distill", requests=len(explored.requests)):
            batch = self.distill_stage(explored, strict=strict,
                                       bucket_layouts=bucket_layouts)
        results = [self.layout_stage(b) for b in batch.buckets]
        with self._span("finalize", buckets=len(results)):
            return self.finalize_stage(batch, results)

    def run(self, request: DesignRequest) -> DesignArtifact:
        """Execute one request end to end (single-batch layout, so the
        artifact carries the full `BatchedLayoutResult` — unless it was
        served from the persistent artifact cache, which stores only the
        serializable `layout_rows`; check `provenance.served_from`)."""
        return self.run_many([request], bucket_layouts=False)[request]
