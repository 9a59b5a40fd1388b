"""Design-service benchmark: N coalesced requests vs N sequential sessions.

The service-level counterpart of `benchmarks/explorer_bench.py` (which
measures the raw sweep program) and `benchmarks/layout_bench.py` (the
raw layout batch): this measures the multi-tenant front door end to end.
The sequential baseline runs each `DesignRequest` in its own fresh
`DesignSession` (one explorer dispatch per request, one whole-batch
layout per request — the legacy `explore` -> `filter` ->
`generate_layouts` shape); the coalesced side submits all N requests to
one `DesignService`, which folds them into a single explorer dispatch
and lays the union of surviving specs out in routing-grid-shape buckets.

Two views per side:

  * cold — fresh process caches (`jax.clear_caches()` first): what a
    fresh fleet pays, including compilation;
  * warm — the same requests resubmitted to the same service / sessions:
    front-cache hits, steady-state relayout only.

A third scenario measures the **async** front door: N tenant threads
submit with jittered arrivals against a running `serve()` pump
(latency-bounded coalescing windows) and block in
`collect(timeout=...)`.  Recorded per run: the realized coalescing
factor (requests per dispatched batch — > 1 means the window actually
merged concurrent tenants) and the per-ticket p50/p95 latency from
submit to artifact-in-hand.  Artifact content is asserted equal to the
sequential baseline, same as the synchronous drain.

A fourth scenario measures the **staged pipeline** executor against
the serial pump on a deliberately multi-batch workload
(`max_coalesce=1`: every request is its own batch, all submitted up
front).  The serial pump runs each batch start-to-finish before the
next; the pipeline overlaps batch N+1's exploration with batch N's
layout and streams layout buckets.  Recorded: wall-clock, per-ticket
p50/p95, per-stage busy seconds, and the explore/layout **overlap
fraction** (simultaneously-busy wall-clock over the smaller stage's
busy time — > 0 means the pipeline actually overlapped; the serial
pump is structurally 0).  Artifacts are asserted ticket-for-ticket
equal to the sequential baseline on both sides.

A fifth scenario measures the **layout worker pool** on the same
multi-batch workload: K=1 vs K=`POOL_WORKERS` layout workers over the
streamed bucket queue, each fault-free and fault-injected (one `node`
fault on a layout bucket — retried in place — and one `slow` fault —
the straggler path: a pool sheds it to a peer via the watchdog, a
single worker has to sit it out).  Recorded per column: wall, ticket
p50/p95, bucket retries/failures, shed count.  `cpu_count` is recorded
at the top level because worker *threads* only buy wall-clock on a
multi-core host — on a 1-core container the fault-free K speedup is
structurally ~1.0x and should be read as environment, not regression.

A sixth **chaos** scenario drives the full fault-tolerance contract:
a guarded service takes an injected layout-bucket kill plus a simulated
preemption mid-run, drains what was admitted, journals the rest to the
WAL beside the artifact cache; a fresh service over the same cache root
replays the journal.  Every ticket must resolve across the two phases
with artifacts equal to the fault-free sequential baseline.

A seventh **bursty** scenario compares fixed coalescing windows with
the controller-driven adaptive window (`repro.telemetry.control`) on
bursty arrivals: tenants arrive in `BURST_COUNT` bursts separated by a
gap, so a narrow fixed window fragments each burst into per-request
dispatches while a wide one taxes every ticket with held-open latency.
Recorded per column: wall, ticket p50/p95, dispatched batch count, and
artifact equality across columns.  The same scenario measures the
telemetry overhead warn-only (one fixed-window run re-executed with a
recorder attached) and — with `--telemetry-dir` — dumps the adaptive
run's span trace (Chrome-trace JSON + per-batch Gantt) and metrics
snapshot for CI to upload as workflow artifacts.

An eighth **fleet** scenario measures the sharded design fleet end to
end: N worker sessions in this process, each with a private L1
artifact cache and one shared `FileRemoteStore` L2, exploring an
island-model request (`DesignRequest.islands > 1`) on a mesh of the
process's devices.  The cold worker dispatches the ring-migration mesh
engine and writes the shared tier; every warm worker serves the same
artifact with zero explorer dispatches
(`served_from="artifact_cache_l2"`, promoted into its own L1).
Recorded: mesh device count, migration topology/rounds,
per-tier hit/write counters, per-worker wall, and `artifacts_equal`
against a one-device baseline — the island engine is bit-identical
across device counts, so the fleet front must equal the baseline's.

Compile counts come from the `nsga2.TRACE_COUNTS["run_cell"]` probe and
the session dispatch counters.  Per-ticket percentiles use
`repro.telemetry.metrics.percentile` — the same quantile math the
service's latency-histogram summaries report.  Results land in
`BENCH_service.json` at the repo root so future PRs have a perf
trajectory.

  PYTHONPATH=src python -m benchmarks.service_bench [--smoke] [--out PATH]
      [--telemetry-dir DIR]

`--smoke` shrinks the request set and MOGA budget for CI.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import platform
import random
import tempfile
import threading
import time

import jax

from repro.api import (DesignRequest, DesignSession, Requirements,
                       TieredArtifactCache)
from repro.core import nsga2
from repro.telemetry import (ControllerConfig, Telemetry, atomic_write_json,
                             percentile, write_metrics_json)
from repro.runtime.fault_tolerance import (FailureInjector, PreemptionGuard,
                                           StragglerMonitor)
from repro.serve.design_service import DesignService, PendingTicket

# Async-scenario knobs: arrivals are jittered uniformly inside the
# jitter span, the pump's admit-until-deadline window is the window
# span; jitter well under window so concurrent tenants coalesce.  CI's
# smoke mode widens both — a descheduled tenant thread on a loaded
# runner must not slip past the deadline and flake the
# coalescing_factor assertion.
ASYNC_WINDOW_S, ASYNC_JITTER_S = 0.25, 0.15
ASYNC_WINDOW_SMOKE_S, ASYNC_JITTER_SMOKE_S = 1.5, 0.3

# Layout-pool scenario knobs: pool width, the shed bar (threshold x EMA
# of a bucket's wall time), and the injected slow fault's sleep — long
# enough to clear the bar once an EMA exists, short enough not to
# dominate the single-worker column's wall.
POOL_WORKERS = 4
POOL_SHED_THRESHOLD = 4.0   # loose: CPU contention on few-core hosts
#   stretches healthy concurrent buckets too; sheds of those are benign
#   (first completion wins, duplicates cancel at pickup) but a
#   hair-trigger bar would shed every bucket on a 1-core runner
POOL_SLOW_S, POOL_SLOW_SMOKE_S = 30.0, 6.0   # must clear threshold x EMA
#   by a margin: full-mode buckets run seconds each

# Bursty-scenario knobs: BURST_COUNT bursts, BURST_GAP_S apart, tenants
# inside a burst jittered within BURST_JITTER_S.  The fixed columns
# bracket the design space — a narrow window (fragments bursts) vs a
# wide one (holds every ticket open); the adaptive column starts at the
# narrow window and lets the controller ease it from the arrival-rate
# EMA.  Burst size is the controller's target batch.
BURST_COUNT, BURST_GAP_S, BURST_JITTER_S = 3, 1.5, 0.1
BURSTY_NARROW_S, BURSTY_WIDE_S = 0.02, 1.0
BURSTY_SEEDS = 6

# Fleet-scenario knobs: worker session count and islands per request.
# The island engine uses the largest divisor of `islands` that fits the
# mesh, so up to FLEET_ISLANDS devices carry the islands while the
# baseline runs the identical request on 1 device.
FLEET_WORKERS = 2
FLEET_ISLANDS = 4

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]

REQUIREMENTS = Requirements(min_tops=0.5, min_snr_db=10.0)
REQUIREMENTS_FULL = Requirements(min_tops=0.5, min_snr_db=15.0)


def _requests(smoke: bool) -> list[DesignRequest]:
    sizes, seeds = ((4096,), (0, 1)) if smoke else \
        ((4096, 8192), (0, 1, 2))
    pop, gens = (48, 8) if smoke else (192, 60)
    reqs = REQUIREMENTS if smoke else REQUIREMENTS_FULL
    return [DesignRequest(array_size=s, seed=sd, pop_size=pop,
                          generations=gens, requirements=reqs, layout=True)
            for s in sizes for sd in seeds]


def _sequential(requests, sessions=None):
    """One fresh session per request: the pre-coalescing baseline."""
    sessions = sessions or [DesignSession() for _ in requests]
    arts = [ses.run(req) for ses, req in zip(sessions, requests)]
    return arts, sessions


def _coalesced(requests, service=None):
    service = service or DesignService(max_coalesce=len(requests))
    tickets = [service.submit(r) for r in requests]
    done = service.run()
    return [done[t] for t in tickets], service


def _async_serve(requests, *, window_s: float, jitter_s: float,
                 timeout_s: float = 600.0):
    """N tenant threads, jittered arrivals, one serve() pump."""
    offsets = [random.Random(i).uniform(0.0, jitter_s)
               for i in range(len(requests))]
    service = DesignService(max_coalesce=len(requests),
                            coalesce_window_s=window_s)
    artifacts = [None] * len(requests)
    latencies = [0.0] * len(requests)
    errors: list[Exception] = []
    gate = threading.Barrier(len(requests) + 1)

    def tenant(i: int, req: DesignRequest) -> None:
        try:
            gate.wait()
            time.sleep(offsets[i])
            t0 = time.perf_counter()
            ticket = service.submit(req)
            artifacts[i] = service.collect(ticket, timeout=timeout_s)
            latencies[i] = time.perf_counter() - t0
        except Exception as e:   # surfaced to the caller below
            errors.append(e)

    threads = [threading.Thread(target=tenant, args=(i, r))
               for i, r in enumerate(requests)]
    for t in threads:
        t.start()
    with service.serve():
        t0 = time.perf_counter()
        gate.wait()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return artifacts, service, wall, latencies


def _burst_requests(smoke: bool) -> list[DesignRequest]:
    pop, gens = (48, 8) if smoke else (96, 24)
    return [DesignRequest(array_size=4096, seed=sd, pop_size=pop,
                          generations=gens, requirements=REQUIREMENTS,
                          layout=True)
            for sd in range(BURSTY_SEEDS)]


def _bursty_serve(requests, *, window_s: float, controller=None,
                  telemetry=None, timeout_s: float = 600.0):
    """Tenant threads arriving in bursts against one serve() pump.
    Burst k's tenants arrive at ~`k * BURST_GAP_S`; `max_coalesce` is
    the burst size, so a perfectly-adapted window coalesces each burst
    into exactly one dispatch without holding it open into the gap."""
    per_burst = (len(requests) + BURST_COUNT - 1) // BURST_COUNT
    offsets = [(i // per_burst) * BURST_GAP_S
               + random.Random(1000 + i).uniform(0.0, BURST_JITTER_S)
               for i in range(len(requests))]
    service = DesignService(max_coalesce=per_burst,
                            coalesce_window_s=window_s,
                            telemetry=telemetry, controller=controller)
    artifacts = [None] * len(requests)
    latencies = [0.0] * len(requests)
    errors: list[Exception] = []
    gate = threading.Barrier(len(requests) + 1)

    def tenant(i: int, req: DesignRequest) -> None:
        try:
            gate.wait()
            time.sleep(offsets[i])
            t0 = time.perf_counter()
            ticket = service.submit(req)
            artifacts[i] = service.collect(ticket, timeout=timeout_s)
            latencies[i] = time.perf_counter() - t0
        except Exception as e:   # surfaced to the caller below
            errors.append(e)

    threads = [threading.Thread(target=tenant, args=(i, r))
               for i, r in enumerate(requests)]
    for t in threads:
        t.start()
    with service.serve():
        t0 = time.perf_counter()
        gate.wait()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return artifacts, service, wall, latencies


def _bursty_column(arts, service, wall, lat, ref) -> dict:
    stats = service.stats()
    return {
        "wall_s": wall,
        "ticket_p50_s": float(percentile(lat, 50)),
        "ticket_p95_s": float(percentile(lat, 95)),
        "batches": int(stats["service_batches"]),
        "explorer_dispatches": int(stats["explorer_dispatches"]),
        "artifacts_equal": (True if ref is None else
                            all(a.summary() == b.summary()
                                for a, b in zip(ref, arts))),
    }


def _bursty(smoke: bool, telemetry_dir=None) -> dict:
    """Adaptive-vs-fixed coalescing on bursty arrivals (plus the
    telemetry-overhead measurement and the CI trace/metrics dump)."""
    requests = _burst_requests(smoke)
    per_burst = (len(requests) + BURST_COUNT - 1) // BURST_COUNT
    # warm the shapes once so no column pays compilation alone
    _bursty_serve(requests, window_s=BURSTY_WIDE_S)

    # -- fixed columns (narrow doubles as the artifact reference) ------
    ref, narrow_svc, narrow_wall, narrow_lat = _bursty_serve(
        requests, window_s=BURSTY_NARROW_S)
    wide_arts, wide_svc, wide_wall, wide_lat = _bursty_serve(
        requests, window_s=BURSTY_WIDE_S)
    # -- telemetry overhead: same wide config, recorder attached -------
    _, _, tel_wall, _ = _bursty_serve(
        requests, window_s=BURSTY_WIDE_S, telemetry=Telemetry())
    # -- adaptive column -----------------------------------------------
    atel = Telemetry()
    cfg = ControllerConfig(min_window_s=BURSTY_NARROW_S,
                           max_window_s=BURSTY_WIDE_S,
                           target_batch=per_burst,
                           min_workers=1, max_workers=1,
                           hysteresis_ticks=3, tick_interval_s=0.05)
    ada_arts, ada_svc, ada_wall, ada_lat = _bursty_serve(
        requests, window_s=BURSTY_NARROW_S, controller=cfg, telemetry=atel)

    if telemetry_dir is not None:
        d = pathlib.Path(telemetry_dir)
        d.mkdir(parents=True, exist_ok=True)
        trace = ada_svc.trace()
        trace.to_json(d / "service_trace.json")
        atomic_write_json(trace.gantt(), d / "service_gantt.json")
        write_metrics_json(ada_svc.metrics(), d / "service_metrics.json")

    return {
        "n_requests": len(requests),
        "bursts": BURST_COUNT,
        "burst_gap_s": BURST_GAP_S,
        "fixed_narrow": dict(
            _bursty_column(ref, narrow_svc, narrow_wall, narrow_lat,
                           None) | {"window_s": BURSTY_NARROW_S}),
        "fixed_wide": dict(
            _bursty_column(wide_arts, wide_svc, wide_wall, wide_lat, ref)
            | {"window_s": BURSTY_WIDE_S}),
        "adaptive": dict(
            _bursty_column(ada_arts, ada_svc, ada_wall, ada_lat, ref)
            | {"window_start_s": BURSTY_NARROW_S,
               "window_final_s": float(ada_svc.coalesce_window_s),
               "control_decisions": len(ada_svc.controller.decisions),
               "window_updates":
                   int(ada_svc.stats()["control_window_updates"])}),
        # warn-only: wall-clock cost of an attached recorder on the
        # identical fixed-wide run (noisy on loaded hosts — a regression
        # signal, not a gate)
        "telemetry_overhead_frac":
            float((tel_wall - wide_wall) / wide_wall),
        "telemetry_spans": len(atel.recorder),
    }


def _staged(requests, *, pipelined: bool, workers: int = 1,
            injector=None, straggler=None, timeout_s: float = 600.0):
    """The multi-batch pipeline workload: every request is its own batch
    (`max_coalesce=1`), all submitted up front.  Under the staged
    executor, batch N+1's exploration overlaps batch N's layout; under
    the serial pump each batch runs start-to-finish before the next.
    `workers`/`injector`/`straggler` parameterize the layout-pool and
    fault-injected columns."""
    service = DesignService(max_coalesce=1, layout_workers=workers,
                            injector=injector, straggler=straggler)
    with service.serve(pipelined=pipelined):
        t0 = time.perf_counter()
        tickets = [service.submit(r) for r in requests]
        artifacts, latencies = [], []
        for t in tickets:   # finalize is FIFO: completion order == order
            artifacts.append(service.collect(t, timeout=timeout_s))
            latencies.append(time.perf_counter() - t0)
        wall = time.perf_counter() - t0
        stats = service.stats()
    return artifacts, stats, wall, latencies


def _pool_injector(smoke: bool) -> FailureInjector:
    # one node fault on the second layout bucket dispatch (retried in
    # place) and one slow fault on the fourth (shed to a peer when the
    # pool is wider than one); indices that never dispatch simply don't
    # fire, so the schedule is safe for any bucket count
    return FailureInjector(
        slow_seconds=POOL_SLOW_SMOKE_S if smoke else POOL_SLOW_S,
        fail_at={"layout": [1, (3, "slow")]})


def _pool_column(arts, stats, wall, lat, seq) -> dict:
    return {
        "wall_s": wall,
        "ticket_p50_s": float(percentile(lat, 50)),
        "ticket_p95_s": float(percentile(lat, 95)),
        "layout_dispatches": int(stats["layout_dispatches"]),
        "bucket_retries": int(stats["bucket_retries"]),
        "bucket_failures": int(stats["bucket_failures"]),
        "shed_buckets": int(stats["shed_buckets"]),
        "shed_losses": int(stats["shed_losses"]),
        "artifacts_equal": all(a.summary() == b.summary()
                               for a, b in zip(seq, arts)),
    }


def _chaos(requests, baseline, *, timeout_s: float = 900.0) -> dict:
    """Kill one layout bucket and preempt the service mid-run, then
    restart.  Phase 1: a guarded service with an injected node fault on
    the first layout bucket and a preemption request at the second
    admission — it drains the already-admitted batches and journals
    every unfinished ticket to the WAL beside the artifact cache.
    Phase 2: a fresh service over the same cache root (the "restarted
    process") replays the journal; drained work is served from disk.
    Every ticket must resolve across the two phases with artifacts
    equal to the fault-free sequential baseline."""
    cache_dir = tempfile.mkdtemp(prefix="acim-chaos-cache-")
    guard = PreemptionGuard()
    injector = FailureInjector(
        guard=guard, fail_at={"layout": [0], "admit": [(1, "preempt")]})
    svc1 = DesignService(DesignSession(artifact_cache=cache_dir),
                         max_coalesce=1, layout_workers=2,
                         guard=guard, injector=injector)
    drained = {}
    t0 = time.perf_counter()
    with svc1.serve():
        tickets = [svc1.submit(r) for r in requests]
        for r, t in zip(requests, tickets):
            try:
                drained[r] = svc1.collect(t, timeout=timeout_s)
            except PendingTicket:
                pass            # journaled: the replaying service owns it
    drain_wall = time.perf_counter() - t0
    s1 = svc1.stats()

    svc2 = DesignService(DesignSession(artifact_cache=cache_dir),
                         max_coalesce=1, layout_workers=2)
    pending = svc2.journal.replay()    # peek; replay() does not clear
    replayed = {}
    t0 = time.perf_counter()
    tickets2 = svc2.replay_journal()
    with svc2.serve():
        for r, t in zip(pending, tickets2):
            replayed[r] = svc2.collect(t, timeout=timeout_s)
    replay_wall = time.perf_counter() - t0
    s2 = svc2.stats()

    # in-flight tickets are journaled too (at-least-once WAL), so a
    # request can resolve in both phases; the drained copy is canonical
    arts = {**replayed, **drained}
    resolved = [arts.get(r) for r in requests]
    return {
        "n_requests": len(requests),
        "drain_wall_s": drain_wall,
        "replay_wall_s": replay_wall,
        "n_drained": len(drained),
        "n_journaled": int(s1["journaled_tickets"]),
        "n_replayed": len(tickets2),
        "preemptions": int(s1["preemptions"]),
        "bucket_retries": int(s1["bucket_retries"]),
        # drained work that reached the cache before the "old process
        # died" is served from disk on replay — convergence, not recompute
        "replay_artifact_cache_hits": int(s2["artifact_cache_hits"]),
        "replay_explorer_dispatches": int(s2["explorer_dispatches"]),
        "all_resolved": all(a is not None and a.ok for a in resolved),
        "artifacts_equal": all(a is not None and a.summary() == b.summary()
                               for a, b in zip(resolved, baseline)),
    }


def _fleet(smoke: bool) -> dict:
    """Sharded-fleet scenario: FLEET_WORKERS sessions in this process,
    each a private L1 over one shared `file://` L2, exploring an island
    request on a mesh of this process's devices.  Worker 0 runs cold
    (mesh explorer dispatch + L2 write); the rest are warm fleet members
    (zero dispatches, served from the shared tier).  The baseline runs
    the identical request on one device — the island engine is
    device-count independent, so every front must be equal.  Workers
    share the process (and so the chip) instead of being child
    processes: a child cannot reach a chip its parent holds.  The
    cross-process L2 round trip is covered by
    `tests/test_design_service_async.py`."""
    pop, gens = (48, 8) if smoke else (96, 40)
    req = DesignRequest(array_size=4096, seed=0, pop_size=pop,
                        generations=gens, requirements=REQUIREMENTS,
                        layout=True, islands=FLEET_ISLANDS, migrate_every=5)
    t0 = time.perf_counter()
    baseline = DesignSession(mesh=1).run(req)
    base_wall = time.perf_counter() - t0

    tmp = pathlib.Path(tempfile.mkdtemp(prefix="acim-fleet-"))
    remote = f"file://{tmp / 'shared-l2'}"
    reports, walls = [], []
    for w in range(FLEET_WORKERS):
        session = DesignSession(artifact_cache=TieredArtifactCache(
            str(tmp / f"worker{w}-l1"), remote))
        t0 = time.perf_counter()
        art = session.run(req)
        walls.append(time.perf_counter() - t0)
        reports.append({"session": session, "artifact": art})

    def stat(rep, key):
        return int(rep["session"].stats[key])

    cold, warm = reports[0], reports[1:]
    cold_prov = cold["artifact"].provenance
    tiers = {k: sum(stat(rep, f"artifact_cache_{k}") for rep in reports)
             for k in ("l1_hits", "l2_hits", "promotions", "l2_writes")}
    return {
        "n_workers": FLEET_WORKERS,
        "islands": FLEET_ISLANDS,
        "migrate_every": req.migrate_every,
        "n_devices": jax.device_count(),
        "mesh_devices": cold_prov.mesh_devices,
        "migration_topology": cold_prov.migration_topology,
        "migration_rounds": cold_prov.migration_rounds,
        "baseline_wall_s": base_wall,
        "baseline_mesh_devices": baseline.provenance.mesh_devices,
        "worker_wall_s": walls,
        "cold_worker": {
            "served_from": cold_prov.served_from,
            "explorer_dispatches": stat(cold, "explorer_dispatches"),
            "l2_writes": stat(cold, "artifact_cache_l2_writes")},
        "warm_workers": [{
            "served_from": rep["artifact"].provenance.served_from,
            "explorer_dispatches": stat(rep, "explorer_dispatches"),
            "layout_dispatches": stat(rep, "layout_dispatches"),
            "l2_hits": stat(rep, "artifact_cache_l2_hits"),
            "promotions": stat(rep, "artifact_cache_promotions")}
            for rep in warm],
        "tier_hits": tiers,
        "artifacts_equal": all(rep["artifact"].summary()
                               == baseline.summary() for rep in reports),
    }


def _timed(fn, *args):
    n0 = nsga2.TRACE_COUNTS["run_cell"]
    t0 = time.perf_counter()
    out, state = fn(*args)
    return out, state, time.perf_counter() - t0, \
        nsga2.TRACE_COUNTS["run_cell"] - n0


def run(smoke: bool = False, telemetry_dir=None) -> dict:
    requests = _requests(smoke)

    jax.clear_caches()
    seq, sessions, seq_cold, seq_traces = _timed(_sequential, requests)
    _, _, seq_warm, _ = _timed(_sequential, requests, sessions)
    seq_dispatches = sum(s.stats["explorer_dispatches"] for s in sessions)

    jax.clear_caches()
    bat, service, bat_cold, bat_traces = _timed(_coalesced, requests)
    _, _, bat_warm, _ = _timed(_coalesced, requests, service)

    artifacts_equal = all(a.summary() == b.summary()
                          for a, b in zip(seq, bat))

    window_s = ASYNC_WINDOW_SMOKE_S if smoke else ASYNC_WINDOW_S
    jitter_s = ASYNC_JITTER_SMOKE_S if smoke else ASYNC_JITTER_S
    asy, asvc, asy_wall, asy_lat = _async_serve(requests, window_s=window_s,
                                                jitter_s=jitter_s)
    astats = asvc.stats()
    async_equal = all(a.summary() == b.summary() for a, b in zip(seq, asy))
    batches = int(astats["service_batches"])

    # warm the per-request layout programs first: the multi-batch workload
    # compiles different batch shapes than the coalesced scenarios, and
    # whichever side ran first would otherwise pay them alone
    _staged(requests, pipelined=False)
    srl, srl_stats, srl_wall, srl_lat = _staged(requests, pipelined=False)
    pipe, pipe_stats, pipe_wall, pipe_lat = _staged(requests, pipelined=True)
    busy = pipe_stats["stage_busy_s"]

    # layout-pool scenario: K=1 fault-free is the pipelined run above
    p4, p4_stats, p4_wall, p4_lat = _staged(
        requests, pipelined=True, workers=POOL_WORKERS)
    f1, f1_stats, f1_wall, f1_lat = _staged(
        requests, pipelined=True, workers=1, injector=_pool_injector(smoke),
        straggler=StragglerMonitor(threshold=POOL_SHED_THRESHOLD))
    f4, f4_stats, f4_wall, f4_lat = _staged(
        requests, pipelined=True, workers=POOL_WORKERS,
        injector=_pool_injector(smoke),
        straggler=StragglerMonitor(threshold=POOL_SHED_THRESHOLD))

    chaos = _chaos(requests, seq)
    bursty = _bursty(smoke, telemetry_dir=telemetry_dir)
    fleet = _fleet(smoke)
    return {
        "n_requests": len(requests),
        "requests": [r.to_dict() for r in requests],
        "smoke": smoke,
        "backend": jax.default_backend(),
        "platform": platform.platform(),
        "cpu_count": os.cpu_count(),
        "sequential": {"cold_s": seq_cold, "warm_s": seq_warm,
                       "run_cell_traces": seq_traces,
                       "explorer_dispatches": seq_dispatches},
        "coalesced": {"cold_s": bat_cold, "warm_s": bat_warm,
                      "run_cell_traces": bat_traces,
                      "explorer_dispatches":
                          int(service.stats()["explorer_dispatches"]),
                      "layout_bucket_dispatches":
                          int(service.stats()["layout_dispatches"])},
        "coalesced_speedup_cold": seq_cold / bat_cold,
        "coalesced_speedup_warm": seq_warm / bat_warm,
        "artifacts_equal": artifacts_equal,
        "async": {
            "window_s": window_s,
            "jitter_s": jitter_s,
            "wall_s": asy_wall,
            "ticket_p50_s": float(percentile(asy_lat, 50)),
            "ticket_p95_s": float(percentile(asy_lat, 95)),
            "batches": batches,
            "coalescing_factor":
                int(astats["service_batch_requests"]) / max(batches, 1),
            "explorer_dispatches": int(astats["explorer_dispatches"]),
            "artifacts_equal": async_equal,
        },
        "pipelined": {
            "batches": int(pipe_stats["service_batches"]),
            "wall_s": pipe_wall,
            "ticket_p50_s": float(percentile(pipe_lat, 50)),
            "ticket_p95_s": float(percentile(pipe_lat, 95)),
            "stage_busy_s": {k: float(v) for k, v in busy.items()},
            "overlap_s": float(pipe_stats["pipeline_overlap_s"]),
            "overlap_fraction":
                float(pipe_stats["pipeline_overlap_fraction"]),
            "artifacts_equal": all(a.summary() == b.summary()
                                   for a, b in zip(seq, pipe)),
            "serial": {
                "batches": int(srl_stats["service_batches"]),
                "wall_s": srl_wall,
                "ticket_p50_s": float(percentile(srl_lat, 50)),
                "ticket_p95_s": float(percentile(srl_lat, 95)),
                "artifacts_equal": all(a.summary() == b.summary()
                                       for a, b in zip(seq, srl)),
            },
            "wall_speedup_vs_serial": srl_wall / pipe_wall,
            "p50_ratio_vs_serial":
                float(percentile(pipe_lat, 50)
                      / percentile(srl_lat, 50)),
            "p95_ratio_vs_serial":
                float(percentile(pipe_lat, 95)
                      / percentile(srl_lat, 95)),
        },
        "layout_pool": {
            "workers": POOL_WORKERS,
            "shed_threshold": POOL_SHED_THRESHOLD,
            "slow_fault_s": POOL_SLOW_SMOKE_S if smoke else POOL_SLOW_S,
            "fault_free": {
                "k1": _pool_column(pipe, pipe_stats, pipe_wall,
                                   pipe_lat, seq),
                "k4": _pool_column(p4, p4_stats, p4_wall, p4_lat, seq),
            },
            "fault_injected": {
                "k1": _pool_column(f1, f1_stats, f1_wall, f1_lat, seq),
                "k4": _pool_column(f4, f4_stats, f4_wall, f4_lat, seq),
            },
            # thread-pool parallelism needs cores: read these against
            # the top-level cpu_count (1-core hosts pin fault-free ~1.0x)
            "wall_speedup_k4_vs_k1": pipe_wall / p4_wall,
            "faulty_wall_speedup_k4_vs_k1": f1_wall / f4_wall,
        },
        "chaos": chaos,
        "bursty": bursty,
        "fleet": fleet,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small request set / MOGA budget for CI")
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_service.json"))
    ap.add_argument("--telemetry-dir", default=None,
                    help="dump the adaptive run's span trace, Gantt, and "
                         "metrics snapshot here (CI uploads these)")
    args = ap.parse_args()
    result = run(smoke=args.smoke, telemetry_dir=args.telemetry_dir)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    for side in ("sequential", "coalesced"):
        r = result[side]
        print(f"{side}: cold={r['cold_s']:.3f}s warm={r['warm_s']:.3f}s "
              f"traces={r['run_cell_traces']} "
              f"dispatches={r['explorer_dispatches']}")
    a = result["async"]
    print(f"async: wall={a['wall_s']:.3f}s p50={a['ticket_p50_s']:.3f}s "
          f"p95={a['ticket_p95_s']:.3f}s batches={a['batches']} "
          f"coalescing_factor={a['coalescing_factor']:.2f} "
          f"artifacts_equal={a['artifacts_equal']}")
    p = result["pipelined"]
    print(f"pipelined: wall={p['wall_s']:.3f}s (serial pump "
          f"{p['serial']['wall_s']:.3f}s, {p['wall_speedup_vs_serial']:.2f}x) "
          f"p50={p['ticket_p50_s']:.3f}s p95={p['ticket_p95_s']:.3f}s "
          f"(serial p50={p['serial']['ticket_p50_s']:.3f}s "
          f"p95={p['serial']['ticket_p95_s']:.3f}s) "
          f"overlap_fraction={p['overlap_fraction']:.2f} "
          f"artifacts_equal={p['artifacts_equal']}")
    lp = result["layout_pool"]
    ff, fi = lp["fault_free"], lp["fault_injected"]
    print(f"layout pool (K={lp['workers']}, cpu_count="
          f"{result['cpu_count']}): fault-free wall "
          f"K1={ff['k1']['wall_s']:.3f}s K4={ff['k4']['wall_s']:.3f}s "
          f"({lp['wall_speedup_k4_vs_k1']:.2f}x); fault-injected wall "
          f"K1={fi['k1']['wall_s']:.3f}s K4={fi['k4']['wall_s']:.3f}s "
          f"({lp['faulty_wall_speedup_k4_vs_k1']:.2f}x) "
          f"retries={fi['k4']['bucket_retries']} "
          f"shed={fi['k4']['shed_buckets']}")
    # artifact equality is load-bearing on every host; the K-speedup is
    # only meaningful with >= K cores (thread-pool parallelism)
    for side in ("fault_free", "fault_injected"):
        for k in ("k1", "k4"):
            assert lp[side][k]["artifacts_equal"], (side, k)
    cores = result["cpu_count"] or 1
    if cores < lp["workers"]:
        print(f"CAVEAT: cpu_count=={cores} < K={lp['workers']} — layout-pool "
              f"wall speedups are structurally ~1.0x on this host; "
              f"skipping the K-speedup assertion")
    else:
        assert lp["wall_speedup_k4_vs_k1"] > 1.0, lp
    b = result["bursty"]
    print(f"bursty: narrow p95={b['fixed_narrow']['ticket_p95_s']:.3f}s "
          f"({b['fixed_narrow']['batches']} batches) wide "
          f"p95={b['fixed_wide']['ticket_p95_s']:.3f}s "
          f"({b['fixed_wide']['batches']} batches) adaptive "
          f"p95={b['adaptive']['ticket_p95_s']:.3f}s "
          f"({b['adaptive']['batches']} batches, window "
          f"{b['adaptive']['window_start_s']:.3f}->"
          f"{b['adaptive']['window_final_s']:.3f}s) "
          f"overhead={b['telemetry_overhead_frac']:+.1%} "
          f"artifacts_equal={b['adaptive']['artifacts_equal']}")
    fl = result["fleet"]
    print(f"fleet: {fl['n_workers']} workers x {fl['islands']} islands on "
          f"{fl['mesh_devices']}/{fl['n_devices']} devices "
          f"({fl['migration_topology']}, {fl['migration_rounds']} rounds): "
          f"cold={fl['worker_wall_s'][0]:.3f}s "
          f"({fl['cold_worker']['served_from']}) warm="
          f"{[f'{w:.3f}s' for w in fl['worker_wall_s'][1:]]} "
          f"(served {[w['served_from'] for w in fl['warm_workers']]}) "
          f"tier_hits={fl['tier_hits']} "
          f"artifacts_equal={fl['artifacts_equal']}")
    c = result["chaos"]
    print(f"chaos: drained {c['n_drained']}/{c['n_requests']} then "
          f"journaled {c['n_journaled']}, replayed {c['n_replayed']} "
          f"(cache hits {c['replay_artifact_cache_hits']}) "
          f"retries={c['bucket_retries']} "
          f"all_resolved={c['all_resolved']} "
          f"artifacts_equal={c['artifacts_equal']}")
    print(f"speedup cold={result['coalesced_speedup_cold']:.2f}x "
          f"warm={result['coalesced_speedup_warm']:.2f}x "
          f"artifacts_equal={result['artifacts_equal']} -> {args.out}")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
