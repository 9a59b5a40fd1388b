"""Driver of `closed_sweep` mixes: one client running sweep jobs back to back.

A job is every array size of the configuration times the mix's fixed
`request_seeds`, submitted together to a fresh session and service; the
run's seed orders the submissions.  Every job lays out the same designs,
so set-up, which serves the job once, builds every layout program the
window meets.  No job starts after the window's length; the job in
flight then finishes and counts.

Mix keys: `request_seeds`, `job_timeout_s`, `trace_seconds` (the part of
the window's first job a traced run profiles).
"""
from __future__ import annotations

import time

import loadgen


def job_cells(mix: dict, sizes, seed: int, job: int) -> list[tuple]:
    """The (array_size, request seed) cells of one sweep job, in the
    submission order the seed gives that job."""
    cells = [(int(s), int(sd)) for s in sizes for sd in mix["request_seeds"]]
    order = loadgen.rng_for(seed, f"sweep_job/{job}").permutation(len(cells))
    return [cells[i] for i in order]


class Driver:
    def __init__(self, system: loadgen.System, mix: dict, seed: int,
                 seconds: float):
        self.system, self.mix = system, mix
        self.seed, self.seconds = seed, seconds

    def _job(self, k: int, tracer=None):
        from repro.serve.design_service import PendingTicket

        cells = job_cells(self.mix, self.system.cfg["array_sizes"],
                          self.seed, k)
        reqs = [self.system.request(*c) for c in cells]
        session = self.system.session()
        svc = self.system.service(session)
        t0 = time.perf_counter()
        deadline = t0 + float(self.mix["job_timeout_s"])
        try:
            s0 = svc.stats()
            with loadgen.annotate("bench.submit"):
                tickets = [svc.submit(r) for r in reqs]
            arts = [None] * len(tickets)
            for i, t in enumerate(tickets):
                while time.perf_counter() < deadline:
                    if tracer is not None:
                        tracer.maybe_stop(time.perf_counter() - t0)
                    try:
                        with loadgen.annotate("bench.collect"):
                            arts[i] = svc.collect(t, timeout=0.25)
                        break
                    except PendingTicket:
                        continue
            delta = loadgen.stat_delta(s0, svc.stats())
        finally:
            if tracer is not None:
                tracer.stop()
            with loadgen.annotate("bench.job_close"):
                svc.close()
        return reqs, arts, session, delta

    def setup(self) -> None:
        """Serve the job once: every layout shape of the window compiles
        (or loads from the persistent cache) here."""
        _, arts, _, _ = self._job(-1)
        bad = [a for a in arts if a is None or not a.ok]
        if bad:
            raise RuntimeError(f"set-up job failed: {len(bad)} requests")

    def window(self, tracer=None) -> loadgen.WindowResult:
        out = loadgen.WindowResult([], [], [], 0.0, [], {})
        t0 = time.perf_counter()
        k = 0
        while time.perf_counter() - t0 < self.seconds:
            t_job = time.perf_counter()
            if tracer is not None and k == 0:
                tracer.start()
            reqs, arts, session, delta = self._job(
                k, tracer if k == 0 else None)
            now = time.perf_counter()
            out.requests += reqs
            out.artifacts += arts
            out.latency_s += [now - t_job] * len(reqs)
            out.sessions.append(session)
            loadgen.add_stats(out.stats, delta)
            k += 1
        out.seconds = time.perf_counter() - t0
        out.designs = sum(len(a.layout_rows) for a in out.artifacts
                          if a is not None and a.ok and a.layout_rows)
        return out

    def close(self) -> None:
        pass
