"""Driver of `open_poisson` mixes: independent users, an open loop.

Requests fall due on a fixed schedule whatever the service does.  Every
seed gets the same multiset of array sizes and of inter-arrival gaps
(the quantiles of an exponential distribution at the mix's rate), in an
order drawn from the seed, and request seeds of its own, all distinct.
So two seeds make the same amount of work, and the same seed the same
requests.  Latency runs from a request's due time to its collected
artifact.

Mix keys: `rate_per_s`, `drain_s` (how long past the last due time a
request may still be collected), `trace_seconds` (the part of the window
a traced run profiles).
"""
from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np

import loadgen


@dataclasses.dataclass(frozen=True)
class Due:
    """One request of an open-loop schedule."""
    at: float            # seconds after the window opens
    array_size: int
    seed: int


def schedule(mix: dict, sizes, seed: int, seconds: float) -> list[Due]:
    """The window's requests, in the order they fall due."""
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = loadgen.rng_for(seed, "open_poisson")
    seeds = loadgen.distinct_seeds(rng, n)
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / rate)
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    size_of = rng.permutation(np.resize(np.asarray(sizes), n))
    return [Due(float(t), int(s), sd)
            for t, s, sd in zip(due, size_of, seeds)]


def warm_bursts(n: int, widest: int) -> list[range]:
    """Index ranges of the warm-up bursts over `n` requests: one burst
    of each size 1..`widest`, then bursts of `widest` until all are
    sent."""
    out, i = [], 0
    for size in range(1, widest + 1):
        out.append(range(i, min(i + size, n)))
        i += size
    while i < n:
        out.append(range(i, min(i + widest, n)))
        i += widest
    return [r for r in out if len(r)]


class Driver:
    """One long-lived service; requests sent on the mix's schedule."""

    def __init__(self, system: loadgen.System, mix: dict, seed: int,
                 seconds: float):
        self.system, self.mix = system, mix
        self.schedule = schedule(mix, system.cfg["array_sizes"], seed,
                                 seconds)
        self.drain_s = float(mix["drain_s"])

    def setup(self) -> None:
        """Warm up, then start the service the window uses.

        The front post-processing builds programs shaped by each
        request's final population, so only the window's own requests
        warm every shape it meets.  A throwaway session and service
        answer them in bursts of every coalesced batch size from 1 to
        `max_coalesce`; the window then runs on a fresh session and
        service, which remember no front and so explore every request
        again."""
        self.warm(self.schedule)
        self.session = self.system.session()
        self.svc = self.system.service(self.session)

    def warm(self, due: list[Due]) -> None:
        session = self.system.session()
        svc = self.system.service(session)
        try:
            widest = self.system.cfg["service"]["max_coalesce"]
            for burst in warm_bursts(len(due), widest):
                tickets = [svc.submit(self.system.request(
                    due[i].array_size, due[i].seed)) for i in burst]
                for t in tickets:
                    art = svc.collect(t, timeout=600.0)
                    if not art.ok:
                        raise RuntimeError(
                            f"warm-up request failed: {art.error}")
        finally:
            svc.close()

    def window(self, tracer=None) -> loadgen.WindowResult:
        reqs = [self.system.request(d.array_size, d.seed)
                for d in self.schedule]
        n = len(reqs)
        tickets = [None] * n
        done = [None] * n
        arts = [None] * n
        ready = threading.Semaphore(0)
        stats0 = self.svc.stats()

        def collector():
            for i in range(n):
                ready.acquire()
                left = t0 + last_due + self.drain_s - time.perf_counter()
                try:
                    with loadgen.annotate("bench.collect"):
                        arts[i] = self.svc.collect(tickets[i],
                                                   timeout=max(left, 0.0))
                    done[i] = time.perf_counter()
                except Exception:     # never collected: counted as failed
                    arts[i] = None

        last_due = self.schedule[-1].at
        lateness = []
        t0 = time.perf_counter()
        worker = threading.Thread(target=collector, name="bench-collector")
        worker.start()
        if tracer is not None:
            tracer.start()
        traced = None
        for i, (d, r) in enumerate(zip(self.schedule, reqs)):
            if (traced is None and tracer is not None
                    and time.perf_counter() - t0 >= tracer.seconds):
                # stopping the profiler stalls this loop for tens of
                # seconds: what follows is no steady state, so the traced
                # part's counters are taken here
                traced = loadgen.stat_delta(stats0, self.svc.stats())
                tracer.stop()
            wait = t0 + d.at - time.perf_counter()
            if wait > 0:
                with loadgen.annotate("bench.wait_arrival"):
                    time.sleep(wait)
            with loadgen.annotate("bench.submit"):
                tickets[i] = self.svc.submit(r)
            lateness.append(time.perf_counter() - t0 - d.at)
            ready.release()
        worker.join()
        if tracer is not None:
            if traced is None:
                traced = loadgen.stat_delta(stats0, self.svc.stats())
            tracer.stop()
        end = max([x for x in done if x is not None], default=t0)
        stats = loadgen.stat_delta(stats0, self.svc.stats())
        return loadgen.WindowResult(
            requests=reqs, artifacts=arts,
            latency_s=[x - (t0 + d.at) for x, d in zip(done, self.schedule)
                       if x is not None],
            seconds=end - t0, sessions=[self.session], stats=stats,
            lateness_s=lateness, traced_stats=traced)

    def close(self) -> None:
        self.svc.close()
