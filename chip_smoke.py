#!/usr/bin/env python3
"""Smoke test of the design service on a TPU: serve the paper sweep, then
check every result against an independent reference.

    python chip_smoke.py             # one chip: serve + compare
    python chip_smoke.py --chips 4   # four chips: the island mesh only

One chip.  A `DesignService` over a `DesignSession` serves the paper's
sweep through `serve()`: arrays of 4 kb, 16 kb and 64 kb, seeds 0 and 1,
population 192 x 60 generations, whole distilled fronts laid out, plus
one explore-only 16 kb request.  The sweep is served twice, by a fresh
session and service each time: the first pass compiles (set-up), the
second runs what is already compiled (steady).  Then, on the chip:

  * every layout batch the service routed (scan engine, Pallas
    wavefront kernel) is routed again by the host `concurrent` engine:
    routed, failed, wirelength and occupancy must be equal;
  * the wavefront kernel must equal the BFS oracle on the served grids;
  * the rank kernel must equal `pareto.non_dominated_rank` on the
    served objectives, at P=192 and at P=4096.

Four chips.  An `islands=8` request and a 4-cell sharded sweep
(`mesh=True`) on the mesh of all devices, each compared with the same
requests on `DesignSession(mesh=1)`: fronts must be bit-equal and the
provenance must show every device in use.

Both forms fail unless JAX's first device is a TPU, and the last line of
standard output is one JSON object, printed only when every check
passed: {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import collections
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SIZES = (4096, 16384, 65536)
SEEDS = (0, 1)
POP, GENS = 192, 60
COLLECT_TIMEOUT_S = 900.0
RANK_SIZES = (192, 4096)


class SmokeFailure(Exception):
    """A check failed: the script exits non-zero and prints no result."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Sums JAX's backend-compile time and persistent-cache hits."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits


def require_tpu():
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeFailure(
            f"no TPU found: JAX's first device is {devices[0].platform!r}")
    return devices


def sweep_requests():
    from repro.api import DesignRequest, Requirements

    # the paper-sweep requirements of bench/configs/paper_layout.json
    reqs = Requirements(min_tops=0.5, min_snr_db=15.0)
    laid = [DesignRequest(array_size=s, seed=sd, pop_size=POP,
                          generations=GENS, requirements=reqs, layout=True)
            for s in SIZES for sd in SEEDS]
    return laid + [DesignRequest(array_size=16384, seed=2, pop_size=POP,
                                 generations=GENS, requirements=reqs,
                                 layout=False)]


def recording_session():
    """A `DesignSession` that keeps every layout batch it routes, so the
    compare phase can check the served occupancy maps."""
    from repro.api import DesignSession

    class RecordingSession(DesignSession):
        def __init__(self):
            super().__init__()
            self.laid = []

        def layout(self, specs, *, coarse=64, capacity=4, engine=None):
            res = super().layout(specs, coarse=coarse, capacity=capacity,
                                 engine=engine)
            self.laid.append((coarse, capacity, res))
            return res

    return RecordingSession()


def serve_sweep(requests):
    """One pass of the sweep through `DesignService.serve()`."""
    from repro.serve.design_service import DesignService

    session = recording_session()
    t0 = time.perf_counter()
    with DesignService(session).serve() as svc:
        tickets = [svc.submit(r) for r in requests]
        arts = [svc.collect(t, timeout=COLLECT_TIMEOUT_S) for t in tickets]
        stats = svc.stats()
    wall = time.perf_counter() - t0

    for req, art in zip(requests, arts):
        tag = f"request {req.array_size}/{req.seed} layout={req.layout}"
        check(art.ok, f"{tag}: {art.error}")
        check(len(art.pareto) > 0, f"{tag}: empty front")
        if req.layout:
            check(art.layout_rows is not None
                  and len(art.layout_rows) == len(art.pareto),
                  f"{tag}: no layout rows")
            check(art.provenance.route_engine == "scan",
                  f"{tag}: routed by {art.provenance.route_engine!r}, "
                  f"not the device scan engine")
    faults = {k: v for k, v in stats.items()
              if isinstance(v, int) and not isinstance(v, bool)
              and any(w in k for w in ("retries", "failures", "restarts"))}
    check(not any(faults.values()), f"service retried or failed: {faults}")
    return arts, session, stats, wall


def compare_layouts(arts, session) -> int:
    """Served scan+kernel layouts against the host concurrent engine."""
    import numpy as np

    from repro.eda.batched_flow import generate_layouts

    ref_rows = {}
    for coarse, capacity, res in session.laid:
        ref = generate_layouts(res.specs, coarse=coarse, capacity=capacity,
                               engine="concurrent")
        for field in ("routed", "failed", "wirelength", "occ_count"):
            check(np.array_equal(getattr(res.routing, field),
                                 getattr(ref.routing, field)),
                  f"scan and concurrent engines differ in {field} "
                  f"on a batch of {len(res.specs)} specs")
        ref_rows.update(zip(ref.specs, ref.metrics_rows()))
    for art in arts:
        if art.layout_rows is None:
            continue
        for spec, row in zip(art.pareto.specs, art.layout_rows):
            check(row == ref_rows[spec],
                  f"served layout row of {spec} differs from the host engine")
    return len(session.laid)


def compare_wavefront(session) -> int:
    """The Pallas wavefront kernel against the BFS oracle on the final
    occupancy of every served grid, seeded at a few cells per grid."""
    import numpy as np

    from repro.kernels.maze_route import wavefront_distance
    from repro.kernels.maze_route.oracle import wavefront_distance_bfs

    rng = np.random.default_rng(0)
    cells = 0
    for _, capacity, res in session.laid:
        occ_count = res.routing.occ_count
        b, gh, gw = occ_count.shape
        iy = np.arange(gh)[None, :, None]
        ix = np.arange(gw)[None, None, :]
        grids = res.routing.grids
        outside = (iy >= grids[:, 0, None, None]) | (ix >= grids[:, 1, None,
                                                                    None])
        occ = (occ_count >= capacity) | outside
        seed = np.zeros_like(occ)
        for i, (h, w) in enumerate(grids):
            seed[i, rng.integers(0, h, 3), rng.integers(0, w, 3)] = True
        got = np.asarray(wavefront_distance(occ, seed, impl="kernel",
                                            interpret=False))
        want = wavefront_distance_bfs(occ, seed)
        check(np.array_equal(got, want),
              f"wavefront kernel differs from BFS on a {occ.shape} batch")
        cells += occ.size
    return cells


def compare_ranks(arts) -> None:
    """The fused Pallas rank kernel against the jnp front peel."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import pareto
    from repro.kernels.pareto_dom.ops import non_dominated_rank

    m = collections.defaultdict(list)
    for art in arts:
        for k in ("snr_db", "tops", "energy_fj_per_mac", "area_f2_per_bit"):
            m[k].append(np.asarray(art.pareto.metrics[k]))
    m = {k: np.concatenate(v) for k, v in m.items()}
    served = np.stack([-m["snr_db"], -m["tops"], m["energy_fj_per_mac"],
                       m["area_f2_per_bit"]], axis=1).astype(np.float32)
    rng = np.random.default_rng(0)
    for p in RANK_SIZES:
        # the served points, resampled to P and perturbed so that the
        # fronts peel in many layers
        rows = served[rng.integers(0, len(served), p)]
        f = jnp.asarray(rows * rng.uniform(0.9, 1.1, rows.shape)
                        .astype(np.float32))
        got = np.asarray(non_dominated_rank(f, interpret=False))
        want = np.asarray(pareto.non_dominated_rank(f))
        check(np.array_equal(got, want),
              f"rank kernel differs from the jnp ranks at P={p}")
        log(f"rank P={p}: {int(want.max()) + 1} fronts, equal")


def one_chip() -> None:
    clock = CompileClock()
    requests = sweep_requests()
    passes = {}
    for name in ("cold", "warm"):
        c0 = clock.snapshot()
        arts, session, stats, wall = serve_sweep(requests)
        c1 = clock.snapshot()
        passes[name] = (arts, session)
        log(f"serve {name}: {len(arts)} requests in {wall:.3f} s, "
            f"compile {c1[0] - c0[0]:.3f} s over {c1[1] - c0[1]} programs, "
            f"{c1[2] - c0[2]} persistent-cache hits, "
            f"{int(stats['layout_dispatches'])} layout batches, "
            f"{int(stats['explorer_dispatches'])} explore dispatches, "
            f"stage busy {json.dumps(stats['stage_busy_s'])}")
        log("  fronts " + " ".join(
            f"{a.request.array_size}/{a.request.seed}:{len(a.pareto)}"
            for a in arts))
    cold, warm = passes["cold"][0], passes["warm"][0]
    check(all(a.summary() == b.summary() for a, b in zip(cold, warm)),
          "cold and warm passes served different artifacts")

    arts, session = passes["warm"]
    t0 = time.perf_counter()
    n = compare_layouts(arts, session)
    log(f"compare layouts: {n} batches equal to the host concurrent engine "
        f"({time.perf_counter() - t0:.3f} s)")
    t0 = time.perf_counter()
    cells = compare_wavefront(session)
    log(f"compare wavefront: {cells} cells equal to BFS "
        f"({time.perf_counter() - t0:.3f} s)")
    compare_ranks(arts)


def four_chips(devices) -> None:
    from repro.api import DesignRequest, DesignSession, Requirements

    check(len(devices) >= 4, f"--chips 4 needs 4 devices, "
                             f"found {len(devices)}")
    reqs = Requirements(min_tops=0.5, min_snr_db=15.0)
    island = DesignRequest(array_size=16384, seed=0, pop_size=POP,
                           generations=GENS, requirements=reqs, layout=False,
                           islands=8, migrate_every=20)
    cells = [DesignRequest(array_size=s, seed=sd, pop_size=POP,
                           generations=GENS, requirements=reqs,
                           layout=False)
             for s, sd in ((4096, 0), (16384, 0), (65536, 0), (65536, 1))]

    def run(session, requests):
        t0 = time.perf_counter()
        out = session.run_many(requests)
        return [out[r] for r in requests], time.perf_counter() - t0

    for label, requests, mesh in (("islands=8", [island], None),
                                  ("sharded 4 cells", cells, True)):
        got, t_mesh = run(DesignSession(mesh=mesh), requests)
        want, t_one = run(DesignSession(mesh=1), requests)
        for g, w in zip(got, want):
            check(g.provenance.mesh_devices == len(devices),
                  f"{label}: ran on {g.provenance.mesh_devices} devices, "
                  f"not {len(devices)}")
            check(w.provenance.mesh_devices == 1,
                  f"{label}: the reference did not run on one device")
            check(g.summary() == w.summary(),
                  f"{label}: mesh front differs from the one-device front")
        log(f"{label}: {len(devices)}-device fronts bit-equal to one device "
            f"(mesh {t_mesh:.3f} s, one device {t_one:.3f} s, topology "
            f"{got[0].provenance.migration_topology}, "
            f"{got[0].provenance.migration_rounds} migrations)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the island-mesh phase on four chips")
    args = ap.parse_args()
    try:
        from repro.runtime.compile_cache import enable_compile_cache

        cache_dir = enable_compile_cache()
        devices = require_tpu()
        dev = devices[0]
        log(f"device: {dev.platform} {dev.device_kind} x {len(devices)}; "
            f"compile cache {cache_dir}")
        t0 = time.perf_counter()
        if args.chips == 4:
            four_chips(devices)
        else:
            one_chip()
        log(f"all phases passed in {time.perf_counter() - t0:.3f} s")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
