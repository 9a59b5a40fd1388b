"""Routing benchmark: Jacobi-sweep wavefronts vs the frontier-bucketed
engine, and the per-slot `lax.scan` routing program vs the concurrent
conflict-aware scheduler.

Two columns, matching the two layers of ROADMAP item 2:

  * `wavefront` — one batch of full distance-field expansions on the
    largest routing grids of the spec set: the jitted jnp reference
    (full-grid Jacobi sweeps, one per BFS level) against the host
    frontier engine (per-level work proportional to the active
    frontier).  Both fields are asserted equal to the pure-Python BFS
    oracle, cell for cell — `fields_equal` in the output.

  * `routing` — the end-to-end batched route of the derived net set:
    engine="scan" (one wavefront dispatch per net slot, O(nets) sweeps)
    against engine="concurrent" (greedy bbox-coloring co-dispatches
    non-conflicting nets, collision-checked commits, O(conflict-depth)
    rounds).  `results_equal` requires routed/failed/wirelength/
    congestion to match exactly — the concurrent engine is the same
    router, faster, not an approximation.

Results land in `BENCH_route.json` at the repo root; CI runs `--smoke`
and asserts both equality flags plus the schema.

  PYTHONPATH=src python -m benchmarks.route_bench [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import platform
import time

import jax
import numpy as np

from benchmarks.layout_bench import SPECS_FULL, SPECS_SMOKE
from repro.eda.batched_flow import (_nets_program, _place_program,
                                    batched_route, stack_layout_operands)
from repro.eda.placer import BatchDims, geometry
from repro.eda.router import grid_shape
from repro.kernels.maze_route import (wavefront_distance,
                                      wavefront_distance_bfs)

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


def _derive_nets(specs, coarse=64):
    geom = geometry()
    dims = BatchDims.for_specs(specs)
    ops = stack_layout_operands(specs, geom)
    tensors = _place_program(ops, dims=dims, geom=geom)
    nets = _nets_program(tensors, ops, dims=dims, geom=geom, coarse=coarse)
    return nets, np.asarray(ops.width), np.asarray(ops.height)


def _wavefront_column(widths, heights, n_fields: int) -> dict:
    """Full-field expansion on the spec set's largest routing grid."""
    gh, gw = max(grid_shape(int(w), int(h), 64)
                 for w, h in zip(widths, heights))
    rng = np.random.default_rng(0)
    occ = rng.random((n_fields, gh, gw)) < 0.15
    seed = np.zeros((n_fields, gh, gw), bool)
    seed[np.arange(n_fields),
         rng.integers(0, gh, n_fields), rng.integers(0, gw, n_fields)] = True
    occ_j, seed_j = jax.numpy.asarray(occ), jax.numpy.asarray(seed)

    oracle = wavefront_distance_bfs(occ, seed)
    jax.block_until_ready(wavefront_distance(occ_j, seed_j, impl="ref"))
    t0 = time.perf_counter()
    ref = wavefront_distance(occ_j, seed_j, impl="ref")
    jax.block_until_ready(ref)
    jacobi_s = time.perf_counter() - t0

    wavefront_distance(occ, seed, impl="frontier")
    t0 = time.perf_counter()
    fro = wavefront_distance(occ, seed, impl="frontier")
    frontier_s = time.perf_counter() - t0

    fields_equal = (np.array_equal(np.asarray(ref), oracle)
                    and np.array_equal(fro, oracle))
    return {
        "grid": [int(gh), int(gw)],
        "n_fields": n_fields,
        "jacobi_warm_s": jacobi_s,
        "frontier_warm_s": frontier_s,
        "frontier_speedup": jacobi_s / frontier_s,
        "fields_equal": fields_equal,
    }


def _routing_column(nets, w, h) -> dict:
    t0 = time.perf_counter()
    scan = batched_route(nets, w, h, engine="scan")
    scan_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    scan = batched_route(nets, w, h, engine="scan")
    scan_warm = time.perf_counter() - t0

    t0 = time.perf_counter()
    conc = batched_route(nets, w, h, engine="concurrent")
    conc_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    conc = batched_route(nets, w, h, engine="concurrent")
    conc_warm = time.perf_counter() - t0

    results_equal = (np.array_equal(conc.routed, scan.routed)
                     and np.array_equal(conc.failed, scan.failed)
                     and np.array_equal(conc.wirelength, scan.wirelength)
                     and np.array_equal(conc.occ_count, scan.occ_count))
    return {
        "net_slots": int(np.asarray(nets.nmask).shape[1]),
        "nets": int(np.asarray(nets.nmask).sum()),
        "scan": {"cold_s": scan_cold, "warm_s": scan_warm},
        "concurrent": {"cold_s": conc_cold, "warm_s": conc_warm,
                       "rounds": conc.rounds,
                       "collisions": conc.collisions},
        "concurrent_speedup_cold": scan_cold / conc_cold,
        "concurrent_speedup_warm": scan_warm / conc_warm,
        "results_equal": results_equal,
    }


def run(smoke: bool = False) -> dict:
    specs = SPECS_SMOKE if smoke else SPECS_FULL
    nets, w, h = _derive_nets(specs)
    wavefront = _wavefront_column(w, h, n_fields=4 if smoke else 8)
    routing = _routing_column(nets, w, h)
    return {
        "specs": [s.as_tuple() for s in specs],
        "smoke": smoke,
        "backend": jax.default_backend(),
        "platform": platform.platform(),
        "wavefront": wavefront,
        "routing": routing,
        "results_equal": (wavefront["fields_equal"]
                          and routing["results_equal"]),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="smaller spec set for CI")
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_route.json"))
    args = ap.parse_args()
    result = run(smoke=args.smoke)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    wf, rt = result["wavefront"], result["routing"]
    print(f"wavefront: jacobi={wf['jacobi_warm_s']:.3f}s "
          f"frontier={wf['frontier_warm_s']:.3f}s "
          f"speedup={wf['frontier_speedup']:.2f}x")
    print(f"routing: scan={rt['scan']['warm_s']:.3f}s "
          f"concurrent={rt['concurrent']['warm_s']:.3f}s "
          f"speedup(warm)={rt['concurrent_speedup_warm']:.2f}x "
          f"rounds={rt['concurrent']['rounds']} "
          f"collisions={rt['concurrent']['collisions']}")
    print(f"results_equal={result['results_equal']} -> {args.out}")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
