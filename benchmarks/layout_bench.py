"""Layout benchmark: sequential per-spec `generate_layout` vs the batched
`generate_layouts` flow on a distilled Pareto set.

The layout counterpart of `benchmarks/explorer_bench.py`: PR 1 made the
MOGA sweep one compiled program; this measures the other half of paper
Fig. 4 — feeding the distilled Pareto set through placement / routing /
DRC.  The sequential baseline is B independent `flow.generate_layout`
calls (host netlist generation, named placement, one wavefront dispatch
per net); the batched path is `repro.api.DesignSession.layout` over
`eda.batched_flow.generate_layouts` (one vmapped placement dispatch,
one scanned routing program expanding all B wavefronts together,
closed-form netlist stats).  Two views:

  * end-to-end cold — includes compilation, what a fresh session pays;
  * warm — a second run with all programs compiled, the steady-state
    cost of laying out another same-shaped Pareto set.

Both paths must agree per spec (routing stats, DRC verdict, bounding
box) — recorded as `results_equal` and asserted in CI alongside
`batched_speedup_warm`.  Results land in `BENCH_layout.json` at the repo
root so future PRs have a perf trajectory.

  PYTHONPATH=src python -m benchmarks.layout_bench [--smoke] [--out PATH]

`--smoke` uses a smaller 8-spec set (array size 4096) for CI.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import platform
import time

import jax

from repro.api import DesignSession
from repro.core.acim_spec import MacroSpec
from repro.eda.flow import generate_layout

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]

# 8-spec Pareto sets (h, w, l, b_adc): distilled fronts at a fixed array
# size, pinned here so the benchmark does not depend on explorer runtime.
SPECS_FULL = tuple(MacroSpec(*s) for s in [
    (128, 128, 2, 3), (128, 128, 4, 3), (256, 64, 2, 5), (256, 64, 4, 4),
    (256, 64, 8, 3), (512, 32, 8, 3), (512, 32, 16, 2), (512, 32, 32, 2),
])
SPECS_SMOKE = tuple(MacroSpec(*s) for s in [
    (64, 64, 2, 3), (64, 64, 2, 4), (64, 64, 4, 2), (64, 64, 8, 3),
    (128, 32, 2, 3), (128, 32, 4, 3), (128, 32, 8, 3), (128, 32, 16, 3),
])


def _sequential(specs):
    return [generate_layout(s) for s in specs]


def _spec_summary_seq(lr):
    return (lr.placement.width, lr.placement.height,
            len(lr.placement.rects), len(lr.routing.wires),
            len(lr.routing.failed), lr.routing.total_wirelength,
            lr.drc.overlaps, lr.drc.out_of_bounds)


def _spec_summaries_bat(res):
    out = []
    rect_counts = [sum(int(m[i].sum()) for _, m in res.tensors.values())
                   for i in range(len(res))]
    for i in range(len(res)):
        out.append((int(res.widths[i]), int(res.heights[i]), rect_counts[i],
                    int(res.routing.routed[i]), int(res.routing.failed[i]),
                    int(res.routing.wirelength[i]),
                    int(res.drc_overlaps[i]), int(res.drc_oob[i])))
    return out


def run(smoke: bool = False) -> dict:
    specs = SPECS_SMOKE if smoke else SPECS_FULL

    t0 = time.perf_counter()
    seq = _sequential(specs)
    seq_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    seq = _sequential(specs)
    seq_warm = time.perf_counter() - t0

    session = DesignSession()
    t0 = time.perf_counter()
    bat = session.layout(specs)
    bat_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    bat = session.layout(specs)
    bat_warm = time.perf_counter() - t0

    results_equal = ([_spec_summary_seq(lr) for lr in seq]
                     == _spec_summaries_bat(bat))
    return {
        "specs": [s.as_tuple() for s in specs],
        "smoke": smoke,
        "backend": jax.default_backend(),
        "platform": platform.platform(),
        "sequential": {"end_to_end_cold_s": seq_cold,
                       "end_to_end_warm_s": seq_warm},
        "batched": {"end_to_end_cold_s": bat_cold,
                    "end_to_end_warm_s": bat_warm},
        "batched_speedup_cold": seq_cold / bat_cold,
        "batched_speedup_warm": seq_warm / bat_warm,
        "batched_le_sequential": (bat_warm <= seq_warm
                                  and bat_cold <= seq_cold),
        "results_equal": results_equal,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="smaller 8-spec set for CI")
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_layout.json"))
    args = ap.parse_args()
    result = run(smoke=args.smoke)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    for side in ("sequential", "batched"):
        r = result[side]
        print(f"{side}: cold={r['end_to_end_cold_s']:.3f}s "
              f"warm={r['end_to_end_warm_s']:.3f}s")
    print(f"speedup(warm)={result['batched_speedup_warm']:.2f}x "
          f"results_equal={result['results_equal']} -> {args.out}")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
