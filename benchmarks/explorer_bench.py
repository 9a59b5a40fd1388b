"""Explorer benchmark: single-size sequential sweep vs batched one-compile
sweep.

Times `explore_sizes`-style sequential exploration (one `nsga2.run`
dispatch per (size, seed) cell, per-cell operand building on the host)
against the coalescing front door (`repro.serve.design_service
.DesignService`: every (size, seed) cell submitted as a `DesignRequest`
and folded into one vmapped device program for the whole sweep), and
counts traces of the generation program via the
`nsga2.TRACE_COUNTS["run_cell"]` probe.  Two views are reported:

  * end-to-end cold — full sweep including compilation and Pareto-front
    distillation, what a fresh interactive session pays;
  * device warm — min-over-reps wall-clock of just the compiled sweep
    program(s), the steady-state cost of re-running the sweep.

Results land in `BENCH_explorer.json` at the repo root so future PRs have
a perf trajectory.

  PYTHONPATH=src python -m benchmarks.explorer_bench [--smoke] [--out PATH]

`--smoke` shrinks population/generations for CI.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import platform
import time

import jax
import jax.numpy as jnp

from repro.api import DesignRequest
from repro.core import explorer, nsga2
from repro.core.batched_explorer import stack_spaces, sweep_program
from repro.serve.design_service import DesignService

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]

SIZES = (4096, 16384, 65536)
SEEDS = (0, 1)


def _sequential_sweep(pop: int, gens: int):
    """The pre-batching baseline: one run per (size, seed) cell."""
    out = {}
    for s in SIZES:
        for sd in SEEDS:
            cfg = nsga2.NSGA2Config(array_size=s, pop_size=pop,
                                    generations=gens, seed=sd)
            popu = nsga2.run(cfg)
            out[(s, sd)] = explorer.pareto_result_from_population(
                s, popu.genes, popu.objs)
    return out


def _batched_sweep(pop: int, gens: int):
    """The unified-API path: every cell is a request, the service
    coalesces all of them into one explorer dispatch."""
    svc = DesignService(max_coalesce=len(SIZES) * len(SEEDS))
    tickets = {(s, sd): svc.submit(DesignRequest(
        array_size=s, seed=sd, pop_size=pop, generations=gens,
        layout=False)) for s in SIZES for sd in SEEDS}
    arts = svc.run()
    stats = svc.stats()
    assert stats["explorer_dispatches"] == 1, stats
    return {c: arts[t].pareto for c, t in tickets.items()}


def _cold(fn, *args):
    n0 = nsga2.TRACE_COUNTS["run_cell"]
    t0 = time.perf_counter()
    out = fn(*args)
    cold = time.perf_counter() - t0
    return out, cold, nsga2.TRACE_COUNTS["run_cell"] - n0


def _device_warm(fn, reps: int = 5) -> float:
    fn()  # ensure compiled
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times)


def run(smoke: bool = False) -> dict:
    pop, gens = (48, 8) if smoke else (192, 60)
    statics = nsga2.EvolveStatics(pop_size=pop)
    cells = [(s, sd) for s in SIZES for sd in SEEDS]

    seq, seq_cold, seq_traces = _cold(_sequential_sweep, pop, gens)
    bat, bat_cold, bat_traces = _cold(_batched_sweep, pop, gens)
    fronts_equal = all(
        {(sp.h, sp.w, sp.l, sp.b_adc) for sp in seq[c].specs}
        == {(sp.h, sp.w, sp.l, sp.b_adc) for sp in bat[c].specs}
        for c in seq
    )

    # device-program steady state (no host-side front distillation)
    def seq_device():
        for s, sd in cells:
            space = nsga2.space_operands(nsga2.NSGA2Config(array_size=s))
            jax.block_until_ready(nsga2.run_cell_jit(
                jax.random.key(sd), space, statics=statics, n_gens=gens))

    spaces = stack_spaces([
        nsga2.space_operands(nsga2.NSGA2Config(array_size=s))
        for s, _ in cells])
    keys = jnp.stack([jax.random.key(sd) for _, sd in cells])

    def bat_device():
        jax.block_until_ready(sweep_program(keys, spaces, statics=statics,
                                            n_gens=gens))

    seq_warm = _device_warm(seq_device)
    bat_warm = _device_warm(bat_device)

    return {
        "sizes": list(SIZES),
        "seeds": list(SEEDS),
        "pop_size": pop,
        "generations": gens,
        "smoke": smoke,
        "backend": jax.default_backend(),
        "platform": platform.platform(),
        "sequential": {"end_to_end_cold_s": seq_cold,
                       "device_warm_s": seq_warm,
                       "generation_program_traces": seq_traces},
        "batched": {"end_to_end_cold_s": bat_cold,
                    "device_warm_s": bat_warm,
                    "generation_program_traces": bat_traces},
        "batched_speedup_cold": seq_cold / bat_cold,
        "batched_speedup_warm": seq_warm / bat_warm,
        "batched_le_sequential": (bat_warm <= seq_warm
                                  and bat_cold <= seq_cold),
        "fronts_equal": fronts_equal,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny pop/generations for CI")
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_explorer.json"))
    args = ap.parse_args()
    result = run(smoke=args.smoke)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    for side in ("sequential", "batched"):
        r = result[side]
        print(f"{side}: cold={r['end_to_end_cold_s']:.3f}s "
              f"device_warm={r['device_warm_s']:.3f}s "
              f"traces={r['generation_program_traces']}")
    print(f"speedup(warm)={result['batched_speedup_warm']:.2f}x "
          f"fronts_equal={result['fronts_equal']} -> {args.out}")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
