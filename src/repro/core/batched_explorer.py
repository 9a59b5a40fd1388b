"""Batched one-compile MOGA explorer: a vmapped multi-cell NSGA-II sweep.

The paper's headline claim is *agile* design-space exploration; the
sequential `explore_sizes` loop undercut it by re-dispatching (and, in the
seed implementation, re-compiling) the whole NSGA-II program per array
size.  Here the full (array_size x seed) sweep is ONE compilation and ONE
device program: every per-cell quantity (array size, gene bounds,
calibration constants) is a traced operand (`nsga2.SpaceOperands`), so
`nsga2.run_cell` is `jax.vmap`-ed over a stacked operand tree and the
generation loop scans over the whole population stack at once.

`explore()` / `explore_sizes()` in `repro.core.explorer` are thin wrappers
over `explore_batch`; `nsga2.run` remains the non-vmapped sequential
reference, and the batched sweep returns bit-identical per-cell fronts
(same RNG stream, same generation program, mapped).

Trace accounting: compiling the sweep bumps `nsga2.TRACE_COUNTS
["run_cell"]` exactly once per program signature — asserted by
`tests/test_batched_explorer.py` and recorded by
`benchmarks/explorer_bench.py`.
"""
from __future__ import annotations

import functools
import time

import jax
import numpy as np

from repro.core import nsga2
from repro.core.constants import CAL28, CalibConstants
from repro.telemetry.spans import trace_span


@functools.partial(jax.jit, static_argnames=("statics", "n_gens"))
def sweep_program(keys, spaces, *, statics: nsga2.EvolveStatics, n_gens: int):
    """The one compiled sweep: vmap of the full per-cell NSGA-II run."""
    cell = functools.partial(nsga2.run_cell, statics=statics, n_gens=n_gens)
    return jax.vmap(cell)(keys, spaces)


def stack_spaces(spaces) -> nsga2.SpaceOperands:
    """Stack per-cell `SpaceOperands` trees into one batched host tree."""
    return jax.tree.map(lambda *xs: np.stack(xs), *spaces)


# One program for a batch's keys: each row is `jax.random.key(seed)`.
_seed_keys = jax.jit(jax.vmap(jax.random.key))


def seed_keys(seeds) -> jax.Array:
    """The stacked `jax.random.key(sd)` of every seed, in one call."""
    return _seed_keys(np.asarray(seeds, np.int64))


def launch_operands(cells, cal: CalibConstants):
    """(keys, spaces) of a cell list, both on the device: host operand
    trees memoised per (array size, calibration), stacked on the host and
    moved in one `device_put`, and the keys made by one program."""
    spaces = stack_spaces([nsga2.host_space_operands(s, cal)
                           for s, _ in cells])
    return seed_keys([sd for _, sd in cells]), jax.device_put(spaces)


def explore_cells(cells, *, pop_size: int = 256, generations: int = 80,
                  crossover_prob: float = nsga2.DEFAULT_CROSSOVER_PROB,
                  mutation_prob: float = nsga2.DEFAULT_MUTATION_PROB,
                  cal: CalibConstants = CAL28,
                  use_pallas_dominance: bool = False,
                  use_pallas_rank: bool = False,
                  program=None, timings: dict | None = None) -> dict:
    """Sweep an explicit (array_size, seed) cell list in one device program.

    The engine entry point under `repro.api.DesignSession` (which coalesces
    concurrent requests into one cell list) and `explore_batch` (which
    crosses sizes x seeds).  Returns {(array_size, seed): ParetoResult} —
    per-cell deduplicated Pareto fronts, identical to what the sequential
    per-size path (`nsga2.run` + the legacy `explorer.explore`) produces
    for the same cell.

    `program` optionally injects a pre-built sweep callable
    (keys, spaces) -> (genes, objs) — the session's program cache — and
    defaults to the module-level `sweep_program`.  `explorer.front_program`
    follows it on the device; the host then only picks rows per cell.

    `timings`, when given, receives `host_s`: the dispatch's host seconds
    outside the blocking fetch (launch plus post-processing).
    """
    from repro.core import explorer  # deferred: explorer wraps this module

    cells = list(dict.fromkeys((int(s), int(sd)) for s, sd in cells))
    if not cells:
        raise ValueError("explore_cells needs at least one (size, seed) cell")
    if program is None:
        statics = nsga2.EvolveStatics(
            pop_size=pop_size, crossover_prob=crossover_prob,
            mutation_prob=mutation_prob,
            use_pallas_dominance=use_pallas_dominance,
            use_pallas_rank=use_pallas_rank)
        program = functools.partial(sweep_program, statics=statics,
                                    n_gens=generations)
    t0 = time.perf_counter()
    with trace_span("launch", cat="explore", cells=len(cells)):
        keys, spaces = launch_operands(cells, cal)
        genes_b, objs_b = program(keys, spaces)
        front_b = explorer.front_program(genes_b, objs_b, spaces, cal=cal)
    return collect_fronts(cells, (genes_b, objs_b, front_b), cal=cal,
                          launched_at=t0, timings=timings)


def collect_fronts(cells, outputs, *, cal: CalibConstants,
                   launched_at: float, timings: dict | None) -> dict:
    """Fetch a dispatch's `outputs` — (genes, objs, `front_program`'s
    output) with the cells on axis 0 — in one blocking call, and make
    each cell's `ParetoResult` on the host.  `timings["host_s"]` gets the
    host seconds from `launched_at` on, the fetch left out."""
    from repro.core import explorer

    fetch_at = time.perf_counter()
    with trace_span("fetch", cat="explore", cells=len(cells)):
        genes_b, objs_b, (mask_b, report_b) = jax.device_get(outputs)
    fetched_at = time.perf_counter()
    fronts = {
        (s, sd): explorer.pareto_result_from_population(
            s, genes_b[i], objs_b[i], cal=cal, mask=mask_b[i],
            report=report_b[i])
        for i, (s, sd) in enumerate(cells)
    }
    if timings is not None:
        timings["host_s"] = ((fetch_at - launched_at)
                             + (time.perf_counter() - fetched_at))
    return fronts


def explore_batch(sizes=(4096, 16384, 65536), seeds=(0,), *,
                  pop_size: int = 256, generations: int = 80,
                  crossover_prob: float = nsga2.DEFAULT_CROSSOVER_PROB,
                  mutation_prob: float = nsga2.DEFAULT_MUTATION_PROB,
                  cal: CalibConstants = CAL28,
                  use_pallas_dominance: bool = False,
                  use_pallas_rank: bool = False) -> dict:
    """Sweep every (array_size, seed) cell in one compiled device program.

    Thin cross-product wrapper over `explore_cells`.
    """
    sizes = tuple(int(s) for s in sizes)
    seeds = tuple(int(s) for s in seeds)
    if not sizes or not seeds:
        raise ValueError(
            f"explore_batch needs at least one (size, seed) cell; got "
            f"sizes={sizes!r}, seeds={seeds!r}")
    return explore_cells([(s, sd) for s in sizes for sd in seeds],
                         pop_size=pop_size, generations=generations,
                         crossover_prob=crossover_prob,
                         mutation_prob=mutation_prob, cal=cal,
                         use_pallas_dominance=use_pallas_dominance,
                         use_pallas_rank=use_pallas_rank)
