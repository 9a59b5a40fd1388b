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

import jax
import jax.numpy as jnp
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
    """Stack per-cell `SpaceOperands` trees into one batched operand tree."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *spaces)


def explore_cells(cells, *, pop_size: int = 256, generations: int = 80,
                  crossover_prob: float = nsga2.DEFAULT_CROSSOVER_PROB,
                  mutation_prob: float = nsga2.DEFAULT_MUTATION_PROB,
                  cal: CalibConstants = CAL28,
                  use_pallas_dominance: bool = False,
                  use_pallas_rank: bool = False,
                  program=None) -> dict:
    """Sweep an explicit (array_size, seed) cell list in one device program.

    The engine entry point under `repro.api.DesignSession` (which coalesces
    concurrent requests into one cell list) and `explore_batch` (which
    crosses sizes x seeds).  Returns {(array_size, seed): ParetoResult} —
    per-cell deduplicated Pareto fronts, identical to what the sequential
    per-size path (`nsga2.run` + the legacy `explorer.explore`) produces
    for the same cell.

    `program` optionally injects a pre-built sweep callable
    (keys, spaces) -> (genes, objs) — the session's program cache — and
    defaults to the module-level `sweep_program`.
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
    with trace_span("launch", cat="explore", cells=len(cells)):
        spaces = stack_spaces([
            nsga2.space_operands(nsga2.NSGA2Config(array_size=s, cal=cal))
            for s, _ in cells])
        keys = jnp.stack([jax.random.key(sd) for _, sd in cells])
        genes_b, objs_b = program(keys, spaces)
    with trace_span("fetch", cat="explore", cells=len(cells)):
        genes_b = np.asarray(genes_b)
        objs_b = np.asarray(objs_b)
    return {
        (s, sd): explorer.pareto_result_from_population(
            s, genes_b[i], objs_b[i], cal=cal)
        for i, (s, sd) in enumerate(cells)
    }


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
