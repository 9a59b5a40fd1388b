"""NSGA-II (Deb et al.) specialized for the EasyACIM design space, in JAX.

The paper uses an off-the-shelf NSGA-II over (H, W, L, B_ADC) with the
Eq. 12 constraints.  Here the whole *run* — init, evaluation, tournament
selection, crossover, mutation, repair, elitist environmental selection,
looped over generations — is one jit-compiled program (`run_cell`);
populations are plain int32 gene arrays so the explorer can also be sharded
across a device mesh (see `repro.parallel.distributed_explorer`).

One-compile sweep contract
--------------------------
Everything that varies across a design-space sweep cell — the array size,
the gene box bounds it implies, and the calibration constants — is carried
as *traced operand arrays* (`SpaceOperands`), never as static config.  The
only static arguments are structural (population size, generation count,
variation probabilities, kernel selection).  Consequently:

  * a sequential sweep over array sizes compiles the generation program
    once and re-dispatches it per size, and
  * `repro.core.batched_explorer.explore_batch` can `jax.vmap` `run_cell`
    over a stacked `SpaceOperands` batch so a whole (array_size x seed)
    sweep is ONE compilation and ONE device program.

Ranks and crowding distances are threaded through the generation carry:
environmental selection ranks the combined 2P population once, and the
surviving P parents inherit their (exact — see `generation_step_op`) ranks
instead of being re-ranked at the top of the next generation.

Gene encoding (all powers of two, matching the binary-ratioed CDAC):
    gene[0] = h_exp   -> H = 2**h_exp
    gene[1] = l_exp   -> L = 2**l_exp
    gene[2] = b_adc
W is implied by the H*W = array_size equality constraint (Eq. 12), so it is
not a free gene — this is exact constraint elimination rather than penalty
handling.  The two inequality constraints (H >= L, H/L >= 2^B) are handled
by *repair* (clamping), which keeps every individual feasible; a
constrained-domination path (Deb's rules) is also provided for generality
and is exercised by the tests.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import estimator, pareto
from repro.core.constants import CAL28, CalibConstants

Array = jax.Array

# Trace-count probe: incremented (as a Python side effect) every time the
# generation program body is traced.  `benchmarks/explorer_bench.py` and the
# batched-explorer tests read deltas of this counter to assert the
# one-compile sweep contract.
TRACE_COUNTS: collections.Counter = collections.Counter()

# Single source of truth for the variation-probability defaults shared by
# NSGA2Config and EvolveStatics.
DEFAULT_CROSSOVER_PROB = 0.9
DEFAULT_MUTATION_PROB = 0.2


@dataclasses.dataclass(frozen=True)
class NSGA2Config:
    array_size: int
    pop_size: int = 256
    generations: int = 80
    crossover_prob: float = DEFAULT_CROSSOVER_PROB
    mutation_prob: float = DEFAULT_MUTATION_PROB
    tournament_pairs: int = 2
    seed: int = 0
    cal: CalibConstants = CAL28
    use_pallas_dominance: bool = False  # Pallas kernel for the P^2 hot spot
    use_pallas_rank: bool = False       # fused Pallas rank-and-crowd path

    @property
    def log2_size(self) -> int:
        s = int(np.log2(self.array_size))
        if 2**s != self.array_size:
            raise ValueError("array_size must be a power of two")
        return s

    @property
    def h_exp_bounds(self) -> tuple[int, int]:
        lo = int(np.log2(self.cal.h_min))
        hi = min(int(np.log2(self.cal.h_max)),
                 self.log2_size - int(np.log2(self.cal.w_min)))
        return lo, hi

    @property
    def l_exp_bounds(self) -> tuple[int, int]:
        return int(np.log2(self.cal.l_min)), int(np.log2(self.cal.l_max))

    @property
    def b_bounds(self) -> tuple[int, int]:
        return self.cal.b_min, self.cal.b_max


class Population(NamedTuple):
    genes: Array   # (P, 3) int32  [h_exp, l_exp, b]
    objs: Array    # (P, 4) float32, minimization orientation


class SpaceOperands(NamedTuple):
    """Traced per-cell design-space operands (see module docstring).

    All leaves are arrays, so a sweep batch is just a tree of stacked
    leaves and `run_cell` vmaps over it without retracing.
    """

    array_size: Array              # () float32
    gene_lo: Array                 # (3,) int32  [h_exp, l_exp, b] lower bounds
    gene_hi: Array                 # (3,) int32  upper bounds (inclusive)
    cal: estimator.CalOperands     # traced calibration scalars


class EvolveStatics(NamedTuple):
    """Structural (hashable, shape-determining) NSGA-II parameters."""

    pop_size: int = 256
    crossover_prob: float = DEFAULT_CROSSOVER_PROB
    mutation_prob: float = DEFAULT_MUTATION_PROB
    use_pallas_dominance: bool = False
    use_pallas_rank: bool = False

    @classmethod
    def from_config(cls, cfg: NSGA2Config) -> "EvolveStatics":
        return cls(pop_size=cfg.pop_size, crossover_prob=cfg.crossover_prob,
                   mutation_prob=cfg.mutation_prob,
                   use_pallas_dominance=cfg.use_pallas_dominance,
                   use_pallas_rank=cfg.use_pallas_rank)


def space_operands(cfg: NSGA2Config) -> SpaceOperands:
    """Fold a static config into the traced operand tree."""
    h_lo, h_hi = cfg.h_exp_bounds
    l_lo, l_hi = cfg.l_exp_bounds
    b_lo, b_hi = cfg.b_bounds
    return SpaceOperands(
        array_size=jnp.float32(cfg.array_size),
        gene_lo=jnp.array([h_lo, l_lo, b_lo], jnp.int32),
        gene_hi=jnp.array([h_hi, l_hi, b_hi], jnp.int32),
        cal=estimator.cal_operands(cfg.cal),
    )


@functools.lru_cache(maxsize=256)
def host_space_operands(array_size: int, cal: CalibConstants = CAL28
                        ) -> SpaceOperands:
    """`space_operands` as read-only host numpy leaves, built once per
    (array size, calibration): a dispatch stacks these on the host and
    hands the batch to the program, with no device work per cell."""
    def frozen(x):
        x = np.asarray(x)
        x.setflags(write=False)
        return x

    ops = space_operands(NSGA2Config(array_size=array_size, cal=cal))
    return jax.tree.map(frozen, ops)


# ----------------------------------------------------------------------
# Operand-traced primitives (the vmappable hot path)
# ----------------------------------------------------------------------
def repair_op(genes: Array, space: SpaceOperands) -> Array:
    """Project genes onto the feasible set (Eq. 12 inequality constraints)."""
    lo, hi = space.gene_lo, space.gene_hi
    h = jnp.clip(genes[:, 0], lo[0], hi[0])
    # H >= L and room for at least b_min ADC bits: L <= H / 2^b_min
    l = jnp.clip(genes[:, 1], lo[1], jnp.minimum(hi[1], h - lo[2]))
    b = jnp.clip(genes[:, 2], lo[2], jnp.minimum(hi[2], h - l))   # H/L >= 2^B
    return jnp.stack([h, l, b], axis=1)


def decode_op(genes: Array, space: SpaceOperands):
    """Genes -> (H, W, L, B) float32 arrays."""
    h = 2.0 ** genes[:, 0].astype(jnp.float32)
    w = space.array_size / h
    l = 2.0 ** genes[:, 1].astype(jnp.float32)
    b = genes[:, 2].astype(jnp.float32)
    return h, w, l, b


def evaluate_op(genes: Array, space: SpaceOperands) -> Array:
    h, w, l, b = decode_op(genes, space)
    return estimator.objectives_from_operands(h, w, l, b, space.cal)


def init_population_op(key: Array, space: SpaceOperands, pop_size: int) -> Array:
    lo, hi = space.gene_lo, space.gene_hi
    kh, kl, kb = jax.random.split(key, 3)
    h = jax.random.randint(kh, (pop_size,), lo[0], hi[0] + 1)
    l = jax.random.randint(kl, (pop_size,), lo[1], hi[1] + 1)
    b = jax.random.randint(kb, (pop_size,), lo[2], hi[2] + 1)
    return repair_op(jnp.stack([h, l, b], 1), space)


def rank_and_crowd(objs: Array, statics: EvolveStatics):
    """(ranks, crowding) for a population, via the configured backend."""
    if statics.use_pallas_rank:
        from repro.kernels.pareto_dom import ops as dom_ops

        return dom_ops.rank_and_crowd(objs)
    if statics.use_pallas_dominance:
        from repro.kernels.pareto_dom import ops as dom_ops

        dom = dom_ops.dominance_matrix(objs)
    else:
        dom = pareto.dominance_matrix(objs)
    ranks = pareto.non_dominated_rank(objs, dom=dom)
    crowd = pareto.crowding_distance(objs, ranks)
    return ranks, crowd


def _tournament(key: Array, ranks: Array, crowd: Array, n: int) -> Array:
    """Binary tournament on (rank asc, crowding desc); returns n winner idx."""
    p = ranks.shape[0]
    idx = jax.random.randint(key, (n, 2), 0, p)
    a, b = idx[:, 0], idx[:, 1]
    a_better = (ranks[a] < ranks[b]) | ((ranks[a] == ranks[b]) & (crowd[a] > crowd[b]))
    return jnp.where(a_better, a, b)


def _variation_op(key: Array, parents: Array, space: SpaceOperands,
                  statics: EvolveStatics) -> Array:
    """Uniform crossover + random-reset mutation on integer genes."""
    p = parents.shape[0]
    kx, kswap, kmut, kval = jax.random.split(key, 4)
    mates = parents[jnp.roll(jnp.arange(p), 1)]
    do_cx = jax.random.bernoulli(kx, statics.crossover_prob, (p, 1))
    swap = jax.random.bernoulli(kswap, 0.5, parents.shape)
    children = jnp.where(do_cx & swap, mates, parents)
    # mutation: re-draw a gene uniformly within its box bounds
    lo, hi = space.gene_lo, space.gene_hi
    u = jax.random.uniform(kval, children.shape)
    rand_gene = (lo + (u * (hi - lo + 1)).astype(jnp.int32)).astype(jnp.int32)
    mut = jax.random.bernoulli(kmut, statics.mutation_prob, children.shape)
    children = jnp.where(mut, rand_gene, children)
    return repair_op(children, space)


def generation_step_op(key: Array, genes: Array, objs: Array, ranks: Array,
                       crowd: Array, space: SpaceOperands,
                       statics: EvolveStatics):
    """One NSGA-II generation with (ranks, crowd) threaded through the carry.

    The incoming (ranks, crowd) describe the parent population, so the
    tournament needs no ranking work; environmental selection ranks the
    combined 2P pool once and the survivors inherit *exact* ranks: the
    elitist truncation keeps every point of rank < r plus part of rank r,
    and all dominators of a kept point have strictly smaller rank, hence
    are also kept — re-peeling the survivors cannot change their ranks.
    Crowding is recomputed on the survivor set (neighbour gaps do change),
    which is a single sort batch, not a P^2 pass.
    """
    ksel, kvar = jax.random.split(key)
    parents_idx = _tournament(ksel, ranks, crowd, statics.pop_size)
    children = _variation_op(kvar, genes[parents_idx], space, statics)
    child_objs = evaluate_op(children, space)
    comb_genes = jnp.concatenate([genes, children], 0)
    comb_objs = jnp.concatenate([objs, child_objs], 0)
    # elitist (mu+lambda) truncation by (rank, -crowding)
    comb_ranks, comb_crowd = rank_and_crowd(comb_objs, statics)
    order = jnp.lexsort((-comb_crowd, comb_ranks))
    keep = order[: statics.pop_size]
    genes_k, objs_k, ranks_k = comb_genes[keep], comb_objs[keep], comb_ranks[keep]
    crowd_k = pareto.crowding_distance(objs_k, ranks_k)
    return genes_k, objs_k, ranks_k, crowd_k


def evolve_from(key: Array, genes: Array, objs: Array, space: SpaceOperands,
                statics: EvolveStatics, n_gens: int):
    """Rank once, then evolve `n_gens` generations (traced; no re-ranking)."""
    ranks, crowd = rank_and_crowd(objs, statics)

    def body(i, state):
        k, g, o, r, c = state
        k, sub = jax.random.split(k)
        g, o, r, c = generation_step_op(sub, g, o, r, c, space, statics)
        return k, g, o, r, c

    _, genes, objs, _, _ = jax.lax.fori_loop(
        0, n_gens, body, (key, genes, objs, ranks, crowd))
    return genes, objs


def run_cell(key: Array, space: SpaceOperands, *, statics: EvolveStatics,
             n_gens: int):
    """One full NSGA-II run for one design-space cell, fully traced.

    This is THE generation program: `run` jits it directly, the batched
    explorer vmaps it over a stacked `SpaceOperands` tree, and the island
    explorer runs it per device under `shard_map`.  Tracing it bumps
    `TRACE_COUNTS["run_cell"]`.
    """
    # lint: disable=inplace-store -- deliberate trace-count probe on a host dict
    TRACE_COUNTS["run_cell"] += 1
    kinit, kgen = jax.random.split(key)
    genes = init_population_op(kinit, space, statics.pop_size)
    objs = evaluate_op(genes, space)
    return evolve_from(kgen, genes, objs, space, statics, n_gens)


@functools.partial(jax.jit, static_argnames=("statics", "n_gens"))
def run_cell_jit(key, space, *, statics, n_gens):
    """Jitted `run_cell` — the sequential single-cell device program."""
    return run_cell(key, space, statics=statics, n_gens=n_gens)


def run(cfg: NSGA2Config, key: Array | None = None) -> Population:
    """Full NSGA-II run; returns the final population (feasible by repair).

    Sequential single-cell path: one compile serves every array size /
    calibration (both are operands), so `explore_sizes` re-dispatches the
    same executable per size.
    """
    if key is None:
        key = jax.random.key(cfg.seed)
    genes, objs = run_cell_jit(key, space_operands(cfg),
                               statics=EvolveStatics.from_config(cfg),
                               n_gens=cfg.generations)
    return Population(genes, objs)


# ----------------------------------------------------------------------
# Config-static compatibility wrappers (tests, examples, external callers)
# ----------------------------------------------------------------------
def repair(genes: Array, cfg: NSGA2Config) -> Array:
    return repair_op(genes, space_operands(cfg))


def decode(genes: Array, cfg: NSGA2Config):
    return decode_op(genes, space_operands(cfg))


def evaluate(genes: Array, cfg: NSGA2Config) -> Array:
    return evaluate_op(genes, space_operands(cfg))


def init_population(key: Array, cfg: NSGA2Config) -> Array:
    return init_population_op(key, space_operands(cfg), cfg.pop_size)


def constraint_violation(genes: Array, cfg: NSGA2Config) -> Array:
    """Total violation (0 for feasible) — used by the constrained-dom path."""
    h = genes[:, 0]
    l = genes[:, 1]
    b = genes[:, 2]
    v1 = jnp.maximum(l - h, 0)            # H >= L
    v2 = jnp.maximum(b - (h - l), 0)      # H/L >= 2^B
    return (v1 + v2).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("cfg",))
def generation_step(key: Array, genes: Array, objs: Array, cfg: NSGA2Config):
    """One NSGA-II generation: select -> vary -> evaluate -> elitist truncate.

    Legacy entry point (re-ranks the parents each call); prefer
    `generation_step_op` with a carried (ranks, crowd) pair.
    """
    statics = EvolveStatics.from_config(cfg)
    space = space_operands(cfg)
    ranks, crowd = rank_and_crowd(objs, statics)
    genes, objs, _, _ = generation_step_op(key, genes, objs, ranks, crowd,
                                           space, statics)
    return genes, objs
