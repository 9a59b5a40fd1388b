"""Pallas TPU kernel for the batched Lee maze-router wavefront.

One program instance per routing grid: the batch is the Pallas grid axis
(`grid=(B,)`), so laying out a whole distilled Pareto set expands B
wavefronts concurrently — the "parallel BFS" of the batched layout flow
(`repro.eda.batched_flow`).  Each program keeps its (H, W) occupancy,
seed, and distance planes entirely in VMEM and runs the min-plus
relaxation on the VPU:

    dist <- min(dist, 1 + min(N, S, E, W))        on free cells

Neighbour access is expressed as static-slice shifts (concatenate with
an `INF` edge row/lane), which lowers to cheap sublane/lane shifts —
there is no gather and no host queue.  Every sweep advances the
frontier one step, and the loop tests its stop rule once per
`SWEEPS_PER_CHECK` sweeps.  Without goals (`wavefront_kernel`) it stops
at the first test whose last sweep changed nothing: the field is
complete, and the trip count is the largest finite distance (rounded up
to the test period), bounded by H * W.  With goals
(`goal_wavefront_kernel`, a few target cells per grid) it also stops
once every goal is resolved: the trip count is then the last goal's
distance, or the largest finite distance when a goal is unreachable.

Why stopping on the goals is exact: synchronous unit-weight relaxation
from distance-0 seeds keeps the invariant that after k sweeps every cell
whose true distance is <= k holds it, and every other cell holds `INF`.
A free goal is resolved once it is finite; a blocked goal (the router
enters it from its best neighbour) once any 4-neighbour is finite — the
least of them is then final, and so is every neighbour at that
distance.  A backtrace from the goals reads only cells closer than the
goal and their neighbours one step closer still, all final; cells not
yet final are `INF` and never match.  Extra sweeps only finalise more
cells, so checking every few sweeps changes no answer.

Semantics match `repro.kernels.maze_route.ref.wavefront_distance_ref`
exactly (seeds pinned to 0 even when occupied; blocked cells never
relax), and the wrapper in `ops.py` pads grids to TPU tile multiples
with blocked cells, which cannot perturb distances.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.maze_route.ref import INF

# Whole grids live in VMEM: about 22 bytes per cell with the pipeline's
# double buffers, so the default 16 MiB scoped limit stops near 0.7 M
# cells.  v5e has 128 MiB of VMEM; this admits grids up to ~4 M cells.
VMEM_LIMIT = 96 * 2 ** 20

# Sweeps per test of the stop rule: each test is two full-plane
# reductions, so the loop body runs this many sweeps between them.
SWEEPS_PER_CHECK = 4


def _shift(x: jax.Array, dy: int, dx: int) -> jax.Array:
    """Shift a (H, W) plane by (dy, dx), filling the exposed edge with INF."""
    h, w = x.shape
    if dy == 1:
        x = jnp.concatenate([jnp.full((1, w), INF, x.dtype), x[:-1]], 0)
    elif dy == -1:
        x = jnp.concatenate([x[1:], jnp.full((1, w), INF, x.dtype)], 0)
    if dx == 1:
        x = jnp.concatenate([jnp.full((h, 1), INF, x.dtype), x[:, :-1]], 1)
    elif dx == -1:
        x = jnp.concatenate([x[:, 1:], jnp.full((h, 1), INF, x.dtype)], 1)
    return x


def _kernel(*refs, n_goals: int):
    # Mosaic cannot carry or reduce i1 planes through the while loop, so
    # the masks stay int32 and the loop counts cells.
    if n_goals:
        goal_ref, occ_ref, seed_ref, dist_ref, stats_ref = refs
    else:
        occ_ref, seed_ref, dist_ref, stats_ref = refs
    seed = seed_ref[0].astype(jnp.int32)
    fixed = occ_ref[0].astype(jnp.int32) | seed      # blocked or a seed
    dist0 = jnp.where(seed != 0, 0, INF).astype(jnp.int32)
    shape = dist0.shape
    if n_goals:
        # goal cells from the prefetched (y, x) pairs; a negative pair
        # matches no cell
        base = pl.program_id(0) * (2 * n_goals)
        iy = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        ix = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        goal = jnp.zeros(shape, jnp.int32)
        for k in range(n_goals):
            hit = (iy == goal_ref[base + 2 * k]) & (
                ix == goal_ref[base + 2 * k + 1])
            goal = goal | hit.astype(jnp.int32)

    def cond(state):
        _, _, changed, pending = state
        return jnp.minimum(changed, pending) > 0

    def body(state):
        nxt, sweeps, _, _ = state
        for _ in range(SWEEPS_PER_CHECK):
            prev = nxt
            best = jnp.minimum(
                jnp.minimum(_shift(prev, 1, 0), _shift(prev, -1, 0)),
                jnp.minimum(_shift(prev, 0, 1), _shift(prev, 0, -1))) + 1
            nxt = jnp.where(fixed == 0, jnp.minimum(prev, best), prev)
        # the block's last sweep: a sweep that changes nothing is the
        # fixed point, and so is every sweep after it
        changed = jnp.sum((nxt < prev).astype(jnp.int32))
        pending = jnp.int32(1)
        if n_goals:
            # free goal: its own distance; blocked goal: its best
            # neighbour's (as of the block's last sweep) + 1
            open_ = (goal != 0) & (jnp.minimum(nxt, best) >= INF)
            pending = jnp.sum(open_.astype(jnp.int32))
        return nxt, sweeps + SWEEPS_PER_CHECK, changed, pending

    # a grid without a seed is all INF already: it runs no sweep
    dist, sweeps, changed, _ = jax.lax.while_loop(
        cond, body, (dist0, jnp.int32(0), jnp.sum(seed), jnp.int32(1)))
    dist_ref[0] = dist
    # row 0: sweeps run; row 1: 1 where the goals stopped the loop while
    # the field was still changing
    row = jax.lax.broadcasted_iota(jnp.int32, stats_ref.shape[1:], 0)
    stats_ref[0] = jnp.where(row == 0, sweeps,
                             (changed > 0).astype(jnp.int32))


def _wavefront_call(occ, seed, goals, interpret):
    b, h, w = occ.shape
    assert h % 8 == 0 and w % 128 == 0, (h, w)
    n_goals = 0 if goals is None else goals.shape[1]
    plane = pl.BlockSpec((1, h, w), lambda i, *_: (i, 0, 0))
    stats = pl.BlockSpec((1, 8, 128), lambda i, *_: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 if n_goals else 0, grid=(b,),
        in_specs=[plane, plane], out_specs=[plane, stats])
    args = (occ.astype(jnp.int8), seed.astype(jnp.int8))
    if n_goals:
        args = (goals.astype(jnp.int32).reshape(-1),) + args
    dist, st = pl.pallas_call(
        functools.partial(_kernel, n_goals=n_goals),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, w), jnp.int32),
                   jax.ShapeDtypeStruct((b, 8, 128), jnp.int32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*args)
    return dist, st[:, 0, 0], st[:, 1, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def wavefront_kernel(occ: jax.Array, seed: jax.Array, *,
                     interpret: bool = False) -> jax.Array:
    """occ, seed: (B, H, W) int8 with H % 8 == 0, W % 128 == 0 (pad with
    blocked cells; see ops).  Returns (B, H, W) int32 BFS distances."""
    return _wavefront_call(occ, seed, None, interpret)[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def goal_wavefront_kernel(occ: jax.Array, seed: jax.Array, goals: jax.Array,
                          *, interpret: bool = False):
    """The wavefront of `wavefront_kernel`, stopped per grid once its
    goals are resolved.

    goals: (B, K, 2) int32 (y, x) cells per grid; a negative pair is no
    goal.  Returns (dist, sweeps, goal_stopped): the (B, H, W) field,
    exact on every cell whose distance is at most the grid's sweeps and
    `INF` on every other; (B,) int32 sweeps run per grid; (B,) int32 1
    where the goals ended the loop before the fixed point.
    """
    return _wavefront_call(occ, seed, goals, interpret)
