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
The router's form (`goal_route_kernel`) then walks each goal's
backtrace to the seed through the field it still holds in VMEM, and
returns the path cells' counts and the goals' entry distances in place
of the field.

Why stopping on the goals is exact: synchronous unit-weight relaxation
from distance-0 seeds keeps the invariant that after k sweeps every cell
whose true distance is <= k holds it, and every other cell holds `INF`.
A free goal is resolved once it is finite; a blocked goal (the router
enters it from its best neighbour) once any 4-neighbour is finite — the
least of them is then final, and so is every neighbour at that
distance.  A backtrace from the goals reads only cells closer than the
goal and their neighbours one step closer still, all final; cells not
yet final are `INF` and never match.  Extra sweeps only finalise more
cells, so checking every few sweeps changes no answer.  The in-kernel
walk is such a backtrace, with the router's `NEIGHBORS` tie-break.

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

from repro.kernels.maze_route.ref import INF, NEIGHBORS

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


def _tile(y, x):
    """The aligned (8, 128) tile of cell (y, x), and the cell's mask in it."""
    rows = pl.ds(pl.multiple_of(y & ~7, 8), 8)
    lanes = pl.ds(pl.multiple_of(x & ~127, 128), 128)
    iy = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    ix = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    return rows, lanes, (iy == (y & 7)) & (ix == (x & 127))


def _read(ref, y, x):
    """ref[0, y, x] of an int32 (1, H, W) VMEM ref, `INF` outside it: one
    tile load and a masked reduction."""
    _, h, w = ref.shape
    inside = (y >= 0) & (y < h) & (x >= 0) & (x < w)
    rows, lanes, hit = _tile(jnp.clip(y, 0, h - 1), jnp.clip(x, 0, w - 1))
    v = jnp.sum(jnp.where(hit, ref[0, rows, lanes], 0))
    return jnp.where(inside, v, INF)


def _bump(ref, y, x, n):
    """ref[0, y, x] += n, for (y, x) inside the (1, H, W) ref."""
    rows, lanes, hit = _tile(y, x)
    ref[0, rows, lanes] = ref[0, rows, lanes] + jnp.where(hit, n, 0)


def _pointers(dist):
    """Each cell's backtrace step, packed as (y << 16) + x: the first
    `NEIGHBORS` entry at distance d - 1.  Arbitrary where no neighbour
    is at d - 1 (seeds and `INF` cells), which a walk never reads."""
    iy = jax.lax.broadcasted_iota(jnp.int32, dist.shape, 0)
    ix = jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    ptr = jnp.zeros(dist.shape, jnp.int32)
    for dy, dx in reversed(NEIGHBORS):       # the first entry wins
        ptr = jnp.where(_shift(dist, -dy, -dx) == dist - 1,
                        ((iy + dy) << 16) + (ix + dx), ptr)
    return ptr


def _walk(goal_ref, base, n_goals, dist_ref, ptr_ref, inc_ref):
    """Backtrace every goal of one grid through its field in VMEM; adds
    each path's cells to inc_ref and returns the goals' d0 and the walk's
    steps (sum of the reached d0).

    The same path as `repro.eda.batched_flow._trace_one`: a free goal is
    entered at its distance, a blocked one at its least 4-neighbour + 1
    by the first `NEIGHBORS` entry at d0 - 1; then each step moves to the
    first `NEIGHBORS` neighbour one closer, down to the seed.  A trip of
    the loop loads the (tr, 128) step tile around each walker and moves
    it to the end of its straight run in that tile (the cells whose step
    is its own), counting the cells it leaves; the seed is counted at
    the end.  The goals walk in one loop, so their dependent reads
    overlap."""
    _, h, _ = ptr_ref.shape
    tr = next(t for t in (64, 32, 16, 8) if h % t == 0)
    inc_ref[0] = jnp.zeros(inc_ref.shape[1:], jnp.int32)
    d0s, walkers = [], []
    for k in range(n_goals):
        ty = goal_ref[base + 2 * k]
        tx = goal_ref[base + 2 * k + 1]
        dv = _read(dist_ref, ty, tx)
        nd = [_read(dist_ref, ty + dy, tx + dx) for dy, dx in NEIGHBORS]
        m = functools.reduce(jnp.minimum, nd)
        d0 = jnp.where(dv < INF, dv, jnp.where(m < INF, m + 1, INF))
        d0 = jnp.where(ty >= 0, d0, INF)
        run = d0 < INF
        blocked = run & (dv >= INF)
        ty, tx = jnp.maximum(ty, 0), jnp.maximum(tx, 0)   # -1: no goal
        ey, ex = ty, tx
        for (dy, dx), v in reversed(list(zip(NEIGHBORS, nd))):
            at = blocked & (v == d0 - 1)
            ey, ex = jnp.where(at, ty + dy, ey), jnp.where(at, tx + dx, ex)
        # a blocked goal is left by its entry step; the walk counts the rest
        _bump(inc_ref, ty, tx, blocked.astype(jnp.int32))
        d0s.append(d0)
        walkers.append((ey, ex, jnp.where(run, d0 - blocked.astype(jnp.int32),
                                           0)))
    iy = jax.lax.broadcasted_iota(jnp.int32, (tr, 128), 0)
    ix = jax.lax.broadcasted_iota(jnp.int32, (tr, 128), 1)

    def cond(walkers):
        return functools.reduce(jnp.maximum, [r for _, _, r in walkers]) > 0

    def body(walkers):
        # every walker's load first, so their latencies overlap
        tiles = []
        for y, x, _ in walkers:
            rows = pl.ds(pl.multiple_of(y & -tr, tr), tr)
            lanes = pl.ds(pl.multiple_of(x & -128, 128), 128)
            tiles.append((rows, lanes, ptr_ref[0, rows, lanes]))
        nxt = []
        for (y, x, r), (rows, lanes, tile) in zip(walkers, tiles):
            y0, x0 = y & -tr, x & -128
            yo, xo = y - y0, x - x0
            move = tile - (((y0 + iy) << 16) + (x0 + ix))   # packed steps
            step = jnp.sum(jnp.where((iy == yo) & (ix == xo), move, 0))
            vert = (step == 1 << 16) | (step == -(1 << 16))
            sign = jnp.where(step > 0, 1, -1)
            # position along the run's direction, and the run's line
            along = jnp.where(vert, iy, ix) * sign
            at = jnp.where(vert, yo, xo) * sign
            line = jnp.where(vert, ix, iy) == jnp.where(vert, xo, yo)
            turn = line & (along > at) & (move != step)
            edge = jnp.where(sign > 0, jnp.where(vert, tr, 128), 1)
            n = jnp.minimum(jnp.min(jnp.where(turn, along, edge)) - at, r)
            left = line & (along >= at) & (along < at + n)
            inc_ref[0, rows, lanes] = (inc_ref[0, rows, lanes]
                                       + left.astype(jnp.int32))
            nxt.append((y + jnp.where(vert, sign, 0) * n,
                        x + jnp.where(vert, 0, sign) * n, r - n))
        return nxt

    walkers = jax.lax.while_loop(cond, body, walkers)
    for (y, x, _), d0 in zip(walkers, d0s):
        _bump(inc_ref, y, x, (d0 < INF).astype(jnp.int32))   # the seed
    steps = sum(jnp.where(d0 < INF, d0, 0) for d0 in d0s)
    return d0s, steps


def _kernel(*refs, n_goals: int, walk: bool):
    # Mosaic cannot carry or reduce i1 planes through the while loop, so
    # the masks stay int32 and the loop counts cells.
    if walk:
        goal_ref, occ_ref, seed_ref, inc_ref, stats_ref, dist_ref, ptr_ref = \
            refs
    elif n_goals:
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
    d0s, steps = [], jnp.int32(0)
    if walk:
        ptr_ref[0] = _pointers(dist)
        d0s, steps = _walk(goal_ref, base, n_goals, dist_ref, ptr_ref,
                           inc_ref)
    # row 0: sweeps run; row 1: 1 where the goals stopped the loop while
    # the field was still changing; row 2: the walk's steps; row 3: the
    # goals' d0, lane k for goal k
    row = jax.lax.broadcasted_iota(jnp.int32, stats_ref.shape[1:], 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, stats_ref.shape[1:], 1)
    out = jnp.where(row == 0, sweeps,
                    jnp.where(row == 1, (changed > 0).astype(jnp.int32),
                              jnp.where(row == 2, steps, 0)))
    for k, d0 in enumerate(d0s):
        out = jnp.where((row == 3) & (lane == k), d0, out)
    stats_ref[0] = out


def _wavefront_call(occ, seed, goals, interpret, walk=False):
    b, h, w = occ.shape
    assert h % 8 == 0 and w % 128 == 0, (h, w)
    n_goals = 0 if goals is None else goals.shape[1]
    plane = pl.BlockSpec((1, h, w), lambda i, *_: (i, 0, 0))
    stats = pl.BlockSpec((1, 8, 128), lambda i, *_: (i, 0, 0))
    # the walk's field and step planes stay in VMEM; its output is the
    # path cells' counts in place of the field.  A step packs a cell as
    # (y << 16) + x; the goals' d0 take one lane each of a stats row.
    assert not walk or (h < 2 ** 15 and w <= 2 ** 16 and n_goals <= 128)
    scratch = [pltpu.VMEM((1, h, w), jnp.int32)] * 2 if walk else []
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 if n_goals else 0, grid=(b,),
        in_specs=[plane, plane], out_specs=[plane, stats],
        scratch_shapes=scratch)
    args = (occ.astype(jnp.int8), seed.astype(jnp.int8))
    if n_goals:
        args = (goals.astype(jnp.int32).reshape(-1),) + args
    plane_out, st = pl.pallas_call(
        functools.partial(_kernel, n_goals=n_goals, walk=walk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, w), jnp.int32),
                   jax.ShapeDtypeStruct((b, 8, 128), jnp.int32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*args)
    if walk:
        return (plane_out, st[:, 3, :n_goals], st[:, 0, 0], st[:, 1, 0],
                st[:, 2, 0])
    return plane_out, st[:, 0, 0], st[:, 1, 0]


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


@functools.partial(jax.jit, static_argnames=("interpret",))
def goal_route_kernel(occ: jax.Array, seed: jax.Array, goals: jax.Array,
                      *, interpret: bool = False):
    """`goal_wavefront_kernel`, then the backtrace of every goal through
    the field it leaves in VMEM.

    goals: (B, K, 2) int32 (y, x) cells per grid; a negative pair is no
    goal and walks nothing.  Returns (inc, d0, sweeps, goal_stopped,
    walk_steps): (B, H, W) int32 path cells per cell, summed over the
    grid's goals (a cell on two goals' paths counts 2); (B, K) int32
    each goal's entry distance, `INF` where unreachable or no goal (its
    path has d0 + 1 cells); (B,) sweeps and goal stops as
    `goal_wavefront_kernel`; (B,) walk steps, the sum of the reached
    goals' d0.
    """
    return _wavefront_call(occ, seed, goals, interpret, walk=True)
