"""Pallas TPU kernel for the batched Lee maze-router wavefront.

One program instance per routing grid: the batch is the Pallas grid axis
(`grid=(B,)`), so laying out a whole distilled Pareto set expands B
wavefronts concurrently — the "parallel BFS" of the batched layout flow
(`repro.eda.batched_flow`).  Each program keeps its (H, W) occupancy,
seed, and distance planes entirely in VMEM and runs the min-plus
relaxation to its fixed point on the VPU:

    dist <- min(dist, 1 + min(N, S, E, W))        on free cells

Neighbour access is expressed as static-slice shifts (concatenate with
an `INF` edge row/lane), which lowers to cheap sublane/lane shifts —
there is no gather and no host queue.  The loop terminates when a sweep
changes nothing; every sweep advances the frontier one step, so the trip
count is the largest finite distance, bounded by H * W.

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


def _kernel(occ_ref, seed_ref, dist_ref):
    # Mosaic cannot carry or reduce i1 planes through the while loop, so
    # the masks stay int32 and the loop counts changed cells.
    seed = seed_ref[0].astype(jnp.int32)
    fixed = occ_ref[0].astype(jnp.int32) | seed      # blocked or a seed
    dist0 = jnp.where(seed != 0, 0, INF).astype(jnp.int32)

    def cond(state):
        _, changed = state
        return changed > 0

    def body(state):
        dist, _ = state
        best = jnp.minimum(
            jnp.minimum(_shift(dist, 1, 0), _shift(dist, -1, 0)),
            jnp.minimum(_shift(dist, 0, 1), _shift(dist, 0, -1))) + 1
        nxt = jnp.where(fixed == 0, jnp.minimum(dist, best), dist)
        return nxt, jnp.sum((nxt < dist).astype(jnp.int32))

    dist, _ = jax.lax.while_loop(cond, body, (dist0, jnp.int32(1)))
    dist_ref[0] = dist


@functools.partial(jax.jit, static_argnames=("interpret",))
def wavefront_kernel(occ: jax.Array, seed: jax.Array, *,
                     interpret: bool = False) -> jax.Array:
    """occ, seed: (B, H, W) int8 with H % 8 == 0, W % 128 == 0 (pad with
    blocked cells; see ops).  Returns (B, H, W) int32 BFS distances."""
    b, h, w = occ.shape
    assert h % 8 == 0 and w % 128 == 0, (h, w)
    return pl.pallas_call(
        _kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, w), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, h, w), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, w), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, w), jnp.int32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(occ.astype(jnp.int8), seed.astype(jnp.int8))
