"""Pipeline parallelism: GPipe schedule over a mesh axis via shard_map +
collective_permute.

At 1000+-node scale the "pod" axis becomes a pipeline axis (stage-sharded
layers, microbatched activations over DCN) rather than pure DP.  This
module implements the schedule generically: `pipeline_apply` runs S stages
over M microbatches in M + S - 1 ticks, activations hopping stage->stage+1
by `jax.lax.ppermute` each tick; bubble fraction (S-1)/(M+S-1), matching
the GPipe analysis.

The per-device program is the user's `stage_fn(stage_params, x)`; outputs
are collected on the last stage and psum-broadcast so every device returns
the full (M, ...) result.  Differentiable end to end (ppermute and psum
have transposes), so the same schedule serves training — exercised by the
tests including a gradient check against the unpipelined reference.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def pipeline_apply(mesh: Mesh, stage_axis: str,
                   stage_fn: Callable[[object, jax.Array], jax.Array],
                   stage_params, microbatches: jax.Array) -> jax.Array:
    """Run `stage_fn` as an S-stage pipeline.

    stage_params: pytree with leading stage axis S (sharded over
    `stage_axis`); microbatches: (M, B, ...) activations (replicated).
    Returns (M, B, ...) outputs (replicated).
    """
    n_stages = mesh.shape[stage_axis]
    m = microbatches.shape[0]
    ticks = m + n_stages - 1
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    pspec = jax.tree.map(lambda _: P(stage_axis), stage_params)

    @functools.partial(
        jax.shard_map, mesh=mesh, check_vma=False,
        in_specs=(pspec, P()), out_specs=P())
    def run(params, mb):
        my_params = jax.tree.map(lambda a: a[0], params)
        stage_id = jax.lax.axis_index(stage_axis)
        zero = jnp.zeros_like(mb[0])
        out_buf = jnp.zeros_like(mb)

        def tick(t, state):
            prev_out, out_buf = state
            recv = jax.lax.ppermute(prev_out, stage_axis, perm)
            feed = jax.lax.dynamic_index_in_dim(
                mb, jnp.clip(t, 0, m - 1), 0, keepdims=False)
            x = jnp.where(stage_id == 0, feed, recv)
            y = stage_fn(my_params, x)
            # microbatch id being finished at the last stage this tick
            mb_id = t - (n_stages - 1)
            is_out = (stage_id == n_stages - 1) & (mb_id >= 0)
            upd = jnp.where(is_out, y, 0.0)
            out_buf = jax.lax.dynamic_update_index_in_dim(
                out_buf,
                jax.lax.dynamic_index_in_dim(out_buf, jnp.clip(mb_id, 0, m - 1),
                                             0, keepdims=False) + upd,
                jnp.clip(mb_id, 0, m - 1), 0)
            return y, out_buf

        _, out_buf = jax.lax.fori_loop(0, ticks, tick, (zero, out_buf))
        # only the last stage holds results: broadcast via psum
        return jax.lax.psum(
            jnp.where(stage_id == n_stages - 1, out_buf, 0.0), stage_axis)

    return run(stage_params, microbatches)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
