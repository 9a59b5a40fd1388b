"""Pallas TPU kernels for NSGA-II's fast non-dominated sort.

Two entry points:

`dominance_matrix_kernel` — the tiled pairwise dominance matrix.  The
O(P^2 * M) matrix is the hot spot of the sort (population P up to several
thousand in the distributed explorer; M = 4 objectives).  Objectives are
passed transposed, (M, P), so population indexes the 128-wide lane
dimension; each (bi, bj) output tile loads two thin (M, b) strips into
VMEM and reduces over M on the VPU.

    D[i, j] = all_m(F[m,i] <= F[m,j]) & any_m(F[m,i] < F[m,j])

`nds_rank_kernel` — the fused rank path.  Instead of materializing the
(P, P) f32 matrix to HBM and running the front-peeling loop as repeated
dense matmuls (the jnp oracle `repro.core.pareto.non_dominated_rank`),
this kernel builds the dominance matrix 32 dominator rows at a time in
VMEM, bit-packs each 32-row strip into one int32 lane vector (a (P/32, P)
scratch — 32x smaller than the bool matrix, 128x smaller than f32), and
peels fronts on-device: per iteration, the still-unranked ("alive") mask
is packed into per-word masks and the remaining in-degree of every point
is a popcount-accumulate over the packed words.  Nothing of size P^2 ever
leaves VMEM, and no (P, P) f32 tensor exists at any point.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(fi_ref, fj_ref, o_ref):
    fi = fi_ref[...]   # (M, bi)
    fj = fj_ref[...]   # (M, bj)
    le = jnp.all(fi[:, :, None] <= fj[:, None, :], axis=0)
    lt = jnp.any(fi[:, :, None] < fj[:, None, :], axis=0)
    o_ref[...] = (le & lt).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def dominance_matrix_kernel(f_t: jax.Array, *, block: int = 256,
                            interpret: bool = False) -> jax.Array:
    """f_t: (M, P) objectives, P % block == 0.  Returns (P, P) int8 where
    D[i, j] = 1 iff point i dominates point j (minimization, Eq. 1)."""
    m, p = f_t.shape
    assert p % block == 0, (p, block)
    grid = (p // block, p // block)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, block), lambda i, j: (0, i)),
            pl.BlockSpec((m, block), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block, block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((p, p), jnp.int8),
        interpret=interpret,
    )(f_t.astype(jnp.float32), f_t.astype(jnp.float32))


# ----------------------------------------------------------------------
# Fused rank path: dominance + bit-pack + front peel, all in VMEM
# ----------------------------------------------------------------------
def _rank_kernel(f_ref, ft_ref, ranks_ref, packed_ref):
    """f_ref (P, M), ft_ref (M, P) — same objectives in both layouts so the
    dominator strip is a sublane slice and the dominated axis stays on
    lanes.  ranks_ref (1, P) int32 out; packed_ref (P//32, P) int32
    scratch: bit k of packed[w, j] == "point 32w+k dominates point j"."""
    p, m = f_ref.shape
    n_words = p // 32
    ft = ft_ref[...]                                     # (M, P)
    strip_bit = jax.lax.broadcasted_iota(jnp.int32, (32, 1), 0)

    def build(wi, carry):
        fi = f_ref[pl.ds(wi * 32, 32), :]                # (32, M) dominators
        le = jnp.all(fi[:, :, None] <= ft[None, :, :], axis=1)   # (32, P)
        lt = jnp.any(fi[:, :, None] < ft[None, :, :], axis=1)
        dom = (le & lt).astype(jnp.int32)
        packed_ref[pl.ds(wi, 1), :] = jnp.sum(dom << strip_bit, axis=0,
                                              keepdims=True)
        return carry

    jax.lax.fori_loop(0, n_words, build, 0)

    # spread[w, i] = bit (i % 32) when i falls in word w: a lane
    # reduction of spread * alive packs the alive mask along the
    # dominator axis, (1, P) -> (W, 1), without a lane->sublane reshape.
    word = jax.lax.broadcasted_iota(jnp.int32, (n_words, p), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n_words, p), 1)
    spread = jnp.where((lane >> 5) == word, 1 << (lane & 31), 0)

    def cond(state):
        _, _, left = state
        return left > 0

    def body(state):
        ranks, front, _ = state
        alive = (ranks < 0).astype(jnp.int32)           # (1, P)
        alive_w = jnp.sum(spread * alive, axis=1, keepdims=True)   # (W, 1)
        masked = packed_ref[...] & alive_w               # (W, P)
        indeg = jnp.sum(jax.lax.population_count(masked),
                        axis=0, keepdims=True)           # (1, P)
        ranks = jnp.where((alive != 0) & (indeg == 0), front, ranks)
        return ranks, front + 1, jnp.sum((ranks < 0).astype(jnp.int32))

    ranks0 = jnp.full((1, p), -1, jnp.int32)
    ranks, _, _ = jax.lax.while_loop(cond, body,
                                     (ranks0, jnp.int32(0), jnp.int32(p)))
    ranks_ref[...] = ranks


@functools.partial(jax.jit, static_argnames=("interpret",))
def nds_rank_kernel(f: jax.Array, *, interpret: bool = False) -> jax.Array:
    """f: (P, M) objectives, P % 256 == 0 (pad with +inf rows; see ops).
    Returns (P,) int32 non-dominated-sort front indices (0 = Pareto)."""
    p, m = f.shape
    assert p % 256 == 0, p
    f = f.astype(jnp.float32)
    ranks = pl.pallas_call(
        _rank_kernel,
        in_specs=[
            pl.BlockSpec((p, m), lambda: (0, 0)),
            pl.BlockSpec((m, p), lambda: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, p), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, p), jnp.int32),
        scratch_shapes=[pltpu.VMEM((p // 32, p), jnp.int32)],
        interpret=interpret,
    )(f, f.T)
    return ranks[0]
