"""Pure-jnp oracle for the maze_route kernels: multi-source Lee wavefront.

`wavefront_distance_ref` computes the full BFS distance field of the
Lee maze router (paper Sec. 2.3 / 3.3) by *fast sweeping*: a round runs
four directional min-plus propagations (+x, -x, +y, -y), each expressed
as a segmented associative scan that carries distance along free runs of
a row/column and is killed by blocked cells; rounds repeat until the
field stops changing.  The fixed point satisfies
``d = min(d0, 1 + min(4-neighbour d))`` on traversable cells — exactly
the BFS distance field of `repro.eda.router`'s former host-Python queue
implementation — but converges in O(bends-in-shortest-paths) rounds
instead of O(path-length) Jacobi steps, which is what makes the batched
layout flow faster than per-spec host BFS on every backend.

Semantics shared with the Pallas kernel (which keeps the simple
step-per-iteration relaxation — same unique fixed point):

  * seeds have distance 0, even when they sit on an occupied cell (a
    router hub is always enterable, matching the old BFS which started
    its queue at ``src`` unconditionally);
  * occupied cells are never traversed (distance stays `INF`) — the
    router handles the "dst occupied but still reachable" exception
    outside the wavefront (see `repro.eda.router.target_distance`).

The field is exact shortest-path distance, so any tie-break used to
backtrace a path from it yields a path of the same length as BFS.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Unreachable marker.  A plain Python int so Pallas kernels can close
# over it as a literal; small enough that INF + INF fits in int32 (the
# sweep composition adds two INF-capped terms before re-capping).
INF = 2 ** 29

# Backtrace preference order (down, up, right, left) as (dy, dx): at
# distance d a path steps to the first neighbour at d - 1 in this order.
# Shared by every backtrace (`repro.eda.router`, the batched router and
# the Pallas walk) so all of them pick identical cells.
NEIGHBORS = ((1, 0), (-1, 0), (0, 1), (0, -1))


def relax_once(dist: jax.Array, free: jax.Array) -> jax.Array:
    """One Jacobi wavefront step: (..., H, W) int32 -> same.

    The building block of the Pallas kernel; exported so tests can
    cross-check the sweeping fixed point against the plain relaxation.
    """
    pad = [(0, 0)] * (dist.ndim - 2)
    padded = jnp.pad(dist, pad + [(1, 1), (1, 1)], constant_values=INF)
    up = padded[..., :-2, 1:-1]
    down = padded[..., 2:, 1:-1]
    left = padded[..., 1:-1, :-2]
    right = padded[..., 1:-1, 2:]
    best = jnp.minimum(jnp.minimum(up, down), jnp.minimum(left, right)) + 1
    return jnp.where(free, jnp.minimum(dist, best), dist)


def _sweep(dist: jax.Array, transit: jax.Array, axis: int,
           reverse: bool) -> jax.Array:
    """Directional propagation: min-plus affine segmented scan.

    Each cell is the map f(x) = min(u, x + w) with u its current
    distance and w its traversal cost (1, or INF on non-transit cells so
    the carry dies at obstacles); composition of such maps is
    associative, so the whole run is one `associative_scan`.
    """
    u = jnp.where(transit, dist, INF)
    w = jnp.where(transit, 1, INF).astype(jnp.int32)

    def op(left, right):
        ul, wl = left
        ur, wr = right
        return (jnp.minimum(ur, jnp.minimum(ul + wr, INF)),
                jnp.minimum(wl + wr, INF))

    uu, _ = jax.lax.associative_scan(op, (u, w), axis=axis, reverse=reverse)
    return jnp.where(transit, jnp.minimum(dist, uu), dist)


def wavefront_distance_ref(occ: jax.Array, seed: jax.Array) -> jax.Array:
    """BFS distance field on a routing grid.

    occ:  (..., H, W) bool — blocked cells (track capacity exhausted).
    seed: (..., H, W) bool — wavefront sources (distance 0).
    Returns (..., H, W) int32 distances; `INF` where unreachable or
    blocked.  Seeds are distance 0 even on blocked cells.
    """
    occ = jnp.asarray(occ, jnp.bool_)
    seed = jnp.asarray(seed, jnp.bool_)
    # Seeds are traversable even when occupied (hub exception); paths
    # re-entering a source can never be shorter, so this is harmless.
    transit = ~occ | seed
    dist0 = jnp.where(seed, 0, INF).astype(jnp.int32)
    ndim = dist0.ndim

    def cond(state):
        _, changed = state
        return changed

    def body(state):
        dist, _ = state
        nxt = dist
        for axis, reverse in ((ndim - 1, False), (ndim - 1, True),
                              (ndim - 2, False), (ndim - 2, True)):
            nxt = _sweep(nxt, transit, axis, reverse)
        return nxt, jnp.any(nxt < dist)

    dist, _ = jax.lax.while_loop(cond, body, (dist0, jnp.bool_(True)))
    return dist
