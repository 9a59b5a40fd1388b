"""Pure-Python BFS oracle for the maze_route family.

`wavefront_distance_bfs` is the slowest, most obviously-correct
implementation of the wavefront contract: a textbook `collections.deque`
breadth-first search, one cell at a time, no numpy vectorization and no
JAX.  It exists so the property suite
(`tests/test_maze_route_properties.py`) can pin every production engine
— the jnp sweeping ref, the Pallas Jacobi kernel, and the
frontier-bucketed numpy engine — against something a reviewer can
verify by reading thirty lines.

Semantics (shared by all four implementations, see `ref.py`):

  * seeds are distance 0, even when they sit on an occupied cell (a
    router hub is always enterable);
  * occupied cells are never *entered* (distance stays `INF`); the
    Lee "blocked destination still enterable" exception lives outside
    the wavefront, in `repro.eda.router.target_distance`.
"""
from __future__ import annotations

import collections

import numpy as np

from repro.kernels.maze_route.ref import INF


def wavefront_distance_bfs(occ, seed) -> np.ndarray:
    """BFS distance field(s), host Python.  occ/seed: (H, W) or (B, H, W)
    array-likes of bool.  Returns int32 distances of the same shape."""
    occ = np.asarray(occ, bool)
    seed = np.asarray(seed, bool)
    if occ.ndim == 3:
        return np.stack([wavefront_distance_bfs(o, s)
                         for o, s in zip(occ, seed)])
    h, w = occ.shape
    dist = np.full((h, w), INF, np.int32)
    queue: collections.deque = collections.deque()
    for y, x in zip(*np.nonzero(seed)):
        dist[y, x] = 0
        queue.append((int(y), int(x)))
    while queue:
        y, x = queue.popleft()
        d = dist[y, x] + 1
        for ny, nx in ((y + 1, x), (y - 1, x), (y, x + 1), (y, x - 1)):
            if 0 <= ny < h and 0 <= nx < w and not occ[ny, nx] \
                    and dist[ny, nx] == INF:
                dist[ny, nx] = d
                queue.append((ny, nx))
    return dist
