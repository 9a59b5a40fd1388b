"""Grid router (paper Sec. 2.3 / 3.3): Lee-style wavefront on a coarse
routing grid, hierarchical per the paper — template internals use
predefined tracks (constant-time), only inter-template nets are maze-routed.

Nets are routed sequentially, longest-first, on an occupancy grid with a
per-track capacity; power and SAR control nets go on reserved tracks
first (the paper's "pre-defined routing tracks for critical nets").

Since PR 2 the wavefront itself is the `repro.kernels.maze_route` op
(jnp reference off-TPU, grid-batched Pallas kernel on TPU) instead of a
host-Python BFS queue: one dispatch computes the full distance field
from the net's hub, and the host only backtraces the (short) paths.
The backtrace is deterministic — at distance d it steps to the first
neighbour at d-1 in `NEIGHBORS` order — and
`repro.eda.batched_flow.batched_route` uses the *same* field and the
same tie-break, which is what makes the batched layout path per-spec
identical to this sequential one.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.eda.placer import Placement
from repro.kernels.maze_route import INF, wavefront_distance

# Backtrace preference order (down, up, right, left) — shared with the
# batched router so sequential and batched paths pick identical cells.
NEIGHBORS = ((1, 0), (-1, 0), (0, 1), (0, -1))


@dataclasses.dataclass(frozen=True)
class Wire:
    net: str
    points: tuple[tuple[int, int], ...]     # grid path (coarse units)
    layer_pattern: str = "HV"


@dataclasses.dataclass
class RoutingResult:
    wires: list[Wire]
    grid_shape: tuple[int, int]
    coarse: int
    failed: list[str]
    total_wirelength: int

    @property
    def success_rate(self) -> float:
        n = len(self.wires) + len(self.failed)
        return len(self.wires) / n if n else 1.0


def grid_shape(width: int, height: int, coarse: int) -> tuple[int, int]:
    """Coarse routing-grid extent for a macro bounding box."""
    return (max(2, height // coarse + 3), max(2, width // coarse + 2))


def target_distance(dist: np.ndarray, dst: tuple[int, int]) -> int:
    """Path length (in steps) from the wavefront source to `dst`.

    A destination pin is always enterable even when its cell is at track
    capacity (the classic Lee-router exception), so a blocked dst costs
    one step more than its best free neighbour.  Returns `INF` when
    unreachable.
    """
    d = int(dist[dst])
    if d < INF:
        return d
    h, w = dist.shape
    best = INF
    for dy, dx in NEIGHBORS:
        ny, nx = dst[0] + dy, dst[1] + dx
        if 0 <= ny < h and 0 <= nx < w:
            best = min(best, int(dist[ny, nx]))
    return min(INF, best + 1) if best < INF else INF


def backtrace(dist: np.ndarray, dst: tuple[int, int]):
    """Walk the distance field from `dst` down to the source.

    Returns the path src -> dst (inclusive), or None when unreachable.
    Tie-break: first neighbour in `NEIGHBORS` order at distance d-1.
    """
    d = target_distance(dist, dst)
    if d >= INF:
        return None
    h, w = dist.shape
    path = [dst]
    cur = dst
    while d > 0:
        for dy, dx in NEIGHBORS:
            ny, nx = cur[0] + dy, cur[1] + dx
            if 0 <= ny < h and 0 <= nx < w and int(dist[ny, nx]) == d - 1:
                cur = (ny, nx)
                break
        else:  # pragma: no cover - the field always contains the chain
            return None
        path.append(cur)
        d -= 1
    return path[::-1]


def route(placement: Placement, nets: list[tuple[str, list[tuple[int, int]]]],
          *, coarse: int = 64, capacity: int = 4,
          impl: str | None = None) -> RoutingResult:
    """Route multi-pin nets (star topology around the first pin) on a
    coarse grid.  nets: (name, [(x, y) pin coords in F units]).

    `impl` passes through to `wavefront_distance` — unset, host calls
    get the frontier-bucketed engine (every impl produces the identical
    field, so the routing result does not depend on the choice)."""
    gh, gw = grid_shape(placement.width, placement.height, coarse)
    occ_count = np.zeros((gh, gw), np.int16)
    wires: list[Wire] = []
    failed: list[str] = []
    total = 0

    def cell(p):
        x, y = p
        return (min(gh - 1, max(0, int(y) // coarse)),
                min(gw - 1, max(0, int(x) // coarse)))

    # longest (bounding box) first
    def span(pins):
        xs = [p[0] for p in pins]
        ys = [p[1] for p in pins]
        return (max(xs) - min(xs)) + (max(ys) - min(ys))

    seed = np.zeros((gh, gw), bool)
    for name, pins in sorted(nets, key=lambda n: -span(n[1])):
        if len(pins) < 2:
            continue
        hub = cell(pins[0])
        occ = occ_count >= capacity
        seed[:] = False
        seed[hub] = True
        dist = np.asarray(wavefront_distance(occ, seed, impl=impl))
        pts: list[tuple[int, int]] = []
        ok = True
        for p in pins[1:]:
            path = backtrace(dist, cell(p))
            if path is None:
                ok = False
                break
            pts.extend(path)
        if ok:
            for y, x in pts:
                occ_count[y, x] += 1
            total += len(pts)
            wires.append(Wire(name, tuple(pts)))
        else:
            failed.append(name)
    return RoutingResult(wires, (gh, gw), coarse, failed, total)
