"""Batched layout generation: the explorer's distilled Pareto set through
place / route / DRC / metrics in a handful of device dispatches.

This is the layout-side counterpart of `repro.core.batched_explorer`
(paper Fig. 4: the MOGA's user-distilled Pareto set flows straight into
automated layout generation).  The sequential `repro.eda.flow
.generate_layout` runs one spec at a time in host Python; here every
stage is array-programmed over a stacked spec batch:

  * **place** — `placer.rect_tensors` (the data-oriented template
    expansion) is `jax.vmap`-ed over a stacked `LayoutOperands` tree:
    one dispatch produces the (B, ..., 4) rect tensors for all specs,
    padded to per-batch index extents (`BatchDims`) with validity masks.
  * **route** — inter-template nets are derived from the rect tensors on
    device, ordered longest-first exactly like the sequential router,
    and routed net-slot by net-slot with the `kernels.maze_route`
    wavefront expanding all B grids at once (grid-batched parallel BFS).
    The backtrace tie-break matches `router.backtrace`, so per-spec
    occupancy, success and wirelength are identical to B sequential
    `route()` calls.
  * **DRC** — a sweep-free pairwise-overlap reduction.  Every column of
    the macro is an x-translate of column 0 (the expansion is
    pitch-matched), and the sequential `drc_lite` never compares rects
    from different columns, so intra-column pair overlaps are counted
    once on column 0 and multiplied by W; bounds checks run over the
    flat (B, R, 4) rect tensor.
  * **metrics / netlist stats** — closed-form (`netlist.stats_for_spec`)
    and vectorized over the batch.

`generate_layouts(specs)` is the engine entry point for one batch;
`iter_layout_buckets(...)` streams a sequence of grid-shape buckets
through it, yielding each bucket's result incrementally (what the
staged pipeline executor consumes).  The supported front-end is
`repro.api.DesignSession` (which chains exploration into it and
buckets multi-tenant batches by routing-grid shape before calling it —
see `repro.serve.design_service`).  Per-spec results
unpack to the sequential dataclasses via `BatchedLayoutResult
.placements()` / `.drc_reports()` for interop, and
`tests/test_batched_flow.py` asserts batched == sequential per spec
(same rects, same route success, same DRC verdict).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import estimator
from repro.core.acim_spec import MacroSpec
from repro.eda import netlist as nl_mod
from repro.eda.flow import DRCReport
from repro.eda.placer import (CATEGORIES, CATEGORY_CELL, BatchDims,
                              LayoutOperands, Placed, Placement,
                              PlacerGeometry, category_names, dims_for_spec,
                              geometry, layout_operands, rect_tensors)
from repro.eda.router import NEIGHBORS, grid_shape
from repro.kernels.maze_route import INF, goal_route, wavefront_distance
from repro.kernels.maze_route.frontier import (canvas_free, canvas_index,
                                               expand_buckets, strides)
from repro.telemetry.spans import trace_span

Array = jax.Array


def stack_layout_operands(specs, geom: PlacerGeometry) -> LayoutOperands:
    """Stack per-spec `LayoutOperands` trees into one batched tree."""
    return jax.tree.map(lambda *xs: jnp.stack(xs),
                        *[layout_operands(s, geom) for s in specs])


# ----------------------------------------------------------------------
# Placement: one vmapped dispatch for the whole batch
# ----------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("dims", "geom"))
def _place_program(ops: LayoutOperands, *, dims: BatchDims,
                   geom: PlacerGeometry):
    return jax.vmap(lambda o: rect_tensors(o, dims, geom))(ops)


def _flat_rects(tensors):
    """(B, R, 4) rects + (B, R) mask from the batched category tensors."""
    b = next(iter(tensors.values()))[0].shape[0]
    rects = jnp.concatenate(
        [tensors[c][0].reshape((b, -1, 4)) for c in CATEGORIES], axis=1)
    mask = jnp.concatenate(
        [tensors[c][1].reshape((b, -1)) for c in CATEGORIES], axis=1)
    return rects, mask


# ----------------------------------------------------------------------
# DRC: sweep-free pairwise-overlap reduction
# ----------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("dims", "geom"))
def _drc_program(tensors, ops: LayoutOperands, *, dims: BatchDims,
                 geom: PlacerGeometry):
    del geom  # geometry is baked into the tensors
    # Column 0 carries every intra-column pair; columns are x-translates.
    col = jnp.concatenate([
        tensors["sram"][0][:, 0],
        tensors["cap"][0][:, 0],
        tensors["sw"][0][:, 0],
        tensors["comp"][0][:, :1],
        tensors["sar"][0][:, :1],
        tensors["dff"][0][:, 0],
    ], axis=1)
    cmask = jnp.concatenate([
        tensors["sram"][1][:, 0],
        tensors["cap"][1][:, 0],
        tensors["sw"][1][:, 0],
        tensors["comp"][1][:, :1],
        tensors["sar"][1][:, :1],
        tensors["dff"][1][:, 0],
    ], axis=1)
    a = col[:, :, None, :]
    b = col[:, None, :, :]
    ov = ((a[..., 0] < b[..., 0] + b[..., 2])
          & (b[..., 0] < a[..., 0] + a[..., 2])
          & (a[..., 1] < b[..., 1] + b[..., 3])
          & (b[..., 1] < a[..., 1] + a[..., 3]))
    c = col.shape[1]
    upper = jnp.arange(c)[:, None] < jnp.arange(c)[None, :]
    valid = cmask[:, :, None] & cmask[:, None, :] & upper[None]
    overlaps = jnp.sum(ov & valid, axis=(1, 2)).astype(jnp.int32) * ops.w

    rects, mask = _flat_rects(tensors)
    oob = ((rects[..., 1] + rects[..., 3] > ops.height[:, None] + 1)
           | (rects[..., 0] + rects[..., 2] > ops.width[:, None] + 1))
    oob = jnp.sum(oob & mask, axis=1).astype(jnp.int32)
    return overlaps, oob


# ----------------------------------------------------------------------
# Net derivation: same nets, same longest-first order as the host flow
# ----------------------------------------------------------------------
class NetBatch(NamedTuple):
    """Routing-ready net slots, already in routing (longest-first) order.

    hub/tgt coordinates are grid cells (gy, gx); masks gate per-target
    and per-net validity (padded slots of smaller specs are invalid)."""

    hubs: Array        # (B, N, 2) int32
    tgts: Array        # (B, N, 2, 2) int32 — up to two star targets
    tmask: Array       # (B, N, 2) bool
    nmask: Array       # (B, N) bool


def _centers(t: Array):
    return t[..., 0] + t[..., 2] // 2, t[..., 1] + t[..., 3] // 2


@functools.partial(jax.jit, static_argnames=("dims", "geom", "coarse"))
def _nets_program(tensors, ops: LayoutOperands, *, dims: BatchDims,
                  geom: PlacerGeometry, coarse: int) -> NetBatch:
    del geom
    bsz = ops.w.shape[0]
    comp_x, comp_y = _centers(tensors["comp"][0])        # (B, W)
    sar_x, sar_y = _centers(tensors["sar"][0])           # (B, W)
    cap_x, cap_y = _centers(tensors["cap"][0])           # (B, W, NLA)
    sram_x, sram_y = _centers(tensors["sram"][0])        # (B, W, H)
    rd_x, rd_y = _centers(tensors["rd"][0])              # (B, RD)

    top = (ops.n_la - 1)[:, None, None]                  # (B, 1, 1)
    cap0 = jnp.stack([cap_x[:, :, 0], cap_y[:, :, 0]], -1)
    capt = jnp.stack([
        jnp.take_along_axis(cap_x, top, axis=2)[:, :, 0],
        jnp.take_along_axis(cap_y, top, axis=2)[:, :, 0]], -1)
    comp = jnp.stack([comp_x, comp_y], -1)               # (B, W, 2)
    sar = jnp.stack([sar_x, sar_y], -1)
    jvalid = jnp.arange(dims.w)[None, :] < ops.w[:, None]

    # per-column nets, interleaved (rbl_j, cmp_j) like the host net list
    rbl_t = jnp.stack([cap0, capt], axis=2)              # (B, W, 2, 2)
    cmp_t = jnp.stack([sar, sar], axis=2)
    col_hubs = jnp.stack([comp, comp], axis=2)           # (B, W, 2net, 2)
    col_tgts = jnp.stack([rbl_t, cmp_t], axis=2)         # (B, W, 2net, 2, 2)
    col_tmask = jnp.broadcast_to(
        jnp.array([[True, True], [True, False]]),
        (bsz, dims.w, 2, 2))
    col_nmask = jnp.broadcast_to(jvalid[:, :, None], (bsz, dims.w, 2))

    # row-driver nets: driver -> farthest column's cell in that row
    r = jnp.arange(dims.rd, dtype=jnp.int32)[None, :]    # (1, RD)
    flat = (ops.w[:, None] - 1) * dims.h + r             # sram[w-1, r]
    far_x = jnp.take_along_axis(sram_x.reshape((bsz, -1)), flat, axis=1)
    far_y = jnp.take_along_axis(sram_y.reshape((bsz, -1)), flat, axis=1)
    rd_hubs = jnp.stack([rd_x, rd_y], -1)                # (B, RD, 2)
    far = jnp.stack([far_x, far_y], -1)
    rd_tgts = jnp.stack([far, far], axis=2)              # (B, RD, 2, 2)
    rd_tmask = jnp.broadcast_to(jnp.array([True, False]),
                                (bsz, dims.rd, 2))
    rd_nmask = r < ops.n_rd[:, None]

    hubs = jnp.concatenate([col_hubs.reshape((bsz, -1, 2)), rd_hubs], 1)
    tgts = jnp.concatenate([col_tgts.reshape((bsz, -1, 2, 2)), rd_tgts], 1)
    tmask = jnp.concatenate([col_tmask.reshape((bsz, -1, 2)), rd_tmask], 1)
    nmask = jnp.concatenate([col_nmask.reshape((bsz, -1)), rd_nmask], 1)

    # longest (bounding box) first, in F units, stable — same key and
    # same tie order as `router.route`'s host sort
    pins = jnp.concatenate([hubs[:, :, None], tgts], axis=2)  # (B, N, 3, 2)
    pmask = jnp.concatenate([jnp.ones_like(tmask[:, :, :1]), tmask], 2)
    px = jnp.where(pmask, pins[..., 0], hubs[:, :, None, 0])
    py = jnp.where(pmask, pins[..., 1], hubs[:, :, None, 1])
    span = (px.max(2) - px.min(2)) + (py.max(2) - py.min(2))
    span = jnp.where(nmask, span, -1)
    order = jnp.argsort(-span, axis=1, stable=True)      # (B, N)

    take = lambda a: jnp.take_along_axis(  # noqa: E731
        a, order.reshape(order.shape + (1,) * (a.ndim - 2)), axis=1)
    hubs, tgts, tmask, nmask = (take(hubs), take(tgts), take(tmask),
                                take(nmask))

    # F-unit pin coords -> clipped per-spec grid cells (gy, gx)
    gh = jnp.maximum(2, ops.height // coarse + 3)[:, None]
    gw = jnp.maximum(2, ops.width // coarse + 2)[:, None]

    def to_cell(xy, gh, gw):
        gy = jnp.clip(xy[..., 1] // coarse, 0, gh - 1)
        gx = jnp.clip(xy[..., 0] // coarse, 0, gw - 1)
        return jnp.stack([gy, gx], axis=-1)

    return NetBatch(to_cell(hubs, gh, gw),
                    to_cell(tgts, gh[..., None], gw[..., None]),
                    tmask, nmask)


# ----------------------------------------------------------------------
# Routing: per net slot, one batched wavefront + on-device backtrace
# ----------------------------------------------------------------------
def _dir_field(dist: Array) -> Array:
    """Backtrace direction of every cell: the first `NEIGHBORS` entry at
    distance d-1 (router.backtrace's tie-break), int8 in {0..3}.

    Vectorized once per wavefront; the per-step walk then costs a single
    scalar gather.  Cells with d == 0 or d == INF hold an arbitrary
    direction — the walk never reads them (sources stop the walk, and
    blocked targets take their special entry step first).  BFS
    guarantees every cell with finite d > 0 has a d-1 neighbour.
    """
    gh, gw = dist.shape
    pad = jnp.pad(dist, 1, constant_values=INF)
    match = jnp.stack([pad[1 + dy:1 + dy + gh, 1 + dx:1 + dx + gw]
                       == dist - 1 for dy, dx in NEIGHBORS])
    return jnp.argmax(match, axis=0).astype(jnp.int8)


def _trace_one(dist: Array, dirf: Array, tgt: Array, active: Array):
    """Backtrace one star target on one grid; returns (inc, wl, reachable).

    Mirrors `router.target_distance` + `router.backtrace`: a blocked dst
    is enterable at +1 from its best neighbour, then the walk follows
    the precomputed direction field — identical cells, so the occupancy
    evolution matches the sequential router exactly.  The walk scatters
    its visited cells once per `chunk` steps (out-of-range rows are
    dropped), not once per step — scatter cost is per-op on CPU.
    """
    gh, gw = dist.shape
    chunk = 16
    ty, tx = tgt[0], tgt[1]
    dv = dist[ty, tx]
    win = jax.lax.dynamic_slice(jnp.pad(dist, 1, constant_values=INF),
                                (ty, tx), (3, 3))
    # NEIGHBORS order: down, up, right, left
    nd0 = jnp.stack([win[2, 1], win[0, 1], win[1, 2], win[1, 0]])
    d0 = jnp.where(dv < INF, dv, jnp.minimum(INF, jnp.min(nd0) + 1))
    reach = d0 < INF
    run = active & reach
    dy_tab = jnp.array([n[0] for n in NEIGHBORS])
    dx_tab = jnp.array([n[1] for n in NEIGHBORS])

    # blocked target: its entry step is not in the direction field
    esel = jnp.argmax(nd0 == d0 - 1)
    blocked = run & (dv >= INF)
    ey = jnp.where(blocked, ty + dy_tab[esel], ty)
    ex = jnp.where(blocked, tx + dx_tab[esel], tx)
    inc = jnp.zeros((gh, gw), jnp.int8).at[
        jnp.stack([jnp.where(run, ty, gh), jnp.where(blocked, ey, gh)]),
        jnp.stack([tx, ex])].add(jnp.int8(1), mode="drop")
    dirf_flat = dirf.reshape(-1)

    def walk(carry, _):
        y, x, d = carry
        sel = dirf_flat[y * gw + x]
        stepping = d > 0
        ny = jnp.where(stepping, y + dy_tab[sel], y)
        nx = jnp.where(stepping, x + dx_tab[sel], x)
        out = (jnp.where(stepping, ny, gh), nx)    # row gh -> dropped
        return (ny, nx, jnp.maximum(d - 1, 0)), out

    def cond(state):
        _, _, d, _ = state
        return d > 0

    def body(state):
        y, x, d, inc = state
        (y, x, d), (ys, xs) = jax.lax.scan(walk, (y, x, d), None,
                                           length=chunk)
        # NB: steps past the path's end all emit the same dropped index,
        # so unique_indices must NOT be asserted here
        return y, x, d, inc.at[ys, xs].add(jnp.int8(1), mode="drop")

    _, _, _, inc = jax.lax.while_loop(
        cond, body,
        (ey, ex, jnp.where(run, jnp.where(blocked, d0 - 1, d0), 0), inc))
    wl = jnp.where(run, d0 + 1, 0)
    return inc, wl, reach


def _route_step(occ_count: Array, hubs: Array, tgts: Array, tmask: Array,
                nmask: Array, *, capacity: int, use_kernel: bool | None):
    """Route one net slot across the whole batch.

    occ_count: (B, Gh, Gw) int32; hubs (B, 2); tgts (B, 2, 2);
    tmask (B, 2); nmask (B,).  Returns (occ_count', ok, wirelength,
    stats): stats is the Pallas kernel's (sweeps, goal_stopped,
    walk_steps) per grid, or None where the jnp ref computes the field.

    The kernel stops each grid's wavefront once the slot's targets are
    resolved and walks their backtraces through the field it holds
    (`goal_route`): the walk reads only cells closer than its target,
    all final by then.  The ref computes whole fields and walks them
    with `_dir_field` and `_trace_one`, the kernel's test oracle.
    """
    _, gh, gw = occ_count.shape
    occ = occ_count >= capacity
    iy = jnp.arange(gh)[None, :, None]
    ix = jnp.arange(gw)[None, None, :]
    seed = ((iy == hubs[:, 0, None, None]) & (ix == hubs[:, 1, None, None])
            & nmask[:, None, None])
    active = tmask & nmask[:, None]
    if use_kernel or (use_kernel is None
                      and jax.default_backend() == "tpu"):
        goals = jnp.where(active[..., None], tgts, -1)
        inc, d0, sweeps, stopped, steps = goal_route(occ, seed, goals)
        reach = d0 < INF
        wl = jnp.where(active & reach, d0 + 1, 0)
        stats = (sweeps, stopped, steps)
    else:
        dist = wavefront_distance(occ, seed, impl="ref")
        dirf = jax.vmap(_dir_field)(dist)
        trace = jax.vmap(jax.vmap(_trace_one, in_axes=(None, None, 0, 0)))
        inc, wl, reach = trace(dist, dirf, tgts, active)
        inc, stats = inc.astype(jnp.int32).sum(axis=1), None
    ok = nmask & jnp.all(reach | ~tmask, axis=1)
    occ_count = occ_count + inc * ok[:, None, None]
    return occ_count, ok, wl.sum(axis=1) * ok, stats


@functools.partial(jax.jit, static_argnames=("capacity", "use_kernel"))
def _route_program(occ0: Array, nets: NetBatch, *, capacity: int,
                   use_kernel: bool | None):
    """All net slots in one compiled program: `lax.scan` over the slot
    axis with the (occupancy, counters) carry — the sequential
    net-by-net data dependence stays, but there is a single dispatch for
    the whole batch instead of one per net.  The last output is the
    Pallas kernel's (sweeps, goal stops, walk steps) per spec, summed
    over the slots, or None on the ref path."""

    def step(carry, slot):
        occ, routed, failed, wirelen = carry
        hubs, tgts, tmask, nmask = slot
        occ, ok, wl, stats = _route_step(
            occ, hubs, tgts, tmask, nmask, capacity=capacity,
            use_kernel=use_kernel)
        return ((occ, routed + ok, failed + (nmask & ~ok), wirelen + wl),
                stats)

    bsz = occ0.shape[0]
    zeros = jnp.zeros((bsz,), jnp.int32)
    slots = jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0), nets)
    (occ, routed, failed, wirelen), stats = jax.lax.scan(
        step, (occ0, zeros, zeros, zeros), slots)
    stats = jax.tree.map(lambda a: a.sum(axis=0), stats)
    return occ, routed, failed, wirelen, stats


# ----------------------------------------------------------------------
# Concurrent-net routing: conflict-aware scheduling over frontier buckets
# ----------------------------------------------------------------------
#
# The scan engine above pays one full-grid wavefront per net *slot* —
# O(nets) sweeps even though most nets never interact.  The concurrent
# engine routes many nets of one spec in the same dispatch and keeps the
# result bit-identical to the sequential router by separating *when a
# field is computed* from *when its route commits*:
#
#   * rounds are colors of the conflict graph: each round greedily picks
#     pending nets, in slot order, whose expanded bounding boxes are
#     pairwise disjoint within a spec (greedy coloring — a net conflicts
#     with an earlier pick, it waits for a later round);
#   * the picked lanes' distance fields are computed together — closed
#     form while the spec has no blocked cell (an obstacle-free
#     rectangle's BFS field is plain Manhattan distance), the bucketed
#     frontier engine (`kernels.maze_route.frontier`) with per-lane
#     early exit afterwards;
#   * routes commit strictly in slot order.  A commit that pushes cells
#     *across* the capacity threshold (newly blocked cells X) is the
#     only event that can perturb later fields, and a buffered field
#     stays exact iff every target distance d0 satisfies
#     d0 <= min over x in X of dist(x): blocking a cell at distance >=
#     d0 cannot change any cell at distance < d0 (its shortest paths
#     can't pass through x), cannot shrink the d0-1 match sets the
#     backtrace reads, and leaves unreachable targets unreachable.
#     Fields that fail the test are occupancy *collisions*: the loser is
#     dropped and recomputed (retried) in a later round against the
#     updated occupancy.
#
# The head of each spec's pending queue is always computed in the round
# (no earlier pick exists to conflict with) and always commits (its
# field is fresh), so every round makes progress and the loop terminates
# in <= nets rounds; in practice rounds ~ conflict depth of the net set.


@dataclasses.dataclass
class RouteSchedule:
    """Trace of the conflict-aware scheduler, for tests.

    dispatches[r] = (spec, slot) lanes whose wavefronts were computed
    together in round r; bboxes is every net's expanded bounding box
    (y0, x0, y1, x1 inclusive, grid cells) so tests can assert no round
    ever co-dispatched two overlapping nets of one spec."""

    dispatches: list
    bboxes: np.ndarray
    rounds: int = 0
    collisions: int = 0
    crossings: int = 0


@dataclasses.dataclass
class _Buffered:
    """A computed-but-not-yet-committed route of one (spec, slot) lane."""

    cells: np.ndarray            # occupancy increments, real-grid flat idx
    wl: int                      # wirelength contribution if committed
    ok: bool                     # every valid target reachable
    d0max: int                   # max finite target distance (-1: none)
    dist: np.ndarray | None      # (C,) canvas field (frontier lanes)
    hub: tuple | None            # (hy, hx): closed-form field (Manhattan)


def _still_valid(e: _Buffered, ys: np.ndarray, xs: np.ndarray,
                 stride: int) -> bool:
    """Does `e`'s route survive cells (ys, xs) becoming blocked?

    Exactness bound (see module comment): valid iff d0max <= min dist(x)
    over the newly blocked cells.  Failed-net entries are always valid —
    an unreachable target stays unreachable under more blocking, and
    nothing else of theirs is ever read."""
    if not e.ok or e.d0max < 0:
        return True
    if e.dist is not None:
        dmin = int(e.dist[canvas_index(ys, xs, stride)].min())
    else:
        hy, hx = e.hub
        dmin = int((np.abs(ys - hy) + np.abs(xs - hx)).min())
    return e.d0max <= dmin


def _ragged_arange(lengths: np.ndarray) -> np.ndarray:
    """Concatenated [0..l) ranges: [0,1,..,l0-1, 0,1,..,l1-1, ...]."""
    ends = np.cumsum(lengths)
    return np.arange(int(ends[-1]) if len(ends) else 0) \
        - np.repeat(ends - lengths, lengths)


def _manhattan_paths(lane, hy, hx, ty, tx):
    """Closed-form backtrace on an obstacle-free grid, all walkers at once.

    On a blocked-free rectangle the field is |dy|+|dx| and the shared
    tie-break (first `NEIGHBORS` entry at d-1: down, up, right, left)
    walks vertically to the hub row, then horizontally — so the full
    path (target included) is two ragged runs.  Returns concatenated
    (lane, y, x) path cells, d0+1 of them per walker."""
    sy = np.sign(hy - ty)
    lv = np.abs(hy - ty) + 1            # vertical run, target included
    sx = np.sign(hx - tx)
    lh = np.abs(hx - tx)                # horizontal run, pivot excluded
    ys_v = np.repeat(ty, lv) + np.repeat(sy, lv) * _ragged_arange(lv)
    xs_v = np.repeat(tx, lv)
    ys_h = np.repeat(hy, lh)
    xs_h = np.repeat(tx + sx, lh) + np.repeat(sx, lh) * _ragged_arange(lh)
    return (np.concatenate([np.repeat(lane, lv), np.repeat(lane, lh)]),
            np.concatenate([ys_v, ys_h]), np.concatenate([xs_v, xs_h]))


def _walk_paths(dist: np.ndarray, lanes, start, steps, stride: int):
    """Vectorized multi-walker backtrace over canvas distance fields.

    Every active walker takes its step simultaneously: 4 neighbour
    gathers, first `NEIGHBORS` match at d-1 (the shared tie-break),
    advance, emit.  Start cells are not emitted (callers emit target and
    blocked-entry cells themselves).  Returns concatenated (lane,
    canvas idx) of stepped-to cells."""
    offs = strides(stride)
    cur, d, who = start.copy(), steps.copy(), np.asarray(lanes).copy()
    out_l: list[np.ndarray] = []
    out_c: list[np.ndarray] = []
    act = d > 0
    cur, d, who = cur[act], d[act], who[act]
    while d.size:
        nbr = dist[who[:, None], cur[:, None] + offs[None, :]]
        sel = np.argmax(nbr == (d - 1)[:, None], axis=1)
        cur = cur + offs[sel]
        out_l.append(who.copy())
        out_c.append(cur.copy())
        d = d - 1
        act = d > 0
        if not act.all():
            cur, d, who = cur[act], d[act], who[act]
    if not out_l:
        return (np.zeros(0, np.int64), np.zeros(0, np.int64))
    return np.concatenate(out_l), np.concatenate(out_c)


def _group_cells(lanes: np.ndarray, cells: np.ndarray, n_lanes: int):
    """Split concatenated (lane, cell) emissions into per-lane arrays."""
    order = np.argsort(lanes, kind="stable")
    lanes, cells = lanes[order], cells[order]
    bounds = np.searchsorted(lanes, np.arange(n_lanes + 1))
    return [cells[bounds[k]:bounds[k + 1]] for k in range(n_lanes)]


def _bbox_overlap(a, b) -> bool:
    return bool(a[0] <= b[2] and b[0] <= a[2]
                and a[1] <= b[3] and b[1] <= a[3])


def _concurrent_route(nets: NetBatch, grids: np.ndarray, occ0: np.ndarray,
                      *, capacity: int, record: bool = False):
    """Route every net of every spec, conflict-aware (see section comment).

    nets: numpy `NetBatch`; occ0: (B, Gh, Gw) int32 with out-of-grid
    cells pre-blocked at `capacity`.  Returns (occ, routed, failed,
    wirelength, rounds, collisions, schedule)."""
    hubs, tgts = np.asarray(nets.hubs), np.asarray(nets.tgts)
    tmask, nmask = np.asarray(nets.tmask), np.asarray(nets.nmask)
    bsz = nmask.shape[0]
    gh, gw = occ0.shape[1:]
    stride = gw + 2
    occ = occ0.copy()
    occ_flat = occ.reshape(bsz, -1)
    offs = strides(stride)

    # Expanded bounding boxes: hub + valid targets, one-cell margin for
    # the blocked-destination entry step.
    py = np.concatenate([hubs[:, :, None, 0],
                         np.where(tmask, tgts[..., 0], hubs[:, :, None, 0])],
                        axis=2)
    px = np.concatenate([hubs[:, :, None, 1],
                         np.where(tmask, tgts[..., 1], hubs[:, :, None, 1])],
                        axis=2)
    bbox = np.stack([py.min(2) - 1, px.min(2) - 1,
                     py.max(2) + 1, px.max(2) + 1], axis=-1)

    pend = [collections.deque(np.nonzero(nmask[b])[0].tolist())
            for b in range(bsz)]
    # In-grid blocked cells per spec (normally grows from empty as
    # commits cross capacity); Manhattan distance from a lane's hub to
    # this set decides closed-form vs frontier expansion per lane.
    blk_yx: list[list[np.ndarray]] = []
    for b in range(bsz):
        by, bx = np.nonzero(occ[b, :grids[b, 0], :grids[b, 1]] >= capacity)
        blk_yx.append([by.astype(np.int64), bx.astype(np.int64)])
    crossed = [bool(blk_yx[b][0].size) for b in range(bsz)]
    routed = np.zeros(bsz, np.int32)
    failed = np.zeros(bsz, np.int32)
    wirelen = np.zeros(bsz, np.int32)
    buffer: dict[tuple[int, int], _Buffered] = {}
    schedule = RouteSchedule([], bbox) if record else None
    rounds = collisions = crossings = 0

    while any(pend):
        rounds += 1
        # ---- color: greedy bbox-disjoint picks over pending, slot order
        man_lanes: list[tuple[int, int]] = []
        bfs_lanes: list[tuple[int, int]] = []
        for b in range(bsz):
            chosen: list[np.ndarray] = []
            picked: list[tuple[int, int]] = []
            for s in pend[b]:
                if (b, s) in buffer:
                    continue
                bb = bbox[b, s]
                if any(_bbox_overlap(bb, c) for c in chosen):
                    # Slots past a conflict cannot commit this round
                    # (commits are in slot order), so computing them now
                    # would be speculative work that the next crossing
                    # would likely throw away — stop the scan here.
                    break
                chosen.append(bb)
                picked.append((b, s))
            if not picked:
                continue
            if not crossed[b]:
                man_lanes.extend(picked)
                continue
            # Crossed spec: a lane whose farthest target (Manhattan) is
            # no farther than the nearest blocked cell never reads a
            # cell the obstacles can shadow (same bound as
            # `_still_valid`), so its field is still closed-form; only
            # the rest pay a frontier expansion.
            ps = np.array([s for _, s in picked])
            hy, hx = hubs[b, ps, 0], hubs[b, ps, 1]
            d0 = (np.abs(tgts[b, ps, :, 0] - hy[:, None])
                  + np.abs(tgts[b, ps, :, 1] - hx[:, None]))
            d0max = np.where(tmask[b, ps], d0, -1).max(1)
            by, bx = blk_yx[b]
            blkmin = (np.abs(by[None, :] - hy[:, None])
                      + np.abs(bx[None, :] - hx[:, None])).min(1)
            for k, lane in enumerate(picked):
                (man_lanes if d0max[k] <= blkmin[k]
                 else bfs_lanes).append(lane)
        if schedule is not None:
            schedule.dispatches.append(man_lanes + bfs_lanes)

        # ---- expand: closed-form fields for still-obstacle-free specs
        if man_lanes:
            lb = np.array([b for b, _ in man_lanes])
            ls = np.array([s for _, s in man_lanes])
            hy, hx = hubs[lb, ls, 0], hubs[lb, ls, 1]
            t_y, t_x = tgts[lb, ls, :, 0], tgts[lb, ls, :, 1]
            tm = tmask[lb, ls]
            d0 = np.abs(t_y - hy[:, None]) + np.abs(t_x - hx[:, None])
            wk, wj = np.nonzero(tm)
            wl_l, wys, wxs = _manhattan_paths(
                wk, hy[wk], hx[wk], t_y[wk, wj], t_x[wk, wj])
            per_lane = _group_cells(wl_l, wys * gw + wxs, len(man_lanes))
            for k, (b, s) in enumerate(man_lanes):
                dk = d0[k][tm[k]]
                buffer[(b, s)] = _Buffered(
                    cells=per_lane[k], wl=int((dk + 1).sum()), ok=True,
                    d0max=int(dk.max()) if dk.size else -1,
                    dist=None, hub=(int(hy[k]), int(hx[k])))

        # ---- expand: bucketed frontier wavefronts, early-exit on targets
        fresh: list[tuple[int, int]] = []
        if bfs_lanes:
            lb = np.array([b for b, _ in bfs_lanes])
            ls = np.array([s for _, s in bfs_lanes])
            nlan = len(bfs_lanes)
            karr = np.arange(nlan, dtype=np.int64)
            occ_l = occ[lb] >= capacity
            free = canvas_free(occ_l)
            dist = np.full((nlan, (gh + 2) * stride), INF, np.int32)
            hy, hx = hubs[lb, ls, 0], hubs[lb, ls, 1]
            sidx = canvas_index(hy, hx, stride)
            dist[karr, sidx] = 0
            t_y, t_x = tgts[lb, ls, :, 0], tgts[lb, ls, :, 1]
            tm = tmask[lb, ls]
            tciv = canvas_index(t_y, t_x, stride)
            tb = occ_l.reshape(nlan, -1)[karr[:, None], t_y * gw + t_x] & tm

            def resolved():
                res = dist[karr[:, None], tciv] < INF
                if tb.any():
                    ndv = dist[karr[:, None, None],
                               tciv[:, :, None] + offs[None, None, :]]
                    res = res | (tb & (ndv < INF).any(-1))
                return (res | ~tm).all(1)

            expand_buckets(free, dist, karr, sidx, stride, resolved)

            dv = dist[karr[:, None], tciv].astype(np.int64)
            ndv = dist[karr[:, None, None],
                       tciv[:, :, None] + offs[None, None, :]]
            nmin = ndv.min(-1).astype(np.int64)
            d0 = np.where(dv < INF, dv, np.minimum(nmin + 1, INF))
            run = tm & (d0 < INF)
            okl = (run | ~tm).all(1)
            blkt = run & (dv >= INF)
            esel = np.argmax(ndv == (d0 - 1)[:, :, None], axis=2)
            entry = tciv + offs[esel]
            start = np.where(blkt, entry, tciv)
            dstart = np.where(blkt, d0 - 1, d0)
            wk, wj = np.nonzero(run & okl[:, None])
            bw = blkt[wk, wj]
            sl, sc = _walk_paths(dist, wk, start[wk, wj], dstart[wk, wj],
                                 stride)
            lanes_all = np.concatenate([wk, wk[bw], sl])
            cidx_all = np.concatenate([tciv[wk, wj], entry[wk, wj][bw], sc])
            cells_all = ((cidx_all // stride - 1) * gw
                         + (cidx_all % stride - 1))
            per_lane = _group_cells(lanes_all, cells_all, nlan)
            for k, (b, s) in enumerate(bfs_lanes):
                dk = d0[k][run[k]]
                buffer[(b, s)] = _Buffered(
                    cells=per_lane[k],
                    wl=int((dk + 1).sum()) if okl[k] else 0,
                    ok=bool(okl[k]),
                    d0max=int(dk.max()) if (okl[k] and dk.size) else -1,
                    dist=dist[k], hub=None)
                fresh.append((b, s))

        # ---- commit: strictly in slot order, collision-test on crossings
        for b in range(bsz):
            while pend[b] and (b, pend[b][0]) in buffer:
                s = pend[b].popleft()
                e = buffer.pop((b, s))
                if not e.ok:
                    failed[b] += 1
                    continue
                routed[b] += 1
                wirelen[b] += e.wl
                uc, cnt = np.unique(e.cells, return_counts=True)
                pre = occ_flat[b, uc]
                occ_flat[b, uc] = pre + cnt
                newly = uc[(pre < capacity) & (pre + cnt >= capacity)]
                if newly.size:
                    crossings += 1
                    crossed[b] = True
                    ys, xs = newly // gw, newly % gw
                    blk_yx[b][0] = np.concatenate([blk_yx[b][0], ys])
                    blk_yx[b][1] = np.concatenate([blk_yx[b][1], xs])
                    for key in [k for k in buffer if k[0] == b]:
                        if not _still_valid(buffer[key], ys, xs, stride):
                            del buffer[key]
                            collisions += 1

        # Surviving frontier fields are views into this round's batch
        # array; copy them out so the batch can be freed.
        for key in fresh:
            if key in buffer and buffer[key].dist is not None:
                buffer[key].dist = buffer[key].dist.copy()

    if schedule is not None:
        schedule.rounds = rounds
        schedule.collisions = collisions
        schedule.crossings = crossings
    return occ, routed, failed, wirelen, rounds, collisions, schedule


class BatchedRouting(NamedTuple):
    routed: np.ndarray          # (B,) int32 — successfully routed nets
    failed: np.ndarray          # (B,) int32
    wirelength: np.ndarray      # (B,) int32 — total path points
    occ_count: np.ndarray       # (B, Gh, Gw) int32 congestion map
    grids: np.ndarray           # (B, 2) per-spec (gh, gw)
    engine: str = "scan"        # "scan" (lax.scan slots) | "concurrent"
    rounds: int = 0             # wavefront dispatch rounds taken
    collisions: int = 0         # buffered routes dropped by a crossing
    schedule: RouteSchedule | None = None
    # Pallas kernel counters, summed over the net slots; None where no
    # kernel ran (the concurrent engine, or the scan over the ref)
    sweeps: np.ndarray | None = None      # (B,) relaxation sweeps run
    goal_stops: np.ndarray | None = None  # (B,) stopped on their targets
    walk_steps: np.ndarray | None = None  # (B,) backtrace steps walked

    @property
    def success_rate(self) -> np.ndarray:
        n = self.routed + self.failed
        return np.where(n > 0, self.routed / np.maximum(n, 1), 1.0)


def batched_route(nets: NetBatch, widths: np.ndarray, heights: np.ndarray,
                  *, coarse: int = 64, capacity: int = 4,
                  use_kernel: bool | None = None,
                  engine: str | None = None,
                  record_schedule: bool = False) -> BatchedRouting:
    """Drive the batched wavefront routing over all specs.

    engine: "concurrent" (conflict-aware host scheduler over frontier
    buckets — the default off-TPU), "scan" (one `lax.scan` wavefront per
    net slot; the default on TPU, where the Pallas kernel batches the
    grids, and whenever `use_kernel` forces a device impl), or None for
    that auto choice.  Both engines produce identical results — the
    concurrent engine is proven and tested against the scan engine and
    the sequential router, not an approximation of them.

    Cells beyond a spec's own routing grid are pre-blocked, so padding a
    small spec up to the batch-max grid cannot open new paths."""
    bsz = len(widths)
    grids = np.array([grid_shape(int(w), int(h), coarse)
                      for w, h in zip(widths, heights)], np.int64)
    gh_max, gw_max = int(grids[:, 0].max()), int(grids[:, 1].max())
    iy = np.arange(gh_max)[None, :, None]
    ix = np.arange(gw_max)[None, None, :]
    blocked = ((iy >= grids[:, 0, None, None])
               | (ix >= grids[:, 1, None, None]))
    occ0_np = np.where(blocked, capacity, 0).astype(np.int32)
    if engine is None:
        engine = ("scan" if use_kernel or jax.default_backend() == "tpu"
                  else "concurrent")
    if engine == "concurrent":
        nets_np = NetBatch(*(np.asarray(a) for a in nets))
        occ, routed, failed, wirelen, rounds, collisions, sched = \
            _concurrent_route(nets_np, grids, occ0_np, capacity=capacity,
                              record=record_schedule)
        occ_np = np.where(blocked, 0, occ).astype(np.int32)
        return BatchedRouting(routed, failed, wirelen, occ_np, grids,
                              "concurrent", rounds, collisions, sched)
    if engine != "scan":
        raise ValueError(f"engine must be 'scan' or 'concurrent', "
                         f"got {engine!r}")
    occ, routed, failed, wirelen, stats = jax.device_get(_route_program(
        jnp.asarray(occ0_np), nets, capacity=capacity, use_kernel=use_kernel))
    occ_np = np.where(blocked, 0, occ).astype(np.int32)
    sweeps, goal_stops, walk_steps = (stats if stats is not None
                                      else (None, None, None))
    return BatchedRouting(routed, failed, wirelen, occ_np, grids,
                          "scan", int(nets.nmask.shape[1]), 0, None,
                          sweeps, goal_stops, walk_steps)


# ----------------------------------------------------------------------
# The end-to-end batched flow
# ----------------------------------------------------------------------
@dataclasses.dataclass
class BatchedLayoutResult:
    """Layouts for a whole spec batch, in padded tensor form.

    Mirrors `flow.LayoutResult` per spec (`metrics_rows` carries the same
    keys minus the wall-clock; `placements()` / `drc_reports()` unpack to
    the sequential dataclasses).  Wire point lists are not materialized —
    the routing stats and the congestion map (`routing.occ_count`) are;
    use the sequential `flow.generate_layout` when full wire geometry is
    needed (e.g. for GDS-like JSON export of a single chosen design
    point).  Timing is a caller concern: `repro.api.DesignSession`
    reports it in the artifact provenance, benchmarks time around the
    call — the library path itself stays clock-free.
    """

    specs: tuple[MacroSpec, ...]
    dims: BatchDims
    geom: PlacerGeometry
    ops: LayoutOperands
    tensors: dict
    routing: BatchedRouting
    drc_overlaps: np.ndarray
    drc_oob: np.ndarray
    netlist_stats: list[dict]

    def __len__(self) -> int:
        return len(self.specs)

    @property
    def widths(self) -> np.ndarray:
        return np.asarray(self.ops.width)

    @property
    def heights(self) -> np.ndarray:
        return np.asarray(self.ops.height)

    @property
    def drc_clean(self) -> np.ndarray:
        return (self.drc_overlaps == 0) & (self.drc_oob == 0)

    def drc_reports(self) -> list[DRCReport]:
        return [DRCReport(int(o), int(b))
                for o, b in zip(self.drc_overlaps, self.drc_oob)]

    def placements(self) -> list[Placement]:
        """Unpack per-spec named `Placement`s (host-side, for interop)."""
        out = []
        np_tensors = {c: (np.asarray(r), np.asarray(m))
                      for c, (r, m) in self.tensors.items()}
        for i, spec in enumerate(self.specs):
            exact = dims_for_spec(spec)
            rects: list[Placed] = []
            for cat in CATEGORIES:
                vals, mask = np_tensors[cat]
                vals = vals[i].reshape(-1, 4)[mask[i].reshape(-1)]
                cell = CATEGORY_CELL[cat]
                rects.extend(
                    Placed(name, cell, *map(int, xywh)) for name, xywh
                    in zip(category_names(cat, exact, spec), vals))
            out.append(Placement(spec, rects, int(self.widths[i]),
                                 int(self.heights[i])))
        return out

    def metrics_rows(self) -> list[dict]:
        """Per-spec metrics: the pure-content keys of
        `LayoutResult.metrics` (no `elapsed_s` — rows are identical for
        a spec regardless of what batch it rode in)."""
        h = np.array([s.h for s in self.specs], np.float32)
        l = np.array([s.l for s in self.specs], np.float32)
        b = np.array([s.b_adc for s in self.specs], np.float32)
        est = np.asarray(estimator.area_f2_per_bit(h, l, b))
        area = (self.widths.astype(np.float64) * self.heights
                / np.array([s.array_size for s in self.specs]))
        succ = self.routing.success_rate
        rows = []
        for i, s in enumerate(self.specs):
            rows.append({
                "h": s.h, "w": s.w, "l": s.l, "b_adc": s.b_adc,
                "layout_area_f2_per_bit": float(area[i]),
                "estimator_area_f2_per_bit": float(est[i]),
                "area_model_error": float(area[i] / est[i] - 1.0),
                "routed_nets": int(self.routing.routed[i]),
                "failed_nets": int(self.routing.failed[i]),
                "route_success": float(succ[i]),
                "wirelength": int(self.routing.wirelength[i]),
                "drc_clean": bool(self.drc_clean[i]),
            })
        return rows

    def to_json(self, path: str) -> None:
        import json

        with open(path, "w") as f:
            json.dump({"specs": [s.as_tuple() for s in self.specs],
                       "points": self.metrics_rows()}, f, indent=1)


def iter_layout_buckets(buckets, *, use_kernel: bool | None = None,
                        engine: str | None = None):
    """Stream a sequence of layout buckets through the batched flow.

    `buckets` is an iterable of `(specs, coarse, capacity)` triples —
    one routing-grid-shape bucket each (see the bucketing in
    `repro.api.session`).  Each bucket's `BatchedLayoutResult` is
    yielded as soon as its dispatch chain completes, so a consumer (the
    staged pipeline executor in `repro.serve.design_service`, or a
    plain `for` loop) can overlap downstream work — artifact
    finalization, the next batch's exploration — with the remaining
    buckets instead of blocking until the whole union is laid out.
    """
    for specs, coarse, capacity in buckets:
        yield generate_layouts(specs, coarse=coarse, capacity=capacity,
                               use_kernel=use_kernel, engine=engine)


def generate_layouts(specs, *, coarse: int = 64, capacity: int = 4,
                     use_kernel: bool | None = None,
                     engine: str | None = None) -> BatchedLayoutResult:
    """Lay out a whole (e.g. Pareto-distilled) spec batch at once.

    Equivalent per spec to calling `flow.generate_layout` B times, but
    placement/DRC/net derivation are single vmapped dispatches and
    routing expands all B wavefronts together.
    """
    specs = tuple(specs)
    if not specs:
        raise ValueError("generate_layouts needs at least one MacroSpec")
    n = len(specs)
    with trace_span("prepare", cat="layout", specs=n):
        geom = geometry()
        dims = BatchDims.for_specs(specs)
        ops = stack_layout_operands(specs, geom)
    with trace_span("place_drc_nets", cat="layout", specs=n):
        tensors = _place_program(ops, dims=dims, geom=geom)
        overlaps, oob = _drc_program(tensors, ops, dims=dims, geom=geom)
        nets = _nets_program(tensors, ops, dims=dims, geom=geom,
                             coarse=coarse)
    with trace_span("route", cat="layout", specs=n):
        routing = batched_route(nets, np.asarray(ops.width),
                                np.asarray(ops.height), coarse=coarse,
                                capacity=capacity, use_kernel=use_kernel,
                                engine=engine)
    with trace_span("netlist_stats", cat="layout", specs=n):
        stats = [nl_mod.stats_for_spec(s) for s in specs]
    return BatchedLayoutResult(
        specs=specs, dims=dims, geom=geom, ops=ops, tensors=tensors,
        routing=routing, drc_overlaps=np.asarray(overlaps),
        drc_oob=np.asarray(oob), netlist_stats=stats)
