"""Shared property suite for every maze_route wavefront implementation.

The dispatch contract of `repro.kernels.maze_route.ops` promises four
bit-identical engines behind `wavefront_distance`:

  impl="bfs"       pure-Python deque BFS (the readable oracle)
  impl="ref"       jitted jnp fast-sweeping reference
  impl="kernel"    grid-batched Pallas Jacobi kernel (interpret off-TPU)
  impl="frontier"  host numpy frontier-bucketed engine

This file pins all four to each other on randomized grids (varied
shapes, obstacle density, multiple seeds) and on the adversarial edges:
fully-blocked grids, seeds sitting on obstacles (hub exception), empty
seed masks, and — for the Pallas path — grids straddling the TPU tile
boundary, where `ops.pad_blocked` must keep the pad region out of the
sweep (a free pad would let wavefronts tunnel around the real grid's
edge; see the pad-boundary regression class below).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.maze_route import (INF, goal_wavefront, wavefront_distance,
                                      wavefront_distance_bfs)
from repro.kernels.maze_route.kernel import SWEEPS_PER_CHECK
from repro.kernels.maze_route.ops import HOST_IMPLS, IMPLS

# The kernel pads to (8, 128) tiles and relaxes the full padded grid per
# Jacobi sweep — fine at test sizes, but each extra case costs real time
# under interpret mode, so the random sweeps keep H, W modest.
ALL_IMPLS = IMPLS


def _field(occ, seed, impl):
    return np.asarray(wavefront_distance(occ, seed, impl=impl))


def _assert_all_impls_match(occ, seed):
    """Every impl must equal the deque-BFS oracle exactly."""
    oracle = wavefront_distance_bfs(occ, seed)
    for impl in ALL_IMPLS:
        np.testing.assert_array_equal(
            _field(occ, seed, impl), oracle,
            err_msg=f"impl={impl!r} diverges from the BFS oracle")


def _random_case(rng, h, w, density, n_seeds):
    occ = rng.random((h, w)) < density
    seed = np.zeros((h, w), bool)
    flat = rng.choice(h * w, size=min(n_seeds, h * w), replace=False)
    seed[flat // w, flat % w] = True
    return occ, seed


class TestFourWayEquality:
    @pytest.mark.parametrize("case", range(12))
    def test_randomized_grids(self, case):
        rng = np.random.default_rng(1000 + case)
        h = int(rng.integers(2, 20))
        w = int(rng.integers(2, 24))
        density = float(rng.uniform(0.0, 0.65))
        n_seeds = int(rng.integers(1, 4))
        occ, seed = _random_case(rng, h, w, density, n_seeds)
        _assert_all_impls_match(occ, seed)

    def test_batched_grids(self):
        rng = np.random.default_rng(7)
        occ = rng.random((3, 9, 13)) < 0.3
        seed = np.zeros((3, 9, 13), bool)
        for b in range(3):
            seed[b, rng.integers(0, 9), rng.integers(0, 13)] = True
        oracle = wavefront_distance_bfs(occ, seed)
        for impl in ALL_IMPLS:
            np.testing.assert_array_equal(_field(occ, seed, impl), oracle)

    def test_fully_blocked_grid(self):
        occ = np.ones((6, 11), bool)
        seed = np.zeros((6, 11), bool)
        seed[2, 3] = True
        oracle = wavefront_distance_bfs(occ, seed)
        # The hub exception: a seed is distance 0 even when occupied,
        # but nothing expands out of it into blocked cells.
        assert oracle[2, 3] == 0
        assert (oracle == INF).sum() == 6 * 11 - 1
        _assert_all_impls_match(occ, seed)

    def test_seed_on_obstacle_does_not_expand_neighbours_through_it(self):
        # Seed on a blocked cell in a corridor: the seed itself reads 0,
        # but its free neighbours are still reached *around* it only.
        occ = np.zeros((3, 7), bool)
        occ[1, 3] = True
        seed = np.zeros((3, 7), bool)
        seed[1, 3] = True
        oracle = wavefront_distance_bfs(occ, seed)
        assert oracle[1, 3] == 0
        assert oracle[1, 2] == 1 and oracle[1, 4] == 1
        _assert_all_impls_match(occ, seed)

    def test_empty_seed_mask_is_all_inf(self):
        occ = np.zeros((5, 9), bool)
        seed = np.zeros((5, 9), bool)
        for impl in ALL_IMPLS:
            assert (_field(occ, seed, impl) == INF).all()

    def test_disconnected_components(self):
        occ = np.zeros((7, 7), bool)
        occ[:, 3] = True                      # full wall
        seed = np.zeros((7, 7), bool)
        seed[3, 0] = True
        oracle = wavefront_distance_bfs(occ, seed)
        assert (oracle[:, 4:] == INF).all()   # far side unreachable
        _assert_all_impls_match(occ, seed)


class TestPadBoundaryRegression:
    """`ops.pad_blocked` pads to (8, 128) tiles with *blocked* cells.

    These shapes straddle the tile boundary in every direction; if the
    pad region were free (or merely left out of the masking), a seed on
    the real grid's edge would leak a wavefront into the pad and around
    obstacles, producing finite distances where the oracle says INF and
    short-circuiting distances along the boundary rows/columns.
    """
    SHAPES = [(8, 128), (7, 128), (9, 128), (8, 127), (8, 129), (9, 129)]

    @pytest.mark.parametrize("shape", SHAPES)
    def test_kernel_matches_oracle_at_tile_boundary(self, shape):
        h, w = shape
        rng = np.random.default_rng(h * 1000 + w)
        occ = rng.random((h, w)) < 0.25
        seed = np.zeros((h, w), bool)
        seed[h - 1, w - 1] = True             # seed on the pad boundary
        oracle = wavefront_distance_bfs(occ, seed)
        np.testing.assert_array_equal(_field(occ, seed, "kernel"), oracle)
        np.testing.assert_array_equal(_field(occ, seed, "frontier"), oracle)

    def test_wavefront_cannot_tunnel_through_pad(self):
        # A wall along the last real column, broken nowhere: cells past
        # it must be unreachable even though the pad region lies just
        # beyond the wall and would offer a bypass if traversable.
        h, w = 8, 126                         # pads to (8, 128): 2 pad cols
        occ = np.zeros((h, w), bool)
        occ[:, w - 2] = True
        seed = np.zeros((h, w), bool)
        seed[4, 0] = True
        for impl in ALL_IMPLS:
            out = _field(occ, seed, impl)
            assert (out[:, w - 1] == INF).all(), \
                f"impl={impl!r} tunnelled around the wall via the pad"

    def test_edge_seed_distances_exact_on_padded_rows(self):
        # Free grid, seed in a corner: distances along the padded edge
        # rows/cols are pure Manhattan — any pad participation would
        # only ever show up here first.
        h, w = 9, 127
        occ = np.zeros((h, w), bool)
        seed = np.zeros((h, w), bool)
        seed[0, 0] = True
        yy, xx = np.mgrid[:h, :w]
        manhattan = (yy + xx).astype(np.int64)
        for impl in ALL_IMPLS:
            np.testing.assert_array_equal(_field(occ, seed, impl), manhattan)


class TestDispatchContract:
    def test_unknown_impl_rejected(self):
        occ = np.zeros((4, 4), bool)
        seed = np.zeros((4, 4), bool)
        seed[0, 0] = True
        with pytest.raises(ValueError, match="impl must be one of"):
            wavefront_distance(occ, seed, impl="dijkstra")

    @pytest.mark.parametrize("impl", HOST_IMPLS)
    def test_host_impls_refuse_tracing(self, impl):
        @jax.jit
        def traced(occ, seed):
            return wavefront_distance(occ, seed, impl=impl)

        occ = jnp.zeros((4, 4), bool)
        seed = jnp.zeros((4, 4), bool).at[0, 0].set(True)
        with pytest.raises(TypeError, match="host engine"):
            traced(occ, seed)

    def test_host_default_is_frontier_and_returns_numpy(self):
        # Concrete arrays off-TPU dispatch to the frontier engine, which
        # returns numpy (callers read the field on host).
        if jax.default_backend() == "tpu":
            pytest.skip("host dispatch path is the off-TPU default")
        occ = np.zeros((5, 6), bool)
        seed = np.zeros((5, 6), bool)
        seed[2, 2] = True
        out = wavefront_distance(occ, seed)
        assert isinstance(out, np.ndarray)
        np.testing.assert_array_equal(out, wavefront_distance_bfs(occ, seed))


def _reach(full, y, x):
    """The sweep at which a goal is resolved: its distance if free, one
    past its nearest neighbour's if blocked (`router.target_distance`);
    `INF` if it cannot be reached."""
    if full[y, x] < INF:
        return int(full[y, x])
    h, w = full.shape
    nb = [full[y + dy, x + dx] for dy, dx in ((1, 0), (-1, 0), (0, 1),
                                              (0, -1))
          if 0 <= y + dy < h and 0 <= x + dx < w]
    m = min(nb, default=INF)
    return int(m) + 1 if m < INF else INF


def _expected_stop(full, seed, goals):
    """(sweeps, goal_stopped) the stop rule must give one grid: the first
    test (every `SWEEPS_PER_CHECK` sweeps) at which every goal is
    resolved, or at which the block's last sweep changed nothing."""
    p = SWEEPS_PER_CHECK
    if not seed.any():
        return 0, 0
    top = int(full[full < INF].max())        # the last sweep that changes
    fixed = p * -(-(top + 1) // p)
    live = [(y, x) for y, x in goals if y >= 0]
    last = max((_reach(full, y, x) for y, x in live), default=0)
    if last >= INF:
        return fixed, 0
    at = p * max(1, -(-last // p))
    return min(at, fixed), int(at < fixed)


class TestGoalStop:
    """`goal_wavefront` stops each grid's Jacobi wavefront once its goal
    cells are resolved.  By the k-sweep invariant the field is then the
    BFS field on every cell at distance <= the sweeps run, and `INF` on
    every other; the sweeps and the stop are exactly what the rule
    says."""

    def _check(self, occ, seed, goals):
        full = wavefront_distance_bfs(occ, seed)
        dist, sweeps, stopped = (np.asarray(a) for a in
                                 goal_wavefront(occ, seed, goals))
        for b in range(occ.shape[0]):
            near = full[b] <= sweeps[b]
            np.testing.assert_array_equal(dist[b][near], full[b][near])
            assert (dist[b][~near] == INF).all()
            assert (sweeps[b], stopped[b]) == _expected_stop(
                full[b], seed[b], goals[b])
            for y, x in goals[b]:
                if y >= 0:       # every goal reads as on the full field
                    assert _reach(dist[b], y, x) == _reach(full[b], y, x)
        return full, dist, sweeps, stopped

    @pytest.mark.parametrize("case", range(6))
    def test_randomized_goals(self, case):
        rng = np.random.default_rng(2000 + case)
        b, h, w = 3, int(rng.integers(3, 18)), int(rng.integers(3, 26))
        occ = rng.random((b, h, w)) < float(rng.uniform(0.0, 0.4))
        seed = np.zeros((b, h, w), bool)
        goals = np.full((b, 2, 2), -1, np.int32)
        for i in range(b):
            seed[i, rng.integers(h), rng.integers(w)] = True
            for k in range(2):
                if k == 0 or rng.random() < 0.7:
                    goals[i, k] = rng.integers(h), rng.integers(w)
        self._check(occ, seed, goals)

    def test_blocked_goal_entered_from_final_neighbours(self):
        # the goal sits on a blocked cell: it resolves when a neighbour
        # turns finite, and the neighbours at its entry distance are
        # final then (the backtrace's entry tie-break reads them)
        occ = np.zeros((1, 7, 9), bool)
        occ[0, 3, 5] = True
        seed = np.zeros_like(occ)
        seed[0, 3, 0] = True
        goals = np.array([[[3, 5], [-1, -1]]], np.int32)
        full, dist, sweeps, stopped = self._check(occ, seed, goals)
        assert full[0, 3, 5] == INF and _reach(full[0], 3, 5) == 5
        assert (sweeps[0], stopped[0]) == (8, 1)
        nb = [(4, 5), (2, 5), (3, 6), (3, 4)]      # NEIGHBORS order
        assert [dist[0][c] for c in nb] == [full[0][c] for c in nb]

    def test_unreachable_goal_runs_to_the_fixed_point(self):
        occ = np.zeros((2, 6, 9), bool)
        occ[:, :, 4] = True                        # a full wall
        seed = np.zeros_like(occ)
        seed[:, 2, 0] = True
        goals = np.array([[[2, 7], [-1, -1]],      # beyond the wall
                          [[2, 2], [3, 8]]], np.int32)
        full, dist, sweeps, stopped = self._check(occ, seed, goals)
        np.testing.assert_array_equal(dist, full)
        assert list(stopped) == [0, 0]

    def test_hand_counted_corridor(self):
        assert SWEEPS_PER_CHECK == 4       # the counts below assume it
        occ = np.zeros((4, 2, 10), bool)
        seed = np.zeros_like(occ)
        seed[:3, 0, 0] = True              # grid 3 has no seed
        goals = np.array([[[0, 5], [-1, -1]],    # distance 5: 2 tests
                          [[0, 0], [-1, -1]],    # on the seed: 1 test
                          [[1, 9], [0, 9]],      # the far corner, 10:
                          [[0, 5], [-1, -1]]],   # ...the fixed point
                         np.int32)
        _, dist, sweeps, stopped = self._check(occ, seed, goals)
        assert list(sweeps) == [8, 4, 12, 0]
        assert list(stopped) == [1, 1, 0, 0]
        assert dist[0, 0, 8] == 8 and dist[0, 1, 8] == INF
        assert (dist[3] == INF).all()
