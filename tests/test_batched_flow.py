"""Batched layout flow vs the sequential per-spec path.

The contract (asserted per spec): identical placed rectangles, identical
DRC verdict, identical routed/failed counts and wirelength — the batched
path is the sequential path, vectorized, not an approximation of it.
"""
import numpy as np
import pytest

from repro.api import DesignRequest, DesignSession, Requirements
from repro.core.acim_spec import MacroSpec
from repro.eda import netlist as nl, router
from repro.eda.batched_flow import (NetBatch, _Buffered, _bbox_overlap,
                                    _concurrent_route, _nets_program,
                                    _place_program, _still_valid,
                                    batched_route, generate_layouts,
                                    stack_layout_operands)
from repro.eda.flow import generate_layout
from repro.eda.placer import BatchDims, geometry
from repro.kernels.maze_route import wavefront_distance_bfs
from repro.kernels.maze_route.frontier import canvas_index

# Mixed extents on purpose: every BatchDims axis gets real padding.
SPECS = (MacroSpec(64, 16, 2, 3), MacroSpec(128, 32, 4, 3),
         MacroSpec(256, 16, 8, 3), MacroSpec(128, 8, 4, 2),
         MacroSpec(64, 8, 2, 5))


@pytest.fixture(scope="module")
def results():
    return generate_layouts(SPECS), [generate_layout(s) for s in SPECS]


class TestEquivalence:
    def test_same_rects_per_spec(self, results):
        bat, seq = results
        for i, lr in enumerate(seq):
            rb = {(r.name, r.cell, r.x, r.y, r.w, r.h)
                  for r in bat.placements()[i].rects}
            rs = {(r.name, r.cell, r.x, r.y, r.w, r.h)
                  for r in lr.placement.rects}
            assert rb == rs, SPECS[i]

    def test_same_drc_verdict_per_spec(self, results):
        bat, seq = results
        for i, lr in enumerate(seq):
            assert int(bat.drc_overlaps[i]) == lr.drc.overlaps
            assert int(bat.drc_oob[i]) == lr.drc.out_of_bounds
            assert bool(bat.drc_clean[i]) == lr.drc.clean
            assert bat.drc_reports()[i] == lr.drc

    def test_same_routing_per_spec(self, results):
        bat, seq = results
        for i, lr in enumerate(seq):
            assert int(bat.routing.routed[i]) == len(lr.routing.wires)
            assert int(bat.routing.failed[i]) == len(lr.routing.failed)
            assert (int(bat.routing.wirelength[i])
                    == lr.routing.total_wirelength)
            assert (float(bat.routing.success_rate[i])
                    == lr.routing.success_rate)

    def test_metrics_rows_match(self, results):
        bat, seq = results
        for row, lr in zip(bat.metrics_rows(), seq):
            m = lr.metrics()
            # batched rows are pure content: no wall-clock key
            assert set(row) == set(m) - {"elapsed_s"}
            for k in ("h", "w", "l", "b_adc", "routed_nets", "failed_nets",
                      "route_success", "wirelength", "drc_clean"):
                assert row[k] == m[k], k
            for k in ("layout_area_f2_per_bit", "estimator_area_f2_per_bit",
                      "area_model_error"):
                assert row[k] == pytest.approx(m[k]), k

    def test_netlist_stats_closed_form(self, results):
        bat, seq = results
        for i, lr in enumerate(seq):
            assert bat.netlist_stats[i] == lr.netlist_stats
            assert nl.stats_for_spec(SPECS[i]) == lr.netlist_stats


class TestBatchedPlacement:
    def test_operand_stack_shape(self):
        ops = stack_layout_operands(SPECS, geometry())
        for leaf in ops:
            assert leaf.shape == (len(SPECS),)

    def test_batch_dims_are_maxima(self):
        d = BatchDims.for_specs(SPECS)
        assert d.w == max(s.w for s in SPECS)
        assert d.n_la == max(s.n_caps for s in SPECS)
        assert d.l == max(s.l for s in SPECS)
        assert d.b == max(s.b_adc for s in SPECS)

    def test_single_spec_batch_matches_sequential(self):
        spec = MacroSpec(64, 16, 2, 3)
        bat = generate_layouts([spec])
        lr = generate_layout(spec)
        assert len(bat) == 1
        row = bat.metrics_rows()[0]
        m = lr.metrics()
        assert row["wirelength"] == m["wirelength"]
        assert row["drc_clean"] and m["drc_clean"]

    def test_congestion_map_totals_wirelength(self, results):
        bat, _ = results
        # every routed path point increments exactly one occupancy cell
        per_spec = bat.routing.occ_count.sum(axis=(1, 2))
        np.testing.assert_array_equal(per_spec, bat.routing.wirelength)

    def test_empty_batch_rejected(self):
        with pytest.raises(ValueError):
            generate_layouts([])


# ----------------------------------------------------------------------
# Conflict-aware concurrent scheduler
# ----------------------------------------------------------------------
def _grid_nets(slots, gh, gw):
    """Build a single-spec NetBatch from (hub, [targets]) grid-cell slots."""
    n = len(slots)
    hubs = np.zeros((1, n, 2), np.int32)
    tgts = np.zeros((1, n, 2, 2), np.int32)
    tmask = np.zeros((1, n, 2), bool)
    nmask = np.ones((1, n), bool)
    for s, (hub, targets) in enumerate(slots):
        hubs[0, s] = hub
        for j, t in enumerate(targets):
            tgts[0, s, j] = t
            tmask[0, s, j] = True
        for j in range(len(targets), 2):
            tgts[0, s, j] = hub
    return NetBatch(hubs, tgts, tmask, nmask)


def _sequential_reference(nets, gh, gw, capacity):
    """`router.route`'s occupancy evolution on grid-cell nets, slot order.

    Reuses the sequential router's own backtrace (tie-break included) so
    the comparison is against the real per-net semantics, not a re-model
    of them."""
    hubs, tgts, tmask, nmask = (np.asarray(a) for a in nets)
    occ_count = np.zeros((gh, gw), np.int32)
    routed = failed = wl = 0
    for s in range(nmask.shape[1]):
        if not nmask[0, s]:
            continue
        seed = np.zeros((gh, gw), bool)
        seed[tuple(hubs[0, s])] = True
        dist = wavefront_distance_bfs(occ_count >= capacity, seed)
        pts, ok = [], True
        for j in range(2):
            if not tmask[0, s, j]:
                continue
            path = router.backtrace(dist, tuple(tgts[0, s, j]))
            if path is None:
                ok = False
                break
            pts.extend(path)
        if ok:
            for y, x in pts:
                occ_count[y, x] += 1
            routed += 1
            wl += len(pts)
        else:
            failed += 1
    return routed, failed, wl, occ_count


def _run_concurrent(nets, gh, gw, capacity):
    grids = np.array([[gh, gw]], np.int64)
    occ0 = np.zeros((1, gh, gw), np.int32)
    return _concurrent_route(nets, grids, occ0, capacity=capacity,
                             record=True)


@pytest.fixture(scope="module")
def netbatch():
    """Real derived nets for SPECS, plus the spec extents."""
    geom = geometry()
    dims = BatchDims.for_specs(SPECS)
    ops = stack_layout_operands(SPECS, geom)
    tensors = _place_program(ops, dims=dims, geom=geom)
    nets = _nets_program(tensors, ops, dims=dims, geom=geom, coarse=64)
    return nets, np.asarray(ops.width), np.asarray(ops.height)


class TestConflictScheduler:
    def test_no_round_codispatches_overlapping_nets(self, netbatch):
        nets, w, h = netbatch
        res = batched_route(nets, w, h, engine="concurrent",
                            record_schedule=True)
        sched = res.schedule
        assert sched is not None and sched.rounds == res.rounds
        assert len(sched.dispatches) == sched.rounds
        checked = 0
        for lanes in sched.dispatches:
            per_spec: dict[int, list] = {}
            for b, s in lanes:
                per_spec.setdefault(b, []).append(sched.bboxes[b, s])
            for boxes in per_spec.values():
                for i in range(len(boxes)):
                    for j in range(i + 1, len(boxes)):
                        assert not _bbox_overlap(boxes[i], boxes[j])
                        checked += 1
        assert checked > 0          # the sweep actually batched something

    def test_identical_bbox_nets_serialize(self):
        # Three nets sharing one corridor: the greedy coloring must put
        # them in three separate rounds, one commit each, no collisions.
        slots = [((2, 2), [(2, 6)])] * 3
        nets = _grid_nets(slots, gh=8, gw=12)
        occ, routed, failed, wl, rounds, collisions, sched = \
            _run_concurrent(nets, 8, 12, capacity=100)
        assert [len(d) for d in sched.dispatches] == [1, 1, 1]
        assert rounds == 3 and collisions == 0
        assert int(routed[0]) == 3 and int(failed[0]) == 0
        assert int(wl[0]) == 3 * 5          # d0 = 4, path = 5 cells each
        s_routed, s_failed, s_wl, s_occ = \
            _sequential_reference(nets, 8, 12, capacity=100)
        assert (int(routed[0]), int(failed[0]), int(wl[0])) \
            == (s_routed, s_failed, s_wl)
        np.testing.assert_array_equal(occ[0], s_occ)

    def test_collision_retry_converges_and_matches_sequential(self):
        # capacity=1: slot 0 (row 0) and slot 1 (row 3) have disjoint
        # bboxes, so they co-dispatch — but slot 0's commit crosses
        # capacity at cells whose distance from slot 1's hub undercuts
        # slot 1's farthest target, so the validity bound must drop and
        # re-route slot 1 (the collision-retry path).
        slots = [((0, 0), [(0, 2)]), ((3, 3), [(3, 9)])]
        nets = _grid_nets(slots, gh=8, gw=12)
        occ, routed, failed, wl, rounds, collisions, sched = \
            _run_concurrent(nets, 8, 12, capacity=1)
        assert len(sched.dispatches[0]) == 2     # co-dispatched round 1
        assert collisions >= 1                   # ...and slot 1 was dropped
        assert rounds >= 2                       # retry took another round
        s_routed, s_failed, s_wl, s_occ = \
            _sequential_reference(nets, 8, 12, capacity=1)
        assert (int(routed[0]), int(failed[0]), int(wl[0])) \
            == (s_routed, s_failed, s_wl)
        np.testing.assert_array_equal(occ[0], s_occ)

    def test_blocked_corridor_failures_match_sequential(self):
        # capacity=1 and four nets forced through one 3-cell corridor
        # mouth: later nets must fail exactly like the sequential router.
        slots = [((4, 0), [(4, 8)]), ((3, 0), [(3, 8)]),
                 ((5, 0), [(5, 8)]), ((4, 1), [(4, 7)])]
        nets = _grid_nets(slots, gh=8, gw=12)
        occ, routed, failed, wl, _, _, _ = \
            _run_concurrent(nets, 8, 12, capacity=1)
        s_routed, s_failed, s_wl, s_occ = \
            _sequential_reference(nets, 8, 12, capacity=1)
        assert (int(routed[0]), int(failed[0]), int(wl[0])) \
            == (s_routed, s_failed, s_wl)
        np.testing.assert_array_equal(occ[0], s_occ)

    def test_engines_bit_identical(self, netbatch):
        nets, w, h = netbatch
        conc = batched_route(nets, w, h, engine="concurrent")
        scan = batched_route(nets, w, h, engine="scan")
        assert conc.engine == "concurrent" and scan.engine == "scan"
        np.testing.assert_array_equal(conc.routed, scan.routed)
        np.testing.assert_array_equal(conc.failed, scan.failed)
        np.testing.assert_array_equal(conc.wirelength, scan.wirelength)
        np.testing.assert_array_equal(conc.occ_count, scan.occ_count)

    def test_unknown_engine_rejected(self, netbatch):
        nets, w, h = netbatch
        with pytest.raises(ValueError, match="engine"):
            batched_route(nets, w, h, engine="astar")


def _multi_grid_nets(per_spec, n_slots):
    """A NetBatch over several specs from (hub, [targets]) slots each;
    slots past a spec's own are padding (`nmask` false)."""
    b = len(per_spec)
    hubs = np.zeros((b, n_slots, 2), np.int32)
    tgts = np.zeros((b, n_slots, 2, 2), np.int32)
    tmask = np.zeros((b, n_slots, 2), bool)
    nmask = np.zeros((b, n_slots), bool)
    for i, slots in enumerate(per_spec):
        one = _grid_nets(slots, 0, 0)
        n = len(slots)
        hubs[i, :n], tgts[i, :n] = one.hubs[0], one.tgts[0]
        tmask[i, :n], nmask[i, :n] = one.tmask[0], True
    return NetBatch(hubs, tgts, tmask, nmask)


def _extent(grids):
    """(widths, heights) whose coarse=1 routing grids are `grids`."""
    grids = np.asarray(grids)
    return grids[:, 1] - 2, grids[:, 0] - 3


# spec 0 (6 x 8): slot 0 walls off column 3 at capacity 1, slot 1 then
# cannot cross it (the fixed-point fallback), slot 2's first target is
# on the wall (entered from a neighbour); spec 1 (4 x 12, a smaller and
# wider grid in the same batch): a target on its hub and a padded slot
WALLS = ([((0, 3), [(5, 3)]), ((2, 0), [(2, 6)]),
          ((1, 1), [(4, 3), (0, 0)])],
         [((1, 1), [(1, 6)]), ((2, 2), [(2, 2)])])


class TestGoalStoppedScan:
    """The scan engine over the Pallas kernel stops each wavefront on
    its net's targets; routing must stay bit-identical to the engines
    that compute whole fields (the concurrent host engine and the scan
    over the jnp ref)."""

    def _engines(self, nets, w, h, **kw):
        kern = batched_route(nets, w, h, engine="scan", use_kernel=True, **kw)
        ref = batched_route(nets, w, h, engine="scan", use_kernel=False,
                            **kw)
        conc = batched_route(nets, w, h, engine="concurrent", **kw)
        for other in (ref, conc):
            np.testing.assert_array_equal(kern.routed, other.routed)
            np.testing.assert_array_equal(kern.failed, other.failed)
            np.testing.assert_array_equal(kern.wirelength, other.wirelength)
            np.testing.assert_array_equal(kern.occ_count, other.occ_count)
        assert ref.sweeps is None and conc.sweeps is None
        assert ref.walk_steps is None and conc.walk_steps is None
        return kern

    @pytest.mark.parametrize("capacity", [4, 1])
    def test_derived_nets_bit_identical(self, netbatch, capacity):
        nets, w, h = netbatch
        kern = self._engines(nets, w, h, capacity=capacity)
        live = kern.routed + kern.failed
        assert (kern.goal_stops <= live).all() and kern.goal_stops.sum() > 0
        if capacity == 1:
            assert kern.failed.sum() > 0       # congestion bites

    def test_walls_blocked_targets_and_padding_bit_identical(self):
        nets = _multi_grid_nets(WALLS, 3)
        w, h = _extent([(6, 8), (4, 12)])
        kern = self._engines(nets, w, h, coarse=1, capacity=1)
        assert list(kern.routed) == [2, 2] and list(kern.failed) == [1, 0]
        # spec 0: the wall's net stops on its target (8 sweeps); the
        # unreachable net runs to the fixed point of its 6 x 3 side (last
        # change at sweep 5, found by the test at 8); slot 2's targets
        # resolve by sweep 5 too, on a field already complete at 8.
        # spec 1: 8 sweeps and 4, both while the field still grows
        assert list(kern.sweeps) == [24, 12]
        assert list(kern.goal_stops) == [1, 2]

    def test_counters_hand_counted(self):
        # one 4 x 12 grid, capacity 4 (nothing blocks), hub-to-target
        # distances 5, 0 and 14, one padded slot; a test every 4 sweeps
        slots = [((1, 1), [(1, 6)]), ((2, 2), [(2, 2)]),
                 ((0, 0), [(3, 11)])]
        nets = _multi_grid_nets([slots], 4)
        nets = NetBatch(nets.hubs[:, [0, 1, 3, 2]], nets.tgts[:, [0, 1, 3, 2]],
                        nets.tmask[:, [0, 1, 3, 2]],
                        nets.nmask[:, [0, 1, 3, 2]])
        w, h = _extent([(4, 12)])
        kern = self._engines(nets, w, h, coarse=1, capacity=4)
        # 8 sweeps (goal at 5, field still growing), 4 (goal on the hub),
        # 0 (no net), 16 (goal at 14 = the far corner: the field's last
        # change is at sweep 14, so the test at 16 finds the fixed point)
        assert list(kern.sweeps) == [8 + 4 + 0 + 16]
        assert list(kern.goal_stops) == [2]
        assert list(kern.routed + kern.failed) == [3]
        # the walk takes d0 steps per target: 5 + 0 + 14; a path has
        # d0 + 1 cells
        assert list(kern.walk_steps) == [19]
        assert list(kern.wirelength) == [19 + 3]


class TestStillValidBound:
    def test_manhattan_entry(self):
        e = _Buffered(cells=np.zeros(0, np.int64), wl=5, ok=True,
                      d0max=4, dist=None, hub=(0, 0))
        far = (np.array([3]), np.array([3]))      # |3|+|3| = 6 >= 4
        near = (np.array([1]), np.array([2]))     # |1|+|2| = 3 <  4
        assert _still_valid(e, *far, stride=14)
        assert not _still_valid(e, *near, stride=14)
        edge = (np.array([2]), np.array([2]))     # exactly d0max: still ok
        assert _still_valid(e, *edge, stride=14)

    def test_dist_field_entry(self):
        gh, gw = 6, 10
        stride = gw + 2
        dist = np.full((gh + 2) * stride, 2 ** 29, np.int32)
        dist[canvas_index(1, 1, stride)] = 2
        e = _Buffered(cells=np.zeros(0, np.int64), wl=4, ok=True,
                      d0max=3, dist=dist, hub=None)
        assert not _still_valid(e, np.array([1]), np.array([1]), stride)
        e2 = _Buffered(cells=np.zeros(0, np.int64), wl=3, ok=True,
                       d0max=2, dist=dist, hub=None)
        assert _still_valid(e2, np.array([1]), np.array([1]), stride)

    def test_failed_and_trivial_entries_always_valid(self):
        failed = _Buffered(cells=np.zeros(0, np.int64), wl=0, ok=False,
                           d0max=9, dist=None, hub=(0, 0))
        trivial = _Buffered(cells=np.zeros(0, np.int64), wl=0, ok=True,
                            d0max=-1, dist=None, hub=(0, 0))
        yx = (np.array([0]), np.array([0]))
        assert _still_valid(failed, *yx, stride=14)
        assert _still_valid(trivial, *yx, stride=14)


class TestDistillAndLayout:
    """Explore -> agile distillation -> batched layouts through one
    `DesignRequest`."""

    def test_explore_to_batched_layouts(self):
        # agile distillation thresholds keep the laid-out batch small
        art = DesignSession().run(DesignRequest(
            array_size=4096, pop_size=48, generations=10, seed=0,
            requirements=Requirements(min_tops=0.5, min_snr_db=10.0),
            layout=True))
        distilled, layouts = art.pareto, art.layouts
        assert len(distilled) == len(layouts) >= 2
        rows = layouts.metrics_rows()
        assert all(r["drc_clean"] for r in rows)
        assert [(r["h"], r["w"], r["l"], r["b_adc"]) for r in rows] \
            == [s.as_tuple() for s in distilled.specs]

    def test_overfiltered_raises(self):
        with pytest.raises(ValueError):
            DesignSession().run(DesignRequest(
                array_size=4096, pop_size=32, generations=5,
                requirements=Requirements(min_tops=1e9), layout=True))
