"""EDA stack: netlist structure, placement legality, routing, area model."""
import pytest

from repro.core.acim_spec import MacroSpec
from repro.eda import netlist as nl
from repro.eda.cells import library
from repro.eda.flow import drc_lite, generate_layout
from repro.eda.placer import place


SMALL = MacroSpec(64, 16, 2, 3)
MED = MacroSpec(128, 32, 4, 3)


class TestNetlist:
    def test_instance_counts(self):
        n = nl.generate(SMALL)
        st = n.stats()
        assert st["by_cell"]["SRAM8T"] == SMALL.array_size
        assert st["by_cell"]["CAPLC"] == SMALL.n_caps * SMALL.w
        assert st["by_cell"]["COMP"] == SMALL.w
        assert st["by_cell"]["DFF"] == SMALL.w * SMALL.b_adc

    def test_rbl_net_spans_column(self):
        n = nl.generate(SMALL)
        rbl = [net for net in n.nets if net.name == "c0_rbl"][0]
        # caps + switches + comparator
        assert len(rbl.pins) >= SMALL.n_caps + 1

    @pytest.mark.parametrize("spec", [SMALL, MED, MacroSpec(128, 128, 2, 3),
                                      MacroSpec(512, 32, 8, 3)])
    def test_closed_form_stats_match_generate(self, spec):
        assert nl.stats_for_spec(spec) == nl.generate(spec).stats()


class TestPlacer:
    @pytest.mark.parametrize("spec", [SMALL, MED, MacroSpec(128, 128, 2, 3)])
    def test_drc_clean(self, spec):
        p = place(spec)
        rep = drc_lite(p)
        assert rep.clean, (spec, rep)

    def test_area_within_model_envelope(self):
        from repro.core import estimator

        p = place(MED)
        est = float(estimator.area_f2_per_bit(MED.h, MED.l, MED.b_adc))
        ratio = p.area_f2_per_bit() / est
        assert 0.9 < ratio < 1.6   # layout = model + routing/driver overhead

    def test_cells_within_bounds(self):
        p = place(SMALL)
        for r in p.rects:
            assert r.x >= 0 and r.y >= 0
            assert r.x + r.w <= p.width and r.y + r.h <= p.height


class TestFlow:
    def test_end_to_end_routes_everything(self):
        lr = generate_layout(SMALL)
        m = lr.metrics()
        assert m["route_success"] == 1.0
        assert m["drc_clean"]
        assert m["failed_nets"] == 0
        assert m["elapsed_s"] < 120

    def test_pareto_to_layout_pipeline(self):
        from repro.api import DesignRequest, DesignSession

        res = DesignSession().run(DesignRequest(
            array_size=4096, pop_size=64, generations=15, seed=1,
            layout=False)).pareto
        spec = res.filter(min_tops=0.05).specs[0] if len(
            res.filter(min_tops=0.05)) else res.specs[0]
        lr = generate_layout(spec)
        assert lr.metrics()["drc_clean"]


class TestCellLibrary:
    def test_footprints_match_calibrated_areas(self):
        from repro.core.constants import CAL28

        lib = library()
        assert lib["SRAM8T"].area == pytest.approx(CAL28.a_sram, rel=0.1)
        assert lib["DFF"].area == pytest.approx(CAL28.a_dff, rel=0.1)
