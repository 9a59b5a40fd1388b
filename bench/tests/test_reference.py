"""The plain references agree with the system under test where both are exact."""
import json
import pathlib

import numpy as np
import pytest

from reference import estimator as ref_est
from reference import layout as ref_layout

CFG = json.loads((pathlib.Path(__file__).resolve().parents[1]
                  / "configs" / "paper_layout.json").read_text())


def _feasible(size):
    return [(h, size // h, l, b)
            for h in (2 ** e for e in range(6, 13)) if size // h >= 8
            for l in (2 ** e for e in range(1, 6)) if l <= h
            for b in range(1, 9) if h // l >= 2 ** b]


def test_config_states_the_calibration_the_program_uses():
    import dataclasses

    from repro.core.constants import CAL28

    assert dataclasses.asdict(CAL28) == CFG["cal"]


@pytest.mark.parametrize("size", [4096, 65536])
def test_estimator_matches_the_program_in_float32(size):
    from repro.core import estimator

    specs = _feasible(size)
    h, w, l, b = (np.array(c, np.float32) for c in zip(*specs))
    want = ref_est.report(h, w, l, b, CFG["cal"])
    got = estimator.evaluate_report(h, w, l, b)
    for name in ref_est.METRICS:
        g = np.asarray(got[name], np.float64)
        gap = np.max(np.abs(g - want[name]) / np.maximum(np.abs(want[name]), 1))
        assert gap < 1e-5, name


def test_bfloat16_control_is_far_off():
    import ml_dtypes

    specs = _feasible(16384)
    h, w, l, b = (np.array(c, np.float64) for c in zip(*specs))
    want = ref_est.report(h, w, l, b, CFG["cal"])
    low = ref_est.report(h, w, l, b, CFG["cal"], ml_dtypes.bfloat16)
    gap = max(np.max(np.abs(np.asarray(low[k], np.float64) - want[k])
                     / np.maximum(np.abs(want[k]), 1)) for k in want)
    assert gap > 1e-3


def test_dominance_and_filter():
    objs = np.array([[0, 0, 0, 0], [1, 0, 0, 0], [0, 1, -1, 0], [0, 0, 0, 0.]])
    assert ref_est.dominated(objs).tolist() == [False, True, False, False]
    rep = {"snr_db": np.array([20.0, 10.0]), "tops": np.array([1.0, 1.0]),
           "energy_fj_per_mac": np.array([5.0, 5.0]),
           "area_f2_per_bit": np.array([1.0, 1.0]),
           "tops_per_w": np.array([1.0, 1.0])}
    assert ref_est.keep(rep, {"min_snr_db": 15.0}).tolist() == [True, False]


@pytest.mark.parametrize("spec", [(64, 64, 2, 5), (128, 32, 4, 3),
                                  (256, 16, 8, 2), (512, 8, 2, 7)])
def test_layout_rows_match_the_program(spec):
    from repro.core.acim_spec import MacroSpec
    from repro.eda.batched_flow import generate_layouts

    prog = generate_layouts([MacroSpec(*spec)], engine="concurrent")
    got = prog.metrics_rows()[0]
    want = ref_layout.row(spec, CFG)
    for k, v in got.items():
        if isinstance(v, float):
            assert v == pytest.approx(want[k], rel=1e-6, abs=1e-6), k
        else:
            assert v == want[k], k
