"""Fig. 8 reproduction: 16 kb ACIM layouts at three design specifications.

Paper values: (a) H=128, L=2, B=3 -> 3.277 TOPS, 4504 F^2/bit;
(b) balanced -> 0.813 TOPS, 2610 F^2/bit; (c) same throughput, +3 dB SNR,
2977 F^2/bit.  The exact (H, W, L) of (b)/(c) are not published; the
estimator pins them to (512,32,8,3) and (256,64,8,3) (see
core/constants.py [T1]), which reproduce throughput to <1% and area to
-19%/-5%.
"""
from __future__ import annotations

from repro.core import estimator
from repro.core.acim_spec import MacroSpec
from repro.eda.flow import generate_layout

PAPER = {
    "a": (MacroSpec(128, 128, 2, 3), 3.277, 4504.0),
    "b": (MacroSpec(512, 32, 8, 3), 0.813, 2610.0),
    "c": (MacroSpec(256, 64, 8, 3), 0.813, 2977.0),
}


def run() -> list[dict]:
    rows = []
    for tag, (spec, paper_tops, paper_area) in PAPER.items():
        lr = generate_layout(spec)
        m = lr.metrics()
        tops = float(estimator.throughput_ops(spec.h, spec.w, spec.l,
                                              spec.b_adc)) / 1e12
        snr = float(estimator.snr_total_db(spec.h, spec.l, spec.b_adc))
        rows.append({
            "point": tag, "h": spec.h, "w": spec.w, "l": spec.l,
            "b_adc": spec.b_adc,
            "tops": tops, "paper_tops": paper_tops,
            "tops_err": tops / paper_tops - 1.0,
            "est_area": m["estimator_area_f2_per_bit"],
            "layout_area": m["layout_area_f2_per_bit"],
            "paper_area": paper_area,
            "area_err_est": m["estimator_area_f2_per_bit"] / paper_area - 1.0,
            "snr_db": snr,
            "drc_clean": m["drc_clean"],
            "route_success": m["route_success"],
            "layout_seconds": m["elapsed_s"],
        })
    return rows


def main() -> None:
    for r in run():
        print(",".join(f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
                       for k, v in r.items()))


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
