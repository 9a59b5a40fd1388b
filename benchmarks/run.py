"""Benchmark driver: one section per paper table/figure, printed as CSV.

  PYTHONPATH=src python -m benchmarks.run [--skip fig9,...]

Sections:
  fig8   — 16 kb layout design points (throughput/area/SNR vs paper)
  fig9   — design-space sweep + monotone trend checks
  fig10  — EE/area span + SOTA comparison
  table2 — flow wall-clock comparison
  snr_mc — Monte-Carlo SNR vs analytical model (Eqs. 2-6)
"""
from __future__ import annotations

import argparse


def _section(name: str) -> None:
    print(f"\n#### {name}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip", default="", help="comma-separated sections")
    args = ap.parse_args()
    skip = set(args.skip.split(",")) if args.skip else set()

    if "fig8" not in skip:
        _section("fig8_layouts")
        from benchmarks import fig8_layouts

        fig8_layouts.main()

    if "fig9" not in skip:
        _section("fig9_design_space")
        from benchmarks import fig9_design_space

        fig9_design_space.main()

    if "fig10" not in skip:
        _section("fig10_sota")
        from benchmarks import fig10_sota

        fig10_sota.main()

    if "table2" not in skip:
        _section("table2_flow")
        from benchmarks import table2_flow

        table2_flow.main()

    if "snr_mc" not in skip:
        _section("snr_model_vs_mc")
        from benchmarks import snr_mc

        snr_mc.main()


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
