"""Reproduce the paper's Fig. 8: three 16 kb ACIM layouts at different
design specifications, through the *batched* layout path — netlist stats,
placement, routing and DRC for all three specs in one dispatch chain
(`repro.api.DesignSession.layout`), the way a distilled Pareto set is
laid out.  Pass --full to also run the sequential `generate_layout` per
spec and export full GDS-like JSON (named cells + wire geometry), which
the batched path intentionally skips.

  PYTHONPATH=src python examples/layout_flow.py [--full]
"""
import pathlib
import sys
import time

from repro.api import DesignSession
from repro.core.acim_spec import MacroSpec
from repro.eda.flow import generate_layout

# (spec, paper TOPS, paper F^2/bit) — see benchmarks/fig8_layouts.py
PAPER = {
    "a": (MacroSpec(128, 128, 2, 3), 3.277, 4504.0),
    "b": (MacroSpec(512, 32, 8, 3), 0.813, 2610.0),
    "c": (MacroSpec(256, 64, 8, 3), 0.813, 2977.0),
}

OUT = pathlib.Path("runs/fig8")


def main() -> None:
    OUT.mkdir(parents=True, exist_ok=True)
    specs = [spec for spec, _, _ in PAPER.values()]
    t0 = time.perf_counter()
    res = DesignSession().layout(specs)
    elapsed = time.perf_counter() - t0
    res.to_json(OUT / "fig8_batched.json")
    for (tag, (spec, _, paper_area)), m in zip(PAPER.items(),
                                               res.metrics_rows()):
        print(f"({tag}) H={spec.h} W={spec.w} L={spec.l} B={spec.b_adc}: "
              f"layout {m['layout_area_f2_per_bit']:.0f} F^2/bit "
              f"(paper {paper_area:.0f}), routed {m['routed_nets']} nets, "
              f"DRC clean={m['drc_clean']}")
    print(f"batched: {len(specs)} layouts in {elapsed:.1f}s "
          f"-> {OUT}/fig8_batched.json")
    if "--full" in sys.argv[1:]:
        for tag, (spec, _, _) in PAPER.items():
            lr = generate_layout(spec)
            lr.to_json(OUT / f"fig8_{tag}.json")
            print(f"({tag}) full layout JSON ({len(lr.placement.rects)} "
                  f"cells, {len(lr.routing.wires)} wires) in "
                  f"{lr.metrics()['elapsed_s']:.1f}s")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
