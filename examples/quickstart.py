"""EasyACIM quickstart: one declarative request through the unified API.

A `DesignRequest` captures the whole query — array size, MOGA budget,
application requirements, layout options — and `DesignSession.run`
answers it end to end (paper Fig. 4): MOGA exploration, agile
distillation, batched layout of the surviving Pareto set.

  PYTHONPATH=src python examples/quickstart.py
"""
import pathlib

from repro.api import DesignRequest, DesignSession, Requirements

OUT = pathlib.Path("runs/quickstart")


def main() -> None:
    OUT.mkdir(parents=True, exist_ok=True)

    req = DesignRequest(array_size=16384, pop_size=192, generations=60,
                        requirements=Requirements(min_tops=1.4,
                                                  min_snr_db=20.0))
    print(f"== request {req.sha()}: 16 kb array, >= 1.4 TOPS, "
          f">= 20 dB SNR ==")
    session = DesignSession()
    art = session.run(req)

    print(f"\n== 1. MOGA design-space exploration ==")
    full = session.fronts_for([req])[req]
    print(f"Pareto-frontier set: {len(full)} solutions")
    for row in sorted(full.to_rows(), key=lambda r: -r["tops"])[:5]:
        print(f"  H={row['h']:4d} W={row['w']:4d} L={row['l']:2d} "
              f"B={row['b_adc']} | {row['tops']:.3f} TOPS, "
              f"{row['tops_per_w']:.0f} TOPS/W, "
              f"{row['area_f2_per_bit']:.0f} F^2/bit, "
              f"SNR {row['snr_db']:.1f} dB")

    print("\n== 2. Agile user distillation (>= 1.4 TOPS, >= 20 dB) ==")
    print(f"{len(art.pareto)} solutions survive")
    spec = art.pareto.best("tops_per_w")
    print(f"most efficient survivor: {spec}")

    print("\n== 3. Batched layout of the whole distilled set ==")
    for m in art.layout_rows:
        print(f"  H={m['h']:4d} W={m['w']:4d}: "
              f"{m['layout_area_f2_per_bit']:.0f} F^2/bit "
              f"(model {m['estimator_area_f2_per_bit']:.0f}), "
              f"{m['routed_nets']} nets routed "
              f"({100 * m['route_success']:.0f}%), "
              f"DRC clean={m['drc_clean']}")
    p = art.provenance
    print(f"\nprovenance: explore {p.explore_s:.1f}s "
          f"(+{p.new_traces} traces), layout {p.layout_s:.1f}s")
    art.to_json(OUT / "artifact.json")
    art.pareto.to_json(OUT / "pareto.json")
    print(f"artifacts in {OUT}/")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
