"""Codesign showcase: recommend an ACIM macro for every assigned
architecture (the paper's Fig. 1 'versatile scenarios', made quantitative).

  PYTHONPATH=src python examples/codesign_sweep.py
"""
from repro.configs import registry as creg
from repro.core.codesign import recommend_macro


def main() -> None:
    print(f"{'arch':24s} {'macro (H,W,L,B)':>20s} {'SNR':>6s} {'util':>5s} "
          f"{'TOPS/W':>7s} {'#macros@1tok/us':>15s}")
    for name in creg.ARCH_IDS:
        cfg = creg.get(name)
        rec = recommend_macro(cfg, array_size=65536, min_snr_db=3.0,
                              pop_size=96, generations=25, seed=7)
        s = rec.spec
        print(f"{cfg.name:24s} {str((s.h, s.w, s.l, s.b_adc)):>20s} "
              f"{rec.snr_db:6.1f} {rec.utilization:5.2f} "
              f"{rec.eff_tops_per_w:7.0f} {rec.macro_count_for_rate:15d}")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
