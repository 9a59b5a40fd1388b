"""Fig. 9 reproduction: the explored design space, categorized by array
size / H / L / B_ADC, with the paper's qualitative trends asserted
quantitatively:
  (a)(b) larger arrays -> higher attainable SNR & throughput; smaller ->
         better energy & area;
  (c)(d) smaller H -> higher throughput, lower SNR, more area;
  (e)(f) smaller L -> higher throughput, higher SNR bound, more area;
  (g)(h) smaller B_ADC -> better energy efficiency, lower SNR.
"""
from __future__ import annotations

import numpy as np

from repro.api import DesignRequest, DesignSession
from repro.core import estimator


def run(sizes=(4096, 16384, 65536), pop=192, gens=60) -> dict:
    session = DesignSession()
    fronts = session.fronts_for([
        DesignRequest(array_size=s, seed=s, pop_size=pop, generations=gens,
                      layout=False) for s in sizes])
    out = {}
    for req, res in fronts.items():
        s = req.array_size
        m = res.metrics
        out[s] = {
            "n_pareto": len(res),
            "snr_max": float(np.max(m["snr_db"])),
            "tops_max": float(np.max(m["tops"])),
            "tops_per_w_max": float(np.max(m["tops_per_w"])),
            "area_min": float(np.min(m["area_f2_per_bit"])),
            "area_max": float(np.max(m["area_f2_per_bit"])),
        }
    return out


def trend_checks() -> dict:
    """Single-variable sweeps at 16 kb (paper Fig. 9 c-h).

    Note on (c)(d): at fixed (L, B_ADC), Eq. 7 is H-independent (H*W = S
    cancels: T = S/(L*t)).  The paper's "smaller H -> higher throughput /
    limited SNR" trend is mediated by the constraint B_ADC <= log2(H/L):
    small H caps the ADC precision, shortening the cycle (more T) and
    capping SNR.  We therefore sweep H with B at its constraint maximum —
    the Pareto-edge coupling Fig. 9 actually shows.
    """
    s = 16384
    h = np.array([64, 128, 256, 512, 1024], np.float32)
    w = s / h
    b_max = np.log2(h / 8.0)                    # L = 8 in this sweep
    t_h = np.asarray(estimator.throughput_ops(h, w, 8, b_max))
    snr_h = np.asarray(estimator.snr_total_db(h, 8, b_max))
    a_h = np.asarray(estimator.area_f2_per_bit(h, 8, 3))

    l = np.array([2, 4, 8, 16, 32], np.float32)
    t_l = np.asarray(estimator.throughput_ops(512, 32, l, 3))
    # SNR *upper bound* vs L (paper e/f): B at its constraint max
    snr_l = np.asarray(estimator.snr_total_db(512, l, np.minimum(
        np.log2(512.0 / l), 8.0)))
    a_l = np.asarray(estimator.area_f2_per_bit(512, l, 3))

    b = np.array([1, 2, 3, 4, 5], np.float32)
    e_b = np.asarray(estimator.energy_efficiency_tops_w(512, 8, b))
    snr_b = np.asarray(estimator.snr_total_db(512, 8, b))

    def mono(x, increasing):
        d = np.diff(x)
        return bool(np.all(d > 0) if increasing else np.all(d < 0))

    return {
        "smaller_H_higher_T": mono(t_h, False),       # T falls as H grows
        "smaller_H_lower_SNR": mono(snr_h, True),     # SNR cap rises with H
        "smaller_H_more_area": mono(a_h, False),
        "smaller_L_higher_T": mono(t_l, False),
        "smaller_L_higher_SNR": mono(snr_l, False),
        "smaller_L_more_area": mono(a_l, False),
        "smaller_B_better_EE": mono(e_b, False),
        "smaller_B_lower_SNR": mono(snr_b, True),
    }


def main() -> None:
    for s, row in run().items():
        print(f"size={s}," + ",".join(f"{k}={v:.4g}" if isinstance(v, float)
                                      else f"{k}={v}" for k, v in row.items()))
    for k, v in trend_checks().items():
        print(f"trend,{k},{v}")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
