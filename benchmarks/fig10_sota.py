"""Fig. 10 reproduction: EasyACIM design space vs SOTA ACIMs on the
(energy efficiency, area) plane.

Paper claims the generated space spans 50-750 TOPS/W and 1500-7500
F^2/bit, with a Pareto frontier competitive with designs A [4], B [5],
C [8].  SOTA reference points (energy-eff TOPS/W, area F^2/bit) are taken
at the 1b-normalized operating points reported in those papers.
"""
from __future__ import annotations

import numpy as np

from repro.api import DesignRequest, DesignSession
from repro.core.pareto import non_dominated_mask
import jax.numpy as jnp

# (label, tops_per_w, area_f2_per_bit) — 1b-normalized literature points
SOTA = [
    ("A_JSSC23_bitflex", 588.0, 6300.0),
    ("B_JSSC22_colADC", 49.3, 3000.0),
    ("C_ISSCC20_7nm", 351.0, 4100.0),
]

PAPER_EE_RANGE = (50.0, 750.0)
PAPER_AREA_RANGE = (1500.0, 7500.0)


def run(sizes=(4096, 16384, 65536)) -> dict:
    fronts = DesignSession().fronts_for([
        DesignRequest(array_size=s, seed=s + 17, pop_size=192,
                      generations=60, layout=False) for s in sizes])
    ee, area = [], []
    for res in fronts.values():
        ee.extend(res.metrics["tops_per_w"].tolist())
        area.extend(res.metrics["area_f2_per_bit"].tolist())
    ee = np.array(ee)
    area = np.array(area)
    # 2D Pareto front on (maximize EE, minimize area)
    f = jnp.stack([-jnp.asarray(ee), jnp.asarray(area)], axis=-1)
    front = np.asarray(non_dominated_mask(f))

    def dominated_by_ours(pt):
        e, a = pt
        return bool(np.any((ee >= e) & (area <= a)))

    return {
        "ee_min": float(ee.min()), "ee_max": float(ee.max()),
        "area_min": float(area.min()), "area_max": float(area.max()),
        "ee_span_covers_paper": bool(ee.min() <= PAPER_EE_RANGE[0] * 1.2
                                     and ee.max() >= PAPER_EE_RANGE[1] * 0.8),
        "area_span_covers_paper": bool(area.min() <= PAPER_AREA_RANGE[0] * 1.2
                                       and area.max() >= PAPER_AREA_RANGE[1] * 0.8),
        "n_front": int(front.sum()),
        "sota_matched": {label: dominated_by_ours((e, a))
                         for label, e, a in SOTA},
    }


def main() -> None:
    for k, v in run().items():
        print(f"{k}={v}")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
