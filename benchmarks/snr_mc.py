"""Monte-Carlo validation of the analytical SNR model (paper Eqs. 2-6).

Simulates the QR macro (ADC quantization + Eq. 5 mismatch/thermal noise)
on random 1b data and compares measured SNR to `estimator.snr_total_db`
across (N, B_ADC) operating points.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import acim_numerics as an
from repro.core import estimator
from repro.core.acim_spec import MacroSpec

POINTS = [(128, 2, 3), (128, 2, 5), (512, 8, 4), (256, 2, 6), (1024, 32, 5)]


def mc_snr_db(spec: MacroSpec, *, rows: int = 256, cols: int = 64,
              noisy: bool = True, seed: int = 0) -> float:
    k = spec.n_caps
    x = jnp.where(jax.random.bernoulli(jax.random.key(seed), 0.5,
                                       (rows, k)), 1.0, -1.0)
    w = jnp.where(jax.random.bernoulli(jax.random.key(seed + 1), 0.5,
                                       (k, cols)), 1.0, -1.0)
    noise = an.NoiseParams.from_cal() if noisy else None
    y = an.acim_matmul_ref(x, w, spec, noise=noise,
                           instance_key=jax.random.key(seed + 2),
                           conversion_key=jax.random.key(seed + 3))
    ref = x @ w
    return 10.0 * float(np.log10(float(jnp.var(ref))
                                 / max(float(jnp.var(y - ref)), 1e-12)))


def main() -> None:
    print("h,l,b_adc,analytic_db,mc_db,delta_db")
    for h, l, b in POINTS:
        spec = MacroSpec(h, 64, l, b)
        ana = float(estimator.snr_total_db(h, l, b))
        mc = mc_snr_db(spec)
        print(f"{h},{l},{b},{ana:.2f},{mc:.2f},{mc - ana:+.2f}")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
