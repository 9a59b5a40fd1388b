"""Pallas kernel microbenchmarks (interpret-mode wall time on CPU is NOT a
TPU perf claim — correctness/overhead tracking only; TPU perf is covered by
the roofline analysis)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.acim_spec import MacroSpec
from repro.kernels.acim_matmul import acim_matmul, acim_matmul_ref
from repro.kernels.pareto_dom import (dominance_matrix, dominance_matrix_ref,
                                      non_dominated_rank,
                                      non_dominated_rank_ref)


def _time(fn, *args, reps: int = 3) -> float:
    fn(*args)  # compile/warm
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps * 1e6


def main() -> None:
    print("name,us_per_call,derived")
    spec = MacroSpec(256, 64, 2, 5)
    x = jnp.where(jax.random.bernoulli(jax.random.key(0), 0.5, (256, 512)),
                  1.0, -1.0)
    w = jnp.where(jax.random.bernoulli(jax.random.key(1), 0.5, (512, 64)),
                  1.0, -1.0)
    t_k = _time(lambda a, b: acim_matmul(a, b, spec), x, w)
    t_r = _time(lambda a, b: acim_matmul_ref(a, b, n=128, b_adc=5), x, w)
    print(f"acim_matmul_pallas_interp,{t_k:.0f},(256x512x64 n=128 b=5)")
    print(f"acim_matmul_ref,{t_r:.0f},oracle")

    f = jax.random.normal(jax.random.key(2), (512, 4))
    t_k = _time(lambda a: dominance_matrix(a), f)
    t_r = _time(lambda a: dominance_matrix_ref(a), f)
    print(f"pareto_dom_pallas_interp,{t_k:.0f},(P=512 M=4)")
    print(f"pareto_dom_ref,{t_r:.0f},oracle")

    t_k = _time(lambda a: non_dominated_rank(a), f)
    t_r = _time(lambda a: non_dominated_rank_ref(a), f)
    print(f"pareto_rank_fused_pallas_interp,{t_k:.0f},(P=512 M=4 bit-packed peel)")
    print(f"pareto_rank_ref,{t_r:.0f},oracle")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
