"""GPipe pipeline over a host-device mesh (subprocess, 4 stages)."""
import pathlib
import subprocess
import sys
import textwrap

import pytest

from repro.parallel.pipeline import bubble_fraction

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_bubble_fraction():
    assert bubble_fraction(4, 12) == pytest.approx(3 / 15)
    assert bubble_fraction(1, 8) == 0.0


@pytest.mark.slow
def test_pipeline_matches_sequential_and_grads():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.parallel.pipeline import pipeline_apply

        mesh = make_mesh((4,), ("stage",))
        S, M, B, D = 4, 8, 2, 16
        key = jax.random.key(0)
        w = 0.3 * jax.random.normal(key, (S, D, D))
        xs = jax.random.normal(jax.random.key(1), (M, B, D))

        def stage_fn(wi, x):
            return jnp.tanh(x @ wi)

        out = pipeline_apply(mesh, "stage", stage_fn, w, xs)

        # sequential reference
        ref = xs
        for i in range(S):
            ref = jnp.tanh(ref @ w[i])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

        # gradient equivalence
        def loss_pipe(w):
            return jnp.sum(pipeline_apply(mesh, "stage", stage_fn, w, xs) ** 2)

        def loss_ref(w):
            y = xs
            for i in range(S):
                y = jnp.tanh(y @ w[i])
            return jnp.sum(y ** 2)

        g1 = jax.grad(loss_pipe)(w)
        g2 = jax.grad(loss_ref)(w)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=1e-4, rtol=1e-4)
        print("OK pipeline fwd+bwd equivalent")
    """)
    import os

    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": str(REPO / "src")})
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
