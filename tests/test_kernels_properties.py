"""Hypothesis property sweeps for the Pallas kernels (interpret mode).

Collected only where hypothesis is installed (`pytest.importorskip`);
deterministic kernel coverage lives in `test_kernels.py`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import pareto  # noqa: E402
from repro.core.acim_spec import MacroSpec  # noqa: E402
from repro.kernels.acim_matmul import acim_matmul, acim_matmul_ref  # noqa: E402
from repro.kernels.maze_route import (INF, wavefront_distance,  # noqa: E402
                                      wavefront_distance_ref)
from repro.kernels.pareto_dom import (dominance_matrix,  # noqa: E402
                                      dominance_matrix_ref,
                                      non_dominated_rank)

settings.register_profile("ci", max_examples=20, deadline=None)
settings.load_profile("ci")


def _pm1(key, shape):
    return jnp.where(jax.random.bernoulli(jax.random.key(key), 0.5, shape),
                     1.0, -1.0)


class TestAcimMatmulProperties:
    @given(st.integers(1, 33), st.integers(1, 200), st.integers(1, 17),
           st.sampled_from([64, 128, 256]), st.integers(1, 6))
    def test_kernel_matches_ref_hypothesis(self, m, k, c, n, b):
        x = _pm1(m + k, (m, k))
        w = _pm1(k + c, (k, c))
        spec = MacroSpec(h=2 * n, w=c, l=2, b_adc=b)
        np.testing.assert_array_equal(
            np.asarray(acim_matmul(x, w, spec)),
            np.asarray(acim_matmul_ref(x, w, n=n, b_adc=b)))


class TestParetoDomProperties:
    @given(st.integers(2, 40), st.integers(2, 5))
    def test_matches_ref_hypothesis(self, p, m):
        f = jax.random.normal(jax.random.key(p * 31 + m), (p, m))
        np.testing.assert_array_equal(np.asarray(dominance_matrix(f)),
                                      np.asarray(dominance_matrix_ref(f)))

    @given(st.integers(2, 40), st.integers(2, 5))
    def test_fused_rank_matches_ref_hypothesis(self, p, m):
        f = jax.random.normal(jax.random.key(p * 13 + m), (p, m))
        np.testing.assert_array_equal(
            np.asarray(non_dominated_rank(f)),
            np.asarray(pareto.non_dominated_rank(f)))


def _bfs_oracle(occ: np.ndarray, seed: np.ndarray) -> np.ndarray:
    """Host queue BFS — the semantics `repro.eda.router` historically had."""
    from collections import deque

    h, w = occ.shape
    dist = np.full((h, w), int(INF), np.int64)
    q = deque()
    for y, x in zip(*np.nonzero(seed)):
        dist[y, x] = 0
        q.append((int(y), int(x)))
    while q:
        y, x = q.popleft()
        for dy, dx in ((1, 0), (-1, 0), (0, 1), (0, -1)):
            ny, nx = y + dy, x + dx
            if 0 <= ny < h and 0 <= nx < w and not occ[ny, nx] \
                    and dist[ny, nx] > dist[y, x] + 1:
                dist[ny, nx] = dist[y, x] + 1
                q.append((ny, nx))
    return dist


class TestMazeRouteProperties:
    @given(st.integers(2, 14), st.integers(2, 18), st.integers(0, 60),
           st.integers(1, 3), st.integers(0, 2 ** 16))
    def test_kernel_and_ref_match_bfs_hypothesis(self, h, w, occ_pct,
                                                 n_seeds, key):
        ko, ks = jax.random.split(jax.random.key(key))
        occ = np.asarray(jax.random.uniform(ko, (h, w)) < occ_pct / 100.0)
        flat = np.asarray(jax.random.choice(ks, h * w,
                                            (min(n_seeds, h * w),),
                                            replace=False))
        seed = np.zeros((h, w), bool)
        seed[flat // w, flat % w] = True
        oracle = _bfs_oracle(occ, seed)
        ref = np.asarray(wavefront_distance_ref(jnp.asarray(occ),
                                                jnp.asarray(seed)))
        np.testing.assert_array_equal(ref, oracle)
        krn = np.asarray(wavefront_distance(jnp.asarray(occ),
                                            jnp.asarray(seed),
                                            impl="kernel"))
        np.testing.assert_array_equal(krn, oracle)
