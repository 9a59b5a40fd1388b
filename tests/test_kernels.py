"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode).

Hypothesis property sweeps live in `test_kernels_properties.py` (skipped
cleanly when hypothesis is not installed)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pareto
from repro.core.acim_spec import MacroSpec
from repro.kernels.acim_matmul import (acim_matmul, acim_matmul_ref,
                                       acim_matmul_ste, mismatch_weights)
from repro.kernels.maze_route import (INF, wavefront_distance,
                                      wavefront_distance_ref)
from repro.kernels.maze_route.ref import relax_once
from repro.kernels.pareto_dom import (dominance_matrix, dominance_matrix_ref,
                                      non_dominated_rank, rank_and_crowd)


def _pm1(key, shape):
    return jnp.where(jax.random.bernoulli(jax.random.key(key), 0.5, shape),
                     1.0, -1.0)


SHAPES = [(16, 64, 16, 64, 3), (7, 100, 33, 64, 3), (128, 512, 64, 128, 5),
          (1, 64, 1, 64, 1), (4, 1000, 20, 256, 6), (5, 64, 130, 32, 4),
          (2, 3, 2, 64, 2)]


class TestAcimMatmul:
    @pytest.mark.parametrize("m,k,c,n,b", SHAPES)
    def test_kernel_matches_ref(self, m, k, c, n, b):
        x = _pm1(m * 7 + k, (m, k))
        w = _pm1(k * 5 + c, (k, c))
        spec = MacroSpec(h=2 * n, w=max(c, 1), l=2, b_adc=b)
        y_k = acim_matmul(x, w, spec)
        y_r = acim_matmul_ref(x, w, n=n, b_adc=b)
        np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))

    def test_batched_leading_dims(self):
        x = _pm1(1, (2, 3, 64))
        w = _pm1(2, (64, 8))
        spec = MacroSpec(128, 8, 2, 3)
        y = acim_matmul(x, w, spec)
        assert y.shape == (2, 3, 8)
        np.testing.assert_array_equal(
            np.asarray(y), np.asarray(acim_matmul_ref(x, w, n=64, b_adc=3)))

    def test_exact_at_high_precision(self):
        # N=128, B=7 -> delta=2: even +-1 sums are exact (no clip at |s|<128)
        x = _pm1(3, (8, 256))
        w = _pm1(4, (256, 16))
        spec = MacroSpec(256, 16, 2, 7)
        y = acim_matmul(x, w, spec)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x @ w))

    def test_ste_gradients(self):
        spec = MacroSpec(128, 16, 2, 4)
        x = _pm1(5, (4, 64))
        w = _pm1(6, (64, 16))
        gx, gw = jax.grad(
            lambda x, w: jnp.sum(acim_matmul_ste(x, w, spec)), argnums=(0, 1)
        )(x, w)
        # STE: gradient of the ideal matmul
        np.testing.assert_allclose(np.asarray(gw),
                                   np.asarray(x.T @ jnp.ones((4, 16))), rtol=1e-6)
        assert bool(jnp.all(jnp.isfinite(gx)))

    def test_mismatch_fold_changes_results_slightly(self):
        from repro.core.acim_numerics import NoiseParams

        spec = MacroSpec(128, 16, 2, 6)
        x = _pm1(7, (16, 64))
        w = _pm1(8, (64, 16))
        w2 = mismatch_weights(w, spec, jax.random.key(0), NoiseParams.from_cal())
        y1 = acim_matmul(x, w, spec)
        y2 = acim_matmul(x, w2, spec)
        rel = float(jnp.mean(jnp.abs(y2 - y1))) / float(jnp.mean(jnp.abs(y1)) + 1e-9)
        assert rel < 0.2   # small static perturbation, not catastrophic


class TestParetoDom:
    @pytest.mark.parametrize("p", [3, 8, 100, 256, 513])
    def test_matches_ref(self, p):
        f = jax.random.normal(jax.random.key(p), (p, 4))
        np.testing.assert_array_equal(np.asarray(dominance_matrix(f)),
                                      np.asarray(dominance_matrix_ref(f)))

    def test_duplicate_rows_dont_dominate(self):
        f = jnp.asarray(np.array([[1., 2.], [1., 2.]], np.float32))
        d = np.asarray(dominance_matrix(f))
        assert not d.any()


class TestFusedRank:
    """Fused dominance + bit-pack + peel kernel vs the jnp oracles."""

    @pytest.mark.parametrize("p,m", [(3, 2), (17, 4), (100, 4), (256, 4),
                                     (300, 3), (512, 4)])
    def test_rank_matches_oracle(self, p, m):
        f = jax.random.normal(jax.random.key(p * 31 + m), (p, m))
        np.testing.assert_array_equal(
            np.asarray(non_dominated_rank(f)),
            np.asarray(pareto.non_dominated_rank(f)))

    def test_rank_with_duplicates_and_chain(self):
        # a strict chain: rank == index; plus duplicated rows sharing a rank
        base = np.arange(6, dtype=np.float32)[:, None] * np.ones((1, 3), np.float32)
        f = jnp.asarray(np.concatenate([base, base[2:3]], 0))
        ranks = np.asarray(non_dominated_rank(f))
        assert (ranks[:6] == np.arange(6)).all()
        assert ranks[6] == ranks[2]

    def test_rank_and_crowd_matches_oracles(self):
        f = jax.random.normal(jax.random.key(9), (130, 4))
        ranks, crowd = rank_and_crowd(f)
        ranks_ref = pareto.non_dominated_rank(f)
        crowd_ref = pareto.crowding_distance(f, ranks_ref)
        np.testing.assert_array_equal(np.asarray(ranks), np.asarray(ranks_ref))
        np.testing.assert_allclose(np.asarray(crowd), np.asarray(crowd_ref))


class TestMazeRoute:
    """Wavefront (parallel BFS) kernel vs the sweeping jnp oracle."""

    def _random_case(self, key, h, w, p_occ=0.3, n_seeds=1):
        ko, ks = jax.random.split(jax.random.key(key))
        occ = jax.random.uniform(ko, (h, w)) < p_occ
        flat = jax.random.choice(ks, h * w, (n_seeds,), replace=False)
        seed = jnp.zeros((h, w), bool).at[flat // w, flat % w].set(True)
        return occ, seed

    @pytest.mark.parametrize("h,w", [(2, 2), (5, 9), (16, 128), (23, 40),
                                     (8, 200)])
    def test_kernel_matches_ref(self, h, w):
        occ, seed = self._random_case(h * 131 + w, h, w)
        np.testing.assert_array_equal(
            np.asarray(wavefront_distance(occ, seed, impl="kernel")),
            np.asarray(wavefront_distance_ref(occ, seed)))

    def test_batched_grids(self):
        occ = jax.random.uniform(jax.random.key(0), (4, 11, 19)) < 0.25
        seed = jnp.zeros((4, 11, 19), bool).at[:, 0, 0].set(True)
        np.testing.assert_array_equal(
            np.asarray(wavefront_distance(occ, seed, impl="kernel")),
            np.asarray(wavefront_distance_ref(occ, seed)))

    def test_sweeping_fixed_point_is_relaxation_fixed_point(self):
        # BFS distances are the unique fixed point of the Jacobi step the
        # Pallas kernel iterates; the sweeping oracle must land on it.
        occ, seed = self._random_case(7, 13, 17, p_occ=0.4)
        dist = wavefront_distance_ref(occ, seed)
        free = ~occ & ~seed
        np.testing.assert_array_equal(np.asarray(relax_once(dist, free)),
                                      np.asarray(dist))

    def test_walled_off_region_unreachable(self):
        occ = jnp.zeros((7, 7), bool).at[:, 3].set(True)
        seed = jnp.zeros((7, 7), bool).at[3, 0].set(True)
        d = np.asarray(wavefront_distance(occ, seed, impl="kernel"))
        assert (d[:, 4:] == INF).all()          # right of the wall
        assert (d[:, :3] < INF).all()           # left side fully reached
        assert d[3, 0] == 0

    def test_occupied_seed_still_expands(self):
        # a router hub on a full track is enterable (distance 0) and the
        # wavefront still leaves it — matching the old host BFS
        occ = jnp.zeros((4, 6), bool).at[1, 1].set(True)
        seed = jnp.zeros((4, 6), bool).at[1, 1].set(True)
        d = np.asarray(wavefront_distance(occ, seed, impl="kernel"))
        assert d[1, 1] == 0 and d[1, 2] == 1 and d[0, 1] == 1

    def test_multi_source(self):
        occ, seed = self._random_case(21, 12, 18, p_occ=0.2, n_seeds=3)
        d = np.asarray(wavefront_distance(occ, seed, impl="kernel"))
        np.testing.assert_array_equal(
            d, np.asarray(wavefront_distance_ref(occ, seed)))
        assert (d[np.asarray(seed)] == 0).all()


class TestKernelChoiceOnTPU:
    """With the backend reported as "tpu", the main path takes the
    compiled Pallas kernels and the device scan engine: no interpret
    mode and no host engine on a chip.  The kernels are stubbed; only
    the choice is under test."""

    @pytest.fixture(autouse=True)
    def _tpu_backend(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def test_traced_wavefront_takes_compiled_kernel(self, monkeypatch):
        from repro.kernels.maze_route import ops
        calls = []

        def kernel(occ, seed, *, interpret):
            calls.append(interpret)
            return jnp.zeros(occ.shape, jnp.int32)

        monkeypatch.setattr(ops, "wavefront_kernel", kernel)
        occ = jnp.zeros((5, 9), bool)
        seed = occ.at[2, 3].set(True)
        jax.make_jaxpr(wavefront_distance)(occ, seed)
        assert calls == [False]

    def test_rank_kernel_not_interpreted(self, monkeypatch):
        from repro.kernels.pareto_dom import ops
        calls = []

        def kernel(f, *, interpret):
            calls.append(interpret)
            return jnp.zeros(f.shape[0], jnp.int32)

        monkeypatch.setattr(ops, "nds_rank_kernel", kernel)
        non_dominated_rank(jnp.ones((10, 4)))
        assert calls == [False]

    def test_batched_route_takes_scan_engine(self, monkeypatch):
        from repro.eda import batched_flow
        calls = []

        def route_program(occ0, nets, *, capacity, use_kernel):
            calls.append(use_kernel)
            zeros = jnp.zeros(occ0.shape[0], jnp.int32)
            return occ0, zeros, zeros, zeros, (zeros, zeros, zeros)

        monkeypatch.setattr(batched_flow, "_route_program", route_program)
        b, n = 2, 3
        nets = batched_flow.NetBatch(
            jnp.zeros((b, n, 2), jnp.int32), jnp.zeros((b, n, 2, 2), jnp.int32),
            jnp.ones((b, n, 2), bool), jnp.ones((b, n), bool))
        out = batched_flow.batched_route(nets, np.array([640, 512]),
                                         np.array([320, 384]))
        assert out.engine == "scan" and calls == [None]
