"""End-to-end training convergence on the structured synthetic stream."""
import numpy as np
import pytest

from repro.configs import registry as creg
from repro.launch.mesh import make_mesh
from repro.train.trainer import TrainerConfig, train


@pytest.mark.slow
def test_reduced_lm_learns(tmp_path):
    cfg = creg.reduced("qwen3_8b")
    mesh = make_mesh((1, 1), ("data", "model"))
    tcfg = TrainerConfig(seq=128, global_batch=8, total_steps=60,
                         ckpt_every=1000, ckpt_dir=str(tmp_path), log_every=0)
    res = train(cfg, mesh, tcfg)
    first = float(np.mean(res.losses[:5]))
    last = float(np.mean(res.losses[-5:]))
    assert last < first - 0.25, (first, last)


@pytest.mark.slow
def test_microbatched_matches_full_batch(tmp_path):
    """Gradient accumulation is loss-equivalent to the monolithic batch."""
    cfg = creg.reduced("qwen2_5_3b")
    mesh = make_mesh((1, 1), ("data", "model"))
    runs = {}
    for mb in (1, 4):
        tcfg = TrainerConfig(seq=64, global_batch=8, total_steps=8,
                             ckpt_every=1000, microbatches=mb,
                             ckpt_dir=str(tmp_path / f"mb{mb}"), log_every=0)
        runs[mb] = train(cfg, mesh, tcfg).losses
    np.testing.assert_allclose(runs[1], runs[4], rtol=2e-2, atol=2e-2)
