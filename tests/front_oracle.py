"""The explore stage's eager front path, kept as the oracle of
`explorer.front_program`.

Per cell: `np.unique` over the population's genes, the non-dominated
mask of the unique rows' objectives, and `estimator.evaluate_report` over
the survivors, each an eager device call.  `Recorder` captures what the
explorers hand `explorer.pareto_result_from_population`, so a test can
hold each served front against the oracle on the same population.
"""
import jax.numpy as jnp
import numpy as np

from repro.core import estimator, explorer, pareto
from repro.core.constants import CAL28


def eager_front(array_size, genes, objs, cal=CAL28):
    """(specs as (h, w, l, b) tuples in order, metrics dict)."""
    genes, objs = np.asarray(genes), np.asarray(objs)
    uniq, idx = np.unique(genes, axis=0, return_index=True)
    objs_u = objs[idx]
    mask = np.asarray(pareto.non_dominated_mask(jnp.asarray(objs_u)))
    g = uniq[mask]
    h = (2 ** g[:, 0]).astype(np.int64)
    w = array_size // h
    l = (2 ** g[:, 1]).astype(np.int64)
    b = g[:, 2].astype(np.int64)
    rep = estimator.evaluate_report(
        *(x.astype(np.float32) for x in (h, w, l, b)), cal)
    specs = list(zip(h.tolist(), w.tolist(), l.tolist(), b.tolist()))
    return specs, {k: np.asarray(v) for k, v in rep.items()}


def assert_matches(result, array_size, genes, objs, cal=CAL28):
    """Same specs in the same order; metrics float32 within 1e-5."""
    specs, metrics = eager_front(array_size, genes, objs, cal)
    assert [(s.h, s.w, s.l, s.b_adc) for s in result.specs] == specs
    assert list(result.metrics) == list(metrics)
    for k, v in metrics.items():
        assert result.metrics[k].dtype == np.float32, k
        np.testing.assert_allclose(result.metrics[k], v, rtol=1e-5,
                                   atol=0, err_msg=k)


class Recorder:
    """Wraps `explorer.pareto_result_from_population` on the module (as
    the explorers look it up at call time) and keeps every call."""

    def __init__(self, monkeypatch):
        self.calls = []
        made = explorer.pareto_result_from_population

        def record(array_size, genes, objs, cal=CAL28, **kw):
            res = made(array_size, genes, objs, cal, **kw)
            self.calls.append({"array_size": array_size, "genes": genes,
                               "objs": objs, "cal": cal, "kw": kw,
                               "result": res})
            return res

        monkeypatch.setattr(explorer, "pareto_result_from_population",
                            record)
