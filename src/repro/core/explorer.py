"""MOGA-based design-space explorer (paper Sec. 3.2) with agile filtering.

`explore()` runs NSGA-II for a user-given array size and returns a
`ParetoResult`: the deduplicated Pareto-frontier set with both raw objective
values and human-oriented metrics.  `ParetoResult.filter(...)` implements the
paper's "agile interaction": users prune the frontier with application
requirements (min SNR, min throughput, max energy, max area) before handing
the survivors to the netlist generator / placer / router
(`repro.eda.flow.generate_layout`).

One-compile sweep contract: every front-end path bottoms out in
`repro.core.batched_explorer.explore_cells` — the array size, gene
bounds, and calibration constants are traced operands of a single
compiled NSGA-II program (`repro.core.nsga2.run_cell`), so a whole
(array_size x seed) sweep is one trace, one compile, and one device
dispatch.  The per-cell fronts are identical to the sequential
`nsga2.run` reference path.

Front-end note: the supported way to drive the flow is `repro.api`
(`DesignRequest` / `DesignSession` / the multi-tenant
`repro.serve.design_service.DesignService`).  `explore()`,
`explore_sizes()` and `distill_and_layout()` below are deprecation
shims over it, kept for source compatibility.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import estimator, nsga2, pareto
from repro.core.acim_spec import MacroSpec
from repro.core.constants import CAL28, CalibConstants
from repro.telemetry.spans import trace_span


@dataclasses.dataclass(frozen=True)
class ParetoResult:
    array_size: int
    specs: tuple[MacroSpec, ...]          # deduplicated Pareto-frontier set
    metrics: dict                          # name -> np.ndarray aligned w/ specs

    def __len__(self) -> int:
        return len(self.specs)

    def filter(self, *, min_snr_db: float = -np.inf, min_tops: float = 0.0,
               max_energy_fj: float = np.inf, max_area: float = np.inf,
               min_tops_per_w: float = 0.0) -> "ParetoResult":
        """Agile user distillation of the Pareto set (paper Fig. 4, arrow
        'remove undesired solutions')."""
        if not self.specs:
            raise ValueError(
                "cannot filter an empty Pareto frontier (an earlier filter "
                "already removed every solution)")
        m = self.metrics
        keep = ((m["snr_db"] >= min_snr_db) & (m["tops"] >= min_tops)
                & (m["energy_fj_per_mac"] <= max_energy_fj)
                & (m["area_f2_per_bit"] <= max_area)
                & (m["tops_per_w"] >= min_tops_per_w))
        idx = np.nonzero(keep)[0]
        return ParetoResult(
            self.array_size,
            tuple(self.specs[i] for i in idx),
            {k: v[idx] for k, v in m.items()},
        )

    def best(self, metric: str, maximize: bool = True) -> MacroSpec:
        if not self.specs:
            raise ValueError(
                f"cannot select best({metric!r}) from an empty Pareto "
                f"frontier; relax the filter requirements")
        v = self.metrics[metric]
        i = int(np.argmax(v) if maximize else np.argmin(v))
        return self.specs[i]

    def to_rows(self) -> list[dict]:
        rows = []
        for i, s in enumerate(self.specs):
            row = {"h": s.h, "w": s.w, "l": s.l, "b_adc": s.b_adc}
            row.update({k: float(v[i]) for k, v in self.metrics.items()})
            rows.append(row)
        return rows

    def to_json(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump({"array_size": self.array_size, "points": self.to_rows()},
                      f, indent=1)

    @classmethod
    def from_rows(cls, array_size: int, rows: list[dict]) -> "ParetoResult":
        """Rebuild from `to_rows()` output.  Metric arrays come back as
        float64 (exact widenings of the stored floats); an empty row list
        yields an empty frontier with no metric columns."""
        spec_keys = ("h", "w", "l", "b_adc")
        specs = tuple(MacroSpec(*(int(r[k]) for k in spec_keys))
                      for r in rows)
        metric_keys = [k for k in (rows[0] if rows else {})
                       if k not in spec_keys]
        metrics = {k: np.array([r[k] for r in rows]) for k in metric_keys}
        return cls(int(array_size), specs, metrics)

    @classmethod
    def from_json(cls, path: str) -> "ParetoResult":
        """Inverse of `to_json`: load a frontier back from disk."""
        with open(path) as f:
            d = json.load(f)
        return cls.from_rows(d["array_size"], d["points"])


@functools.partial(jax.jit, static_argnames=("cal",))
def front_program(genes, objs, spaces: nsga2.SpaceOperands, *,
                  cal: CalibConstants = CAL28):
    """What the host needs of B final populations, in one program.

    genes (B, N, 3) int32, objs (B, N, 4) float32, `spaces` the stacked
    operands of the B cells.  Returns `(mask, report)`: mask (B, N) is
    True where no row of the cell's population dominates the row, and
    report (B, len(REPORT_METRICS), N) float32 holds the
    `estimator.evaluate_report` columns of every row.  The shapes follow
    the sweep's (B, N), never the size of a front; `cal` is static.
    """
    mask = jax.vmap(pareto.non_dominated_mask)(objs)
    h, w, l, b = jax.vmap(nsga2.decode_op)(genes, spaces)
    rep = estimator.evaluate_report(h, w, l, b, cal)
    return mask, jnp.stack([rep[k] for k in estimator.REPORT_METRICS], 1)


def pareto_result_from_population(array_size: int, genes: np.ndarray,
                                  objs: np.ndarray,
                                  cal: CalibConstants = CAL28, *,
                                  mask: np.ndarray | None = None,
                                  report: np.ndarray | None = None
                                  ) -> ParetoResult:
    """Distill a final NSGA-II population into a `ParetoResult` (one
    `design.explore.postprocess` span per cell): its distinct genes, in
    sorted order, that no row of the population dominates.

    `mask` and `report` are this cell's rows of `front_program`'s
    output; the explorers compute them for the whole batch in one
    program.  Without them the cell runs `front_program` alone.
    Identical genes have identical objectives and do not dominate each
    other, so a gene's first row speaks for all of its rows.
    """
    with trace_span("postprocess", cat="explore", cells=1,
                    array_size=int(array_size)):
        genes = np.asarray(genes)
        if mask is None or report is None:
            space = jax.tree.map(
                lambda x: x[None],
                nsga2.host_space_operands(int(array_size), cal))
            mask, report = jax.device_get(front_program(
                genes[None], np.asarray(objs)[None], space, cal=cal))
            mask, report = mask[0], report[0]
        uniq, first = np.unique(genes, axis=0, return_index=True)
        keep = np.asarray(mask)[first]
        genes, rows = uniq[keep], first[keep]
        h = (2 ** genes[:, 0]).astype(np.int64)
        w = (array_size // h).astype(np.int64)
        l = (2 ** genes[:, 1]).astype(np.int64)
        b = genes[:, 2].astype(np.int64)
        specs = tuple(MacroSpec(int(hh), int(ww), int(ll), int(bb))
                      for hh, ww, ll, bb in zip(h, w, l, b))
        metrics = dict(zip(estimator.REPORT_METRICS,
                           np.asarray(report)[:, rows]))
    return ParetoResult(array_size, specs, metrics)


def _deprecated(old: str, new: str) -> None:
    warnings.warn(
        f"repro.core.explorer.{old} is deprecated; use {new} "
        f"(see docs/api.md)", DeprecationWarning, stacklevel=3)


def explore(array_size: int, *, pop_size: int = 256, generations: int = 80,
            seed: int = 0, cal: CalibConstants = CAL28,
            use_pallas_dominance: bool = False,
            use_pallas_rank: bool = False) -> ParetoResult:
    """Deprecated shim over `repro.api`: run the MOGA explorer for one
    array size and return the (undistilled) `ParetoResult`.

    Use `DesignSession().run(DesignRequest(array_size, layout=False))`
    instead; repeated shim calls share the process-wide default session's
    program and front caches."""
    from repro.api import DesignRequest, default_session

    _deprecated("explore", "repro.api.DesignSession.run")
    req = DesignRequest(array_size=array_size, seed=seed, pop_size=pop_size,
                        generations=generations, cal=cal,
                        use_pallas_dominance=use_pallas_dominance,
                        use_pallas_rank=use_pallas_rank, layout=False)
    return default_session().run(req).pareto


def explore_sizes(sizes=(4096, 16384, 65536), *, seed: int = 0,
                  **kw) -> dict[int, ParetoResult]:
    """Deprecated shim over `repro.api`: Fig. 9(a)(b)-style sweep over
    array sizes, coalesced by a `DesignService` into one compiled
    program / one dispatch for the whole sweep."""
    from repro.api import DesignRequest, default_session
    from repro.serve.design_service import DesignService

    _deprecated("explore_sizes", "repro.serve.design_service.DesignService")
    sizes = tuple(sizes)
    svc = DesignService(session=default_session(),
                        max_coalesce=max(len(sizes), 1))
    tickets = {int(s): svc.submit(DesignRequest(
        array_size=int(s), seed=seed, layout=False, **kw)) for s in sizes}
    arts = svc.run()
    return {s: arts[tickets[int(s)]].pareto for s in sizes}


def distill_and_layout(array_size: int, *, pop_size: int = 256,
                       generations: int = 80, seed: int = 0,
                       cal: CalibConstants = CAL28, coarse: int = 64,
                       capacity: int = 4, use_pallas_dominance: bool = False,
                       use_pallas_rank: bool = False, **filter_kw):
    """Deprecated shim over `repro.api`: MOGA sweep -> agile distillation
    -> batched layout generation (paper Fig. 4 end to end).

    `filter_kw` are `ParetoResult.filter` thresholds (the
    `repro.api.Requirements` fields).  Returns `(distilled, layouts)`
    exactly like `DesignSession.run(...)`'s artifact carries them."""
    from repro.api import DesignRequest, Requirements, default_session

    _deprecated("distill_and_layout", "repro.api.DesignSession.run")
    req = DesignRequest(array_size=array_size, seed=seed, pop_size=pop_size,
                        generations=generations, cal=cal,
                        use_pallas_dominance=use_pallas_dominance,
                        use_pallas_rank=use_pallas_rank,
                        requirements=Requirements(**filter_kw),
                        coarse=coarse, capacity=capacity, layout=True)
    artifact = default_session().run(req)
    return artifact.pareto, artifact.layouts


def full_design_space(array_size: int, cal: CalibConstants = CAL28):
    """Exhaustive enumeration of the (small, power-of-two) feasible space.

    The feasible space per array size is tiny (< 400 points), so exhaustive
    evaluation is tractable; the explorer's value is (a) fidelity to the
    paper's flow, (b) scaling to non-power-of-two/continuous extensions, and
    (c) this enumeration gives the tests a ground-truth Pareto front to
    compare NSGA-II against.
    """
    cfg = nsga2.NSGA2Config(array_size=array_size, cal=cal)
    h_lo, h_hi = cfg.h_exp_bounds
    l_lo, l_hi = cfg.l_exp_bounds
    b_lo, b_hi = cfg.b_bounds
    pts = [(he, le, b)
           for he in range(h_lo, h_hi + 1)
           for le in range(l_lo, min(l_hi, he) + 1)
           for b in range(b_lo, min(b_hi, he - le) + 1)]
    genes = jnp.asarray(np.array(pts, np.int32))
    objs = nsga2.evaluate(genes, cfg)
    return genes, objs
