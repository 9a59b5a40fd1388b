"""What decides `correct`: the window's answers against the plain reference.

Each number compared has a limit of its own, kept in `bench/limits.json`
with the readings it was set from (see PERF.md):

  * `failed`: requests that never got an artifact, or got an error;
  * `obj_gap`: the widest gap between a served metric (every column of
    every explored front, of every distilled set and the estimator
    columns of every layout row) and the float64 reference model, as
    |served - reference| / max(|reference|, 1);
  * `wrong_request`: served designs that do not answer the request:
    another array size than asked for;
  * `not_explored`: answers not produced by an explore dispatch in the
    window (`provenance.served_from`): a cache that made the window's
    work free would not measure the service;
  * `front_dominated`: served front points that another point of the
    same front dominates, by the reference's objectives, plus duplicate
    designs;
  * `distill_mismatch`: designs the distillation kept or dropped against
    the reference filter (points within 1e-5 of a threshold excepted);
  * `front_missed`: the widest share, over the window's explored fronts,
    of points in which a front differs from the exact Pareto set of the
    whole feasible space (float64 reference): points it lacks plus
    points it holds that are not on that set, over the set's size;
  * `layout_mismatch`: layout rows whose placement area, routed and
    failed nets, wirelength or DRC differ from the reference flow, over
    every distinct design of the window (each routed once).

`control=True` puts the reference, evaluated in bfloat16, in the place of
every served metric: the benchmark's control, which has to fail.
"""
from __future__ import annotations

import concurrent.futures
import json
import multiprocessing
import os
import pathlib

import numpy as np

from reference import estimator as ref_est
from reference import layout as ref_layout

LIMITS = pathlib.Path(__file__).resolve().parent / "limits.json"
ROW_EXACT = ("h", "w", "l", "b_adc", "routed_nets", "failed_nets",
             "wirelength", "drc_clean")


def limits() -> dict:
    return {k: v["limit"] for k, v in json.loads(LIMITS.read_text()).items()}


def _gap(served, want) -> float:
    served = np.asarray(served, np.float64)
    want = np.asarray(want, np.float64)
    if not served.size:
        return 0.0
    return float(np.max(np.abs(served - want) / np.maximum(np.abs(want), 1.0)))


class Reference:
    """Memoised reference reports and layout rows for one configuration."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self._reports: dict = {}
        self._rows: dict = {}
        self._pareto: dict = {}

    def report(self, specs, dtype=np.float64) -> dict:
        key = (tuple(specs), np.dtype(dtype).name)
        if key not in self._reports:
            h, w, l, b = (np.array(c, np.float64) for c in zip(*specs)) \
                if specs else [np.zeros(0)] * 4
            self._reports[key] = ref_est.report(h, w, l, b, self.cfg["cal"],
                                                dtype)
        return self._reports[key]

    def pareto(self, size: int) -> set:
        if size not in self._pareto:
            self._pareto[size] = ref_est.pareto_set(size, self.cfg["cal"])
        return self._pareto[size]

    def rows(self, specs) -> dict:
        """Reference layout rows of `specs`, routed in worker processes
        that import only the reference (one design each, largest first)."""
        todo = sorted(set(specs) - set(self._rows),
                      key=lambda s: -s[0] * s[1])
        if todo:
            workers = max(1, min(8, (os.cpu_count() or 2) - 1, len(todo)))
            ctx = multiprocessing.get_context("spawn")
            with concurrent.futures.ProcessPoolExecutor(
                    workers, mp_context=ctx) as pool:
                for spec, row in zip(todo, pool.map(
                        ref_layout.row, todo, [self.cfg] * len(todo))):
                    self._rows[spec] = row
        return {s: self._rows[s] for s in specs}


def _spec_tuples(result) -> list:
    return [s.as_tuple() for s in result.specs]


def _metric_columns(result, rep: dict, control_rep: dict | None) -> float:
    """Widest gap over the metric columns a served ParetoResult carries."""
    gap = 0.0
    for name in ref_est.METRICS:
        if name not in result.metrics:
            continue
        served = (np.asarray(control_rep[name], np.float64)
                  if control_rep is not None else result.metrics[name])
        gap = max(gap, _gap(served, rep[name]))
    return gap


def numbers(cfg: dict, groups, *, control: bool = False,
            ref: Reference | None = None) -> dict:
    """The compared numbers of one window.

    `groups` is a list of (session, [(request, artifact or None)]): the
    session that served each request, so its explored front can be read
    back with `fronts_for` (a front-cache hit, no dispatch)."""
    import ml_dtypes

    ref = ref or Reference(cfg)
    low = ml_dtypes.bfloat16 if control else None
    out = {"failed": 0, "obj_gap": 0.0, "wrong_request": 0,
           "not_explored": 0, "front_dominated": 0, "front_missed": 0.0,
           "distill_mismatch": 0}
    laid: list = []
    for session, pairs in groups:
        ok = [(r, a) for r, a in pairs if a is not None and a.ok]
        out["failed"] += len(pairs) - len(ok)
        out["not_explored"] += sum(a.provenance.served_from != "explorer"
                                   for _, a in ok)
        fronts = session.fronts_for([r for r, _ in ok]) if ok else {}
        for r, a in ok:
            front = fronts[r]
            specs = _spec_tuples(front)
            rep = ref.report(specs)
            crep = ref.report(specs, low) if control else None
            out["obj_gap"] = max(out["obj_gap"],
                                 _metric_columns(front, rep, crep))
            out["wrong_request"] += sum(
                h * w != r.array_size for h, w, _, _ in specs) + sum(
                h * w != r.array_size for h, w, _, _ in _spec_tuples(a.pareto))
            objs = ref_est.objectives(rep)
            out["front_dominated"] += (int(ref_est.dominated(objs).sum())
                                       + len(specs) - len(set(specs)))
            exact = ref.pareto(r.array_size)
            out["front_missed"] = max(out["front_missed"],
                                      len(exact ^ set(specs)) / len(exact))
            req = r.requirements.as_filter_kwargs()
            keep = ref_est.keep(rep, req)
            near = ref_est.near_threshold(rep, req)
            want = {s for s, k, n in zip(specs, keep, near) if k and not n}
            fuzzy = {s for s, n in zip(specs, near) if n}
            got = set(_spec_tuples(a.pareto)) - fuzzy
            out["distill_mismatch"] += len(want ^ got)
            dspecs = _spec_tuples(a.pareto)
            drep = ref.report(dspecs)
            out["obj_gap"] = max(out["obj_gap"], _metric_columns(
                a.pareto, drep, ref.report(dspecs, low) if control else None))
            if r.layout:
                if a.layout_rows is None or len(a.layout_rows) != len(dspecs):
                    out["failed"] += 1
                    continue
                laid += list(zip(dspecs, a.layout_rows))
    if any(r.layout for _, pairs in groups for r, _ in pairs):
        out["obj_gap"] = max(out["obj_gap"], _row_gap(laid, ref, low))
        out["layout_mismatch"] = _layout(laid, ref)
    return out


def _layout(laid, ref: Reference) -> int:
    """Rows that differ from the reference flow's row of their design."""
    want = ref.rows([s for s, _ in laid])
    return sum(any(row[k] != want[spec][k] for k in ROW_EXACT)
               or row["layout_area_f2_per_bit"]
               != want[spec]["layout_area_f2_per_bit"]
               for spec, row in laid)


def _row_gap(laid, ref: Reference, low) -> float:
    """Widest gap of the layout rows' estimator columns."""
    if not laid:
        return 0.0
    specs = [s for s, _ in laid]
    want = np.asarray(ref.report(specs)["area_f2_per_bit"], np.float64)
    if low is not None:
        served = np.asarray(ref.report(specs, low)["area_f2_per_bit"],
                            np.float64)
    else:
        served = np.array([r["estimator_area_f2_per_bit"] for _, r in laid])
    area = np.array([r["layout_area_f2_per_bit"] for _, r in laid])
    err = np.array([r["area_model_error"] for _, r in laid])
    return max(_gap(served, want), _gap(err, area / want - 1.0)
               if low is None else _gap(area / served - 1.0, area / want - 1.0))


def verdict(nums: dict, lim: dict | None = None) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every number within its
    limit."""
    lim = lim or limits()
    checks = {k: {"value": v, "limit": lim[k]} for k, v in nums.items()}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks

