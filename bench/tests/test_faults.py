"""A run drives the whole harness, and `correct` comes out false when the
timed path is broken underneath it.

Each test runs a cell cut to CPU size (`tiny.py`) with the look for a chip
skipped.  The faults a one-chip design-service cell can have: an answer
altered where it is produced (a front's metric, a layout row), half of
a coalesced batch left out and answered from the rest, and half of the
population evaluated.  The control, the reference in bfloat16 in the
program's place, gets the same verdict function and has to fail it.
"""
import dataclasses
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

import check
import loadgen
import run
import tiny

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _run(cell, seed=2 ** 33 + 5):
    return run.run(cell, seed, 3.0, False, require=tiny.any_device)


@pytest.fixture(scope="module")
def sweep():
    return tiny.cell("sweep_layout")


@pytest.fixture(scope="module")
def survey():
    return tiny.cell("survey_open", rate_per_s=3.0)


def test_sound_runs_are_correct(sweep, survey):
    for cell in (sweep, survey):
        res = _run(cell)
        assert res["correct"], res["checks"]
        assert res["attempted"] > 0 and res["failed"] == 0
        assert list(res)[-1] == "checks"


def test_an_altered_answer_is_caught(survey, monkeypatch):
    from repro.core import explorer

    made = explorer.pareto_result_from_population

    def altered(*a, **k):
        res = made(*a, **k)
        metrics = dict(res.metrics)
        metrics["tops"] = metrics["tops"] * np.where(
            np.arange(len(res)) == 0, 1.02, 1.0).astype(np.float32)
        return dataclasses.replace(res, metrics=metrics)

    monkeypatch.setattr(explorer, "pareto_result_from_population", altered)
    res = _run(survey)
    assert not res["correct"]
    assert res["checks"]["obj_gap"]["value"] > res["checks"]["obj_gap"]["limit"]


def test_an_altered_layout_row_is_caught(sweep, monkeypatch):
    from repro.eda.batched_flow import BatchedLayoutResult

    rows = BatchedLayoutResult.metrics_rows

    def altered(self):
        out = rows(self)
        out[0] = dict(out[0], wirelength=out[0]["wirelength"] + 1)
        return out

    monkeypatch.setattr(BatchedLayoutResult, "metrics_rows", altered)
    res = _run(sweep)
    assert not res["correct"]
    assert res["checks"]["layout_mismatch"]["value"] > 0


def test_half_the_batch_left_out_is_caught(sweep, monkeypatch):
    from repro.api import session as session_mod

    explore = session_mod.explore_cells

    def half(cells, **kw):
        cells = list(dict.fromkeys(cells))
        done = explore(cells[:max(len(cells) // 2, 1)], **kw)
        first = done[cells[0]]
        return {c: done.get(c, first) for c in cells}

    monkeypatch.setattr(session_mod, "explore_cells", half)
    res = _run(sweep)
    assert not res["correct"]


def test_half_the_population_is_caught(survey, monkeypatch):
    from repro.api import session as session_mod

    explore = session_mod.explore_cells

    def half(cells, *, pop_size, program, **kw):
        return explore(cells, pop_size=pop_size // 2, **kw)

    monkeypatch.setattr(session_mod, "explore_cells", half)
    res = _run(survey)
    assert not res["correct"]
    assert (res["checks"]["front_missed"]["value"]
            > res["checks"]["front_missed"]["limit"])


def test_the_control_is_not_correct(survey):
    system = loadgen.System(survey["config"])
    mix = survey["traffic"]
    driver = loadgen.driver(mix["kind"])(system, mix, 11, 3.0)
    driver.setup()
    try:
        win = driver.window()
    finally:
        driver.close()
    groups = run._groups(win)
    sound, _ = check.verdict(check.numbers(survey["config"], groups))
    control, checks = check.verdict(
        check.numbers(survey["config"], groups, control=True))
    assert sound and not control
    assert checks["obj_gap"]["value"] > checks["obj_gap"]["limit"]


def _bench(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


ARGS = ["--workload", "survey_open", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def test_no_chip_no_result():
    proc = _bench(ARGS, ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_the_benchmark_alone_gives_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _bench(ARGS, tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
