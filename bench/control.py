"""Readings for the limits of `correct`: the program's, and the control's.

    python3 bench/control.py --workload sweep_layout --seeds 12 --seconds 12

Sets the cell up once, then for each seed runs a short window at the
cell's own load and size and computes every compared number twice: from
what the program served (the lower readings) and with the plain
reference, evaluated in bfloat16, put in the place of every served
metric (the control, the upper readings), with the verdict each would
get.  Prints one JSON line per seed
and the per-metric widest gaps, then the largest program reading and the
smallest control reading of each number.  Not part of a benchmark run.
"""
import argparse
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

import check  # noqa: E402
import loadgen  # noqa: E402
import run  # noqa: E402
from reference import estimator as ref_est  # noqa: E402


def metric_gaps(groups, ref: check.Reference) -> dict:
    """Widest gap of each metric column over the window's fronts."""
    out: dict = {}
    for session, pairs in groups:
        ok = [r for r, a in pairs if a is not None and a.ok]
        for r, front in session.fronts_for(ok).items():
            rep = ref.report([s.as_tuple() for s in front.specs])
            for name in ref_est.METRICS:
                if name in front.metrics:
                    g = check._gap(front.metrics[name], rep[name])
                    out[name] = max(out.get(name, 0.0), g)
    return out


def plant_half_population() -> None:
    from repro.api import session as session_mod

    explore = session_mod.explore_cells

    def half(cells, *, pop_size, program, **kw):
        return explore(cells, pop_size=pop_size // 2, **kw)

    session_mod.explore_cells = half


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--first-seed", type=int, default=5_000_000_000)
    ap.add_argument("--fault", choices=["half_population"])
    args = ap.parse_args()
    if args.fault == "half_population":
        plant_half_population()
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = run.load_cell(args.workload)
    run.require_chips(cell["chips"])
    system = loadgen.System(cell["config"])
    traffic = cell["traffic"]
    Driver = loadgen.driver(traffic["kind"])
    driver = Driver(system, traffic, args.first_seed, args.seconds)
    driver.setup()
    ref = check.Reference(cell["config"])
    lows, highs = {}, {}
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        step = Driver(system, traffic, seed, args.seconds)
        if hasattr(driver, "svc"):         # one long-lived service
            step.session, step.svc = driver.session, driver.svc
        win = step.window()
        groups = run._groups(win)
        prog = check.numbers(cell["config"], groups, ref=ref)
        ctrl = check.numbers(cell["config"], groups, control=True, ref=ref)
        for name, v in prog.items():
            lows[name] = max(lows.get(name, v), v)
        for name, v in ctrl.items():
            highs[name] = min(highs.get(name, v), v)
        print(json.dumps({"seed": seed, "requests": len(win.requests),
                          "program": prog, "control": ctrl,
                          "program_correct": check.verdict(prog)[0],
                          "control_correct": check.verdict(ctrl)[0],
                          "metric_gaps": metric_gaps(groups, ref)}),
              flush=True)
    driver.close()
    print(json.dumps({"lower_readings": lows, "control_readings": highs,
                      "seeds": args.seeds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
