"""Find an open-loop cell's knee: the highest rate the service sustains.

    python3 bench/knee.py --workload survey_open --rates 8,16,24,32 --seconds 15

Sets the cell up once, then offers each rate for `--seconds` in turn on
the same service, each rate's requests warmed first as the cell's
set-up warms its own, and prints per rate the requests sent and completed,
p50/p95 latency (due -> collected) and the backlog trend: the median
latency of the last quarter of the requests over that of the first.
A rate is sustained while the trend stays near 1.  Run once, on the
chip, when a cell is defined; the cell's mix then fixes 0.8 x the knee.
Not part of a benchmark run.
"""
import argparse
import json
import pathlib
import statistics
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

import loadgen  # noqa: E402
import run  # noqa: E402
from compile_clock import CompileClock  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = run.load_cell(args.workload)
    run.require_chips(cell["chips"])
    clock = CompileClock()
    system = loadgen.System(cell["config"])
    traffic = dict(cell["traffic"])
    Driver = loadgen.driver(traffic["kind"])
    driver = Driver(system, traffic, args.seed, args.seconds)
    driver.setup()
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic["rate_per_s"] = rate
        step = Driver(system, traffic, args.seed + 1 + k, args.seconds)
        step.warm(step.schedule)
        step.session, step.svc = driver.session, driver.svc
        built = clock.snapshot()[1]
        win = step.window()
        built = clock.snapshot()[1] - built
        lat = win.latency_s
        q = max(len(lat) // 4, 1)
        trend = statistics.median(lat[-q:]) / statistics.median(lat[:q])
        print(json.dumps({
            "rate_per_s": rate, "sent": len(win.requests),
            "completed": len(lat),
            "p50_s": loadgen.percentile(lat, 50),
            "p95_s": loadgen.percentile(lat, 95),
            "trend": trend, "dispatches": win.stats.get("explorer_dispatches"),
            "late_max_s": max(win.lateness_s),
            "built_in_window": built}), flush=True)
    driver.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
