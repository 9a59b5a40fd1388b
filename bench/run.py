"""The design service's chip benchmark: one run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of `workloads` in `BENCHMARK.json`: a configuration
(`bench/configs/<config>.json`: the requests, the service's settings,
the calibration and the guarantees) under a traffic mix
(`bench/traffic/<traffic>.json`, read by the driver its `kind` names,
`bench/traffic/<kind>.py`).  A run builds the system as the
configuration says, warms up every shape the mix uses (set-up), measures
for `--seconds`, then checks what the window served against the plain
reference in `bench/reference/` (`check.py`).

With `--trace 0` the result's metrics are the cell's end-to-end metrics,
each read by `bench/end_to_end/<base>.py`; with `--trace 1` a part of the
window is profiled and the metrics are the cell's per-layer metrics,
each read by `bench/layer_metrics/<base>.py`.  `<base>` is the metric's
name up to its first `.`, so `device_idle_share.sweep` and
`device_idle_share.survey` share one reader.
The last line of standard output is one JSON object; the numbers
compared for `correct` come last in it, and as the last lines of
standard error.  Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
# the TPU runtime logs to a fixed /tmp path unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_cell(name: str, root: pathlib.Path = ROOT) -> dict:
    """The cell, its configuration, its mix and its metrics, by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return {
        "name": name, "chips": w["chips"],
        "config": json.loads((root / cfg_entry["file"]).read_text()),
        "traffic": json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def require_chips(n: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devices[0].platform!r}")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devices)}")
    return devices


def reader(kind: str, metric: str):
    """`bench/<kind>/<base>.py`'s `read(ctx)`, `<base>` being the metric's
    name up to its first `.`."""
    import loadgen

    base = metric.split(".")[0]
    return loadgen.load_module(BENCH / kind / f"{base}.py",
                               f"{kind}_{base}").read


def read_metrics(kind: str, metrics: list, ctx: dict) -> dict:
    """{name: {"value", "unit"}} of each metric whose reader finds
    something to read."""
    out = {}
    for m in metrics:
        v = reader(kind, m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


class Tracer:
    """Profiles the first `seconds` of what the driver brackets, with a
    `bench.traced_window` host span around it.  The driver starts and
    stops it from one thread."""

    def __init__(self, out_dir: pathlib.Path, seconds: float):
        self.dir, self.seconds = out_dir, seconds
        self._ann = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        # host spans at level 1 (the harness's annotations, dispatches),
        # no Python tracer: it slows a layout job more than tenfold
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench.traced_window")
        self._ann.__enter__()

    def maybe_stop(self, elapsed: float) -> None:
        if elapsed >= self.seconds:
            self.stop()

    def stop(self) -> None:
        import jax

        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
            jax.profiler.stop_trace()


def run(cell: dict, seed: int, seconds: float, trace: bool, *,
        require=require_chips, trace_dir: pathlib.Path | None = None
        ) -> dict:
    """One run of `cell`; returns the result object (not yet printed)."""
    from repro.runtime.compile_cache import enable_compile_cache

    import check
    import loadgen
    from compile_clock import CompileClock

    enable_compile_cache()
    devices = require(cell["chips"])
    clock = CompileClock()
    system = loadgen.System(cell["config"])
    traffic = cell["traffic"]
    driver = loadgen.driver(traffic["kind"])(system, traffic, seed, seconds)
    driver.setup()
    setup_s = time.time() - T_START
    tracer = None
    if trace:
        tracer = Tracer(trace_dir or ROOT / ".bench_trace" / cell["name"],
                        float(traffic["trace_seconds"]))
    c0 = clock.snapshot()
    win = driver.window(tracer)
    c1 = clock.snapshot()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    driver.close()
    lateness = win.lateness_s or [0.0]
    lat = win.latency_s or [0.0]
    pct = loadgen.percentile
    log(f"window: {len(win.requests)} requests in {win.seconds:.6f} s; "
        f"latency p50 {pct(lat, 50):.6f} s, p95 "
        f"{pct(lat, 95):.6f} s; generator late by mean "
        f"{statistics.fmean(lateness):.6f} s, max {max(lateness):.6f} s; "
        f"peak_bytes_in_use {peak}; programs built in the window: "
        f"{c1[1] - c0[1]}, of them {c1[2] - c0[2]} loaded from the "
        f"persistent cache")

    groups = _groups(win)
    nums = check.numbers(cell["config"], groups)
    correct, checks = check.verdict(nums)
    attempted = len(win.requests)
    failed = nums["failed"]
    dev = devices[0]
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    if trace:
        import trace_reduce

        red = trace_reduce.reduce_dir(str(tracer.dir))
        if trace_dir is None:
            shutil.rmtree(tracer.dir, ignore_errors=True)
        ctx = {"window": win, "trace": red,
               "compiles_in_window": c1[1] - c0[1]}
        metrics = read_metrics("layer_metrics", cell["per_layer"], ctx)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result.update(metrics=metrics, device=device,
                      breakdown=red["breakdown"])
    else:
        ctx = {"window": win, "setup_s": setup_s}
        result.update(metrics=read_metrics("end_to_end", cell["end_to_end"],
                                           ctx), device=device)
    result["checks"] = checks
    return result


def _groups(win) -> list:
    """(session, [(request, artifact)]) per serving session."""
    per = len(win.requests) // len(win.sessions)
    return [(s, list(zip(win.requests[i * per:(i + 1) * per],
                         win.artifacts[i * per:(i + 1) * per])))
            for i, s in enumerate(win.sessions)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
