"""The program's own spans in a profiler trace: where the host time went
and which host work the device waited on.

The design service opens every span as a `design.<cat>.<name>` profiler
annotation (`repro.telemetry.spans.trace_span`).  From a trace this
reads those host events beside the harness's `bench.*` spans and the
device operations `trace_reduce` reads, and gives, over the traced
window (`bench.traced_window`):

  * `span_s` / `span_n`: per `design.*` name, its self time (its
    duration less that of the `design.*` spans nested in it on the same
    thread) and its count, clipped to the window;
  * `idle_by_span`: the device's idle seconds, each idle stretch named
    by the innermost span open during it.  A program span counts as
    nested in any harness span open around it, on whatever thread;
    among spans of one kind the latest-started is the innermost;
  * `idle_gaps`: the longest idle gaps, named as `idle_by_span` names
    them at each gap's middle;
  * `explore_host_ms_per_dispatch`: the self time of
    `design.explore.launch` plus `design.explore.postprocess`, in ms per
    execution of the explore program (`jit_sweep_program`);
  * `layout_host_idle_share`: the idle time named by a `design.layout.*`
    or `design.stage.layout` span, in percent of the window.

A program without these annotations (before they existed) gives empty
`span_s` and `idle_by_span` and no derived numbers.

    python3 bench/program_spans.py --workload <cell> --seed <n> \\
        --seconds <s> [--keep DIR]

runs one traced run of the cell (as `bench/run.py --trace 1` does, on a
TPU), prints the run's result line, then one JSON line of the above.
`--reduce DIR` reduces a trace already captured under DIR instead.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import json
import pathlib
import sys
import tempfile

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

import trace_reduce  # noqa: E402

PREFIX = "design."
OUTSIDE = "host: outside the harness spans"
LAYOUT = ("design.layout.", "design.stage.layout")


def read_design_spans(path: str) -> list:
    """(name, start_ns, end_ns, thread) of every `design.*` host event;
    `thread` tells the host threads apart."""
    from jax.profiler import ProfileData

    out = []
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        if not plane.name.startswith("/host:"):
            continue
        for t, line in enumerate(plane.lines):
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns, (p, t))
                    for e in line.events if e.name.startswith(PREFIX)]
    return out


def self_times(design: list, lo: int, hi: int):
    """({name: self seconds}, {name: count}) within [lo, hi]."""
    self_ns = collections.Counter()
    count = collections.Counter()
    by_thread = collections.defaultdict(list)
    for name, s, e, thread in design:
        if e > lo and s < hi:
            by_thread[thread].append((max(s, lo), min(e, hi), name))
    for spans in by_thread.values():
        spans.sort(key=lambda x: (x[0], -x[1]))
        stack: list = []
        for s, e, name in spans:
            while stack and stack[-1][1] <= s:
                stack.pop()
            count[name] += 1
            self_ns[name] += e - s
            if stack:
                parent = stack[-1]
                self_ns[parent[2]] -= min(e, parent[1]) - s
            stack.append((s, e, name))
    return ({n: v * 1e-9 for n, v in self_ns.items()}, dict(count))


def _segments(spans: list, lo: int, hi: int) -> list:
    """[(start, end, label)] covering [lo, hi], the label being the
    innermost of `spans` ((name, start, end, is_program)) open there."""
    ev = []
    for i, (_, s, e, _) in enumerate(spans):
        s, e = max(s, lo), min(e, hi)
        if s < e:
            ev += [(s, 1, i), (e, 0, i)]
    ev.sort()

    def depth(i):
        name, s, e, program = spans[i]
        return (program, s, -e)

    out, active, t = [], set(), lo
    for at, starts, i in ev + [(hi, 0, None)]:
        if at > t:
            label = (spans[max(active, key=depth)][0] if active
                     else OUTSIDE)
            out.append((t, at, label))
            t = at
        if i is None:
            break
        (active.add if starts else active.discard)(i)
    return out


def _idle(busy_iv: list, lo: int, hi: int) -> list:
    gaps, prev = [], lo
    for s, e in list(busy_iv) + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, min(s, hi)))
        prev = max(prev, e)
    return [(s, e) for s, e in gaps if e > s]


def reduce(devices: dict, bench_spans: list, design: list,
           top: int = 10) -> dict:
    red = trace_reduce.reduce(devices, bench_spans)
    win = [(s, e) for n, s, e in bench_spans if n == "bench.traced_window"]
    lo, hi = win[0] if win else (0, 0)
    span_s, span_n = self_times(design, lo, hi)
    labelled = ([(n, s, e, False) for n, s, e in bench_spans
                 if n != "bench.traced_window"]
                + [(n, s, e, True) for n, s, e, _ in design])
    segs = _segments(labelled, lo, hi)
    starts = [s for s, _, _ in segs]
    first = min(devices) if devices else None
    busy = []
    if first is not None:
        ops = [(s, e) for _, s, e, _ in devices[first].ops
               if e > lo and s < hi]
        mods = [(s, e) for _, s, e in devices[first].modules
                if e > lo and s < hi]
        busy = trace_reduce._union(ops or mods)
    idle = _idle(busy, lo, hi) if first is not None else []
    by_span = collections.Counter()
    for s, e in idle:
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(segs) and segs[i][0] < e:
            a, b, label = segs[i]
            by_span[label] += (min(b, e) - max(a, s)) * 1e-9
            i += 1
    gaps = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
        i = bisect.bisect_right(starts, (s + e) // 2) - 1
        gaps.append([segs[i][2] if i >= 0 else OUTSIDE, (e - s) * 1e-9])
    out = {"window_s": red["window_s"], "busy_s": red["busy_s"],
           "span_s": span_s, "span_n": span_n,
           "idle_by_span": dict(by_span), "idle_gaps": gaps}
    runs = sum(v for k, v in red["module_n"].items()
               if "sweep_program" in k)
    host = sum(span_s.get(f"design.explore.{n}", 0.0)
               for n in ("launch", "postprocess"))
    if runs and host:
        out["explore_host_ms_per_dispatch"] = 1000.0 * host / runs
    layout = sum(v for k, v in by_span.items() if k.startswith(LAYOUT))
    if span_s and red["window_s"]:
        out["layout_host_idle_share"] = 100.0 * layout / red["window_s"]
    return out


def reduce_dir(trace_dir: str) -> dict:
    path = trace_reduce.find_xplane(trace_dir)
    return reduce(*trace_reduce.read(path), read_design_spans(path))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reduce", metavar="DIR",
                    help="reduce the trace captured under DIR")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--keep", metavar="DIR",
                    help="where to keep the run's trace (default: a "
                         "temporary directory, removed)")
    args = ap.parse_args(argv)
    if args.reduce:
        print(json.dumps(reduce_dir(args.reduce)), flush=True)
        return 0
    if not (args.workload and args.seed is not None and args.seconds):
        ap.error("give --reduce DIR, or --workload, --seed and --seconds")
    import run

    cell = run.load_cell(args.workload)
    with tempfile.TemporaryDirectory() as tmp:
        trace_dir = pathlib.Path(args.keep or tmp)
        try:
            result = run.run(cell, args.seed, args.seconds, True,
                             trace_dir=trace_dir)
        except run.NoChip as e:
            run.log(f"bench: {e}")
            return 2
        print(json.dumps(result), flush=True)
        print(json.dumps(reduce_dir(str(trace_dir))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
