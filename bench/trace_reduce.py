"""Reduce a JAX profiler trace (`.xplane.pb`) to device metrics.

What the benchmark reads from a trace, per device and then averaged over
the devices used:

  * busy: the union of the intervals in which an operation ran;
  * the traced window: the harness's `bench.traced_window` host span,
    else the extent of the device events;
  * per program ("module", the jitted function's name): device time and
    the number of executions;
  * collective time: operations that exchange data between chips;
  * the longest idle gaps, each named by the harness's own `bench.*`
    host span that was open in it (what the host was doing).

Two layouts of the trace are read.  On a TPU each device has a plane
`/device:TPU:<n>` whose line `XLA Ops` holds the operations and whose
line `XLA Modules` holds one event per program execution.  On the CPU
the operations are events on the host's threads that carry an
`hlo_module` stat and a `device_ordinal`.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os

import numpy as np

COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "reduce-scatter", "all-to-all")


@dataclasses.dataclass
class Device:
    ops: list = dataclasses.field(default_factory=list)      # (name, s, e, module)
    modules: list = dataclasses.field(default_factory=list)  # (name, s, e)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _module_name(name: str) -> str:
    """`jit__route_program(42)` -> `jit__route_program`."""
    return name.split("(")[0].strip()


def read(path: str):
    """(devices, host spans) of one trace file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict = collections.defaultdict(Device)
    spans = []
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and "SparseCore" not in name:
            dev = devices[int(name.split(":")[-1].split()[0])]
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                                 None) for e in line.events]
                elif line.name == "XLA Modules":
                    dev.modules += [(_module_name(e.name), e.start_ns,
                                     e.start_ns + e.duration_ns)
                                    for e in line.events]
        elif name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                        continue
                    if e.duration_ns <= 0:
                        continue
                    stats = dict(e.stats)
                    if "hlo_module" in stats:
                        dev = devices[int(stats.get("device_ordinal", 0))]
                        dev.ops.append((e.name, e.start_ns,
                                        e.start_ns + e.duration_ns,
                                        str(stats["hlo_module"])))
    return dict(devices), spans


def _union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce(devices: dict, spans: list) -> dict:
    """The benchmark's numbers from one trace; times in seconds."""
    win = [(s, e) for n, s, e in spans if n == "bench.traced_window"]
    if win:
        lo, hi = win[0]
    else:
        ev = [(s, e) for d in devices.values() for _, s, e, _ in d.ops]
        ev += [(s, e) for d in devices.values() for _, s, e in d.modules]
        lo = min((s for s, _ in ev), default=0)
        hi = max((e for _, e in ev), default=0)
    window_ns = max(hi - lo, 0)
    per = {}
    for dev_id, d in sorted(devices.items()):
        ops = [(n, *_clip(s, e, lo, hi), m) for n, s, e, m in d.ops
               if e > lo and s < hi]
        mods = sorted(((n, *_clip(s, e, lo, hi)) for n, s, e in d.modules
                       if e > lo and s < hi), key=lambda m: m[1])
        busy_iv = _union([(s, e) for _, s, e, _ in ops]
                         or [(s, e) for _, s, e in mods])
        # each program's device time: the union of its operations (an op
        # belongs to the program execution whose interval holds its start)
        starts = np.array([s for _, s, _ in mods])
        by_mod = collections.defaultdict(list)
        for _, s, e, m in ops:
            if m is None and mods:
                i = int(np.searchsorted(starts, s, side="right")) - 1
                m = mods[i][0] if i >= 0 and s < mods[i][2] else "(none)"
            by_mod[m].append((s, e))
        module_s = {m: sum(e - s for s, e in _union(iv)) * 1e-9
                    for m, iv in by_mod.items()}
        if not ops:
            module_s = collections.Counter()
            for n, s, e in mods:
                module_s[n] += (e - s) * 1e-9
        module_n = collections.Counter(n for n, _, _ in mods)
        coll_iv = [(s, e) for n, s, e, _ in ops
                   if any(c in n.lower() for c in COLLECTIVES)]
        coll_mods = set()
        if mods and coll_iv:
            for s, _ in coll_iv:
                i = int(np.searchsorted(starts, s, side="right")) - 1
                if i >= 0 and s < mods[i][2]:
                    coll_mods.add(i)
        per[dev_id] = {
            "busy_s": sum(e - s for s, e in busy_iv) * 1e-9,
            "busy_iv": busy_iv,
            "module_s": dict(module_s),
            "module_n": dict(module_n),
            "collective_s": sum(e - s for s, e in _union(coll_iv)) * 1e-9,
            "collective_modules": len(coll_mods),
        }
    n = max(len(per), 1)
    mods_all = collections.Counter()
    counts = collections.Counter()
    for p in per.values():
        for k, v in p["module_s"].items():
            mods_all[k] += v / n
        for k, v in p["module_n"].items():
            counts[k] += v / n
    first = per[min(per)] if per else None
    return {
        "devices": len(per),
        "window_s": window_ns * 1e-9,
        "busy_s": sum(p["busy_s"] for p in per.values()) / n,
        "module_s": dict(mods_all),
        "module_n": dict(counts),
        "collective_s": sum(p["collective_s"] for p in per.values()) / n,
        "collective_modules": (sum(p["collective_modules"]
                                   for p in per.values()) / n),
        "breakdown": {
            "device_ops": [[k, v] for k, v in mods_all.most_common(10)],
            "idle_gaps": (idle_gaps(first["busy_iv"], lo, hi, spans)
                          if first else []),
        },
    }


def idle_gaps(busy_iv: list, lo: int, hi: int, spans: list, top: int = 10):
    """The longest gaps between device operations inside the window,
    each named by the innermost `bench.*` host span open at its middle."""
    gaps = []
    prev = lo
    for s, e in busy_iv + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        mid = (s + e) // 2
        open_ = [(ss, n) for n, ss, ee in spans
                 if ss <= mid < ee and n != "bench.traced_window"]
        label = max(open_)[1] if open_ else "host: outside the harness spans"
        out.append([label, (e - s) * 1e-9])
    return out


def reduce_dir(trace_dir: str) -> dict:
    return reduce(*read(find_xplane(trace_dir)))
