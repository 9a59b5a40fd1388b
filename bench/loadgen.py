"""What the benchmark's traffic drivers share.

A traffic mix is a data file, `bench/traffic/<mix>.json`; its `kind`
names the driver that reads it, `bench/traffic/<kind>.py`, whose
`Driver(system, mix, seed, seconds)` has `setup()`, `window(tracer)`
(a `WindowResult`) and `close()`.  A later mix of a known kind is a data
file alone; a new kind is a new driver file beside it.

Here: seeded random streams, the system under test built from the
configuration file (`System`), what a window produced (`WindowResult`),
the service's counter deltas, and the percentile the metrics use.  The
drivers use only the service's public surface: `DesignService`
`submit`/`collect`/`stats` under `serve()`, and `DesignSession
.fronts_for` once the window has closed.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import pathlib
import sys

import numpy as np

TRAFFIC = pathlib.Path(__file__).resolve().parent / "traffic"
SEED_SPACE = 2 ** 31 - 1      # request seeds fit a signed 32-bit key


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per purpose, from any non-negative seed
    (also one above 32 bits)."""
    tag = int.from_bytes(stream.encode(), "little")
    return np.random.default_rng([int(seed) % (2 ** 64), tag])


def distinct_seeds(rng: np.random.Generator, n: int) -> list[int]:
    out: dict[int, None] = {}
    while len(out) < n:
        for v in rng.integers(0, SEED_SPACE, size=n - len(out)):
            out[int(v)] = None
    return list(out)


def percentile(values, q: float) -> float:
    """The q-th percentile, linear between closest ranks."""
    xs = sorted(values)
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def load_module(path: pathlib.Path, name: str):
    """The module in file `path`, loaded once under `name`."""
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def driver(kind: str):
    """The driver class of a mix's `kind`: `bench/traffic/<kind>.py`."""
    return load_module(TRAFFIC / f"{kind}.py", f"traffic_{kind}").Driver


# ----------------------------------------------------------------------
# The system under test, built from the configuration file
# ----------------------------------------------------------------------
class System:
    """Builds requests, sessions and services as the configuration says."""

    def __init__(self, cfg: dict):
        from repro.api import DesignRequest, Requirements
        from repro.core.constants import CalibConstants

        self.cfg = cfg
        req = dict(cfg["request"])
        self.requirements = Requirements(**req.pop("requirements"))
        self.cal = CalibConstants(**cfg["cal"])
        self._base = req
        self._DesignRequest = DesignRequest

    def request(self, array_size: int, seed: int):
        return self._DesignRequest(array_size=array_size, seed=seed,
                                   cal=self.cal,
                                   requirements=self.requirements,
                                   **self._base)

    def session(self):
        from repro.api import DesignSession

        return DesignSession(mesh=self.cfg["session"]["mesh"])

    def service(self, session):
        from repro.serve.design_service import DesignService

        return DesignService(session, **self.cfg["service"]).serve()


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class WindowResult:
    """What a driver's window produced, for the metrics and the check."""
    requests: list            # DesignRequest per attempted request
    artifacts: list           # DesignArtifact or None (never collected)
    latency_s: list           # due -> collected, per collected request
    seconds: float            # the window, to the end of the work it counts
    sessions: list            # the sessions that served the window
    stats: dict               # summed counter deltas of the services
    lateness_s: list = dataclasses.field(default_factory=list)
    designs: int = 0          # laid-out designs delivered
    traced_stats: dict | None = None   # counter deltas of the traced part


def stat_delta(before: dict, after: dict) -> dict:
    out = {}
    for k, v in after.items():
        if isinstance(v, bool):
            continue
        if isinstance(v, (int, float)):
            out[k] = v - before.get(k, 0)
        elif k == "stage_busy_s":
            out[k] = {s: v[s] - before.get(k, {}).get(s, 0.0) for s in v}
    return out


def add_stats(total: dict, delta: dict) -> None:
    for k, v in delta.items():
        if isinstance(v, dict):
            sub = total.setdefault(k, {})
            for s, x in v.items():
                sub[s] = sub.get(s, 0.0) + x
        else:
            total[k] = total.get(k, 0) + v
