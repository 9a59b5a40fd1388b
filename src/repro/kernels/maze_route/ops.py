"""Public maze_route entry point: shape handling, padding, impl selection.

`wavefront_distance` accepts a single (H, W) grid or a batched (B, H, W)
stack and returns int32 BFS distances (`INF` = unreachable).  Four
implementations sit behind it, all bit-identical on every accepted
input (the shared property suite `tests/test_maze_route_properties.py`
pins them to each other and to the Python oracle):

  impl="ref"       jitted jnp fast-sweeping oracle (`ref.py`)
  impl="kernel"    grid-batched Pallas Jacobi kernel (`kernel.py`)
  impl="frontier"  host numpy frontier-bucketed engine (`frontier.py`)
  impl="bfs"       pure-Python deque BFS oracle (`oracle.py`)

Selection (`impl=None`): under a jit trace the inputs are tracers, so
the choice is between the traceable implementations — the Pallas kernel
on TPU, the jitted ref elsewhere (Pallas interpret mode re-enters
Python per while-loop step: fine for tests, not for a hot path).  On
concrete host arrays off-TPU the frontier engine wins — per-level work
is proportional to the active frontier, not H×W — and is the default;
it returns numpy (callers on this path, e.g. `repro.eda.router`, read
the field on host anyway).  Host-only impls raise under tracing rather
than silently falling back.  Tests force the kernel in interpret mode
off-TPU with impl="kernel" and assert it matches the ref.

`goal_wavefront` is the kernel alone, stopped per grid once the given
goal cells are resolved (the router's targets): the field is then exact
only out to the goals, which is all a backtrace from them reads.  It
also returns the sweeps each grid ran.  Full-field callers pass no
goals and use `wavefront_distance`.

Padding: the kernel needs TPU tile multiples (sublane 8, lane 128).
`pad_blocked` pads the occupancy with *blocked* cells and the seed with
zeros — the pad region is masked out of the sweep explicitly, so no
wavefront can enter it and tunnel around the real grid's edge, and
distances inside the real grid are untouched (regression-tested along
the pad boundary in the property suite).  Different-sized grids in one
batch are handled the same way by the caller (`repro.eda.batched_flow`
blocks every cell beyond a spec's own grid bounds).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.maze_route.frontier import wavefront_distance_frontier
from repro.kernels.maze_route.kernel import (goal_wavefront_kernel,
                                             wavefront_kernel)
from repro.kernels.maze_route.oracle import wavefront_distance_bfs
from repro.kernels.maze_route.ref import INF, wavefront_distance_ref

_ref_jit = jax.jit(wavefront_distance_ref)

IMPLS = ("ref", "kernel", "frontier", "bfs")
HOST_IMPLS = ("frontier", "bfs")     # numpy in / numpy out, never traced


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _traced(*arrays) -> bool:
    return any(isinstance(a, jax.core.Tracer) for a in arrays)


def pad_blocked(occ: jax.Array, seed: jax.Array):
    """Pad (B, H, W) grids to the TPU tile multiples with an explicitly
    *blocked* pad region (occ=1, seed=0).

    Blocked padding is the correctness argument, not a convenience: a
    free pad region would participate in the relaxation and let
    wavefronts leave the real grid at its edge and re-enter elsewhere,
    shortening distances along the boundary.  Returns
    (occ_padded, seed_padded, (h, w)) with the original extent for
    de-padding.
    """
    _, h, w = occ.shape
    ph, pw = (-h) % 8, (-w) % 128
    pad = [(0, 0), (0, ph), (0, pw)]
    occ_p = jnp.pad(occ.astype(jnp.int8), pad, constant_values=1)
    seed_p = jnp.pad(seed.astype(jnp.int8), pad, constant_values=0)
    return occ_p, seed_p, (h, w)


def wavefront_distance(occ: jax.Array, seed: jax.Array, *,
                       interpret: bool | None = None,
                       impl: str | None = None) -> jax.Array:
    """BFS distance field(s) for the Lee maze router.

    occ, seed: (H, W) or (B, H, W) bool.  Returns int32 distances of the
    same shape; seeds are 0 (even if occupied), blocked cells `INF`.
    Host impls ("frontier", "bfs") return numpy arrays; traced/"ref"/
    "kernel" return jax arrays.
    """
    if impl is None:
        if _traced(occ, seed) or jax.default_backend() == "tpu":
            impl = "kernel" if jax.default_backend() == "tpu" else "ref"
        else:
            impl = "frontier"
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    if impl in HOST_IMPLS:
        if _traced(occ, seed):
            raise TypeError(
                f"impl={impl!r} is a host engine and cannot run under a "
                "jit trace; use impl='ref'/'kernel' inside traced code")
        occ_np = np.asarray(occ, bool)
        seed_np = np.asarray(seed, bool)
        if impl == "frontier":
            return wavefront_distance_frontier(occ_np, seed_np)
        return wavefront_distance_bfs(occ_np, seed_np)

    occ = jnp.asarray(occ)
    seed = jnp.asarray(seed)
    squeeze = occ.ndim == 2
    if squeeze:
        occ, seed = occ[None], seed[None]
    if impl == "ref":
        out = _ref_jit(occ, seed)
        return out[0] if squeeze else out
    if interpret is None:
        interpret = _should_interpret()
    occ_p, seed_p, (h, w) = pad_blocked(occ, seed)
    out = wavefront_kernel(occ_p, seed_p, interpret=interpret)[:, :h, :w]
    return out[0] if squeeze else out


def goal_wavefront(occ: jax.Array, seed: jax.Array, goals: jax.Array, *,
                   interpret: bool | None = None):
    """The Pallas wavefront of (B, H, W) grids, each stopped once its
    goals are resolved (`kernel.goal_wavefront_kernel`).

    goals: (B, K, 2) int32 (y, x) cells; a negative pair is no goal.
    Returns (dist, sweeps, goal_stopped): int32 distances, exact on every
    cell whose distance is at most the grid's sweeps and `INF` beyond;
    (B,) sweeps run; (B,) 1 where the goals stopped the grid before its
    fixed point.
    """
    if interpret is None:
        interpret = _should_interpret()
    occ_p, seed_p, (h, w) = pad_blocked(jnp.asarray(occ), jnp.asarray(seed))
    dist, sweeps, stopped = goal_wavefront_kernel(occ_p, seed_p, goals,
                                                  interpret=interpret)
    return dist[:, :h, :w], sweeps, stopped
