"""Production mesh construction.

`make_production_mesh` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
must set XLA_FLAGS before any jax initialization.

Single pod: 16 x 16 = 256 chips, axes ("data", "model") — "model" maps onto
the high-bandwidth ICI torus dimension (TP/EP/SP collectives stay intra-pod),
"data" carries DP/FSDP.  Multi-pod: 2 x 16 x 16 = 512 chips with an outer
"pod" axis that only sees the per-step gradient all-reduce (DCN-friendly).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (tests / elastic re-mesh).  Auto axes: the step
    builders place arrays with `NamedSharding`s and leave the rest of the
    partitioning to the compiler."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def dp_size(mesh) -> int:
    import numpy as np

    return int(np.prod([mesh.shape[a] for a in ("pod", "data")
                        if a in mesh.axis_names]))
