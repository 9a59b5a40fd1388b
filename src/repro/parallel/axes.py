"""Logical activation axes -> mesh axes (MaxText-style logical axis rules).

Models annotate key intermediates with `logical(x, "batch", "seq", "heads",
None)`; under a `set_rules(...)` context (installed by the train/serve step
builders) each logical name maps to a mesh axis (or None) and the annotation
becomes a `with_sharding_constraint`.  Outside the context it is a no-op, so
single-device smoke tests run the exact same model code.

This is how head-count-awkward architectures (arctic: 56 heads on 16-way
TP) stay efficient: their rules map the attention *sequence* axis to
"model" (context parallelism) instead of the head axis.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Mapping, Optional

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

_RULES: contextvars.ContextVar[Optional[tuple[Mesh, Mapping[str, object]]]] = \
    contextvars.ContextVar("logical_axis_rules", default=None)


@contextlib.contextmanager
def set_rules(mesh: Mesh, rules: Mapping[str, object]):
    """rules: logical name -> mesh axis name | tuple of axis names | None."""
    token = _RULES.set((mesh, dict(rules)))
    try:
        yield
    finally:
        _RULES.reset(token)


def current_rules():
    return _RULES.get()


def _spec_from(rules: Mapping[str, object], names: tuple) -> P:
    """Resolve names -> mesh axes, dropping duplicate axis uses (first dim
    keeps the axis; later dims fall back to None)."""
    used: set = set()
    out = []
    for n in names:
        ax = rules.get(n) if isinstance(n, str) else None
        flat = ax if isinstance(ax, tuple) else (ax,) if ax else ()
        if any(a in used for a in flat):
            ax = None
            flat = ()
        used.update(flat)
        out.append(ax)
    return P(*out)


def resolve(names: tuple) -> Optional[P]:
    ctx = _RULES.get()
    if ctx is None:
        return None
    _, rules = ctx
    return _spec_from(rules, names)


def logical(x: jax.Array, *names) -> jax.Array:
    """Constrain x's sharding by logical axis names (no-op w/o rules)."""
    ctx = _RULES.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = _spec_from(rules, names)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
