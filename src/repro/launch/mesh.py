"""Production mesh construction (deliverable e).

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init; tests see
the single real CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the solver's host-side prep
    (``jnp.median`` over a feature-sharded vector, top-k merges) relies on
    the compiler inserting the collectives, which Explicit axes refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(data=16, model=16) single pod; (pod=2, data=16, model=16) two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over the real local devices (CPU tests / examples)."""
    n = jax.device_count()
    assert n % model == 0
    return _auto_mesh((n // model, model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")
