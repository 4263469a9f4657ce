"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first jax use.

  single-pod : (16, 16)    axes (data, model)          = 256 chips (one v5e pod)
  multi-pod  : (2, 16, 16) axes (pod, data, model)     = 512 chips
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "batch_axes", "make_host_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / smoke runs)."""
    n = jax.device_count()
    if data is None:
        data = n // model
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape, axes):
    # Auto axes: the models place activations with with_sharding_constraint,
    # which only refers to Auto axes (jax.make_mesh defaults to Explicit)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def batch_axes(mesh) -> tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
