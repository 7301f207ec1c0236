"""Production mesh construction.

Defined as a function (not a module-level constant) so importing this
module never touches jax device state — required because the dry-run must
set XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
