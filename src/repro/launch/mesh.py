"""Production mesh builders.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — required for the dry-run's
device-count override to work and for tests to stay single-device.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 per pod (256 v5e chips); 2 pods stack a leading 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests, CPU runs)."""
    n = len(jax.devices())
    data = data or (n // model)
    return _make_mesh((data, model), ("data", "model"))
