"""Production mesh construction.

Kept as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to materialize the placeholder devices.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the program places arrays by sharding annotations
    return jax.make_mesh(shape, axes, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(*, model: int = 1, data: int | None = None):
    """Tiny mesh over whatever devices exist (tests / local runs).

    ``data`` caps the data-parallel width to a subset of the available
    devices — the elastic re-scale path builds a *smaller* mesh in the same
    process this way (``train.fault_tolerance.elastic_reshard_cnn``)."""
    import numpy as np

    devs = jax.devices()
    n = len(devs)
    model = min(model, n)
    width = n // model if data is None else min(data, n // model)
    assert width >= 1, (n, model, data)
    grid = np.asarray(devs[:width * model], dtype=object).reshape(
        width, model)
    return jax.sharding.Mesh(grid, ("data", "model"))


def data_axis_size(mesh) -> int:
    """Width of the data-parallel axis (1 when the mesh has none)."""
    return int(mesh.shape.get("data", 1))
