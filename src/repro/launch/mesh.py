"""Mesh construction. Functions only — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import)."""
from __future__ import annotations

import jax

from repro.core.catalog import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_dev_mesh(n_devices: int | None = None):
    """Small mesh over whatever devices exist (tests / CPU runs)."""
    n = n_devices or len(jax.devices())
    if n == 1:
        return auto_mesh((1, 1), ("data", "model"))
    model = 1
    for m in (4, 2):
        if n % m == 0:
            model = m
            break
    return auto_mesh((n // model, model), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
