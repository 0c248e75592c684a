"""The cloud-'Shape' catalog (paper: CPU/GPU container shapes -> TPU v5e slices).

Each shape is a mesh the scoping engine can compile against; multi-pod shapes add
the ``pod`` axis crossed by DCI.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
from jax.sharding import AxisType

from repro.core.cost_model import V5E, HardwareSpec


@dataclass(frozen=True)
class CloudShape:
    name: str
    mesh_shape: tuple
    axes: tuple
    hw: HardwareSpec = V5E

    @property
    def chips(self) -> int:
        n = 1
        for s in self.mesh_shape:
            n *= s
        return n

    @property
    def price_per_hour(self) -> float:
        return self.chips * self.hw.price_per_chip_hour

    def make_mesh(self):
        return auto_mesh(self.mesh_shape, self.axes)


def auto_mesh(shape, axes):
    """A device mesh whose axes GSPMD partitions: ``with_sharding_constraint``
    accepts only Auto axes, and ``jax.make_mesh`` defaults to Explicit."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


CATALOG: list[CloudShape] = [
    CloudShape("v5e-4", (2, 2), ("data", "model")),
    CloudShape("v5e-8", (2, 4), ("data", "model")),
    CloudShape("v5e-16", (4, 4), ("data", "model")),
    CloudShape("v5e-32", (4, 8), ("data", "model")),
    CloudShape("v5e-64", (8, 8), ("data", "model")),
    CloudShape("v5e-128", (8, 16), ("data", "model")),
    CloudShape("v5e-256", (16, 16), ("data", "model")),
    CloudShape("2x-v5e-256", (2, 16, 16), ("pod", "data", "model")),
]

_BY_NAME: dict[str, CloudShape] = {s.name: s for s in CATALOG}


def get_shape(name: str) -> CloudShape:
    try:
        return _BY_NAME[name]
    except KeyError:
        raise KeyError(f"unknown cloud shape {name!r}; known: "
                       f"{[s.name for s in CATALOG]}") from None


def register_shape(shape: CloudShape, overwrite: bool = False) -> CloudShape:
    """Add a custom shape to the catalog (e.g. fleet scenarios injecting
    non-standard slices or alternate HardwareSpecs)."""
    if shape.name in _BY_NAME and not overwrite:
        raise ValueError(f"shape {shape.name!r} already registered "
                         "(pass overwrite=True to replace)")
    if shape.name in _BY_NAME:
        CATALOG[[s.name for s in CATALOG].index(shape.name)] = shape
    else:
        CATALOG.append(shape)
    _BY_NAME[shape.name] = shape
    return shape
