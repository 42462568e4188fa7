"""Mesh factory.

A function, not a module-level constant: importing this module never touches
jax device state (device count is locked at first jax init).
"""
from __future__ import annotations

import jax


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """A mesh of ``shape`` over the local devices, with every axis
    ``Auto``."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
