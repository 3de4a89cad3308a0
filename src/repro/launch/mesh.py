"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state.
"""

from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == need:
        return jax.make_mesh(shape, axes)
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devices)}")
    # more devices than needed (the 512-device dry-run building the 256-chip
    # single-pod mesh): use a prefix slice.
    return jax.sharding.Mesh(
        np.asarray(devices[:need]).reshape(shape), axes)


def mesh_chip_count(mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
