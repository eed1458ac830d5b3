"""Mesh definitions: the assignment's production shapes and meshes built from
the devices actually present.

Functions, not module-level constants: importing this module never touches
jax device state.  Every axis is ``AxisType.Auto`` (GSPMD propagation); enter
a mesh with ``jax.set_mesh(mesh)`` so bare-``PartitionSpec`` sharding
constraints (models.transformer.constrain_act) resolve against it.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for smoke tests / local runs."""
    return _mesh((1, 1), ("data", "model"))


def make_elastic_mesh(n_devices: int, model_parallel: int = 16):
    """Largest (data, model) mesh from ``n_devices`` present or surviving
    devices (launch/train.py, elastic restarts in train/elastic.py).  Drops
    stragglers that break divisibility."""
    model_parallel = min(model_parallel, n_devices)
    data = n_devices // model_parallel
    return _mesh((data, model_parallel), ("data", "model"))
