"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax


def make_mesh(shape: tuple, axes: tuple, devices=None):
    """jax.make_mesh with Auto (propagated) sharding on every axis."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1, n_devices: int = 0):
    """A (data, model) mesh over the first `n_devices` local devices, or all
    of them with 0: a one-chip run on a host that shows several asks for 1."""
    devices = jax.devices()
    if n_devices:
        if n_devices > len(devices):
            raise ValueError(f"asked for {n_devices} devices, "
                             f"{len(devices)} visible")
        devices = devices[:n_devices]
    n = len(devices)
    if n % model:
        raise ValueError(f"{n} devices do not split into model={model}")
    return make_mesh((n // model, model), ("data", "model"), devices=devices)


def data_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def n_data_nodes(mesh) -> int:
    n = 1
    for a in data_axes(mesh):
        n *= mesh.shape[a]
    return n
