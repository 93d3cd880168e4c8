"""Production mesh construction.

Mesh shapes (TPU v5e):
  single-pod: (16, 16)      axes ("data", "model")   = 256 chips
  multi-pod:  (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

A FUNCTION, not a module constant: importing this module never touches jax
device state (the dry-run pins the host-device count before first jax use).
"""
from __future__ import annotations

import numpy as np

import jax


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (sharding propagated by
    the compiler). All mesh construction in this repo goes through here."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devices)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "(launch/dryrun.py sets this automatically)"
        )
    return make_mesh(shape, axes, devices=devices[:need])


def data_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of a production mesh (pod extends DP)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
