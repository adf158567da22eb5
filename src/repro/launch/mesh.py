"""Production mesh construction (TPU v5e-256, 1 or 2 pods).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
Meshes are built by `repro.compat.make_mesh`, which makes every axis
`Auto`.
"""

from __future__ import annotations

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over locally available devices (tests / examples)."""
    return make_mesh((data, model), ("data", "model"))


def mesh_shape_dict(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
