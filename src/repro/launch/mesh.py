"""Production meshes.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state: the dry-run sets XLA_FLAGS for 512 host devices
BEFORE calling this; tests/benches see the real single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    # Auto axes: the model code places arrays with with_sharding_constraint
    # and lets the partitioner propagate (jax.make_mesh defaults to Explicit)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; multi-pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, multi_pod: bool = False):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    if multi_pod:
        return _mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _mesh((n_data, n_model), ("data", "model"))


def dp_axes(mesh) -> tuple[str, ...]:
    """Data-parallel mesh axes: ('pod','data') on multi-pod, else ('data',)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
