"""Production meshes.

Kept as functions (not module constants) so importing never touches jax
device state -- the dry-run must set XLA_FLAGS before first jax init.
"""
from __future__ import annotations

import jax


def _mesh_kwargs(n_axes: int) -> dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_mesh_kwargs(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    return jax.make_mesh((data, model), ("data", "model"), **_mesh_kwargs(2))


def shard_map_unchecked(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off -- the serving
    engines pass replicated params explicitly."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def dp_axes(mesh) -> tuple:
    """The data-parallel axes: ('pod','data') on multi-pod, ('data',) else."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def tp_size(mesh) -> int:
    return mesh.shape.get("model", 1)
