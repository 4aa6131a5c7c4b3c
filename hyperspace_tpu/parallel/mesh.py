"""Device-mesh helpers.

The sharding model (per the public scaling-book recipe): pick a Mesh, annotate
shardings with NamedSharding/PartitionSpec, let XLA insert collectives over
ICI (intra-slice) / DCN (multi-slice). Hyperspace workloads shard on one data
axis — rows/buckets — so the default mesh is 1-D ("shards"); index builds map
bucket b to shard b % n.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SHARD_AXIS = "shards"

# Hierarchical (multi-slice) axis names: "ici" is the fast intra-slice
# interconnect, "dcn" the slower cross-slice network. Shardings put the
# row dimension over BOTH axes so every chip holds a shard; collectives
# over ("dcn", "ici") lower hierarchically — XLA reduces within each slice
# over ICI first and only per-group partials cross DCN (the scaling-book
# recipe for multi-host reductions).
AXIS_DCN = "dcn"
AXIS_ICI = "ici"
HIER_AXES = (AXIS_DCN, AXIS_ICI)

def device_mesh(num_devices: int | None = None, axis: str = SHARD_AXIS) -> Mesh:
    devices = jax.devices()
    n = num_devices or len(devices)
    if n > len(devices):
        raise ValueError(f"Requested {n} devices, have {len(devices)}")
    return Mesh(np.array(devices[:n]), (axis,))


def hierarchical_mesh(num_slices: int, devices_per_slice: int) -> Mesh:
    """A 2-D (dcn, ici) mesh for multi-slice deployments: row i of the
    device grid is one slice (ICI-connected); slices talk over DCN. On a
    single host this still runs (axes are logical), which is how the CPU
    harness exercises the multi-slice code path."""
    devices = jax.devices()
    n = num_slices * devices_per_slice
    if n > len(devices):
        raise ValueError(f"Requested {n} devices, have {len(devices)}")
    grid = np.array(devices[:n]).reshape(num_slices, devices_per_slice)
    return Mesh(grid, HIER_AXES)


def is_hierarchical(mesh: Mesh) -> bool:
    """True for multi-axis (multi-slice) meshes. Row-moving paths (build
    exchange, mesh join probe) check this and stay intra-slice only."""
    return len(mesh.axis_names) != 1


def slice_submeshes(mesh: Mesh) -> list[Mesh]:
    """One flat 1-D mesh per slice of a hierarchical mesh: row i of the
    (dcn, ici) device grid becomes an independent ("shards",) mesh whose
    collectives ride that slice's ICI only. Multi-slice index builds
    partition their source rows across these submeshes so the bucket
    all_to_all never crosses DCN."""
    if not is_hierarchical(mesh):
        return [mesh]
    return [Mesh(row, (SHARD_AXIS,)) for row in mesh.devices]


def mesh_row_axes(mesh: Mesh):
    """The axis spec that shards the row dimension over every device of
    this mesh: the single data axis on a 1-D mesh, the (dcn, ici) pair on
    a hierarchical one."""
    names = tuple(mesh.axis_names)
    return names[0] if len(names) == 1 else names


def shard_rows(mesh: Mesh, axis: "str | tuple[str, ...] | None" = None) -> NamedSharding:
    """Rows sharded along the leading dim (over every mesh axis by default)."""
    return NamedSharding(mesh, P(axis if axis is not None else mesh_row_axes(mesh)))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def num_shards(mesh: Mesh, axis: "str | tuple[str, ...] | None" = None) -> int:
    if axis is None:
        axis = mesh_row_axes(mesh)
    if isinstance(axis, tuple):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


def visible_devices(cap: int = 0) -> list:
    """The device list scale-out placement may target. ``cap`` > 0 clamps
    the list (``HYPERSPACE_MESH_DEVICES``); the order is ``jax.devices()``
    order, which is stable for a process lifetime — placement determinism
    leans on that."""
    devices = jax.devices()
    return list(devices[:cap] if cap > 0 else devices)


def active_mesh(session) -> Mesh | None:
    """The execution mesh requested by `hyperspace.tpu.exec.meshDevices`;
    None when the conf asks for at most one device. Raises ValueError when
    the conf asks for more devices than exist — a mesh the user configured
    never silently drops to one device."""
    if session is None:
        return None
    n = session.conf.exec_mesh_devices
    if n <= 1:
        return None
    from ..utils.backend import device_count

    if device_count() < n:
        raise ValueError(
            f"hyperspace.tpu.exec.meshDevices={n} but only {device_count()} "
            "devices are visible"
        )
    slices = session.conf.exec_mesh_slices
    if slices > 1:
        return hierarchical_mesh(slices, n // slices)
    return device_mesh(n)
