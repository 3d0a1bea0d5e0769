"""Device mesh construction — single-host and pod-scale (multi-host).

The TPU mesh replaces the reference's cluster of gRPC peers for key
ownership: where gubernator consistent-hashes each key to one of N nodes
(reference replicated_hash.go:104-119), we hash each key to one of D devices
on the mesh. On one host that is a 1-D axis "shard" over the local devices.
On a pod slice the mesh is 2-D — ("host", "device") — with the SAME linear
shard numbering laid out host-major: shard s lives on host s // dl, local
device s % dl (dl = devices per host). Collectives address the pair of axes
jointly, so ICI does the exchange within a host row and DCN across rows,
and shard ownership (mesh.shard_of — pure fingerprint arithmetic) is stable
under (host, device) addressing: re-meshing the same D devices from 1 host
to H hosts moves no keys.

Multi-host resolution (make_mesh): an explicit `hosts=` argument wins, then
GUBER_MESH_HOSTS (the simulated multi-process mode — CI/test meshes fold
xla_force_host_platform_device_count CPU devices into H "hosts" inside one
process), then `jax.process_count()` when the runtime really is
multi-process (each process contributes its local devices to its own host
row). Cross-region stays on the host peer plane (peers/).
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec

SHARD_AXIS = "shard"  # the 1-D single-host axis (seed layout)
HOST_AXIS = "host"  # pod meshes: leading axis, one row per host
DEVICE_AXIS = "device"  # pod meshes: trailing axis, devices within a host


def env_mesh_hosts() -> Optional[int]:
    """GUBER_MESH_HOSTS: fold the device pool into this many simulated hosts
    (2-D mesh in ONE process — the CI/test stand-in for a real multi-process
    pod slice). Unset/empty → topology from the runtime."""
    raw = os.environ.get("GUBER_MESH_HOSTS", "").strip()
    if not raw:
        return None
    hosts = int(raw)
    if hosts < 1:
        raise ValueError(f"GUBER_MESH_HOSTS must be >= 1, got {hosts}")
    return hosts


def make_mesh(
    n_devices: Optional[int] = None,
    devices: Optional[Sequence] = None,
    hosts: Optional[int] = None,
) -> Mesh:
    """Mesh over `n_devices` (default: all addressable devices). 1-D
    ("shard",) for a single host; 2-D ("host", "device") when the topology
    is multi-host — explicit `hosts=`, then GUBER_MESH_HOSTS (simulated),
    then jax.process_count() (real pod slices). Devices are ordered
    host-major (process_index, id) so the linear shard id s ↔ (s // dl,
    s % dl) addressing is stable whichever host enumerates them."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    devices = sorted(devices, key=lambda d: (d.process_index, d.id))
    D = len(devices)
    if hosts is None:
        hosts = env_mesh_hosts()
    if hosts is None:
        hosts = jax.process_count() if jax.process_count() > 1 else 1
    if hosts <= 1:
        return Mesh(np.asarray(devices), (SHARD_AXIS,))
    if D % hosts != 0:
        raise ValueError(
            f"mesh of {D} devices cannot split over {hosts} hosts evenly"
        )
    grid = np.asarray(devices).reshape(hosts, D // hosts)
    return Mesh(grid, (HOST_AXIS, DEVICE_AXIS))


# ------------------------------------------------- topology introspection
# Every mesh consumer (sharded.py, a2a.py, ring.py, global_sync.py,
# parallel/telemetry.py) addresses the shard dimension through these, so
# the 1-D and 2-D layouts stay interchangeable at every call site.


def shard_axes(mesh: Mesh):
    """The axis name(s) the leading shard dimension spans: "shard" on 1-D
    meshes, ("host", "device") on pod meshes. Valid as the `axis_name` of
    every collective used here (all_to_all / all_gather / ppermute /
    axis_index flatten tuples host-major)."""
    names = tuple(mesh.axis_names)
    return names if len(names) > 1 else names[0]


def shard_spec(mesh: Mesh) -> PartitionSpec:
    """PartitionSpec sharding an array's leading axis over every mesh axis
    jointly — the drop-in replacement for the seed's P("shard")."""
    axes = shard_axes(mesh)
    return PartitionSpec(axes)


def mesh_hosts(mesh: Mesh) -> int:
    """Host rows in the mesh (1 on single-host meshes)."""
    return int(mesh.shape[HOST_AXIS]) if HOST_AXIS in mesh.shape else 1


def devices_per_host(mesh: Mesh) -> int:
    dl = mesh.shape.get(DEVICE_AXIS) if HOST_AXIS in mesh.shape else None
    return int(dl) if dl is not None else int(mesh.devices.size)


def host_of_shard(mesh: Mesh, shard: np.ndarray) -> np.ndarray:
    """Owning host row for linear shard ids — the host-major addressing
    contract (shard s ↔ host s // dl)."""
    return np.asarray(shard) // devices_per_host(mesh)


def shard_of(fp: np.ndarray, n_shards: int) -> np.ndarray:
    """Owning shard for each fingerprint. Uses high bits so the shard choice is
    independent of the in-table slot (fp mod capacity uses low bits) — the
    analog of the reference using separate hashes for peer ownership and
    worker sharding (replicated_hash.go:78-91 vs workers.go:185-189)."""
    return ((fp >> 32) % n_shards).astype(np.int64)
