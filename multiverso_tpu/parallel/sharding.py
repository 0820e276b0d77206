"""Mesh + sharding helpers.

This is where the reference's server-shard placement logic
(``WorkerTable::Partition`` splitting requests across server processes;
SURVEY.md §2.10) becomes declarative: a table picks a ``NamedSharding`` and
XLA materializes the partitioning and the collectives.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["make_mesh", "table_mesh", "replicated", "shard_along",
           "host_to_global", "batch_placer"]

_SHARD_AXIS = "shard"


def make_mesh(axis_sizes: Sequence[int], axis_names: Sequence[str],
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a named device mesh, e.g. ``make_mesh((2, 4), ("dp", "tp"))``."""
    if devices is None:
        devices = jax.devices()
    n = int(np.prod(axis_sizes))
    if n != len(devices):
        raise ValueError(
            f"mesh {tuple(axis_sizes)} needs {n} devices, have {len(devices)}")
    arr = np.asarray(devices).reshape(tuple(axis_sizes))
    return Mesh(arr, tuple(axis_names))


def table_mesh(mesh: Optional[Mesh] = None) -> Mesh:
    """1-D mesh over *all* devices used for table sharding.

    Tables always shard over the flattened device list — the analog of the
    reference sharding every table across every server process regardless of
    app topology.  Independent of whatever multi-axis mesh the app uses for
    its compute step.
    """
    if mesh is not None:
        devices = mesh.devices.flatten()
    else:
        devices = np.asarray(jax.devices())
    return Mesh(devices, (_SHARD_AXIS,))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_along(mesh: Mesh, ndim: int, dim: int = 0,
                axis: str = _SHARD_AXIS) -> NamedSharding:
    """Shard dimension ``dim`` of an ndim-array along ``axis``; rest replicated."""
    spec = [None] * ndim
    spec[dim] = axis
    return NamedSharding(mesh, P(*spec))


def host_to_global(x: np.ndarray, sharding: NamedSharding) -> jax.Array:
    """Place a host array onto devices with the given sharding."""
    return jax.device_put(x, sharding)


def batch_placer(mesh: Mesh, batch_axis: str = "worker", dtype=None):
    """Resolve the data-parallel axis and build a batch-placing closure.

    Shared by the apps' fused steps: dim 0 of each input shards over the
    mesh's ``batch_axis`` (falling back to the mesh's first axis).  A host
    batch goes to its shards in ONE ``device_put`` — never through device
    0 first.  A batch whose leading dim isn't divisible by the axis size
    is replicated instead (correct, just unsharded: every device then
    computes the whole batch), which is logged once per placer with the
    shapes.  Returns ``(axis_name, place)``.
    """
    from ..log import Log

    axis = batch_axis if batch_axis in mesh.shape else list(mesh.shape)[0]
    n = int(mesh.shape[axis])
    rep = replicated(mesh)
    warned = False

    def place(a):
        nonlocal warned
        if isinstance(a, jax.Array):
            a = a if dtype is None else a.astype(dtype)
        else:
            a = np.asarray(a, dtype)
        if a.shape[0] % n:
            if not warned:
                warned = True
                Log.info("batch_placer: batch %s does not divide mesh axis "
                         "'%s' (%d): REPLICATED on every device, not "
                         "sharded", tuple(a.shape), axis, n)
            return jax.device_put(a, rep)
        return jax.device_put(a, shard_along(mesh, a.ndim, 0, axis))

    return axis, place
