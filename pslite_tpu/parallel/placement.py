"""Host->device placement helpers shared by the dense and sparse engines.

On a multi-process mesh (jax.distributed), ``device_put`` cannot target
non-addressable devices; globally-known host data goes through the
callback form, and per-process contributions through
``make_array_from_process_local_data``.
"""

from __future__ import annotations

import numpy as np


def mesh_is_multiprocess(mesh) -> bool:
    return len({d.process_index for d in mesh.devices.flat}) > 1


def local_shard_count(mesh) -> int:
    """Mesh positions owned by THIS process."""
    import jax

    me = jax.process_index()
    return sum(1 for d in mesh.devices.flat if d.process_index == me)


def staging_xp(arr):
    """numpy for a host-origin array, jax.numpy for a device array: the
    module to normalize it with (cast, broadcast, pad) before placement.
    A host array is normalized on the host and then placed shard by shard
    (``device_put`` of a numpy array moves each shard straight to its
    device); ``jnp.asarray`` would first land the whole array on device 0
    and reshard it from there."""
    import jax

    if isinstance(arr, jax.Array):
        import jax.numpy as jnp

        return jnp
    return np


def place_host_array(mesh, host_arr, sharding, multiprocess=None):
    """Place a (globally known) host array onto a sharding, working on
    single- AND multi-process meshes."""
    import jax

    if multiprocess is None:
        multiprocess = mesh_is_multiprocess(mesh)
    if not multiprocess:
        return jax.device_put(host_arr, sharding)
    return jax.make_array_from_callback(
        host_arr.shape, sharding, lambda idx: host_arr[idx]
    )


def to_host_global(arr, multiprocess: bool):
    """The FULL value of a sharded array as a numpy array on THIS host.

    Single-process: a plain device fetch.  Multi-process: a collective —
    every participating process must call this on the same array in the
    same order (jax.experimental.multihost_utils.process_allgather
    assembles the non-addressable shards across hosts)."""
    if not multiprocess:
        return np.asarray(arr)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(arr, tiled=True))
