"""Device mesh + vnode -> shard mapping.

Analog of the reference's WorkerSlotMapping / vnode mapping
(`src/common/src/hash/consistent_hash/vnode_mapping/`, `hash/
table_distribution.rs`): vnodes are assigned to parallel units in contiguous
blocks. Contiguous blocks (not round-robin) keep a shard's key-range compact,
which is what the sorted-run state wants, and make rescale a block-boundary
move (`scale.rs:2329` analog) rather than a full reshuffle.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.vnode import VNODE_COUNT

SHARD_AXIS = "shard"
# Serving replicas: a second, named mesh axis. State PartitionSpecs only
# ever name SHARD_AXIS, and jax replicates over any mesh axis a spec
# does not mention — so the same P("shard") specs shard vnode blocks
# over the data axis and mirror them across replicas with zero operator
# changes. Collectives (all_to_all/psum/pmax) also name only SHARD_AXIS,
# which scopes them to the per-replica data group.
REPLICA_AXIS = "replica"

shard_map = jax.shard_map


def make_mesh(n_devices: Optional[int] = None,
              devices: Optional[Sequence] = None,
              replicas: int = 1) -> Mesh:
    """Mesh over the shard axis, optionally times a replica axis.

    `replicas=1` builds the exact 1-D `(n,)` mesh the engine has always
    used — same devices, same axis tuple — so every existing program
    lowers byte-for-byte identically. `replicas=r > 1` asks for
    `n_devices * r` devices and shapes them `(n_devices, r)` with axes
    `(shard, replica)`: device [d, k] holds data-shard d of replica k.

    The devices are the default platform's own: asking for more than it
    has raises — a mesh never reaches for another platform's devices
    (tier-1's default platform IS the 8 virtual CPU devices)."""
    replicas = max(1, int(replicas))
    want = None if n_devices is None else int(n_devices) * replicas
    if devices is None:
        devices = jax.devices()
        if want is not None:
            if len(devices) < want:
                raise ValueError(
                    f"need {want} devices but the default platform "
                    f"({devices[0].platform}) has {len(devices)}")
            devices = devices[:want]
    devices = np.asarray(devices)
    if replicas == 1:
        return Mesh(devices, (SHARD_AXIS,))
    if devices.size % replicas:
        raise ValueError(
            f"{devices.size} devices do not divide into {replicas} replicas")
    return Mesh(devices.reshape(devices.size // replicas, replicas),
                (SHARD_AXIS, REPLICA_AXIS))


def data_shards(mesh: Mesh) -> int:
    """Size of the vnode-partition (data) axis. Equals `devices.size` on
    the classic 1-D mesh; on a replicated 2-D mesh it is the per-replica
    shard count — the number every capacity/exchange/stat shape keys on."""
    return int(mesh.shape[SHARD_AXIS])


def mesh_replicas(mesh: Mesh) -> int:
    """Replica-axis size (1 on the classic 1-D mesh)."""
    return int(mesh.shape.get(REPLICA_AXIS, 1))


def vnode_block_bounds(n_shards: int, vnode_count: int = VNODE_COUNT
                       ) -> np.ndarray:
    """start vnode of each shard's contiguous block, plus end sentinel."""
    return (np.arange(n_shards + 1) * vnode_count) // n_shards


def shard_of_vnode(vnodes, n_shards: int, vnode_count: int = VNODE_COUNT):
    """Owning shard of each vnode — the exact inverse of
    `vnode_block_bounds`: shard k owns [bounds[k], bounds[k+1]), i.e.
    the largest k with (k*vnode_count)//n_shards <= v. The naive
    `(v*n)//vnode_count` disagrees at block boundaries whenever n_shards
    does not divide vnode_count (vnode 85 of 256 under 3 shards sits in
    block 1 but floor(85*3/256)=0), silently splitting a block across
    two shards. Works on numpy or jnp arrays (pure int arithmetic,
    jit-safe)."""
    return ((vnodes + 1) * n_shards - 1) // vnode_count


def state_sharding(mesh: Mesh) -> NamedSharding:
    """State arrays are [n_shards, ...] sharded on the leading axis."""
    return NamedSharding(mesh, P(SHARD_AXIS))
