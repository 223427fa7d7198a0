"""Parallel execution: vnode-sharded dataflow over a jax device mesh.

The reference's only compute parallelism is streaming data parallelism:
rows hash to one of VNODE_COUNT virtual nodes (CRC32, `consistent_hash/
vnode.rs:30`), vnodes map to parallel actors, and a HashDataDispatcher +
MergeExecutor pair moves rows between them over gRPC with credit-based
backpressure (`dispatch.rs:777`, `merge.rs:235`, `exchange/permit.rs:35`).

TPU-native re-design: the parallel units are mesh shards. vnode -> shard is a
static contiguous-block map, the hash exchange is a single
`lax.all_to_all` over ICI inside a `shard_map`'d epoch step, and barrier
alignment is implicit — the all-to-all IS the barrier-granular exchange, so
no per-channel alignment machinery is needed. Backpressure degenerates to the
host feeding epochs one at a time.
"""
from .mesh import make_mesh, shard_of_vnode, vnode_block_bounds  # noqa: F401
