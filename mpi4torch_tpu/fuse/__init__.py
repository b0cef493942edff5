"""Fused bucketed collectives.

The per-leaf collective pattern (one Allreduce per pytree leaf —
parallel/dp.py, parallel/zero.py, utils/lbfgs.py) pays per-collective
launch plus ring latency hundreds of times per step for mostly-tiny
tensors.  This package eliminates that overhead the way production
stacks do ("The Big Send-off", arxiv 2504.18658; GC3 from the compiler
side): flatten the tree into a few dtype-homogeneous flat **buckets**
(~``bucket_bytes`` each, layout cached per tree structure) and issue one
collective — under SPMD, one ``lax.psum`` — per bucket; an explicit
``overlap=`` hands the buckets to the split-phase scheduler
(:mod:`mpi4torch_tpu.overlap`) to keep several in flight.

Entry points::

    comm.Allreduce_tree(grads, mpi.MPI_SUM, mean=True)   # facade sugar

    from mpi4torch_tpu import fuse
    fuse.fused_allreduce_tree(comm, tree, mpi.MPI_SUM, compression="q8")
    fuse.fused_reduce_scatter_tree(comm, grads, mean=True)   # ZeRO grads
    fuse.fused_allgather_tree(comm, shards, template)        # ZeRO params

    with mpi.config.fusion_scope(1 << 20):   # 1 MiB buckets for a block
        ...
    with mpi.config.fusion_scope(0):         # opt out: per-leaf ops
        ...

Everything stays AD-transparent: bucketing is differentiable
reshape/concat/slice glue around the facade's ``custom_vjp``
collectives, so the backward pass of a fused collective is itself fused
bucketed communication, and ``compression=`` quantizes fused buckets
exactly like single tensors (per-bucket codec, facade degrade/raise
rules).  See doc/fusion.md.
"""

from __future__ import annotations

from .bucketing import (BucketLayout, LeafSlot, ShardLayout, ShardSlot,
                        bucket_layout, flatten_buckets,
                        flatten_shard_buckets, shard_layout,
                        unflatten_buckets, unflatten_shard_rows)
from .collectives import (FUSE_TAG_BASE, fused_allgather_tree,
                          fused_allreduce_tree, fused_reduce_scatter_tree)

__all__ = [
    "BucketLayout",
    "LeafSlot",
    "ShardLayout",
    "ShardSlot",
    "bucket_layout",
    "flatten_buckets",
    "flatten_shard_buckets",
    "shard_layout",
    "unflatten_buckets",
    "unflatten_shard_rows",
    "fused_allreduce_tree",
    "fused_reduce_scatter_tree",
    "fused_allgather_tree",
    "FUSE_TAG_BASE",
]
