"""Data parallelism: the reference's canonical strategy, generalized.

The reference demonstrates DP as a user pattern (reference:
examples/simple_linear_regression.py:27-35, doc/examples.rst:24-65,
README.md:34-46): average the replicated parameters with an Allreduce whose
adjoint turns per-rank loss gradients into their global mean, then Allreduce
the local loss.  These helpers package that recipe for arbitrary pytrees and
loss functions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..constants import MPI_SUM


def all_average_tree(comm, tree, bucket_bytes=None, overlap=None):
    """Allreduce-average every leaf of a pytree.

    The DP lock-step primitive: forward is the identity on replicated
    values; the adjoint Allreduce makes downstream gradients the mean over
    ranks (reference: doc/examples.rst:46-65).

    Rides the fused bucketed path (:mod:`mpi4torch_tpu.fuse`) by
    default: one Allreduce per ~``bucket_bytes`` dtype-homogeneous
    bucket instead of one per leaf (under SPMD one ``lax.psum`` forward
    and one in the adjoint; a leaf that fills a bucket, as every matrix
    of a real model does, goes in its own shape), and the
    ``/ comm.size`` mean folded into a single post-fuse scale per bucket
    instead of one division per leaf.  Results stay bitwise lock-step
    across ranks (every rank gets the same bits from one all-reduce),
    and the eager backend is bit-identical to the historical per-leaf
    form.  Opt out with ``bucket_bytes=0`` or
    ``config.fusion_scope(0)``.  (The SPMD path's reduce-scatter +
    all-gather pair and its staging went in PR 35: on the chip the pair
    was an all-reduce and then an all-gather, every one exposed.)

    ``overlap`` (None → the :func:`mpi4torch_tpu.config.overlap_scope`
    / process default): truthy selects the split-phase overlap
    scheduler (:mod:`mpi4torch_tpu.overlap`) under the SPMD backend —
    each bucket's reduce-scatter starts while earlier buckets are still
    completing, up to the window depth in flight (its start and wait
    halves are that same pair, so on the chip it pays the pair's third
    more) — and the nonblocking
    Isend/Irecv pipeline on the eager backend.  Bit-identical to the
    blocking form either way."""
    return comm.Allreduce_tree(tree, MPI_SUM, bucket_bytes=bucket_bytes,
                               mean=True, overlap=overlap)


def dp_loss(comm, local_loss_fn, params, batch):
    """Global DP loss = mean over ranks of ``local_loss_fn`` on the rank's
    batch shard, with the parameter-averaging Allreduce that keeps per-rank
    optimizer replicas arithmetically identical."""
    params = all_average_tree(comm, params)
    return comm.Allreduce(local_loss_fn(params, batch), MPI_SUM) / comm.size


def dp_value_and_grad(comm, local_loss_fn):
    """``jax.value_and_grad`` for a data-parallel loss.

    Returns ``f(params, batch) -> (global_loss, mean_grads)``; every rank
    receives identical gradients, so any optimizer stays in lock-step
    (including history-carrying ones like L-BFGS — the property the
    reference's example is built to demonstrate)."""
    def value_and_grad(params, batch):
        return jax.value_and_grad(
            lambda p: dp_loss(comm, local_loss_fn, p, batch))(params)
    return value_and_grad
