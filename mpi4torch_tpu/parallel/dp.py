"""Data parallelism: the reference's canonical strategy, generalized.

The reference demonstrates DP as a user pattern (reference:
examples/simple_linear_regression.py:27-35, doc/examples.rst:24-65,
README.md:34-46): average the replicated parameters with an Allreduce whose
adjoint turns per-rank loss gradients into their global mean, then Allreduce
the local loss.  These helpers package that recipe for arbitrary pytrees and
loss functions.

What the recipe needs of the parameter Allreduce is its ADJOINT, the mean
of the ranks' gradients; its forward is the identity on replicas that are
equal, and torch autograd gave the reference no other way to put an
Allreduce into the backward pass.  :func:`replicated_tree` is that adjoint
under a forward that sends nothing, for a caller whose replicas ARE equal
(a training step whose every update was one all-reduce's output, as
:func:`~mpi4torch_tpu.models.transformer.train_step`'s is);
:func:`all_average_tree` stays the reference's recipe, and the one for
replicas that may differ.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import config as _config
from ..constants import MPI_SUM
from ..utils.profiling import bucket_scope


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


def replicated_tree(comm, tree, bucket_bytes=None, overlap=None):
    """Enter a pytree that every rank of ``comm`` holds alike into a
    differentiated loss: forward, every leaf as it came in (no
    collective, no copy); adjoint, the mean of the ranks' cotangents.

    The contract: the leaves are equal on every rank of ``comm`` when
    this is called.  It does not make them so (:func:`all_average_tree`
    does, at an all-reduce of the tree every forward pass); on equal
    replicas that average is the identity, and this is it without the
    wire.

    The adjoint is :func:`all_average_tree`'s, bucket for bucket: the
    cotangents take the layout ``comm.Allreduce_tree`` gives the tree
    (a leaf that fills a bucket in its own shape, small leaves of one
    dtype tupled up to ``bucket_bytes``; ``0`` a leaf a rule), each
    bucket is scaled by ``1 / comm.size`` once and all-reduced through
    the same fused path, so the ``fusion_scope`` / ``compression_scope`` /
    ``algorithm_scope`` / ``overlap_scope`` of the call and the
    ``deterministic_mode`` of the differentiation choose its wire as
    they choose the average's, and every rank receives the same bits.
    One differentiation rule a bucket: a bucket's all-reduce depends on
    that bucket's cotangents alone, so it can run as soon as the
    backward pass has produced them.  Under a truthy ``overlap`` the
    split-phase window chains its buckets itself
    (:mod:`mpi4torch_tpu.overlap`), and the tree is one rule.

    What runs is ``Allreduce``'s forward on the cotangents, which is
    what its own adjoint runs (the reference's
    ``MPIAllreduceSumBackward``): the average's adjoint bit for bit on
    both backends, fused and per leaf, in a world of any size, under
    every scope above (tests/test_replicated_tree.py).  Two schedules
    are not their own transpose and get the forward's association where
    the average's adjoint has the transposed one, the same sum in
    another order: ``bidir`` outside ``deterministic_mode`` (its
    half-rings swap directions in the adjoint), and the eager
    ``overlap`` pipeline (its adjoint is the Isend/Irecv pipeline
    reversed; this is the pipeline's ascending-rank fold, the blocking
    path's bits)."""
    from ..fuse.bucketing import (bucket_layout, leaf_buckets,
                                  unflatten_buckets)
    from ..fuse.collectives import _resolve_bucket_bytes
    from ..overlap import resolve_overlap

    leaves, treedef = jax.tree.flatten(tree)
    bb = _resolve_bucket_bytes(bucket_bytes)
    if resolve_overlap(overlap):
        rule_of = [0] * len(leaves)
    elif bb <= 0:
        rule_of = range(len(leaves))
    else:
        rule_of = [slot.bucket for slot in bucket_layout(leaves, bb).slots]
    members = {}
    for i, rule in enumerate(rule_of):
        members.setdefault(rule, []).append(i)
    codec, algorithm, scoped_overlap = (
        _config.default_compression(), _config.default_algorithm(),
        _config.default_overlap())
    size = comm.size

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def enter(rule, xs):
        return xs

    def adjoint(rule, _, cotangents):
        buckets, layout = leaf_buckets(cotangents, bb)
        with _config.compression_scope(codec), \
                _config.algorithm_scope(algorithm), \
                _config.overlap_scope(scoped_overlap), \
                bucket_scope("replicated_tree", rule, len(members)):
            reduced = comm.Allreduce_tree(
                [b / size for b in buckets], MPI_SUM, bucket_bytes=bb,
                overlap=overlap)
        return (unflatten_buckets(reduced, layout),)

    enter.defvjp(lambda rule, xs: (xs, None), adjoint)
    out = list(leaves)
    with jax.named_scope("mpi4torch.replicated_tree"):
        for rule, held in members.items():
            for i, x in zip(held, enter(rule, [leaves[i] for i in held])):
                out[i] = x
    return jax.tree.unflatten(treedef, out)


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
