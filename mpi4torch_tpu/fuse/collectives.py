"""Fused bucketed tree collectives.

One collective per *bucket* instead of per leaf:

* :func:`fused_allreduce_tree` — the DP primitive.  Every bucket of the
  blocking path is ONE whole ``comm.Allreduce``, on both backends.
  Mode A (SPMD mesh) lowers an exact-SUM bucket to a single
  ``lax.psum`` — one ``stablehlo.all_reduce`` a bucket forward and one
  in the adjoint, and no ``reduce_scatter``, ``all_gather`` or
  ``optimization_barrier`` — and a bucket that holds one leaf (every
  matrix of a real model) travels in the leaf's own shape, with no
  flat view before or after.  Mode B (eager thread-SPMD) runs one
  rendezvous collective per bucket (bit-identical to the per-leaf
  ascending-rank fold), or — with ``overlap=True`` — the
  :func:`_pipeline_allreduce` schedule: nonblocking per-bucket
  gather-fold collectives built from the existing ``Isend``/``Irecv``/
  ``WaitHandle`` machinery, issuing bucket ``i+1``'s transfers before
  waiting on bucket ``i`` (``JoinDummiesHandle`` chains the issue
  order; the buffered eager sends make the overlap real).

  Until PR 35 a Mode A bucket was a flat ring reduce-scatter +
  all-gather pair, bucket ``i``'s all-gather staged behind bucket
  ``i+1``'s reduce-scatter through ``optimization_barrier`` to keep two
  collectives in flight.  That was written on a CPU.  On the chip every
  collective is a synchronous instruction (``dp_collective_exposed_ms``
  equal to ``dp_collective_ms`` in every check since PR 24), so the
  staging hid nothing, and the TPU's compiler makes an all-reduce and a
  slice of a flat ``psum_scatter``: each direction ran an all-reduce and
  then an all-gather, a third more wire time than the all-reduce alone
  (PERF.md, PR 35).  Asking for collectives in flight is the explicit
  ``overlap=`` scheduler's (:mod:`mpi4torch_tpu.overlap`).

* :func:`fused_reduce_scatter_tree` / :func:`fused_allgather_tree` —
  the ZeRO pair: block buckets whose row ``r`` concatenates every member
  leaf's ``r``-th padded segment, so one axis-0 ``Reduce_scatter``
  (→ ``lax.psum_scatter`` under SPMD) or one ``Allgather`` moves every
  leaf's shard at once (parallel/zero.py rides these by default).

AD transparency is compositional: bucketing is differentiable
reshape/concat/slice glue (fuse/bucketing.py) and every collective here
is the facade's own ``custom_vjp`` op, so the backward pass of a fused
bucketed collective is itself fused bucketed communication — the
adjoint of a bucket's all-reduce is one all-reduce of the cotangent
bucket, in reverse bucket order.

Compression composes per bucket: ``compression="q8"`` (or an active
``compression_scope``) sends each float bucket through the quantized
ring pipeline of :mod:`mpi4torch_tpu.compress` — fused buckets are also
quantized, with the facade's degrade/raise dtype rules applied
per-bucket (a scope default leaves integer buckets exact; an explicit
codec on a non-float bucket raises).
"""

from __future__ import annotations

import collections
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from .. import config as _config
from .. import constants as C
from ..ops.spmd import _ring_table
from ..resilience import guards as _guards
from ..runtime import CommError
from ..utils.profiling import bucket_scope
from .bucketing import (flatten_buckets, flatten_shard_buckets,
                        flatten_shard_rows, leaf_buckets, shard_layout,
                        unflatten_buckets, unflatten_gathered,
                        unflatten_shard_rows)

# Tag block reserved for the eager overlap pipeline: high enough to stay
# clear of user p2p tags; each bucket consumes a stride of
# (size + GRAD_TAG_OFFSET + 1) tags so a bucket's gradient tags
# (tag + 10, ops/eager.py) can never collide with another bucket's
# forward tags.
FUSE_TAG_BASE = 1 << 20


def _resolve_bucket_bytes(bucket_bytes) -> int:
    if bucket_bytes is None:
        return _config.default_bucket_bytes()
    # Same validation as the config setters: a negative size is a caller
    # bug, not a request for the per-leaf path.
    return _config._validated_bucket_bytes(bucket_bytes)


def _is_mode_a(comm) -> bool:
    """True when the communicator currently resolves to the SPMD mesh
    backend (single-trace Mode A) rather than the eager thread runtime."""
    from ..ops.spmd import SpmdBackend
    return isinstance(comm._backend(), SpmdBackend)


def _bucket_codec(comm, bucket, codec, op: int, explicit: bool):
    """The facade's per-tensor compression rules, applied per bucket:
    scope defaults degrade non-float buckets and non-SUM ops to exact;
    an explicit codec on a non-float bucket raises (comm._codec_for)."""
    from ..comm import _codec_for
    bcodec = _codec_for(bucket, codec, explicit)
    if bcodec is not None and op != C.MPI_SUM and not explicit:
        bcodec = None
    return bcodec


def _plan_bucket(comm, bucket, op: int, codec, algo, *, explicit: bool,
                 algo_explicit: bool, owns_resolution: bool, size: int,
                 mode_a: bool):
    """Per-bucket codec/algorithm resolution — ONE implementation for
    the blocking fused path and the split-phase overlap scheduler
    (mpi4torch_tpu.overlap), so the two schedules can never drift on
    which bucket rides which wire.

    Applies, in order: the facade's per-tensor compression rules on
    THIS bucket's dtype; the codec/algorithm reconcile (explicit
    conflicts raise, scope halves yield); backend-side applicability
    degrades for scope defaults (2-axis backends yield non-native
    schedules to auto; a non-dividing config.hier_group_size degrades
    hier/torus to ring); and, for still-unresolved Mode A buckets, the
    tune selector keyed on this bucket's byte size."""
    from ..comm import _reconcile_codec_algorithm
    bcodec = _bucket_codec(comm, bucket, codec, op, explicit)
    bcodec, balgo = _reconcile_codec_algorithm(
        bcodec, algo, codec_explicit=explicit, algo_explicit=algo_explicit)
    if not algo_explicit:
        if owns_resolution:
            if balgo not in (None, "ring", "hier", "torus"):
                balgo = None
        elif balgo in ("hier", "torus"):
            from ..tune import resolve_hier_group
            try:
                resolve_hier_group(size)
            except CommError:
                balgo = "ring"
    if balgo is None and mode_a:
        from .. import tune as _tune
        balgo = _tune.select_auto(
            collective="allreduce",
            nbytes=bucket.size * bucket.dtype.itemsize, dtype=bucket.dtype,
            nranks=size,
            deterministic=_config.deterministic_reductions(),
            codec=bcodec)
    return bcodec, balgo


def _pipeline_allreduce(comm, buckets: Sequence, op: int, *,
                        depth: int = 2):
    """Eager overlap scheduler: nonblocking per-bucket sum-allreduce.

    Each bucket's collective is the gather+ascending-rank-fold form
    posted through the existing WaitHandle machinery — ``size-1``
    buffered ``Isend``/``Irecv`` pairs per bucket (payloads land in the
    destination mailboxes immediately; nothing blocks until ``Wait``).
    The scheduler keeps ``depth`` buckets in flight: bucket ``i+1``'s
    transfers are issued before bucket ``i``'s ``Wait``s, and
    ``JoinDummiesHandle`` chains each bucket's receives onto the
    previous bucket's send descriptor so the issue order is explicit in
    the dependency graph.  The fold is the same ascending-rank
    association as the rendezvous path — results are bit-identical to
    it (and to the per-leaf path).  Gradients need no extra code: the
    ``Isend``/``Irecv``/``Wait`` custom VJPs route each peer's cotangent
    back over ``tag + 10``, so the backward pass is the same pipeline
    in the reverse direction.
    """
    from ..comm import JoinDummies, JoinDummiesHandle

    if op != C.MPI_SUM:
        raise CommError(
            "the fused overlap pipeline supports MPI_SUM only; pass "
            "overlap=False (per-bucket rendezvous collectives) for other "
            "reductions")
    from ..ops.eager import GRAD_TAG_OFFSET

    n, rank = comm.size, comm.rank
    nb = len(buckets)
    if n == 1 or nb == 0:
        return [jnp.asarray(b) for b in buckets]
    # Per-bucket tag block: n-1 forward tags plus their tag+10 gradient
    # shadow — the next bucket's block starts past both, so a slow rank's
    # forward receive can never swallow a fast rank's backward gradient.
    stride = n + GRAD_TAG_OFFSET + 1
    outs: list = [None] * nb
    pending: collections.deque = collections.deque()
    prev_send = [None]

    def start(i: int) -> None:
        b = jnp.asarray(buckets[i])
        tag0 = FUSE_TAG_BASE + i * stride
        sends, recvs = [], []
        for off in range(1, n):
            sends.append(comm.Isend(b, _ring_table(n, off), tag0 + off))
            recvs.append(comm.Irecv(jnp.zeros_like(b),
                                    _ring_table(n, n - off), tag0 + off))
        # Chain every receive onto this bucket's sends (and the previous
        # bucket's last send, pinning issue order across buckets).  The
        # forward edge send -> recv-Wait is what makes the BACKWARD
        # deadlock-free: it reverses into recvWait-bwd -> isend-bwd, so
        # each rank posts its (buffered) gradient sends before blocking
        # in an Isend VJP's gradient receive.  Without the edge the two
        # backward chains are independent and the autodiff scheduler may
        # run the blocking receives first — observed as a symmetric
        # all-rank deadlock on the last bucket.
        dummies = [h.dummy for h in sends]
        if prev_send[0] is not None:
            dummies.append(prev_send[0].dummy)
        recvs = [JoinDummiesHandle(r, dummies) for r in recvs]
        prev_send[0] = sends[-1]
        pending.append((i, b, sends, recvs))

    def finish() -> None:
        i, b, sends, recvs = pending.popleft()
        vals: list = [None] * n
        vals[rank] = b
        for off, r in enumerate(recvs, start=1):
            vals[(rank - off) % n] = comm.Wait(r)
        # Finite guard (mpi4torch_tpu.resilience) over the per-peer
        # bucket contributions: a corrupt payload off the p2p wire is
        # attributed to its sender before the fold can mix it in.
        _guards.check_contributions(vals, "Iallreduce_tree")
        out = C.reduce_ordered(op, vals)
        # Completing the sends through JoinDummies keeps every Isend on
        # the differentiation path even though its Wait output is a pure
        # dependency token — the backward's remote-gradient receives
        # must run on all ranks symmetrically (ops/eager.py isend bwd).
        outs[i] = JoinDummies(out, [comm.Wait(h) for h in sends])

    for i in range(nb):
        with bucket_scope("Iallreduce_tree", i, nb):
            start(i)
        if len(pending) >= max(int(depth), 1):
            finish()
    while pending:
        finish()
    return outs


def fused_allreduce_tree(comm, tree, op: int = C.MPI_SUM, *,
                         compression=None, bucket_bytes=None,
                         mean: bool = False,
                         overlap: Optional[bool] = None,
                         algorithm=None, tier_window=None):
    """Allreduce every leaf of ``tree`` through dtype-homogeneous
    buckets — one collective per bucket instead of per leaf.

    ``bucket_bytes``: target bucket size (None → the ``fusion_scope`` /
    process default, ~4 MiB; 0/False → unfused per-leaf ops).
    ``mean=True`` divides each reduced bucket by ``comm.size`` once —
    the DP rank-mean as a single post-fuse scale per bucket (MPI_SUM
    only).  ``compression`` follows the facade's Allreduce contract,
    applied per bucket.  ``overlap``: None picks the
    ``config.overlap_scope`` / process default, which is the blocking
    path (one whole collective a bucket, nothing staged); a truthy value
    under the SPMD backend selects the split-phase scheduler
    (:mod:`mpi4torch_tpu.overlap`) and under the eager runtime the
    nonblocking Isend/Irecv pipeline (:func:`_pipeline_allreduce`) —
    exact MPI_SUM only; requesting it with a codec or another reduction
    raises rather than silently degrading to the blocking rendezvous.

    ``algorithm`` follows the facade's Allreduce contract
    (:mod:`mpi4torch_tpu.tune`), applied *per bucket*: an explicit name
    pins every bucket; with auto selection the tune selector picks per
    bucket size, so the full body buckets keep the ring (one
    ``lax.psum``) — or, past the measured
    ``config.bandwidth_crossover_bytes``, the multipath bandwidth
    algorithm (``bidir``'s counter-rotating dual ring) — while a small
    tail bucket below the measured latency crossover takes the
    latency-optimal schedule (``rhd``/``tree``) instead of paying
    O(nranks) ring steps for a few KiB.  Compressed buckets stay on the
    algorithms their codec declares — for the block-q8 family that
    includes the bandwidth tier, so a compressed body bucket past the
    crossover rides the quantized ``bidir`` dual ring (in-schedule
    requantizing hops on both link rotations) and the two biggest wire
    wins compose instead of excluding each other.

    ``tier_window`` widens the split-phase window on tier-stacked
    communicators with a slow outer tier (see
    :func:`mpi4torch_tpu.overlap.overlap_allreduce_tree`); ``None``
    derives it from the configured ``tier_bandwidths`` skew
    (:func:`mpi4torch_tpu.overlap.tier_window_depth` — no tier config,
    no change)."""
    if mean and op != C.MPI_SUM:
        raise CommError(
            f"mean=True is the rank-mean of an MPI_SUM reduction; got "
            f"{C.op_name(op)}")
    bb = _resolve_bucket_bytes(bucket_bytes)
    size = comm.size
    mode_a = _is_mode_a(comm)
    explicit = compression is not None
    from ..comm import _resolve_algorithm, _resolve_compression
    from ..overlap import resolve_overlap
    overlap_explicit = overlap is not None
    overlap = resolve_overlap(overlap)
    codec = _resolve_compression(compression)
    algo_explicit = algorithm not in (None, False, "auto")
    owns_resolution = getattr(comm._backend(),
                              "owns_algorithm_resolution", False)
    if owns_resolution:
        # 2-axis hier backend: skip the flat-world registry gates, same
        # as comm.Allreduce — validate the name only; the backend
        # enforces what it can lower (explicit raises, scope defaults
        # yield to its native schedule via the per-bucket degrade
        # below).
        from ..tune import get_algorithm
        requested = (algorithm if algo_explicit
                     else None if algorithm in (False, "auto")
                     else _config.default_algorithm())
        algo = (None if requested in (None, "auto")
                else get_algorithm(requested).name)
    else:
        algo = _resolve_algorithm(algorithm, size)

    # Which overlap machinery can serve this communicator: the SPMD
    # mesh (and the 2-axis hier backend, through the generic
    # compute-at-start handles) take the split-phase scheduler
    # (mpi4torch_tpu.overlap); the eager runtime takes the
    # Isend/Irecv pipeline.
    sched_ok = mode_a or owns_resolution
    if overlap and not sched_ok:
        # Overlap request on the eager backend: the pipeline is
        # exact-SUM/ring-only.  An EXPLICIT overlap= fails loudly on a
        # conflict — silently falling back to the blocking rendezvous
        # would leave the caller believing they got the nonblocking
        # schedule; a scope/process default (config.default_overlap)
        # degrades to it instead, the standard scope rule.  Validated
        # before the fusion-off early return so the argument check does
        # not depend on ambient fusion_scope state.
        if not overlap_explicit:
            if (op != C.MPI_SUM or codec is not None
                    or algo not in (None, "ring")):
                overlap = False
        else:
            if op != C.MPI_SUM:
                raise CommError(
                    "the fused overlap pipeline supports MPI_SUM only; "
                    "pass overlap=False (per-bucket rendezvous "
                    f"collectives) for {C.op_name(op)} reductions")
            if codec is not None:
                raise CommError(
                    "the fused overlap pipeline is exact-only; compressed "
                    f"buckets (codec {codec.name!r}"
                    + ("" if explicit else ", from the active "
                       "compression_scope/process default") +
                    ") take the per-bucket rendezvous path — pass "
                    "overlap=False, or compression=False to pipeline exact")
            if algo not in (None, "ring"):
                raise CommError(
                    "the fused overlap pipeline's gather-fold IS the ring "
                    f"association; algorithm={algo!r}"
                    + ("" if algorithm is not None else " (from the active "
                       "algorithm_scope/process default)") +
                    " cannot ride it — pass overlap=False for per-bucket "
                    "rendezvous collectives on that algorithm")
    if overlap and sched_ok and codec is not None and overlap_explicit:
        # Split-phase transfers are exact: with the overlap request
        # explicit, an explicit codec is a hard conflict; a scope codec
        # is the non-explicit half and yields to the exact split wire.
        # (With overlap itself a scope default, the codec is honored
        # instead: compressed buckets take the blocking codec pipeline
        # in their start slot while exact neighbors ride split-phase —
        # the per-bucket degrade, mpi4torch_tpu.overlap.scheduler.)
        if explicit:
            raise CommError(
                f"compression={codec.name!r} cannot ride the split-phase "
                "overlap window — the codec pipeline is a fused "
                "multi-step collective with no start/wait form; drop "
                "overlap= (blocking compressed buckets) or compression= "
                "(exact split-phase buckets)")
        codec = None

    if bb <= 0:
        out = jax.tree.map(
            lambda p: comm.Allreduce(p, op, compression=compression,
                                     algorithm=algorithm), tree)
        if mean:
            out = jax.tree.map(lambda p: p / size, out)
        return out

    if overlap:
        # Both overlap schedulers split and window 1-D buckets.
        buckets, layout = flatten_buckets(tree, bb)
        if not sched_ok:
            from ..overlap import overlap_depth
            reduced = _pipeline_allreduce(comm, buckets, op,
                                          depth=overlap_depth(overlap))
            if mean:
                reduced = [b / size for b in reduced]
            return unflatten_buckets(reduced, layout)

        # The split-phase overlap scheduler (mpi4torch_tpu.overlap):
        # windowed Allreduce_start/Wait pairs, sharing THIS function's
        # per-bucket codec/algorithm plan so the split-phase and
        # blocking schedules can never drift on which bucket rides
        # which wire.
        from ..overlap import (overlap_allreduce_tree, overlap_depth,
                               tier_window_depth)

        def plan(i, b):
            return _plan_bucket(
                comm, b, op, codec, algo, explicit=explicit,
                algo_explicit=algo_explicit,
                owns_resolution=owns_resolution, size=size, mode_a=mode_a)

        return overlap_allreduce_tree(
            comm, buckets, layout, op, depth=overlap_depth(overlap),
            mean=mean, plan=plan,
            tier_window=(tier_window_depth() if tier_window is None
                         else tier_window))

    # The blocking path, both backends: every bucket is ONE whole
    # Allreduce through the facade (on the SPMD mesh one ``lax.psum``,
    # its adjoint one ``lax.psum``; why no pair, no staging: the module
    # head), a bucket of one leaf in the leaf's own shape.
    buckets, layout = leaf_buckets(tree, bb)
    nb = layout.num_buckets
    reduced = []
    for i, b in enumerate(buckets):
        # Per-bucket codec/algorithm pick (_plan_bucket, shared with the
        # split-phase scheduler): the facade's dtype degrade, the
        # codec/algorithm reconcile, backend-side applicability
        # degrades, and — for still-unresolved Mode A buckets — the
        # tune selector keyed on THIS bucket's byte size, so small tail
        # buckets take the latency algorithm where the autotuner's
        # measurements say so while q8 buckets stay on the ring.
        bcodec, balgo = _plan_bucket(
            comm, b, op, codec, algo, explicit=explicit,
            algo_explicit=algo_explicit, owns_resolution=owns_resolution,
            size=size, mode_a=mode_a)
        # Re-resolution guard: the degrade decision was already made
        # here, so hand the facade the resolved codec, or False to pin
        # exact (compression=None would re-read the scope default and
        # re-apply a codec this bucket — or an explicit
        # compression=False — just opted out of).
        arg = bcodec if bcodec is not None else (
            False if (codec is not None or explicit) else None)
        with bucket_scope("Allreduce_tree", i, nb, codec=bcodec):
            out = comm.Allreduce(b, op, compression=arg, algorithm=balgo)
        reduced.append(out / size if mean else out)
    return unflatten_buckets(reduced, layout)


def fused_reduce_scatter_tree(comm, tree, op: int = C.MPI_SUM, *,
                              bucket_bytes=None, mean: bool = False,
                              overlap=None):
    """Reduce-scatter every leaf of ``tree`` in block buckets: returns
    the tree of this rank's flat per-leaf shards (length
    ``ceil(leaf.size / size)`` each, zero-padded — the ZeRO gradient
    representation of parallel/zero.py), computed with ONE
    ``Reduce_scatter`` per bucket (→ one native ``psum_scatter`` under
    SPMD).  ``mean=True`` divides each shard bucket by ``comm.size``
    once (MPI_SUM only).  Always exact (the ZeRO internals are pinned
    exact; see compress docs).

    ``overlap`` (None → the :func:`config.overlap_scope` / process
    default): truthy under the SPMD backend runs the split-phase
    window (:func:`mpi4torch_tpu.overlap.overlap_reduce_scatter_tree`)
    — up to ``depth`` bucket reduce-scatters in flight, bit-identical
    to the blocking form."""
    if mean and op != C.MPI_SUM:
        raise CommError(
            f"mean=True is the rank-mean of an MPI_SUM reduction; got "
            f"{C.op_name(op)}")
    bb = _resolve_bucket_bytes(bucket_bytes)
    size = comm.size
    from ..overlap import overlap_depth, resolve_overlap
    overlap = resolve_overlap(overlap)
    if overlap and bb > 0 and _is_mode_a(comm):
        from ..overlap import overlap_reduce_scatter_tree
        return overlap_reduce_scatter_tree(
            comm, tree, op, bucket_bytes=bb, depth=overlap_depth(overlap),
            mean=mean)
    if bb <= 0:
        def per_leaf(g):
            flat = jnp.asarray(g).reshape(-1)
            per = -(-flat.shape[0] // size)
            padded = jnp.pad(flat, (0, per * size - flat.shape[0]))
            rs = comm.Reduce_scatter(padded, op, 0)
            return rs / size if mean else rs
        return jax.tree.map(per_leaf, tree)

    buckets, layout = flatten_shard_buckets(tree, size, bb)
    rows = []
    for i, b in enumerate(buckets):
        with bucket_scope("Reduce_scatter_tree", i, layout.num_buckets):
            row = comm.Reduce_scatter(b, op, 0).reshape(-1)
        rows.append(row / size if mean else row)
    return unflatten_shard_rows(rows, layout)


def fused_allgather_tree(comm, shard_tree, template, *, bucket_bytes=None,
                         overlap=None):
    """Gather a tree of flat per-leaf shards (the output shape of
    :func:`fused_reduce_scatter_tree` /
    :func:`~mpi4torch_tpu.parallel.zero.zero3_shard_params`) back into
    full leaves shaped like ``template``, with ONE ``Allgather`` per
    bucket.  Differentiable: the adjoint is the fused per-bucket
    reduce-scatter of the cotangents (the ZeRO-3 wire pattern).  Always
    exact — parameter shards must not ride a lossy codec.

    ``overlap`` (None → the :func:`config.overlap_scope` / process
    default): truthy under the SPMD backend runs the double-buffered
    parameter *prefetch* (:func:`mpi4torch_tpu.overlap.
    prefetch_allgather_tree`) — bucket ``k+1``'s all-gather starts
    before bucket ``k``'s Wait, bit-identical to the blocking form."""
    bb = _resolve_bucket_bytes(bucket_bytes)
    size = comm.size
    from ..overlap import overlap_depth, resolve_overlap
    overlap = resolve_overlap(overlap)
    if overlap and bb > 0 and _is_mode_a(comm):
        from ..overlap import prefetch_allgather_tree
        return prefetch_allgather_tree(
            comm, shard_tree, template, bucket_bytes=bb,
            depth=overlap_depth(overlap))
    if bb <= 0:
        def per_leaf(shard, t):
            full = comm.Allgather(shard, 0, compression=False)
            return full[:t.size].reshape(t.shape).astype(t.dtype)
        return jax.tree.map(per_leaf, shard_tree, template)

    layout = shard_layout(template, size, bb)
    rows = flatten_shard_rows(shard_tree, layout)
    blocks = []
    for i, row in enumerate(rows):
        with bucket_scope("Allgather_tree", i, layout.num_buckets):
            full = comm.Allgather(row, 0, compression=False)
        blocks.append(full.reshape(size, -1))
    out = unflatten_gathered(blocks, layout)
    return jax.tree.map(lambda x, t: x.astype(t.dtype), out, template)
