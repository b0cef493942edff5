"""Pytree → flat-bucket layout machinery for the fused collectives.

The DP/ZeRO recipes issue one collective per pytree leaf, so a
ResNet/Transformer step pays per-collective launch + ring-latency cost
hundreds of times, mostly for tiny tensors ("The Big Send-off", arxiv
2504.18658, makes the production case; GC3 the compiler-side one).  The
fix is the classic bucketing transform: flatten the tree into a small
number of **dtype-homogeneous flat buckets** of ~``bucket_bytes`` each
and run one collective per bucket.

Everything here is pure layout bookkeeping plus differentiable
``reshape``/``concatenate``/``slice`` glue:

* :func:`bucket_layout` computes a :class:`BucketLayout` for a tree
  *structure* — which leaf lands in which bucket at which offset.  It is
  ``functools.lru_cache``'d on ``(treedef, leaf avals, bucket_bytes)``,
  so re-flattening the same gradient tree every training step costs a
  dict lookup, not a re-plan (the "layout cached per pytree structure"
  contract of ISSUE 2).
* :func:`flatten_buckets` / :func:`unflatten_buckets` move values
  between the tree and the flat buckets.  Both are compositions of
  differentiable jnp ops, so the adjoint of "flatten → collective →
  unflatten" is "flatten → adjoint collective → unflatten" — bucketing
  preserves the framework's AD-transparency for free.

Bucket assignment is greedy in leaf order, per dtype: a leaf joins its
dtype's open bucket unless that would push the bucket past
``bucket_bytes`` (then a fresh bucket opens).  A single leaf larger than
``bucket_bytes`` gets a bucket of its own — leaves are never split, so
every leaf maps to one contiguous ``[offset, offset+size)`` slot of one
bucket.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class LeafSlot:
    """Where one leaf lives: bucket ``bucket``, elements
    ``[offset, offset + size)``, restored to ``shape``/``dtype``."""
    bucket: int
    offset: int
    size: int
    shape: Tuple[int, ...]
    dtype: Any


@dataclass(frozen=True)
class BucketLayout:
    """Full placement of a tree structure into flat buckets."""
    treedef: Any
    slots: Tuple[LeafSlot, ...]          # one per leaf, in tree order
    bucket_sizes: Tuple[int, ...]        # elements per bucket
    bucket_dtypes: Tuple[Any, ...]
    bucket_bytes: int

    @property
    def num_buckets(self) -> int:
        return len(self.bucket_sizes)


def _leaf_avals(leaves) -> Tuple[Tuple[Tuple[int, ...], Any], ...]:
    """Hashable (shape, dtype) signature per leaf — the cache key part
    that, together with the treedef, pins the layout.  Reads ``.shape``/
    ``.dtype`` attributes when present so ``jax.ShapeDtypeStruct``
    templates work (the zero3 template contract), falling back to
    ``jnp`` inspection for python scalars."""
    out = []
    for l in leaves:
        shape = tuple(getattr(l, "shape", None) or jnp.shape(l))
        dt = getattr(l, "dtype", None)
        out.append((shape, jnp.dtype(dt) if dt is not None
                    else jnp.result_type(l)))
    return tuple(out)


@functools.lru_cache(maxsize=512)
def _layout(treedef, avals, bucket_bytes: int) -> BucketLayout:
    open_bucket = {}                      # dtype -> (bucket idx, fill elems)
    sizes: List[int] = []
    dtypes: List[Any] = []
    slots: List[LeafSlot] = []
    for shape, dtype in avals:
        n = 1
        for s in shape:
            n *= int(s)
        itemsize = jnp.dtype(dtype).itemsize
        cur = open_bucket.get(dtype)
        if cur is not None:
            b, fill = cur
            if (fill + n) * itemsize > bucket_bytes and fill > 0:
                cur = None                # would overflow: close it
        if cur is None:
            b, fill = len(sizes), 0
            sizes.append(0)
            dtypes.append(dtype)
        slots.append(LeafSlot(bucket=b, offset=fill, size=n,
                              shape=shape, dtype=dtype))
        fill += n
        sizes[b] = fill
        open_bucket[dtype] = (b, fill)
    return BucketLayout(treedef=treedef, slots=tuple(slots),
                        bucket_sizes=tuple(sizes),
                        bucket_dtypes=tuple(dtypes),
                        bucket_bytes=int(bucket_bytes))


def bucket_layout(tree, bucket_bytes: int) -> BucketLayout:
    """The (cached) :class:`BucketLayout` for ``tree``'s structure."""
    leaves, treedef = jax.tree.flatten(tree)
    return _layout(treedef, _leaf_avals(leaves), int(bucket_bytes))


def leaf_buckets(tree, bucket_bytes: int):
    """``tree -> (buckets, layout)`` with a bucket that holds ONE leaf
    left in that leaf's own shape; a bucket of several leaves is their
    1-D concatenation.  A collective is element-wise, so the shape is
    free to choose, and on the TPU a ``reshape(-1)`` of a tiled matrix
    is a relayout, a copy of the whole leaf (PERF.md, PR 35)."""
    leaves, treedef = jax.tree.flatten(tree)
    layout = _layout(treedef, _leaf_avals(leaves), int(bucket_bytes))
    parts: List[List[Any]] = [[] for _ in layout.bucket_sizes]
    for leaf, slot in zip(leaves, layout.slots):
        parts[slot.bucket].append(jnp.asarray(leaf))
    buckets = [p[0] if len(p) == 1
               else jnp.concatenate([x.reshape(-1) for x in p])
               for p in parts]
    return buckets, layout


def flatten_buckets(tree, bucket_bytes: int):
    """``tree -> (buckets, layout)``: the list of 1-D dtype-homogeneous
    flat buckets holding every leaf, plus the layout to undo it."""
    buckets, layout = leaf_buckets(tree, bucket_bytes)
    return [b.reshape(-1) for b in buckets], layout


def unflatten_buckets(buckets: Sequence, layout: BucketLayout):
    """Inverse of :func:`flatten_buckets` and of :func:`leaf_buckets`
    (over possibly-transformed bucket values of the same sizes/dtypes):
    a leaf that fills its bucket is the bucket, reshaped if it came
    flat; any other is a slice of a flat one."""
    leaves = []
    for s in layout.slots:
        b = buckets[s.bucket]
        if s.size != layout.bucket_sizes[s.bucket]:
            b = jax.lax.slice_in_dim(b, s.offset, s.offset + s.size)
        leaves.append(b.reshape(s.shape))
    return jax.tree.unflatten(layout.treedef, leaves)


# ---------------------------------------------------------------------------
# Sharded layouts: buckets whose rows are rank segments
# ---------------------------------------------------------------------------
#
# The ZeRO wire pattern works on per-leaf *shards*: each leaf is
# flattened, zero-padded to a multiple of the communicator size n, and
# rank r owns segment r (parallel/zero.py).  The fused forms below pack
# many leaves' segments into one (n, total_per_rank) block bucket so one
# Reduce_scatter (axis 0, n rows) or one Allgather delivers EVERY leaf's
# shard at once: row r is the concatenation, in slot order, of each
# leaf's r-th segment.


@dataclass(frozen=True)
class ShardSlot:
    bucket: int
    offset: int        # within a row, in elements
    per_rank: int      # ceil(leaf.size / n)
    size: int          # unpadded leaf element count
    shape: Tuple[int, ...]
    dtype: Any


@dataclass(frozen=True)
class ShardLayout:
    treedef: Any
    slots: Tuple[ShardSlot, ...]
    row_sizes: Tuple[int, ...]           # per-rank elements per bucket
    bucket_dtypes: Tuple[Any, ...]
    nranks: int
    bucket_bytes: int

    @property
    def num_buckets(self) -> int:
        return len(self.row_sizes)


@functools.lru_cache(maxsize=512)
def _shard_layout(treedef, avals, nranks: int,
                  bucket_bytes: int) -> ShardLayout:
    open_bucket = {}
    rows: List[int] = []
    dtypes: List[Any] = []
    slots: List[ShardSlot] = []
    for shape, dtype in avals:
        n = 1
        for s in shape:
            n *= int(s)
        per = -(-n // nranks)             # ceil-padded per-rank length
        itemsize = jnp.dtype(dtype).itemsize
        cur = open_bucket.get(dtype)
        if cur is not None:
            b, fill = cur
            # Bucket budget counts the FULL padded leaf (n ranks x per),
            # the actual wire/HBM footprint of the block bucket.
            if (fill + per) * nranks * itemsize > bucket_bytes and fill > 0:
                cur = None
        if cur is None:
            b, fill = len(rows), 0
            rows.append(0)
            dtypes.append(dtype)
        slots.append(ShardSlot(bucket=b, offset=fill, per_rank=per,
                               size=n, shape=shape, dtype=dtype))
        fill += per
        rows[b] = fill
        open_bucket[dtype] = (b, fill)
    return ShardLayout(treedef=treedef, slots=tuple(slots),
                       row_sizes=tuple(rows), bucket_dtypes=tuple(dtypes),
                       nranks=int(nranks), bucket_bytes=int(bucket_bytes))


def shard_layout(tree, nranks: int, bucket_bytes: int) -> ShardLayout:
    leaves, treedef = jax.tree.flatten(tree)
    return _shard_layout(treedef, _leaf_avals(leaves), int(nranks),
                         int(bucket_bytes))


def flatten_shard_buckets(tree, nranks: int, bucket_bytes: int):
    """``tree -> (block buckets, layout)``: each bucket has shape
    ``(nranks, row_size)`` — row r holds every member leaf's (zero-padded)
    r-th segment, so a single axis-0 Reduce_scatter delivers rank r all
    of its leaf shards in one collective."""
    leaves, treedef = jax.tree.flatten(tree)
    layout = _shard_layout(treedef, _leaf_avals(leaves), int(nranks),
                           int(bucket_bytes))
    parts: List[List[Any]] = [[] for _ in layout.row_sizes]
    for leaf, slot in zip(leaves, layout.slots):
        flat = jnp.asarray(leaf).reshape(-1)
        padded = slot.per_rank * nranks
        if padded != slot.size:
            flat = jnp.pad(flat, (0, padded - slot.size))
        parts[slot.bucket].append(flat.reshape(nranks, slot.per_rank))
    buckets = [p[0] if len(p) == 1 else jnp.concatenate(p, axis=1)
               for p in parts]
    return buckets, layout


def unflatten_shard_rows(rows: Sequence, layout: ShardLayout):
    """Split per-rank bucket rows (shape ``(row_size,)`` each) back into
    the tree of flat per-leaf shards (length ``per_rank`` each) — the
    representation :func:`mpi4torch_tpu.parallel.zero.zero_step` updates."""
    leaves = [
        jax.lax.slice_in_dim(rows[s.bucket], s.offset,
                             s.offset + s.per_rank)
        for s in layout.slots
    ]
    return jax.tree.unflatten(layout.treedef, leaves)


def flatten_shard_rows(shard_tree, layout: ShardLayout):
    """Inverse of :func:`unflatten_shard_rows`: pack a tree of flat
    per-leaf shards into per-bucket rows of ``row_size`` elements.

    The shard tree must have the layout's structure (the template's) —
    a stale shard tree zipped against a fresh layout would silently
    misassign shards to slots, so the mismatch raises here, like the
    per-leaf ``jax.tree.map`` it replaced."""
    leaves, treedef = jax.tree.flatten(shard_tree)
    if treedef != layout.treedef:
        raise ValueError(
            f"shard tree structure {treedef} does not match the layout's "
            f"template structure {layout.treedef}; rebuild the shards "
            "from the current template (zero3_shard_params)")
    parts: List[List[Any]] = [[] for _ in layout.row_sizes]
    for leaf, slot in zip(leaves, layout.slots):
        flat = jnp.asarray(leaf).reshape(-1)
        if flat.shape[0] != slot.per_rank:
            raise ValueError(
                f"shard of {flat.shape[0]} elements where the template "
                f"expects {slot.per_rank} (leaf shape {slot.shape}); the "
                "shard tree does not belong to this template")
        parts[slot.bucket].append(flat)
    return [p[0] if len(p) == 1 else jnp.concatenate(p) for p in parts]


def unflatten_gathered(full_rows: Sequence, layout: ShardLayout):
    """From per-bucket gathered blocks of shape ``(nranks, row_size)``
    back to the tree of FULL leaves: leaf j is the concatenation over
    ranks of its segment column, unpadded and reshaped."""
    leaves = []
    for s in layout.slots:
        block = jax.lax.slice_in_dim(full_rows[s.bucket], s.offset,
                                     s.offset + s.per_rank, axis=1)
        flat = block.reshape(-1)
        leaves.append(jax.lax.slice_in_dim(flat, 0, s.size)
                      .reshape(s.shape))
    return jax.tree.unflatten(layout.treedef, leaves)
