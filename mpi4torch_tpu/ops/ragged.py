"""Ragged (per-rank-varying) collectives under static shapes.

The reference's Gather/Scatter/Alltoall accept *per-rank-varying* segment
sizes, realized with MPI_Gatherv-style derived datatypes
(reference: csrc/extension.cpp:540-554, 947-979).  Under single-trace SPMD
every rank runs one XLA program with static shapes, so varying sizes are
expressed the XLA way instead (SURVEY.md §7 hard part 2): **capacity-padded
buffers + validity counts + masks**.  These ops carry exactly the
information of their MPI_*v counterparts — (payload, counts) in,
(payload, counts) out — and work identically on both backends, since they
are built purely on the facade's dense collectives (hence AD-transparent:
cotangents route back through the same exchange, and padding slots never
receive or leak gradient).

The eager runtime additionally supports the reference's *true* varying
sizes on the dense ops themselves (shapes are per-rank concrete there);
these ragged forms are the portable recipe that also compiles.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp


def segment_mask(counts, capacity: int):
    """``(...,)`` (or scalar) int counts → ``(..., capacity)`` validity
    mask of 0/1 int32 (a scalar count yields a ``(capacity,)`` mask)."""
    pos = jnp.arange(capacity)
    return (pos < jnp.asarray(counts)[..., None]).astype(jnp.int32)


def position_onehot(pos, capacity: int):
    """``(...,)`` (or scalar) int positions → ``(..., capacity)`` one-hot
    0/1 int32 mask selecting exactly slot ``pos``.

    The single-position counterpart of :func:`segment_mask`, and the
    per-slot KV-cache write mask of the continuous-batching decode step
    (:mod:`mpi4torch_tpu.serve`): each slot of the batch writes its new
    K/V row at its OWN position, so the scalar-``pos``
    ``dynamic_update_slice`` of the single-sequence decode path becomes
    a masked ``where`` over the static ``max_seq`` buffer — same static
    shapes, one compiled program for any mix of per-slot positions.
    Out-of-range positions produce an all-zero row (no write), which is
    what an inactive slot wants."""
    p = jnp.arange(capacity)
    return (p == jnp.asarray(pos)[..., None]).astype(jnp.int32)


def _masked(x, counts, capacity: int):
    m = segment_mask(counts, capacity)
    m = m.reshape(m.shape + (1,) * (x.ndim - m.ndim))
    # where, not multiply: padding slots may hold NaN/inf (e.g. leftovers
    # of a masked softmax) and NaN*0 would survive as NaN.
    return jnp.where(m != 0, x, jnp.zeros((), x.dtype))


def _validated_rowblock(opname: str, x, size: int) -> int:
    """Check a ``(size, capacity, *feat)`` per-destination block; return
    the capacity."""
    if x.ndim < 2 or x.shape[0] != size:
        raise ValueError(
            f"{opname} expects x of shape (size={size}, capacity, *feat); "
            f"got {x.shape}")
    return x.shape[1]


def _validated_counts_vector(opname: str, counts, size: int, capacity: int):
    """Check a ``(size,)`` counts vector; clamp to [0, capacity] so the
    transmitted counts always agree with what the mask lets through — an
    out-of-range count would otherwise arrive inconsistent with the
    zero-padded valid data."""
    counts = jnp.asarray(counts)
    if counts.shape != (size,):
        raise ValueError(
            f"{opname}: counts must have shape ({size},); got "
            f"{counts.shape}")
    return jnp.clip(counts, 0, capacity)


def _validated_scalar_count(opname: str, x, count):
    """Check a ``(capacity, *feat)`` payload + scalar count; return
    ``(capacity, clamped count)``."""
    if x.ndim < 1:
        raise ValueError(
            f"{opname} expects x of shape (capacity, *feat); got {x.shape}")
    capacity = x.shape[0]
    count = jnp.asarray(count)
    if count.ndim != 0:
        raise ValueError(
            f"{opname}: count must be a scalar (this rank's valid length); "
            f"got shape {count.shape} — per-destination counts belong to "
            "ragged_alltoall")
    return capacity, jnp.clip(count, 0, capacity)


def block_gather(pool, table):
    """Static-shape gather of a paged KV pool through a block table.

    ``pool``: ``(num_blocks, block_size, *feat)`` — the fixed-size page
    pool (the serving KV cache's paged form; one shared block-id space).
    ``table``: ``(rows, n_blk)`` int block ids, ``-1`` (any negative)
    marking an unmapped entry.  Returns ``(rows, n_blk * block_size,
    *feat)``: each row's pages concatenated in table order, unmapped
    entries yielding all-zero pages (the inert padded tail — downstream
    causal/validity masks must make them irrelevant, and the serving
    decode's per-row causal frontier does exactly that).

    The table is DATA, not structure: one compiled program serves every
    table state, which is the no-retrace contract that lets the pool
    churn freely under one decode-step executable.  Values move by
    gather only — never arithmetic — so mapped pages come back
    bit-identical in ``pool``'s dtype."""
    pool = jnp.asarray(pool)
    if pool.ndim < 2:
        raise ValueError(
            f"block_gather expects pool of shape (num_blocks, "
            f"block_size, *feat); got {pool.shape}")
    t = jnp.asarray(table, jnp.int32)
    if t.ndim != 2:
        raise ValueError(
            f"block_gather expects a (rows, n_blk) table; got shape "
            f"{t.shape}")
    nb, bs = pool.shape[0], pool.shape[1]
    g = jnp.take(pool, jnp.clip(t, 0, nb - 1).reshape(-1), axis=0)
    g = g.reshape(t.shape + pool.shape[1:])        # (rows, n_blk, bs, *f)
    valid = (t >= 0).reshape(t.shape + (1,) * (g.ndim - 2))
    g = jnp.where(valid, g, jnp.zeros((), pool.dtype))
    return g.reshape((t.shape[0], t.shape[1] * bs) + pool.shape[2:])


def block_scatter(pool, block_ids, offsets, values, active=None):
    """Write one row per writer into a paged pool, in place where the
    caller lets it be — the block-granular counterpart of the
    :func:`position_onehot` slot-table cache write.

    ``pool``: ``(num_blocks, block_size, *feat)``.  ``block_ids`` /
    ``offsets``: ``(writers,)`` int — writer ``w`` targets
    ``pool[block_ids[w], offsets[w]]`` with ``values[w]`` (``(writers,
    *feat)``).  ``active`` (``(writers,)`` bool/int, optional) masks
    writers out entirely; out-of-range ids/offsets (including the
    engine's ``-1`` free-slot convention) also write nothing, so an
    inactive row needs no special-cased table state.

    Writers must target DISTINCT (block, offset) cells — the serving
    invariant that live slots own disjoint write positions (shared
    prefix blocks are read-only; writes land in private pages, the
    copy-on-write rule).  Under that invariant the write is exact:
    written cells carry ``values``' bits cast to ``pool``'s dtype and
    every other cell keeps its bits.  Static shapes throughout — same
    compiled program for any table churn.

    The write is a true scatter of ``writers`` rows: no array of the
    pool's size is formed beside the result, and a compiled caller that
    donates ``pool`` (the serving decode step does) gets the rows
    written into the pool's own buffer.  A writer that must not write
    is sent to an id past the pool, which the scatter drops — never
    left at ``-1``, which ``.at[]`` would wrap to the last page (the
    rule ``serve.kv.install_rows_paged`` puts on its index)."""
    pool = jnp.asarray(pool)
    values = jnp.asarray(values)
    if pool.ndim < 2:
        raise ValueError(
            f"block_scatter expects pool of shape (num_blocks, "
            f"block_size, *feat); got {pool.shape}")
    if values.shape[1:] != pool.shape[2:]:
        raise ValueError(
            f"block_scatter values feature shape {values.shape[1:]} "
            f"must match pool feature shape {pool.shape[2:]}")
    nb, bs = pool.shape[0], pool.shape[1]
    b = jnp.asarray(block_ids, jnp.int32)
    o = jnp.asarray(offsets, jnp.int32)
    live = (b >= 0) & (b < nb) & (o >= 0) & (o < bs)
    if active is not None:
        live = live & (jnp.asarray(active).astype(bool))
    # Distinct ids past the pool for the dropped writers: the scatter is
    # promised unique indices.
    b = jnp.where(live, b, nb + jnp.arange(b.shape[0], dtype=jnp.int32))
    return pool.at[b, jnp.where(live, o, 0)].set(
        values.astype(pool.dtype), mode="drop", unique_indices=True)


def ragged_alltoall(comm, x, send_counts) -> Tuple:
    """All-to-all with per-destination-varying segment sizes (the
    MPI_Alltoallv analogue; reference's same-axis Alltoall with varying
    ``numelem``, csrc/extension.cpp:947-979).

    ``x``: ``(size, capacity, *feat)`` — row block ``i`` is destined for
    rank ``i``, of which the first ``send_counts[i]`` entries are valid.
    ``send_counts``: ``(size,)`` integers, each ``<= capacity``.

    Returns ``(recv, recv_counts)``: ``recv[s]`` is the block rank ``s``
    sent here (``(size, capacity, *feat)``), with invalid slots zeroed;
    ``recv_counts[s]`` its valid length.  Differentiable in ``x``; padding
    slots get zero gradient (they are masked before the exchange, so the
    adjoint exchange routes nothing into them)."""
    size = comm.size
    capacity = _validated_rowblock("ragged_alltoall", x, size)
    send_counts = _validated_counts_vector("ragged_alltoall send_counts",
                                           send_counts, size, capacity)

    xz = _masked(x, send_counts, capacity)
    # Gather sources along a fresh axis of their own, keep my
    # destination block: (size, 1, cap, *feat) -> my (1, size, cap,
    # *feat).  The fresh axis is for the adjoint, which splits the
    # gathered axis: gathered along the rows, that axis is size * cap
    # long, and the v5e's compiler took minutes over its split at
    # 100,000 rows (PERF.md section 6, PR 48).
    recv = comm.Alltoall(xz[:, None], gatheraxis=1, scatteraxis=0, numelem=1)
    recv = recv.reshape(x.shape)
    rc = comm.Alltoall(send_counts.reshape(size, 1), gatheraxis=1,
                       scatteraxis=0, numelem=1)
    return recv, rc.reshape(size)


def ragged_allgather(comm, x, count) -> Tuple:
    """Allgather with per-rank-varying valid lengths (the MPI_Allgatherv
    analogue; reference: csrc/extension.cpp:633-734 with varying shard
    sizes).

    ``x``: ``(capacity, *feat)`` with the first ``count`` rows valid.
    Returns ``(gathered, counts)``: ``gathered`` is ``(size, capacity,
    *feat)`` — rank ``s``'s padded block at index ``s``, invalid slots
    zeroed — and ``counts`` is ``(size,)``.  ``jnp.concatenate`` of the
    per-rank valid prefixes reconstructs the reference's exact Allgatherv
    result (see tests)."""
    capacity, count = _validated_scalar_count("ragged_allgather", x, count)
    xz = _masked(x, count, capacity)
    # compression=False: ragged reassembly slices exact padded values;
    # a scope-level codec must not quantize them.
    gathered = comm.Allgather(xz[None], gatheraxis=0, compression=False)
    counts = comm.Allgather(count[None], gatheraxis=0)
    return gathered, counts


def ragged_gather(comm, x, count, root: int = 0) -> Tuple:
    """Gather-to-root with per-rank-varying valid lengths (the MPI_Gatherv
    analogue; reference's Gather with varying shard sizes,
    csrc/extension.cpp:540-577 + tests/test_collectives.py varying
    ``numelem``).

    ``x``: ``(capacity, *feat)`` with the first ``count`` rows valid
    (``count`` may differ per rank and may be traced).  Returns
    ``(gathered, counts)``: on the root, ``gathered`` is ``(size,
    capacity, *feat)`` — rank ``s``'s padded block at index ``s``,
    invalid slots zeroed — and ``counts`` is ``(size,)``; on non-roots
    both are zeros of the same shapes (the reference's zeroed-non-root
    convention).  ``jnp.concatenate`` of the valid prefixes on the root
    reconstructs MPI_Gatherv's packed result (see tests).
    Differentiable in ``x``: the adjoint routes cotangents back through
    the scatter, and padding slots get zero gradient."""
    capacity, count = _validated_scalar_count("ragged_gather", x, count)
    xz = _masked(x, count, capacity)
    gathered = comm.Gather(xz[None], gatheraxis=0, root=root)
    counts = comm.Gather(count[None], gatheraxis=0, root=root)
    return gathered, counts


def ragged_scatter(comm, x, counts, root: int = 0) -> Tuple:
    """Scatter-from-root with per-receiver-varying valid lengths (the
    MPI_Scatterv analogue; reference's Scatter with per-rank ``numelem``,
    csrc/extension.cpp:819-871, tests/test_collectives.py:121-125).

    ``x`` (meaningful on the root): ``(size, capacity, *feat)`` — row
    block ``i`` goes to rank ``i``.  ``counts`` (meaningful on the root):
    ``(size,)`` valid lengths, one per receiver — like MPI_Scatterv's
    root-side ``sendcounts``, non-root values are ignored and learned
    from the root.  Returns ``(recv, my_count)``: this rank's
    ``(capacity, *feat)`` block with slots beyond ``my_count`` zeroed.
    Inverse of :func:`ragged_gather` on the valid prefixes.
    Differentiable in ``x``; padding slots never leak gradient."""
    size = comm.size
    capacity = _validated_rowblock("ragged_scatter", x, size)
    counts = _validated_counts_vector("ragged_scatter", counts, size,
                                      capacity)
    # Receivers learn their count from the root (MPI_Scatterv packs this
    # into recvcount; here the whole counts row rides one small Bcast_).
    # i32 is the wire format only: my_count comes back in the caller's
    # count dtype so gather->scatter round trips keep their dtype.
    wire = comm.Bcast_(counts.astype(jnp.int32), root=root)
    my_count = jnp.take(wire, jnp.asarray(comm.rank), axis=0).astype(
        counts.dtype)
    recv = comm.Scatter(x, scatteraxis=0, numelem=1, root=root)
    recv = recv.reshape((capacity,) + x.shape[2:])
    return _masked(recv, my_count, capacity), my_count
