"""Deterministic per-rank input pipeline.

The reference ships no data loader (SURVEY.md §5 — its examples slice
arrays by hand, exactly like this repo's did); a complete framework
needs one.  Two pieces, both rank-convention-compatible with the
communicators:

* :func:`shard_batches` — seeded global shuffle + equal per-rank,
  equal-per-step batch shards.  Shapes are STATIC across steps and
  ranks (remainders dropped), because every batch feeds a jitted step:
  a ragged final batch would retrace — and under SPMD, desynchronize
  collectives across ranks (the CollectiveMismatchError class of bug).
  The permutation depends only on ``(seed, epoch)``, so every rank
  derives the SAME global order from its own call — no coordination
  collective needed for data order, matching how the examples derive
  rank-local data from ``comm.rank``.

* :func:`prefetch_to_device` — double-buffered ``jax.device_put``:
  batch ``i+k``'s host→device transfer overlaps step ``i``'s compute
  (transfers are async; JAX only blocks when the buffer is USED).  On
  a TPU the HBM copy rides the PCIe link while the MXU works —
  the standard input-pipeline overlap, here without tf.data.
"""

from __future__ import annotations

import collections
import itertools
from typing import Any, Iterable, Iterator, Optional

import numpy as np


def shard_batches(data, batch_size: int, *, rank: int = 0, size: int = 1,
                  seed: int = 0, epoch: int = 0, shuffle: bool = True):
    """Yield this rank's batches for one epoch, deterministically.

    ``data`` is an array or a tuple/list of arrays sharing a leading
    axis (features, labels, ...).  Each yielded element mirrors that
    structure with leading axis ``batch_size``.  The global order is a
    permutation seeded by ``(seed, epoch)`` (identical on every rank);
    rank ``r`` takes batches ``r, r+size, r+2*size, ...`` of the
    permuted stream, so the union over ranks of one step's batches is a
    contiguous slice of the global order — the moral equivalent of
    `DistributedSampler(shuffle=True, drop_last=True)`.

    Remainder examples (those not filling ``size`` full batches) are
    dropped to keep shapes static; with ``shuffle`` they rotate with
    the epoch permutation, so nothing is starved across epochs.
    """
    single = not isinstance(data, (tuple, list))
    # One host conversion up front — device (jnp) inputs would otherwise
    # pay a full dataset device->host copy per yielded batch.
    arrays = tuple(np.asarray(a)
                   for a in ((data,) if single else data))
    n = int(np.shape(arrays[0])[0])
    for a in arrays[1:]:
        if int(np.shape(a)[0]) != n:
            raise ValueError(
                f"leading axes disagree: {np.shape(a)[0]} vs {n}")
    if batch_size <= 0:
        raise ValueError(f"batch_size must be positive, got {batch_size}")
    if not (0 <= rank < size):
        raise ValueError(f"rank {rank} out of range for size {size}")

    if shuffle:
        order = np.random.default_rng((seed, epoch)).permutation(n)
    else:
        order = np.arange(n)
    steps = n // (batch_size * size)
    if steps == 0:
        # Dropping a remainder is documented; silently dropping the
        # WHOLE epoch is a footgun (an empty training loop surfaces as
        # an unrelated error far away).
        raise ValueError(
            f"dataset of {n} examples yields zero steps at "
            f"batch_size={batch_size} x size={size}")
    for step in range(steps):
        lo = (step * size + rank) * batch_size
        idx = order[lo:lo + batch_size]
        batch = tuple(a[idx] for a in arrays)
        yield batch[0] if single else batch


def shard_batches_comm(data, batch_size: int, comm, **kw):
    """:func:`shard_batches` with rank/size taken from a communicator.

    Eager-backend only: the SPMD backend's ``comm.rank`` is a traced
    value, while sharding indices here are host-side numpy.  Under
    ``run_spmd``, feed every rank the full batch stream and slice with
    ``jax.lax.dynamic_slice`` on the traced rank instead (the pattern
    in ``__graft_entry__.dryrun_multichip``).
    """
    rank = comm.rank
    if not isinstance(rank, int):
        raise TypeError(
            "shard_batches_comm needs a concrete (eager-backend) rank; "
            "under run_spmd slice the full stream with the traced "
            "comm.rank instead")
    return shard_batches(data, batch_size, rank=rank, size=comm.size, **kw)


def prefetch_to_device(batches: Iterable[Any], size: int = 2,
                       device: Optional[Any] = None) -> Iterator[Any]:
    """Iterate ``batches`` with up to ``size`` of them already staged on
    device.  ``jax.device_put`` is asynchronous, so staging batch
    ``i+size-1`` while the caller computes on batch ``i`` overlaps the
    host→device transfer with compute; the queue bounds staged-batch
    device memory.  ``size=1`` degrades to plain per-step device_put.
    """
    import jax

    if size < 1:
        raise ValueError(f"prefetch size must be >= 1, got {size}")
    it = iter(batches)
    queue: collections.deque = collections.deque()

    def stage(b):
        return jax.tree.map(lambda a: jax.device_put(a, device), b)

    for b in itertools.islice(it, size):
        queue.append(stage(b))
    while queue:
        nxt = queue.popleft()
        for b in itertools.islice(it, 1):
            queue.append(stage(b))
        yield nxt
