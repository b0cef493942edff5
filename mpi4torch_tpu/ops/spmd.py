"""Mode A: SPMD-traced differentiable collectives over a named mesh axis.

This is the TPU performance path: the whole per-rank program is traced once
under ``jax.shard_map`` over a :class:`jax.sharding.Mesh`, and every
communication op lowers to the XLA collective that rides ICI/DCN:

    Allreduce(SUM)   -> lax.psum            (self-adjoint custom_vjp)
    Allreduce(MAX/..)-> lax.pmax/pmin/fold  (backward raises, parity with
                                             MPIUnimplementedNode)
    Bcast_/Reduce_   -> masked psum pair    (adjoint pair, like
                                             csrc/extension.cpp:310-464)
    Gather/Allgather -> lax.all_gather      (adjoint: lax.psum_scatter —
                                             a *native* reduce-scatter; the
                                             mathematically correct Allgather
                                             adjoint, cf. the reference's
                                             root=1 quirk at
                                             csrc/extension.cpp:627)
    Scatter          -> masked psum + slice (adjoint: all_gather + mask)
    Alltoall         -> lax.all_to_all      (adjoint: axes-swapped all_to_all,
                                             csrc/extension.cpp:912)
    Isend/Irecv/Wait -> lax.ppermute        (matched send/recv pairs fuse
                                             into ONE collective_permute at
                                             trace time; adjoint is the
                                             inverse permutation — the
                                             reverse-direction gradient ring
                                             of csrc/extension.cpp:1159-1218,
                                             compiler-scheduled)

Rank identity is symbolic (:class:`RankExpr`): ``comm.rank`` records affine
shifts like ``(comm.rank + 1) % comm.size`` so that point-to-point
destinations stay *static* permutations — XLA cannot permute on a traced
destination, and the static form is exactly what the TPU ICI torus wants.
``comm.rank`` materializes to ``lax.axis_index`` when used in arithmetic
with arrays.

Misuse detectors carried over from the eager runtime, but *at trace time*
(strictly better than MPI's runtime deadlock): unmatched sends/receives
raise when the SPMD region closes; double-Wait and spliced handles raise
immediately (reference guards csrc/extension.cpp:1196-1202, 1231-1237).

The per-rank-varying shard shapes of the eager runtime are impossible under
single-trace SPMD (XLA static shapes; SURVEY.md §7 hard part 2) — ops here
require mesh-uniform shapes and raise otherwise; ragged distributions are
served by the eager runtime or by padding+masking at the user level.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import config as _config
from .. import constants as C
from ..runtime import (
    BifurcationError,
    CommError,
    DeadlockError,
)

# ---------------------------------------------------------------------------
# Context
# ---------------------------------------------------------------------------


@dataclass
class _PendingP2P:
    kind: str                 # "send" | "recv"
    perm: Tuple[int, ...]     # canonical send permutation (dest of each
                              # rank); a recv stores the inverse of its
                              # source table — matched when equal
    tag: int
    value: Any                # payload (send) / buffer (recv)
    handle_state: "_HandleState"


@dataclass
class _HandleState:
    kind: str                 # "send" | "recv"
    perm: Tuple[int, ...]
    tag: int
    waited: bool = False
    matched: bool = False
    loop: Any = None          # loop-through (send)
    result: Any = None        # ppermute output (recv)


@dataclass
class _CollState:
    """One posted split-phase collective (mpi4torch_tpu.overlap):
    phase 1 (the *start*) already issued its communication; ``complete``
    finishes phase 2 at Wait time (``None`` = the start emitted the
    whole collective and Wait is a barrier-tied completion point)."""
    opname: str               # "Allreduce" | "Reduce_scatter" | "Allgather"
    complete: Any = None      # callable(phase1_value) -> final value
    waited: bool = False


@dataclass
class SpmdContext:
    """An active SPMD trace region bound to a mesh axis."""
    axis_name: str
    size: int
    pending: List[_PendingP2P] = field(default_factory=list)
    handles: Dict[int, _HandleState] = field(default_factory=dict)
    # Split-phase collective handles (mpi4torch_tpu.overlap): keyed by
    # the phase-1 buffer tracer id, like the p2p handle table; the
    # pending list backs the un-waited-at-region-exit guard.
    coll_handles: Dict[int, _CollState] = field(default_factory=dict)
    coll_pending: List[_CollState] = field(default_factory=list)


_SPMD_CTX: contextvars.ContextVar[Optional[SpmdContext]] = \
    contextvars.ContextVar("mpi4torch_tpu_spmd_ctx", default=None)


def current_spmd_context() -> Optional[SpmdContext]:
    return _SPMD_CTX.get()


# ---------------------------------------------------------------------------
# Symbolic rank
# ---------------------------------------------------------------------------


class RankExpr:
    """Symbolic ``axis_index + offset (mod size)``.

    Keeps ring arithmetic like ``(comm.rank + 1) % comm.size`` *static* so
    Isend/Irecv destinations lower to a fixed ``collective_permute``
    schedule.  Any other arithmetic (e.g. ``res * comm.rank``) materializes
    the traced ``lax.axis_index`` value.
    """

    __slots__ = ("axis_name", "size", "offset", "wrapped")

    def __init__(self, axis_name: str, size: int, offset: int = 0,
                 wrapped: bool = False):
        self.axis_name = axis_name
        self.size = size
        # ``wrapped`` records whether the user applied ``% size``; only then
        # does materialization wrap.  ``comm.rank + 1`` as a plain value is
        # rank+1 (8 on the last of 8 ranks), NOT (rank+1) % size.
        self.offset = offset % size if wrapped else offset
        self.wrapped = wrapped

    # -- static shift algebra ------------------------------------------------
    def __add__(self, k):
        if isinstance(k, int) and not self.wrapped:
            return RankExpr(self.axis_name, self.size, self.offset + k)
        # Arithmetic past a `% size` is no longer an affine-shift-with-one-
        # wrap; materialize to the traced value for correctness.
        return self._materialize() + k

    __radd__ = __add__

    def __sub__(self, k):
        if isinstance(k, int) and not self.wrapped:
            return RankExpr(self.axis_name, self.size, self.offset - k)
        return self._materialize() - k

    def __mod__(self, m):
        if isinstance(m, int) and m == self.size:
            return RankExpr(self.axis_name, self.size, self.offset,
                            wrapped=True)
        return self._materialize() % m

    def __xor__(self, k):
        # `comm.rank ^ k` is the butterfly-exchange peer — a static
        # bijection whenever every `i ^ k` stays in [0, size), which it
        # does exactly when size is a multiple of the smallest power of
        # two above k.  Yields a PermRank so Isend/Irecv lower the
        # exchange to ONE collective_permute, same as ring shifts.
        if isinstance(k, int) and self.offset == 0 and not self.wrapped:
            table = [i ^ k for i in range(self.size)]
            if any(not (0 <= t < self.size) for t in table):
                raise CommError(
                    f"comm.rank ^ {k} leaves [0, {self.size}) on some rank "
                    f"(e.g. rank {table.index(max(table))} -> {max(table)}); "
                    "a butterfly exchange needs the axis size to cover the "
                    "xor image"
                )
            return PermRank(self.axis_name, self.size, table)
        return self._materialize() ^ k

    __rxor__ = __xor__

    # -- materialization -----------------------------------------------------
    def _materialize(self):
        idx = lax.axis_index(self.axis_name)
        if self.offset:
            out = idx + self.offset
            return out % self.size if self.wrapped else out
        return idx

    def __jax_array__(self):
        return self._materialize()

    def __mul__(self, other):
        return self._materialize() * other

    __rmul__ = __mul__

    def __rsub__(self, other):
        return other - self._materialize()

    def __eq__(self, other):
        if isinstance(other, RankExpr):
            return (self.axis_name == other.axis_name
                    and self.size == other.size
                    and self.offset == other.offset
                    and self.wrapped == other.wrapped)
        return self._materialize() == other

    def __hash__(self):
        return hash((self.axis_name, self.size, self.offset, self.wrapped))

    def __int__(self):
        raise CommError(
            "comm.rank is symbolic under SPMD tracing (one trace for all "
            "ranks); it cannot be converted to a Python int.  Use it in "
            "array arithmetic (it materializes to lax.axis_index) or in "
            "ring shifts like (comm.rank + 1) % comm.size for p2p "
            "destinations.  For concrete Python ranks use the eager "
            "thread-SPMD runtime (run_ranks)."
        )

    __index__ = __int__

    def __repr__(self):
        return f"RankExpr({self.axis_name!r}, size={self.size}, offset={self.offset})"


class PermRank:
    """Symbolic p2p peer given by an explicit per-rank table: on rank ``i``
    the peer is ``table[i]``.  Produced by rank algebra (``comm.rank ^ 1``)
    or passed directly to Isend/Irecv as a sequence.  The table must be a
    bijection — every static permutation lowers to ONE collective_permute,
    covering the reference's arbitrary dest/source contract
    (csrc/extension.cpp:1071-1157) on the SPMD performance path."""

    __slots__ = ("axis_name", "size", "table")

    def __init__(self, axis_name: str, size: int, table):
        table = tuple(int(t) for t in table)
        if len(table) != size:
            raise CommError(
                f"peer table has {len(table)} entries for axis size {size}"
            )
        if sorted(table) != list(range(size)):
            raise CommError(
                f"peer table {table} is not a permutation of 0..{size - 1}; "
                "a point-to-point exchange under SPMD must be a bijection "
                "(two ranks sending to one destination would need MPI "
                "message queues, which the single-trace program has no "
                "analogue for)"
            )
        self.axis_name = axis_name
        self.size = size
        self.table = table

    def _materialize(self):
        return jnp.asarray(self.table)[lax.axis_index(self.axis_name)]

    def __jax_array__(self):
        return self._materialize()

    def __repr__(self):
        return f"PermRank({self.axis_name!r}, table={self.table})"


@functools.lru_cache(maxsize=512)
def _perm_desc(perm: Tuple[int, ...]) -> str:
    """Human form of a send permutation for error messages.  Memoized:
    region-close checks and every posted p2p op re-describe the same
    handful of permutations on each traced call."""
    n = len(perm)
    shifts = {(perm[r] - r) % n for r in range(n)}
    if len(shifts) == 1:
        return f"ring shift {next(iter(shifts))}"
    return f"perm {list(perm)}"


@functools.lru_cache(maxsize=512)
def _ring_table(n: int, k: int) -> Tuple[int, ...]:
    """Send-permutation table of the ring shift ``+k`` on ``n`` ranks.
    Memoized: every Isend/Irecv of a ring schedule (and every step of a
    bucketed pipeline) resolves the same (n, k) to the same tuple —
    recomputing it per traced call is pure overhead."""
    return tuple((r + k) % n for r in range(n))


def _peer_table(ctx: SpmdContext, peer, what: str) -> Tuple[int, ...]:
    """Resolve a p2p peer spec to the per-rank peer table t (t[r] = rank r's
    peer), validated to be a static bijection."""
    n = ctx.size
    if isinstance(peer, RankExpr):
        if peer.axis_name != ctx.axis_name:
            raise CommError(
                f"{what} rank belongs to axis {peer.axis_name!r}, not the "
                f"communicator's axis {ctx.axis_name!r}"
            )
        if not peer.wrapped and peer.offset != 0:
            # `comm.rank + k` without `% size` is out of [0, size) on some
            # rank — MPI would reject it there; under a single trace we
            # reject it everywhere instead of silently wrapping.
            raise CommError(
                f"{what} rank `comm.rank {peer.offset:+d}` is out of range "
                f"on some ranks (size {ctx.size}); write "
                f"`(comm.rank {peer.offset:+d}) % comm.size` for a ring "
                "shift"
            )
        return _ring_table(n, peer.offset % n)
    if isinstance(peer, PermRank):
        if peer.axis_name != ctx.axis_name or peer.size != n:
            raise CommError(
                f"{what} peer table belongs to axis {peer.axis_name!r} "
                f"(size {peer.size}), not the communicator's axis "
                f"{ctx.axis_name!r} (size {n})"
            )
        return peer.table
    if isinstance(peer, (list, tuple)) and all(
            isinstance(t, (int,)) or hasattr(t, "__index__") for t in peer):
        return PermRank(ctx.axis_name, n, peer).table
    raise CommError(
        f"Under SPMD tracing, the {what} of a point-to-point op must be a "
        "static permutation of comm.rank: a ring shift like "
        "(comm.rank + 1) % comm.size, a butterfly like comm.rank ^ 1, or an "
        f"explicit per-rank table of length {n}; got {peer!r}.  A literal "
        "rank would mean every rank sends to the same destination, which is "
        "not a permutation.  Use the eager thread-SPMD runtime for "
        "arbitrary concrete destinations."
    )


def _invert_perm(table: Tuple[int, ...]) -> Tuple[int, ...]:
    inv = [0] * len(table)
    for r, t in enumerate(table):
        inv[t] = r
    return tuple(inv)


_IDENTITY_CACHE: Dict[int, Tuple[int, ...]] = {}


def _identity_perm(n: int) -> Tuple[int, ...]:
    p = _IDENTITY_CACHE.get(n)
    if p is None:
        p = _IDENTITY_CACHE[n] = tuple(range(n))
    return p


# ---------------------------------------------------------------------------
# Collectives
# ---------------------------------------------------------------------------


# Schedule thresholds live in config.py (promoted from module constants
# here, ISSUE 3 satellite): config.ordered_fold_gather_max_bytes() gates
# the all-gather+fold vs chunked-ring form of the deterministic ordered
# reduction, config.ordered_ring_chunk_bytes() sets the ring-fold
# pipeline granularity, config.bcast_tree_max_bytes() the Bcast_ tree/
# psum dispatch.  All three are validated setters that the tune
# autotuner can override from measurement.


def _gather_fold_allreduce(ctx: SpmdContext, x, op: int):
    """All-gather + fixed ascending-rank fold (the small-payload form)."""
    stacked = lax.all_gather(x, ctx.axis_name, axis=0, tiled=False)
    out = stacked[0]
    for i in range(1, ctx.size):
        out = C.combine2(op, out, stacked[i])
    return out


def _ring_fold_allreduce(ctx: SpmdContext, x, op: int):
    """Chunked pipelined ring fold: same fixed ascending-rank reduction
    order as :func:`_gather_fold_allreduce` — hence bit-identical to it and
    to the eager (MPI-linear-order) oracle — with peak extra memory that is
    RANK-COUNT-INDEPENDENT (≈2× the tensor: the chunked input view plus
    the tree-broadcast receive buffer, with one in-flight chunk on the
    wire per step) instead of the gather form's size× tensor.

    Chunk ``j`` rides the ring 0→1→…→size-1, each hop adding that rank's
    contribution on the right of the fold (``combine2(acc, mine)``, the
    exact association of the gather fold); chunks pipeline one step apart
    under one ``lax.scan`` (O(1) compiled program).

    **Phase pipelining** (``config.phase_pipelined_ring()``, default on):
    a chunk whose fold completed on the last rank starts its all-gather
    relay around the same ring IMMEDIATELY — while later chunks are
    still folding — so the reduce-scatter tail and the all-gather head
    overlap chunk-wise inside one fused scan of ``nchunks + 2(size-1)``
    steps with two chunk-sized permutes per step, and the trailing
    full-payload tree-broadcast barrier (``ceil(log2 size)`` sequential
    whole-tensor hops ≈ ``nchunks·log2(size)`` chunk-times of wire on
    top of the fold) disappears entirely.  With the knob off, the
    two-phase baseline runs: the fold scan, then the binomial-tree
    broadcast from the last rank.  Both forms fold in the identical
    ascending-rank association and move completed chunks by pure data
    movement (permute + select), so the bits are identical either way
    (the masked-psum broadcast could flip the sign of -0.0; neither the
    tree nor the relay can)."""
    n = ctx.size
    idx = lax.axis_index(ctx.axis_name)
    shape, dtype = x.shape, x.dtype
    total = x.size
    chunk_elems = max(
        1, _config.ordered_ring_chunk_bytes() // dtype.itemsize)
    nchunks = -(-total // chunk_elems)
    padded = nchunks * chunk_elems
    flat = x.reshape(-1)
    if padded != total:
        flat = jnp.concatenate(
            [flat, jnp.zeros(padded - total, dtype)])
    xc = flat.reshape(nchunks, chunk_elems)
    ring = [(i, (i + 1) % n) for i in range(n)]

    if not _config.phase_pipelined_ring():
        # Two-phase baseline: fold every chunk (size+nchunks-1 steps),
        # then one full-payload tree broadcast from the last rank.
        nsteps = n + nchunks - 1

        def step(carry, t):
            prev, out = carry
            recv = lax.ppermute(prev, ctx.axis_name, perm=ring)
            j = t - idx
            active = (j >= 0) & (j < nchunks)
            jc = jnp.clip(j, 0, nchunks - 1)
            mine = lax.dynamic_index_in_dim(xc, jc, axis=0, keepdims=False)
            acc = jnp.where(idx == 0, mine, C.combine2(op, recv, mine))
            row = lax.dynamic_index_in_dim(out, jc, axis=0, keepdims=False)
            store = active & (idx == n - 1)
            out = lax.dynamic_update_index_in_dim(
                out, jnp.where(store, acc, row), jc, axis=0)
            nxt = jnp.where(active, acc, prev)
            return (nxt, out), None

        init = (jnp.zeros(chunk_elems, dtype), jnp.zeros_like(xc))
        (_, folded), _ = lax.scan(step, init, jnp.arange(nsteps))
        result = _tree_bcast_value(ctx, folded.reshape(-1), n - 1)
        return result[:total].reshape(shape)

    # Phase-pipelined form: fold lane (identical schedule and bits to
    # the baseline) + relay lane — chunk j, completed on rank n-1 at
    # step j+n-1, is injected into the relay and rides the +1 ring;
    # rank r (relay distance hops = (r+1) % n from the last rank)
    # receives it at step j + n-1 + hops, stores it, and forwards it
    # (rank n-2, the final receiver, stops the loop).  Chunks arrive
    # one step apart, so a single relay slot suffices.
    nsteps = nchunks + 2 * (n - 1)
    hops = (idx + 1) % n

    def pstep(carry, t):
        fold_prev, relay_prev, out = carry
        fold_recv = lax.ppermute(fold_prev, ctx.axis_name, perm=ring)
        relay_recv = lax.ppermute(relay_prev, ctx.axis_name, perm=ring)

        # Fold lane (baseline association, untouched).
        j = t - idx
        active_f = (j >= 0) & (j < nchunks)
        jc = jnp.clip(j, 0, nchunks - 1)
        mine = lax.dynamic_index_in_dim(xc, jc, axis=0, keepdims=False)
        acc = jnp.where(idx == 0, mine, C.combine2(op, fold_recv, mine))
        fold_next = jnp.where(active_f, acc, fold_prev)

        # Relay lane: inject on completion (rank n-1), forward elsewhere.
        land = active_f & (idx == n - 1)
        jr = t - (n - 1) - hops
        active_r = (jr >= 0) & (jr < nchunks) & (hops >= 1)
        jrc = jnp.clip(jr, 0, nchunks - 1)
        relay_next = jnp.where(
            land, acc,
            jnp.where(active_r & (idx != n - 2), relay_recv, relay_prev))

        # Store: the landing rank keeps its completed chunk, every other
        # rank the relayed one — mutually exclusive (hops >= 1 excludes
        # rank n-1 from active_r), so one store slot per step.
        do_store = land | active_r
        loc = jnp.where(land, jc, jrc)
        val = jnp.where(land, acc, relay_recv)
        row = lax.dynamic_index_in_dim(out, loc, axis=0, keepdims=False)
        out = lax.dynamic_update_index_in_dim(
            out, jnp.where(do_store, val, row), loc, axis=0)
        return (fold_next, relay_next, out), None

    init = (jnp.zeros(chunk_elems, dtype), jnp.zeros(chunk_elems, dtype),
            jnp.zeros_like(xc))
    (_, _, gathered), _ = lax.scan(pstep, init, jnp.arange(nsteps))
    return gathered.reshape(-1)[:total].reshape(shape)


def _ring_fold_reduce_scatter(ctx: SpmdContext, x, op: int, ax: int,
                              shard: int):
    """Chunked ring fold that delivers segment ``s`` of the ascending-rank
    reduction directly to rank ``s`` — the deterministic reduce-scatter for
    payloads past the gather threshold, without the full-tensor broadcast
    the allreduce form would waste on a 1/size result (wire ≈2× payload
    per link; output memory = the shard, not the tensor).

    Two pipelined lanes under one ``lax.scan``, each one chunk wide:

    * **fold lane** — exactly :func:`_ring_fold_allreduce`'s schedule:
      chunk ``j`` folds ascending 0→…→size-1 (bit-identical association),
      completing on the last rank at step ``j + size - 1``.
    * **relay lane** — a completed chunk whose owner is not the last rank
      keeps riding the same +1 ring, unreduced, until it reaches
      ``owner(j) = j // chunks_per_segment``; pure data movement, so bits
      are untouched.  Chunks are ≥ size steps apart at any (rank, step),
      so one relay slot suffices (window length ≤ size-1).
    """
    n = ctx.size
    idx = lax.axis_index(ctx.axis_name)
    xm = jnp.moveaxis(x, ax, 0)
    rest_shape = xm.shape[1:]
    seg_elems = shard * math.prod(rest_shape)
    xm = xm.reshape(n, seg_elems)

    chunk_elems = max(
        1, _config.ordered_ring_chunk_bytes() // x.dtype.itemsize)
    cps = -(-seg_elems // chunk_elems)            # chunks per segment
    padded = cps * chunk_elems
    if padded != seg_elems:
        xm = jnp.concatenate(
            [xm, jnp.zeros((n, padded - seg_elems), x.dtype)], axis=1)
    xc = xm.reshape(n * cps, chunk_elems)
    nchunks = n * cps

    ring = [(i, (i + 1) % n) for i in range(n)]
    # Last capture: chunk j at step j + n-1 + hops(owner); hops ≤ n-1.
    nsteps = nchunks + 2 * n - 2
    hops = (idx + 1) % n                          # ring distance n-1 → idx

    def step(carry, t):
        fold_prev, relay_prev, out = carry
        fold_recv = lax.ppermute(fold_prev, ctx.axis_name, perm=ring)
        relay_recv = lax.ppermute(relay_prev, ctx.axis_name, perm=ring)

        # Fold lane (identical schedule to _ring_fold_allreduce).
        j = t - idx
        active_f = (j >= 0) & (j < nchunks)
        jc = jnp.clip(j, 0, nchunks - 1)
        mine = lax.dynamic_index_in_dim(xc, jc, axis=0, keepdims=False)
        acc = jnp.where(idx == 0, mine, C.combine2(op, fold_recv, mine))
        fold_next = jnp.where(active_f, acc, fold_prev)

        # Landing on the last rank: keep my own segment, relay the rest.
        owner_f = jc // cps
        land = active_f & (idx == n - 1)
        land_mine = land & (owner_f == idx)
        land_relay = land & (owner_f != idx)

        # Relay lane: the chunk passing rank idx at step t is
        # j_r = t - (n-1) - hops (it left the last rank at j_r + n - 1).
        jr = t - (n - 1) - hops
        active_r = (jr >= 0) & (jr < nchunks) & (hops >= 1)
        jrc = jnp.clip(jr, 0, nchunks - 1)
        capture = active_r & ((jrc // cps) == idx)
        relay_next = jnp.where(
            land_relay, acc,
            jnp.where(active_r & ~capture, relay_recv, relay_prev))

        # land_mine (idx == n-1) and capture (hops >= 1 excludes n-1) are
        # mutually exclusive — one store slot per step.
        do_store = land_mine | capture
        loc = jnp.where(land_mine, jc, jrc) % cps
        val = jnp.where(land_mine, acc, relay_recv)
        row = lax.dynamic_index_in_dim(out, loc, axis=0, keepdims=False)
        out = lax.dynamic_update_index_in_dim(
            out, jnp.where(do_store, val, row), loc, axis=0)
        return (fold_next, relay_next, out), None

    init = (jnp.zeros(chunk_elems, x.dtype),
            jnp.zeros(chunk_elems, x.dtype),
            jnp.zeros((cps, chunk_elems), x.dtype))
    (_, _, out), _ = lax.scan(step, init, jnp.arange(nsteps))
    seg = out.reshape(-1)[:seg_elems].reshape((shard,) + rest_shape)
    return jnp.moveaxis(seg, 0, ax)


def _ordered_fold_allreduce(ctx: SpmdContext, x, op: int):
    """Fixed ascending-rank fold: deterministic, bit-identical to the eager
    (MPI-linear-order) oracle.  Used for ops with no native XLA collective
    and, under config.deterministic_reductions(), for SUM.  Small payloads
    take the all-gather+fold (latency-optimal); large ones the chunked ring
    (rank-count-independent extra memory) — same bits either way."""
    if ctx.size == 1:
        return x
    gathered_bytes = x.size * x.dtype.itemsize * ctx.size
    if gathered_bytes <= _config.ordered_fold_gather_max_bytes():
        return _gather_fold_allreduce(ctx, x, op)
    return _ring_fold_allreduce(ctx, x, op)


# ---------------------------------------------------------------------------
# Algorithm schedules (mpi4torch_tpu.tune).  `ring` is the XLA-native
# default below; these are the explicit latency/topology alternatives.
# Every combine in them is an explicit combine2 with a FIXED association,
# so rhd/tree/hier are deterministic by construction (the eager
# rendezvous folds with the matching association — constants.reduce_rhd/
# reduce_tree/reduce_grouped — so Mode A and Mode B are bit-comparable
# per algorithm under deterministic_mode).
# ---------------------------------------------------------------------------


def _rhd_allreduce_value(ctx: SpmdContext, x, op: int):
    """Recursive-halving/doubling (butterfly) allreduce — the
    latency-optimal schedule: 2·log2(N) ``collective_permute`` hops of
    halving/doubling width (vs the ring's ~2(N-1) chunk steps), same
    2·S·(N-1)/N bytes on the wire.  Power-of-two worlds only.

    Halving phase: at distance ``d = N/2, N/4, …, 1`` each rank keeps
    the working-buffer half whose segment-index bit ``d`` matches its
    own rank bit, sends the other half to partner ``rank ^ d`` (one
    ppermute per round — the xor permutation carries both directions),
    and combines.  After log2(N) rounds rank ``r`` holds segment ``r``
    of the reduction in the balanced-tree association of
    :func:`constants.reduce_rhd`.  Doubling phase: the same butterfly
    in reverse concatenates the segments back to the full tensor."""
    n = ctx.size
    if n == 1:
        return x
    if n & (n - 1):
        raise CommError(
            f"the 'rhd' (recursive halving/doubling) schedule needs a "
            f"power-of-two world; got {n} ranks — use 'tree' for the "
            "logarithmic schedule at this size, or 'ring'")
    axis = ctx.axis_name
    idx = lax.axis_index(axis)
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    total = flat.size
    seg = -(-total // n)
    if seg * n != total:
        flat = jnp.concatenate([flat, jnp.zeros(seg * n - total, dtype)])
    buf = flat

    d = n // 2
    while d >= 1:
        m = buf.size // 2
        lo, hi = buf[:m], buf[m:]
        bit = (idx & d) != 0
        send = jnp.where(bit, lo, hi)
        kept = jnp.where(bit, hi, lo)
        recv = lax.ppermute(send, axis,
                            perm=[(i, i ^ d) for i in range(n)])
        buf = C.combine2(op, kept, recv)
        d //= 2

    d = 1
    while d < n:
        recv = lax.ppermute(buf, axis,
                            perm=[(i, i ^ d) for i in range(n)])
        bit = (idx & d) != 0
        buf = jnp.where(bit,
                        jnp.concatenate([recv, buf]),
                        jnp.concatenate([buf, recv]))
        d *= 2
    return buf[:total].reshape(shape)


def _tree_reduce_value(ctx: SpmdContext, x, op: int, root: int):
    """Binomial-tree reduce-to-root — the inverse of
    :func:`_tree_bcast_value`'s logarithmic pattern: at step
    ``s = 2^(k-1), …, 2, 1`` relative ranks ``[s, 2s)`` (when present)
    send their partials to ``[0, s)``, one full-payload
    ``collective_permute`` per round, ``ceil(log2 N)`` rounds total.
    Non-root results are zeroed (the Reduce_ contract).  The
    association matches :func:`constants.reduce_tree`, so the eager
    rendezvous fold is bit-identical."""
    n = ctx.size
    if n == 1:
        return x
    axis = ctx.axis_name
    idx = lax.axis_index(axis)
    rel = (idx - root) % n
    acc = x
    s = 1
    while s < n:
        s *= 2
    s //= 2
    while s >= 1:
        perm = [((r + s + root) % n, (r + root) % n)
                for r in range(s) if r + s < n]
        if perm:
            recv = lax.ppermute(acc, axis, perm=perm)
            is_recv = (rel < s) & (rel + s < n)
            acc = jnp.where(is_recv, C.combine2(op, acc, recv), acc)
        s //= 2
    return _mask_to_root(ctx, acc, root)


def _tree_allreduce_value(ctx: SpmdContext, x, op: int):
    """Logarithmic tree allreduce: binomial reduce to rank 0
    (:func:`_tree_reduce_value`) + binomial broadcast back
    (:func:`_tree_bcast_value`) — 2·ceil(log2 N) full-payload hops,
    the latency fallback for non-power-of-two worlds where ``rhd``
    cannot run."""
    if ctx.size == 1:
        return x
    return _tree_bcast_value(ctx, _tree_reduce_value(ctx, x, op, 0), 0)


def _hier_group_for(ctx: SpmdContext) -> int:
    """Intra-group size of the single-axis ``hier`` schedule — the
    shared tune.resolve_hier_group rule (config.hier_group_size when
    set, else the sqrt-nearest divisor), single-sourced so Mode A and
    the eager rendezvous fold can never drift."""
    from ..tune import resolve_hier_group

    return resolve_hier_group(ctx.size)


def _grouped_sum_schedule(x, g: int, rs, ar, ag):
    """The 2-level SUM allreduce body shared by BOTH hier forms — the
    single-axis (``axis_index_groups``) and the 2-axis (per-mesh-axis)
    communicator: pad the flat payload to ``g`` rows, intra-tier
    reduce-scatter, inter-tier allreduce, intra-tier all-gather.  Each
    of ``rs``/``ar``/``ag`` is ``(axis_name, axis_index_groups)``
    (groups ``None`` = the whole named axis).  One implementation so
    the padding rule and the stage order can never drift between the
    two forms."""
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    total = flat.size
    seg = -(-total // g)
    if seg * g != total:
        flat = jnp.concatenate([flat, jnp.zeros(seg * g - total, dtype)])
    xc = flat.reshape(g, seg)
    part = lax.psum_scatter(xc, rs[0], scatter_dimension=0,
                            axis_index_groups=rs[1], tiled=True)
    part = lax.psum(part, ar[0], axis_index_groups=ar[1])
    out = lax.all_gather(part, ag[0], axis=0, tiled=True,
                         axis_index_groups=ag[1])
    return out.reshape(-1)[:total].reshape(shape)


def _grouped_ordered_fold(x, op: int, g: int, ngroups: int, inner,
                          outer):
    """Deterministic 2-level grouped fold shared by both hier forms:
    ascending fold within the ``g``-rank inner tier, then ascending
    fold of the ``ngroups`` group partials — the fixed association of
    :func:`constants.reduce_grouped`.  ``inner``/``outer``:
    ``(axis_name, axis_index_groups)``."""
    stacked = lax.all_gather(x, inner[0], axis=0, tiled=False,
                             axis_index_groups=inner[1])
    intra = stacked[0]
    for i in range(1, g):
        intra = C.combine2(op, intra, stacked[i])
    stacked2 = lax.all_gather(intra, outer[0], axis=0, tiled=False,
                              axis_index_groups=outer[1])
    out = stacked2[0]
    for b in range(1, ngroups):
        out = C.combine2(op, out, stacked2[b])
    return out


def _hier_allreduce_value(ctx: SpmdContext, x, op: int):
    """Hierarchical 2-level allreduce on a single mesh axis: intra-group
    reduce-scatter → inter-group allreduce → intra-group all-gather,
    with groups of ``g`` consecutive ranks (``axis_index_groups``; the
    2D-mesh form in :class:`HierMeshBackend` keys the tiers off the
    mesh axes themselves).  Wire per rank:
    ``2·S·(g-1)/g`` intra + ``2·(S/g)·(n/g-1)/(n/g)`` inter — on a
    two-tier network (ICI within a host/slice, DCN across) the
    inter-tier traffic drops by the group factor vs a flat ring.

    SUM outside deterministic mode lowers to the native grouped
    ``psum_scatter``/``psum``/``all_gather`` triple (one
    ``stablehlo.reduce_scatter`` + ``all_reduce`` + ``all_gather``, the
    schedule's census signature); every other case takes the grouped
    ordered fold — the fixed association of
    :func:`constants.reduce_grouped`."""
    n = ctx.size
    if n == 1:
        return x
    axis = ctx.axis_name
    g = _hier_group_for(ctx)
    ngroups = n // g
    inner = [[b * g + i for i in range(g)] for b in range(ngroups)]
    outer = [[i + b * g for b in range(ngroups)] for i in range(g)]

    if op == C.MPI_SUM and not _config.deterministic_reductions():
        return _grouped_sum_schedule(x, g, (axis, inner), (axis, outer),
                                     (axis, inner))
    # Deterministic / non-native ops: grouped ordered fold (ascending
    # within each group, then ascending over group partials).
    return _grouped_ordered_fold(x, op, g, ngroups, (axis, inner),
                                 (axis, outer))


# ---------------------------------------------------------------------------
# Bandwidth tier (mpi4torch_tpu.tune `bidir`/`torus`): multipath
# schedules that stripe the payload across independent communication
# channels — the two directions of a bidirectional link (`bidir`) or the
# axes of a 2-level factorization (`torus`) — so the large-payload
# regime reaches the wire bandwidth a single unidirectional ring leaves
# on the table ("The Big Send-off", arXiv:2504.18658; GC3,
# arXiv:2201.11840).  The channel split point is shared with the eager
# folds (constants.multipath_split), keeping Mode A / Mode B
# bit-comparable per algorithm under deterministic_mode.
# ---------------------------------------------------------------------------


# The unroll-vs-scan threshold of the bidir chains lives in config.py
# (config.chain_unroll_max, promoted from the module constant here —
# ISSUE 5 satellite, matching the ISSUE 3 threshold-promotion pattern):
# worlds up to that size unroll hop-by-hop (distinct permute ops, the
# HLO-census surface); larger worlds roll each phase into a lax.scan so
# the compiled program stays O(1) in the rank count.  run_spmd keys its
# jit cache on the thresholds fingerprint, so overriding it retraces.


def _ring_allreduce_chain(ctx: SpmdContext, flat, op: int, direction: int):
    """One explicit directional ring allreduce over ``collective_permute``:
    reduce-scatter (N-1 hops) + all-gather (N-1 hops) on the ring
    ``i -> (i + direction) % N``, payload split into N segments.

    This is the building block of the ``bidir`` dual-ring: two chains of
    opposite ``direction`` share no values, so XLA schedules their
    permutes concurrently — each rides its own direction of the
    bidirectional ICI link, with no serialization barrier between the
    chains.  Segment ``j`` folds cyclically from rank ``j`` onward in
    ring order (``combine2(partial, mine)`` per hop), completing at rank
    ``(j - direction) % N``; the all-gather then relays completed
    segments ``N-1`` more hops.  Returns the unpadded flat result.

    Small worlds unroll the 2(N-1) hops (each permute a distinct HLO op
    — the census surface); past ``config.chain_unroll_max()`` ranks
    each phase rolls into a ``lax.scan`` so the compiled program stays
    O(1) in the world size (the wire schedule is identical — one
    chunk-sized permute per step, same segment walk)."""
    n = ctx.size
    axis = ctx.axis_name
    idx = lax.axis_index(axis)
    total = flat.size
    seg = -(-total // n)
    if seg * n != total:
        flat = jnp.concatenate(
            [flat, jnp.zeros(seg * n - total, flat.dtype)])
    segs = flat.reshape(n, seg)
    d = 1 if direction >= 0 else -1
    perm = [(i, (i + d) % n) for i in range(n)]

    # Reduce-scatter: at step t rank r forwards the partial of segment
    # (r - d·t) % n and folds its own contribution into the arriving
    # partial of segment (r - d·(t+1)) % n.
    part = lax.dynamic_index_in_dim(segs, idx, axis=0, keepdims=False)

    def rs_step(carry, t):
        recv = lax.ppermute(carry, axis, perm=perm)
        j = (idx - d * (t + 1)) % n
        mine = lax.dynamic_index_in_dim(segs, j, axis=0, keepdims=False)
        return C.combine2(op, recv, mine), None

    unroll_max = _config.chain_unroll_max()
    if n <= unroll_max:
        for t in range(n - 1):
            part, _ = rs_step(part, t)
    else:
        part, _ = lax.scan(rs_step, part, jnp.arange(n - 1))

    # All-gather: rank r owns completed segment (r + d) % n; completed
    # segments ride the same ring N-1 more hops.
    out = jnp.zeros((n, seg), flat.dtype)
    out = lax.dynamic_update_index_in_dim(out, part, (idx + d) % n, axis=0)

    def ag_step(carry, t):
        cur, acc = carry
        cur = lax.ppermute(cur, axis, perm=perm)
        acc = lax.dynamic_update_index_in_dim(
            acc, cur, (idx - d * t) % n, axis=0)
        return (cur, acc), None

    if n <= unroll_max:
        carry = (part, out)
        for t in range(n - 1):
            carry, _ = ag_step(carry, t)
        out = carry[1]
    else:
        (_, out), _ = lax.scan(ag_step, (part, out), jnp.arange(n - 1))
    return out.reshape(-1)[:total]


def _bidir_allreduce_value(ctx: SpmdContext, x, op: int,
                           reverse: bool = False):
    """Bidirectional dual-ring allreduce (``bidir``): the flat payload
    splits at :func:`constants.multipath_split` into two halves that
    ride counter-rotating :func:`_ring_allreduce_chain` chains
    concurrently — two independent ``collective_permute`` chains, one
    per link direction, ~2× link utilization on any world size.

    ``reverse`` swaps the halves' directions: the adjoint of a ring
    segment is a ring segment in the reverse direction, so the backward
    pass reuses the forward machinery with swapped channels.

    Under ``deterministic_reductions`` the halves are disjoint element
    ranges of an ELEMENTWISE fold, so the deterministic association of
    ``bidir`` is the plain ascending-rank oracle — the ordered fold
    (bit-identical to ring's, and to the eager rendezvous fold for
    ``algorithm="bidir"``); the cyclic per-segment associations of the
    wire schedule are not rank-independent and are never used for
    bit-exact results."""
    n = ctx.size
    if n == 1:
        return x
    if op in (C.MPI_MINLOC, C.MPI_MAXLOC):
        C.combine2(op, x, x)  # raises NotImplementedError with explanation
    if _config.deterministic_reductions():
        return _ordered_fold_allreduce(ctx, x, op)
    shape = x.shape
    flat = x.reshape(-1)
    total = flat.size
    m = C.multipath_split(total)
    d0, d1 = (-1, 1) if reverse else (1, -1)
    h0 = _ring_allreduce_chain(ctx, flat[:m], op, d0)
    if m >= total:
        return h0.reshape(shape)
    h1 = _ring_allreduce_chain(ctx, flat[m:], op, d1)
    return jnp.concatenate([h0, h1]).reshape(shape)


def _torus_allreduce_value(ctx: SpmdContext, x, op: int):
    """Multi-axis torus multipath allreduce (``torus``) on a flat axis:
    the 2-level factorization of :func:`_hier_allreduce_value` (inner
    tier of ``g`` consecutive ranks × outer tier of ``n/g`` groups,
    ``tune.resolve_hier_group``) viewed as a virtual 2D torus, with the
    payload STRIPED across the two axes instead of staged through one:
    half 0 runs its grouped reduce-scatter → allreduce → all-gather
    channel with the inner tier first, half 1 the same channel with the
    tiers transposed — two concurrent channels whose first-stage
    collectives ride different (virtual) axes.  The 2-axis mesh form
    (:func:`_torus2d_fwd_value`) keys the channels off real mesh axes,
    one ring channel per axis.

    Deterministic / non-native ops fold each half in its channel's
    fixed 2-level association — exactly
    :func:`constants.reduce_torus`, the eager rendezvous fold."""
    n = ctx.size
    if n == 1:
        return x
    if op in (C.MPI_MINLOC, C.MPI_MAXLOC):
        C.combine2(op, x, x)  # raises NotImplementedError with explanation
    axis = ctx.axis_name
    g = _hier_group_for(ctx)
    ngroups = n // g
    inner = [[b * g + i for i in range(g)] for b in range(ngroups)]
    outer = [[i + b * g for b in range(ngroups)] for i in range(g)]
    shape = x.shape
    flat = x.reshape(-1)
    total = flat.size
    m = C.multipath_split(total)
    # Channel slices are taken lazily (half 1 only after half 0's
    # schedule is emitted) — the uniform channel-emission order of the
    # one IR lowering (csched.lower), shared with the bidir chains.
    if op == C.MPI_SUM and not _config.deterministic_reductions():
        o0 = _grouped_sum_schedule(flat[:m], g, (axis, inner),
                                   (axis, outer), (axis, inner))
        o1 = (_grouped_sum_schedule(flat[m:], ngroups, (axis, outer),
                                    (axis, inner), (axis, outer))
              if m < total else None)
    else:
        o0 = _grouped_ordered_fold(flat[:m], op, g, ngroups,
                                   (axis, inner), (axis, outer))
        o1 = (_grouped_ordered_fold(flat[m:], op, ngroups, g,
                                    (axis, outer), (axis, inner))
              if m < total else None)
    if o1 is None:
        return o0.reshape(shape)
    return jnp.concatenate([o0, o1]).reshape(shape)


def _csched_args(ctx: SpmdContext, x):
    """Static call data the IR program builder keys on — pure shape/
    dtype reads, no ops added to the trace."""
    shape = jnp.shape(x)
    return (math.prod(shape) if shape else 1,
            jnp.dtype(jnp.result_type(x)).itemsize)


def _allreduce_fwd_value(ctx: SpmdContext, x, op: int,
                         algorithm: str = "ring"):
    """ONE dispatch for every allreduce schedule: build the algorithm's
    IR program (mpi4torch_tpu.csched — the hand-written forms above are
    its registered per-step emitter bodies and the bit-identity
    references `make ir-smoke` pins) and lower it at the call site.
    ``synth:<digest>`` names lower installed synthesized programs the
    same way."""
    from .. import csched

    nelems, itemsize = _csched_args(ctx, x)
    prog = csched.allreduce_program(
        algorithm, ctx.size, op,
        deterministic=_config.deterministic_reductions(),
        nelems=nelems, itemsize=itemsize)
    return csched.lower_allreduce(prog, ctx, x, op)


def _allreduce_bwd_value(ctx: SpmdContext, g, algorithm: str):
    """The SUM-allreduce adjoint: the TRANSPOSED program of the forward
    (csched.transpose — allreduce programs are self-adjoint with every
    directional step's ring reversed, so ``bidir``'s halves swap
    directions and every other schedule re-runs as-is, exactly the
    hand-written per-algorithm backwards)."""
    from .. import csched

    nelems, itemsize = _csched_args(ctx, g)
    prog = csched.allreduce_program(
        algorithm, ctx.size, C.MPI_SUM,
        deterministic=_config.deterministic_reductions(),
        nelems=nelems, itemsize=itemsize)
    return csched.lower_allreduce(csched.transpose(prog), ctx, g,
                                  C.MPI_SUM)


def _bwd_scope(opname: str):
    """Named scope for collective adjoints so profiler traces show explicit
    *Backward spans — the reference's only observability surface is its
    autograd node names (SURVEY.md §5 tracing; e.g. MPIAllreduceSumBackward,
    csrc/extension.cpp:256-258).  The p2p trio is not covered: its reverse
    ring is XLA's built-in transpose of the matched ppermute, which carries
    the forward scope's transpose metadata rather than a dedicated span."""
    return jax.named_scope(f"mpi4torch.{opname}Backward")

def _auto_allreduce_algorithm(ctx: SpmdContext, x) -> str:
    """Trace-time auto selection (mpi4torch_tpu.tune), three tiers: the
    measured cache winner for this (dtype, size-bucket, nranks,
    platform) key when one exists; a latency algorithm (``rhd``/
    ``tree``) below the measured latency crossover; the multipath
    bandwidth tier (``bidir``) at/above the measured bandwidth
    crossover; else ``ring``.  Pure function of static call data + the
    tune cache, and ``run_spmd`` keys its jit cache on the cache
    generation, so selection can never silently diverge from a
    compiled program."""
    from .. import tune as _tune

    xa = jnp.asarray(x)
    return _tune.select_auto(
        collective="allreduce",
        nbytes=xa.size * xa.dtype.itemsize,
        dtype=xa.dtype, nranks=ctx.size,
        deterministic=_config.deterministic_reductions())


def allreduce(ctx: SpmdContext, x, op: int, algorithm=None,
              algorithm_explicit: bool = False):
    """SPMD Allreduce (reference: csrc/extension.cpp:274-308).

    ``algorithm`` picks the wire schedule (mpi4torch_tpu.tune): ``ring``
    (default; SUM lowers to ``lax.psum``), ``rhd`` (latency-optimal
    butterfly, power-of-two worlds), ``tree`` (logarithmic, any world),
    or ``hier`` (2-level grouped).  ``None`` = selector-driven auto
    choice.  The backward uses the *matching* algorithm — the adjoint of
    an rhd-sum is an rhd-sum of the cotangents; other ops' backward
    raises, matching MPIUnimplementedNode (csrc/extension.cpp:194-202).

    ``algorithm_explicit`` carries the facade's degrade/raise rule into
    validation that only this backend can perform (e.g. a
    ``config.hier_group_size`` that does not divide THIS communicator):
    explicit requests raise, scope defaults degrade to ``ring``."""
    # Finite guard (mpi4torch_tpu.resilience): trace-time hook — with
    # config.comm_finite_guard off (default) this returns x untouched
    # and the lowering is bit-identical to a guard-less build
    # (tests/test_resilience.py holds it); "warn"/"raise"
    # add an is_finite reduce + host callback.  The mode rides the
    # thresholds fingerprint, so toggling retraces.
    from ..resilience import guards as _guards
    x = _guards.spmd_finite_value(x, "Allreduce")
    # Mode A step-event hook (mpi4torch_tpu.obs): same trace-time
    # discipline as the finite guard — no tracer (or mode_a off) means
    # zero ops added (tests/test_obs.py holds it); a
    # mode_a tracer adds one host callback per collective entry, and
    # the flag rides the thresholds fingerprint so toggling retraces.
    from ..obs.trace import spmd_collective_event
    x = spmd_collective_event(x, "Allreduce")
    if algorithm is None:
        algorithm = _auto_allreduce_algorithm(ctx, x)
    if algorithm in ("hier", "torus") and ctx.size > 1:
        # Both 2-level schedules share the group rule
        # (tune.resolve_hier_group) and its degrade/raise behavior.
        try:
            _hier_group_for(ctx)
        except CommError:
            if algorithm_explicit:
                raise
            algorithm = "ring"

    @jax.custom_vjp
    def f(v):
        return _allreduce_fwd_value(ctx, v, op, algorithm)

    def bwd(_, g):
        if op != C.MPI_SUM:
            raise RuntimeError(
                f"Backward pass for Allreduce with {C.op_name(op)} is not "
                "implemented — only MPI_SUM is differentiable (reference: "
                "MPIUnimplementedNode, csrc/extension.cpp:194-202)"
            )
        with _bwd_scope("Allreduce"):
            return (_allreduce_bwd_value(ctx, g, algorithm),)

    f.defvjp(lambda v: (_allreduce_fwd_value(ctx, v, op, algorithm), None),
             bwd)
    return f(x)


def _mask_to_root(ctx: SpmdContext, x, root: int):
    idx = lax.axis_index(ctx.axis_name)
    return jnp.where(idx == root, x, jnp.zeros_like(x))


# Payloads at or below this take the binomial-tree broadcast (log2(N)
# collective_permute hops); larger ones take the root-masked psum.  Wire
# accounting (per rank received, payload S, N ranks):
#   psum/all-reduce  : 2*S*(N-1)/N  — XLA lowers all-reduce to
#                      reduce-scatter + all-gather on the torus, within 2x
#                      of the S broadcast lower bound; StableHLO exposes no
#                      native broadcast collective, so this is the best
#                      bandwidth-shape available (proved by the HLO
#                      assertions in tests/test_hlo.py).
#   binomial tree    : S exactly (optimal), but over log2(N) *sequential*
#                      full-payload hops — latency log2(N) beats the ring's
#                      ~2(N-1) chunk steps for small S and loses for large.
# Crossover at ICI-like alpha/bw sits near a few hundred KiB; 256 KiB is
# the conservative static switch (shapes are static under jit, so the
# choice is per-callsite and compiles to exactly one strategy).  The
# threshold lives in config.py (config.bcast_tree_max_bytes, validated
# setter; the tune autotuner can override it from measurement).
# Calibration NEEDS n > 1 devices: on a single chip both lowerings
# degenerate to identity (a 1-rank Bcast has no wire).


def _tree_bcast_value(ctx: SpmdContext, x, root: int):
    """Binomial-tree broadcast over collective_permute: round k sends from
    relative ranks [0, 2^k) to [2^k, 2^{k+1})."""
    n = ctx.size
    idx = lax.axis_index(ctx.axis_name)
    rel = (idx - root) % n
    val = _mask_to_root(ctx, x, root)
    step = 1
    while step < n:
        perm = [((r + root) % n, (r + step + root) % n)
                for r in range(min(step, n - step))]
        recv = lax.ppermute(val, ctx.axis_name, perm)
        val = jnp.where((rel >= step) & (rel < 2 * step), recv, val)
        step *= 2
    return val


def _bcast_value(ctx: SpmdContext, x, root: int, algorithm=None):
    """Bcast_ through the IR: ``tree`` is the binomial program (whose
    transpose IS the tree Reduce_ program — the derived-backward pair),
    ``ring`` the mask+psum pair, ``None`` the size dispatch
    (config.bcast_tree_max_bytes) — the csched builder mirrors the
    historical dispatch bit for bit."""
    from .. import csched

    if ctx.size == 1:
        return x
    nelems, itemsize = _csched_args(ctx, x)
    prog = csched.bcast_program(algorithm, ctx.size, root,
                                nbytes=nelems * itemsize)
    return csched.lower_value(prog, ctx, x, C.MPI_SUM)


def _reduce_value(ctx: SpmdContext, x, op: int, root: int,
                  algorithm=None):
    """Reduce_ through the IR: ``tree`` is the binomial reduce program;
    everything else is the ring allreduce program with a root mask
    appended (non-root results zeroed, reference:
    csrc/extension.cpp:443-447)."""
    from .. import csched

    nelems, itemsize = _csched_args(ctx, x)
    prog = csched.reduce_program(
        algorithm, ctx.size, op, root,
        deterministic=_config.deterministic_reductions(),
        nelems=nelems, itemsize=itemsize)
    return csched.lower_value(prog, ctx, x, op)


def bcast_(ctx: SpmdContext, x, root: int, algorithm=None):
    """SPMD broadcast (reference: csrc/extension.cpp:333-365); adjoint is
    Reduce_(SUM, root) on the matching algorithm
    (csrc/extension.cpp:310-331).  ``algorithm``: ``tree`` pins the
    binomial-tree lowering, ``ring`` the root-masked psum; ``None``
    keeps the size dispatch (config.bcast_tree_max_bytes)."""
    _check_root(ctx, root)

    @jax.custom_vjp
    def f(v):
        return _bcast_value(ctx, v, root, algorithm)

    def bwd(_, g):
        with _bwd_scope("Bcast"):
            return (_reduce_value(ctx, g, C.MPI_SUM, root, algorithm),)

    f.defvjp(lambda v: (_bcast_value(ctx, v, root, algorithm), None), bwd)
    return f(x)


def reduce_(ctx: SpmdContext, x, op: int, root: int, algorithm=None):
    """SPMD reduce-to-root with zeroed non-root results (reference:
    csrc/extension.cpp:405-464); adjoint is Bcast_(root) on the matching
    algorithm; only SUM differentiable.  ``algorithm``: ``tree`` pins
    the binomial reduce (``ceil(log2 N)`` permute hops instead of a
    masked all-reduce); ``ring``/``None`` the masked psum form."""
    _check_root(ctx, root)

    @jax.custom_vjp
    def f(v):
        return _reduce_value(ctx, v, op, root, algorithm)

    def bwd(_, g):
        if op != C.MPI_SUM:
            raise RuntimeError(
                f"Backward pass for Reduce_ with {C.op_name(op)} is not "
                "implemented — only MPI_SUM is differentiable (reference: "
                "MPIUnimplementedNode, csrc/extension.cpp:194-202)"
            )
        with _bwd_scope("Reduce"):
            return (_bcast_value(ctx, g, root, algorithm),)

    f.defvjp(lambda v: (_reduce_value(ctx, v, op, root, algorithm), None),
             bwd)
    return f(x)


from .eager import _norm_axis  # shared axis normalization


def allgather(ctx: SpmdContext, x, gatheraxis: int):
    """SPMD allgather along an arbitrary axis (reference:
    csrc/extension.cpp:633-734).  Adjoint: ``lax.psum_scatter`` — the
    native TPU reduce-scatter, which is the mathematically correct adjoint
    (the reference's backward has the constant-root quirk at
    csrc/extension.cpp:627; see ops/eager.py docstring)."""
    ax = _norm_axis(gatheraxis, jnp.ndim(x))

    @jax.custom_vjp
    def f(v):
        return lax.all_gather(v, ctx.axis_name, axis=ax, tiled=True)

    def bwd(_, g):
        with _bwd_scope("Allgather"):
            return (lax.psum_scatter(g, ctx.axis_name, scatter_dimension=ax,
                                     tiled=True),)

    f.defvjp(lambda v: (lax.all_gather(v, ctx.axis_name, axis=ax, tiled=True),
                        None), bwd)
    return f(x)


def reduce_scatter(ctx: SpmdContext, x, op: int, scatteraxis: int):
    """SPMD block reduce-scatter (TPU-native addition; no reference
    counterpart — see ops/eager.py reduce_scatter for the contract).

    MPI_SUM lowers to ONE native ``lax.psum_scatter`` — the wire-optimal
    collective (half a ring allreduce: (N-1)/N of the tensor on the wire
    instead of 2(N-1)/N) and the reason this op exists: ZeRO gradient
    sharding (parallel/zero.py) pays allreduce wire cost without it.
    Non-SUM ops and deterministic mode reduce via
    ``_allreduce_fwd_value`` + shard slice (native pmax/pmin where XLA
    has them, the bit-exact ordered fold for the rest and for SUM under
    deterministic mode).  Adjoint (SUM only): ``lax.all_gather`` of the
    shard cotangents."""
    ax = _norm_axis(scatteraxis, jnp.ndim(x))
    if x.shape[ax] % ctx.size != 0:
        raise CommError(
            f"Reduce_scatter axis {scatteraxis} length {x.shape[ax]} must "
            f"be divisible by the communicator size {ctx.size}")
    shard = x.shape[ax] // ctx.size

    def fwd_value(v):
        if op == C.MPI_SUM and not _config.deterministic_reductions():
            return lax.psum_scatter(v, ctx.axis_name, scatter_dimension=ax,
                                    tiled=True)
        start = lax.axis_index(ctx.axis_name) * shard
        if op in (C.MPI_MAX, C.MPI_MIN):
            # One native collective covers the full tensor; slice after.
            total = _allreduce_fwd_value(ctx, v, op)
            return lax.dynamic_slice_in_dim(total, start, shard, ax)
        if op in (C.MPI_MINLOC, C.MPI_MAXLOC):
            C.combine2(op, v, v)  # raises NotImplementedError
        # Ordered fold (SUM under deterministic mode, and ops with no
        # native collective).  Small payloads: all-gather, then slice each
        # rank's contribution to MY segment BEFORE folding — the
        # element-wise fold commutes with slicing (bit-identical to the
        # eager oracle) at 1/size the reduction work; XLA does NOT push
        # the slice through the fold itself (verified on compiled HLO: the
        # adds stay full-length when slicing after).  Large payloads: the
        # relay-routed chunked ring fold (rank-count-independent extra
        # memory, shard-sized output, VERDICT r4 weak 2) delivers each
        # rank its segment of the same ascending-rank bits directly.
        if v.size * v.dtype.itemsize * ctx.size \
                <= _config.ordered_fold_gather_max_bytes():
            stacked = lax.all_gather(v, ctx.axis_name, axis=0, tiled=False)
            pieces = lax.dynamic_slice_in_dim(stacked, start, shard, 1 + ax)
            out = pieces[0]
            for i in range(1, ctx.size):
                out = C.combine2(op, out, pieces[i])
            return out
        return _ring_fold_reduce_scatter(ctx, v, op, ax, shard)

    @jax.custom_vjp
    def f(v):
        return fwd_value(v)

    def bwd(_, g):
        if op != C.MPI_SUM:
            raise RuntimeError(
                f"Backward pass for Reduce_scatter with {C.op_name(op)} is "
                "not implemented — only MPI_SUM is differentiable "
                "(reference: MPIUnimplementedNode, "
                "csrc/extension.cpp:194-202)"
            )
        with _bwd_scope("Reduce_scatter"):
            return (lax.all_gather(g, ctx.axis_name, axis=ax, tiled=True),)

    f.defvjp(lambda v: (fwd_value(v), None), bwd)
    return f(x)


def gather(ctx: SpmdContext, x, gatheraxis: int, root: int):
    """SPMD gather-to-root (reference: csrc/extension.cpp:497-599): an
    all-gather with non-root results zeroed (the reference's non-root
    outputs are undefined; zeros are the well-defined superset).  Adjoint:
    the root's gradient is scattered back — here a root-masked psum_scatter.

    Cost note (documented per VERDICT round 1): every rank pays the full
    all-gather bandwidth, S*(N-1)/N received per rank, even though
    non-roots zero the result.  A true gather would cost non-roots
    nothing, but StableHLO has no gather-to-one collective and a ppermute
    relay to the root serializes N-1 hops; under SPMD's static shapes the
    all-gather (then mask) is the efficient compiled form — and the root,
    the rank that matters, receives exactly its optimal S*(N-1)/N.
    """
    _check_root(ctx, root)
    ax = _norm_axis(gatheraxis, jnp.ndim(x))

    def fwd_value(v):
        full = lax.all_gather(v, ctx.axis_name, axis=ax, tiled=True)
        return _mask_to_root(ctx, full, root)

    @jax.custom_vjp
    def f(v):
        return fwd_value(v)

    def bwd(_, g):
        # Only the root's upstream gradient is real (non-root forward
        # outputs are zeros); one root-masked psum_scatter delivers each
        # rank its segment of it — Scatter(grad, ax, numelem, root),
        # csrc/extension.cpp:466-495.
        with _bwd_scope("Gather"):
            return (lax.psum_scatter(_mask_to_root(ctx, g, root),
                                     ctx.axis_name, scatter_dimension=ax,
                                     tiled=True),)

    f.defvjp(lambda v: (fwd_value(v), None), bwd)
    return f(x)


def scatter(ctx: SpmdContext, x, scatteraxis: int, numelem: int, root: int):
    """SPMD scatter-from-root (reference: csrc/extension.cpp:769-884).

    Under single-trace SPMD all ranks pass same-shaped inputs and segments
    are equal-sized; ``numelem`` must equal ``axis_len // size`` (the eager
    runtime serves per-rank-varying ``numelem``).  The root's data wins
    (non-root inputs ignored, csrc/extension.cpp:788-796) — implemented as
    a root-masked psum (broadcast) followed by a static per-rank slice.
    Adjoint: Gather(grad, scatteraxis, root) (csrc/extension.cpp:736-767).
    """
    _check_root(ctx, root)
    ax = _norm_axis(scatteraxis, jnp.ndim(x))
    axlen = x.shape[ax]
    if axlen % ctx.size != 0 or numelem != axlen // ctx.size:
        raise ValueError(
            f"Scatter under SPMD requires numelem ({numelem}) == axis length "
            f"({axlen}) // mesh size ({ctx.size}); per-rank-varying segments "
            "need the eager runtime (SURVEY.md §7 hard part 2)"
        )

    def fwd_value(v):
        # Root-masked psum_scatter: ONE native reduce-scatter collective
        # delivers each rank exactly its segment of the root's tensor —
        # 1/N the bandwidth of broadcast-then-slice.
        return lax.psum_scatter(_mask_to_root(ctx, v, root), ctx.axis_name,
                                scatter_dimension=ax, tiled=True)

    @jax.custom_vjp
    def f(v):
        return fwd_value(v)

    def bwd(_, g):
        with _bwd_scope("Scatter"):
            full = lax.all_gather(g, ctx.axis_name, axis=ax, tiled=True)
            # Gradient is real only on root (non-root inputs were ignored);
            # keep the collective in every rank's program (the moral of the
            # reference's JoinDummies(zeros, {gather}) trick,
            # csrc/extension.cpp:756-766) and mask.
            return (_mask_to_root(ctx, full, root),)

    f.defvjp(lambda v: (fwd_value(v), None), bwd)
    return f(x)


def alltoall(ctx: SpmdContext, x, gatheraxis: int, scatteraxis: int,
             numelem: int):
    """SPMD all-to-all (reference: csrc/extension.cpp:917-987, there a loop
    of Scatters): lowers to the single native ``lax.all_to_all`` collective —
    split the local block along ``scatteraxis``, exchange, concatenate along
    ``gatheraxis``.  Adjoint: the axes-swapped all-to-all
    (csrc/extension.cpp:886-915)."""
    ga = _norm_axis(gatheraxis, jnp.ndim(x))
    sa = _norm_axis(scatteraxis, jnp.ndim(x))
    axlen = x.shape[sa]
    if axlen % ctx.size != 0 or numelem != axlen // ctx.size:
        raise ValueError(
            f"Alltoall under SPMD requires numelem ({numelem}) == scatter "
            f"axis length ({axlen}) // mesh size ({ctx.size}); "
            "per-rank-varying segments need the eager runtime"
        )

    @jax.custom_vjp
    def f(v):
        return lax.all_to_all(v, ctx.axis_name, split_axis=sa,
                              concat_axis=ga, tiled=True)

    def bwd(_, g):
        with _bwd_scope("Alltoall"):
            return (lax.all_to_all(g, ctx.axis_name, split_axis=ga,
                                   concat_axis=sa, tiled=True),)

    f.defvjp(lambda v: (lax.all_to_all(v, ctx.axis_name, split_axis=sa,
                                       concat_axis=ga, tiled=True), None),
             bwd)
    return f(x)


def _check_root(ctx: SpmdContext, root: int) -> None:
    if not (0 <= root < ctx.size):
        raise CommError(f"invalid root rank {root} (axis size {ctx.size})")


# ---------------------------------------------------------------------------
# Dependency tokens
# ---------------------------------------------------------------------------


def join_dummies(loopthrough, dummies):
    """Same construction as the eager implementation — an
    ``optimization_barrier``-tied identity with zero-but-ordered cotangents
    — which is already trace-compatible (see ops/eager.py:join_dummies and
    reference csrc/extension.cpp:989-1046)."""
    from .eager import join_dummies as _jd
    return _jd(loopthrough, dummies)


# ---------------------------------------------------------------------------
# Point-to-point: Isend / Irecv / Wait via matched collective_permute
# ---------------------------------------------------------------------------


def _emit_permute(ctx: SpmdContext, value, perm: Tuple[int, ...]):
    if perm == _identity_perm(ctx.size):
        # Self-send on every rank (MPI permits Isend(dest=rank)): a local
        # buffer hand-off — no collective needed, the value IS the message.
        return value
    return lax.ppermute(value, ctx.axis_name,
                        perm=[(i, perm[i]) for i in range(ctx.size)])


def _try_match(ctx: SpmdContext) -> None:
    """Pair pending sends with pending recvs of the same tag and the same
    canonical send permutation; each pair fuses into one collective_permute
    whose output is stored on the recv handle."""
    sends = [p for p in ctx.pending if p.kind == "send"]
    recvs = [p for p in ctx.pending if p.kind == "recv"]
    for s in sends:
        for r in recvs:
            if s.tag == r.tag and s.perm == r.perm:
                if (tuple(s.value.shape) != tuple(r.value.shape)
                        or s.value.dtype != r.value.dtype):
                    raise CommError(
                        f"matched Isend/Irecv on tag {s.tag} disagree on "
                        f"shape/dtype: send {s.value.shape}/{s.value.dtype} "
                        f"vs recv buffer {r.value.shape}/{r.value.dtype}"
                    )
                y = _emit_permute(ctx, s.value, s.perm)
                r.handle_state.result = y
                r.handle_state.matched = True
                s.handle_state.matched = True
                ctx.pending.remove(s)
                ctx.pending.remove(r)
                return _try_match(ctx)


def _fresh(x):
    """Pass through an optimization barrier to obtain a unique tracer
    object — the handle identity key (the analogue of the reference's
    buffer-pointer hash, csrc/extension.cpp:1100)."""
    return lax.optimization_barrier(x)


_SPMD_DESC_LEN = 8


def isend(ctx: SpmdContext, x, dest, tag: int) -> List:
    """SPMD nonblocking send (reference: csrc/extension.cpp:1071-1113).

    ``dest`` must be a static permutation of ``comm.rank`` — a ring shift
    ``(comm.rank + k) % comm.size``, a butterfly ``comm.rank ^ k``, an
    explicit per-rank table, or ``comm.rank`` itself (self-send, a local
    hand-off).  The actual transfer is emitted as a ``collective_permute``
    the moment the matching Irecv appears in the trace; XLA schedules the
    start/done pair asynchronously — the compiler plays the role of
    MPI_Isend/MPI_Wait.
    Returns the raw 3-tensor handle [descriptor, buffer, loopthrough]."""
    perm = _peer_table(ctx, dest, "destination")
    buf = _fresh(x)
    desc = lax.optimization_barrier(
        (jnp.zeros(_SPMD_DESC_LEN, jnp.float32), buf))[0]
    state = _HandleState(kind="send", perm=perm, tag=tag, loop=buf)
    ctx.handles[id(buf)] = state
    ctx.pending.append(_PendingP2P("send", perm, tag, x, state))
    _try_match(ctx)
    return [desc, buf, buf]


def irecv(ctx: SpmdContext, x, source, tag: int) -> List:
    """SPMD nonblocking receive (reference: csrc/extension.cpp:1115-1157).
    ``source`` must be a static permutation of ``comm.rank`` (see
    :func:`isend`); a source table matches sends whose destination table is
    its inverse."""
    src_table = _peer_table(ctx, source, "source")
    send_perm = _invert_perm(src_table)
    buf = _fresh(x)
    desc = lax.optimization_barrier(
        (jnp.zeros(_SPMD_DESC_LEN, jnp.float32), buf))[0]
    state = _HandleState(kind="recv", perm=send_perm, tag=tag)
    ctx.handles[id(buf)] = state
    ctx.pending.append(_PendingP2P("recv", send_perm, tag, buf, state))
    _try_match(ctx)
    return [desc, buf, buf]


def wait(ctx: SpmdContext, handle: List):
    """SPMD Wait (reference: csrc/extension.cpp:1220-1265).

    Completion is a trace-level event: for a recv handle, returns the
    matched permute's output (gradients flow through the permute's own
    adjoint — the reverse-direction ring); for a send handle, returns the
    loop-through.  Guards: unknown/spliced handles and double waits raise
    (csrc/extension.cpp:1196-1202, 1231-1237); an unmatched handle raises a
    trace-time DeadlockError — strictly earlier than MPI's runtime hang."""
    desc, buf, loop = handle
    state = ctx.handles.get(id(buf))
    if state is None:
        raise BifurcationError(
            "Detected bifurcation in Wait handle usage: this handle's buffer "
            "does not belong to any posted request in the active SPMD region "
            "(handles must not be rebuilt from parts of other handles; "
            "reference guard csrc/extension.cpp:1231-1237)"
        )
    if state.waited:
        raise BifurcationError(
            "Detected bifurcation in Wait handle usage: this request was "
            "already waited on (a WaitHandle completes exactly once)"
        )
    state.waited = True
    if state.kind == "send":
        # A send may be waited on before its matching Irecv appears later
        # in the program (e.g. blocking Send = Isend+Wait): completion of a
        # buffered send is local.  The permute is emitted when the match
        # arrives; a send that never matches is caught at region close.
        # Tie the returned loop-through to the descriptor chain so
        # JoinDummiesHandle ordering survives into the compiled program.
        return lax.optimization_barrier((loop, desc))[0]
    if not state.matched:
        raise DeadlockError(
            f"trace-time deadlock: Wait on a receive (tag {state.tag}, "
            f"{_perm_desc(state.perm)}) before the matching Isend appears in "
            "the program.  Under single-trace SPMD every rank runs the same "
            "program, so a blocking Recv with no prior matching send means "
            "ALL ranks block in Recv — a real deadlock under MPI too.  Post "
            "the Isend first (Isend -> Recv -> Wait, as in the reference "
            "examples), or use Irecv and delay the Wait past the send."
        )
    return lax.optimization_barrier((state.result, desc))[0]


# ---------------------------------------------------------------------------
# Split-phase collectives (mpi4torch_tpu.overlap): Allreduce_start /
# Reduce_scatter_start / Allgather_start + collective Wait.
#
# The start issues the collective's first (or only) phase at its trace
# position; the Wait completes it — possibly much later, with user
# compute in between.  Because StableHLO preserves trace order and the
# Wait ties its completion through a differentiable optimization_barrier
# (onto the handle's descriptor slot, where JoinDummiesHandle chains
# land), XLA's latency-hiding scheduler is free to slide the collective
# under everything issued between start and Wait — the SPMD analogue of
# the eager runtime's Isend/Irecv/WaitHandle machinery, with the same
# misuse guards (double-Wait raises; an un-waited handle at region exit
# raises, the collective analogue of an unmatched Isend).
#
# AD transparency is compositional: both phases are the module's own
# custom_vjp collectives glued by differentiable barriers, so the
# backward pass is itself split-phase with the wait chain REVERSED —
# the adjoint of the Wait's all-gather (a reduce-scatter of the
# cotangents) runs at the Wait's position in the reversed program, i.e.
# FIRST, and the adjoint of the start's reduce-scatter (an all-gather)
# runs last: the deadlock-free ordering that JoinDummiesHandle chaining
# provides on the eager path falls out of the transpose here.
# ---------------------------------------------------------------------------


def _register_coll(ctx: SpmdContext, opname: str, value, complete=None
                   ) -> List:
    """Post a split-phase collective: wrap the phase-1 value in the raw
    3-tensor handle ``[descriptor, buffer, loopthrough]`` (the eager
    WaitHandle layout) and record the completion state keyed by the
    buffer tracer — the same identity scheme as the p2p handles."""
    buf = _fresh(value)
    desc = lax.optimization_barrier(
        (jnp.zeros(_SPMD_DESC_LEN, jnp.float32), buf))[0]
    state = _CollState(opname=opname, complete=complete)
    ctx.coll_handles[id(buf)] = state
    ctx.coll_pending.append(state)
    return [desc, buf, buf]


def allreduce_start(ctx: SpmdContext, x, op: int, algorithm=None,
                    algorithm_explicit: bool = False) -> List:
    """Split-phase SPMD Allreduce, phase 1.

    Ring-SUM outside deterministic mode issues the reduce-scatter half
    here and leaves the all-gather half to the Wait — the two phases of
    a ring allreduce straddling whatever the user computes in between
    (exactly the pair the fused bucket path stages, fuse/collectives.py,
    so split-phase and fused-blocking buckets are bit-identical).  Every
    other form — deterministic mode, non-SUM ops, non-ring algorithms —
    computes the SAME fold as the blocking op entirely in phase 1 (the
    blocking value, only scheduled earlier), and the Wait is a
    barrier-tied completion point; bit-identity with the blocking form
    holds by construction in every case."""
    x = jnp.asarray(x)
    if algorithm is None:
        algorithm = _auto_allreduce_algorithm(ctx, x)
    n = ctx.size
    use_pair = (op == C.MPI_SUM and n > 1
                and not _config.deterministic_reductions()
                and algorithm in (None, "ring"))
    if not use_pair:
        val = allreduce(ctx, x, op, algorithm,
                        algorithm_explicit=algorithm_explicit)
        return _register_coll(ctx, "Allreduce", val)

    shape = x.shape
    total = x.size
    seg = -(-total // n)
    flat = x.reshape(-1)
    if seg * n != total:
        flat = jnp.concatenate(
            [flat, jnp.zeros(seg * n - total, x.dtype)])
    part = reduce_scatter(ctx, flat.reshape(n, seg), op, 0)

    def complete(val):
        full = allgather(ctx, val, 0)
        return full.reshape(-1)[:total].reshape(shape)

    return _register_coll(ctx, "Allreduce", part, complete)


def reduce_scatter_start(ctx: SpmdContext, x, op: int,
                         scatteraxis: int) -> List:
    """Split-phase SPMD Reduce_scatter: the single native collective is
    issued here (one ``psum_scatter`` for SUM — the ZeRO gradient
    primitive); the Wait is the barrier-tied completion point that pins
    where its result may be consumed.  Same value and bits as the
    blocking op — only the schedule differs."""
    val = reduce_scatter(ctx, x, op, scatteraxis)
    return _register_coll(ctx, "Reduce_scatter", val)


def allgather_start(ctx: SpmdContext, x, gatheraxis: int) -> List:
    """Split-phase SPMD Allgather: the ``all_gather`` is issued here —
    this is the ZeRO-3 parameter *prefetch* primitive: start the gather
    of shard k+1 while layer k's forward is still computing, Wait it
    where the parameters are consumed.  Same value and bits as the
    blocking op."""
    val = allgather(ctx, x, gatheraxis)
    return _register_coll(ctx, "Allgather", val)


_NOT_COLL = object()


def collective_wait(ctx: SpmdContext, handle: List):
    """Complete a split-phase collective handle; returns ``_NOT_COLL``
    when the handle does not belong to the collective table (the caller
    falls through to the p2p Wait).  Guards mirror the p2p trio's:
    exactly-once completion (a double Wait raises
    :class:`BifurcationError`), and region exit raises on un-waited
    handles (see :class:`_bind_spmd`)."""
    desc, buf, loop = handle
    state = ctx.coll_handles.get(id(buf))
    if state is None:
        return _NOT_COLL
    if state.waited:
        raise BifurcationError(
            "Detected bifurcation in Wait handle usage: this split-phase "
            f"{state.opname} was already waited on (a WaitHandle "
            "completes exactly once)")
    state.waited = True
    ctx.coll_pending.remove(state)
    # Tie the phase-1 value to the descriptor chain so JoinDummiesHandle
    # dependencies (and the scheduler's cross-bucket ordering ties)
    # survive into the compiled program — the p2p Wait's discipline.
    val = lax.optimization_barrier((buf, desc))[0]
    if state.complete is not None:
        val = state.complete(val)
    return val


# ---------------------------------------------------------------------------
# Backend + harness
# ---------------------------------------------------------------------------


class SpmdBackend:
    """Binds the facade op table to an active SPMD trace context."""

    def __init__(self, ctx: SpmdContext):
        self._ctx = ctx

    @property
    def rank(self) -> RankExpr:
        return RankExpr(self._ctx.axis_name, self._ctx.size)

    @property
    def size(self) -> int:
        return self._ctx.size

    def allreduce(self, x, op, algorithm=None, algorithm_explicit=False):
        return allreduce(self._ctx, x, op, algorithm,
                         algorithm_explicit=algorithm_explicit)

    def allreduce_compressed(self, x, op, codec, algorithm=None,
                             algorithm_explicit=False):
        from ..compress import spmd as _cspmd
        return _cspmd.allreduce(self._ctx, x, op, codec,
                                algorithm=algorithm,
                                algorithm_explicit=algorithm_explicit)

    def allgather_compressed(self, x, gatheraxis, codec):
        from ..compress import spmd as _cspmd
        return _cspmd.allgather(self._ctx, x, gatheraxis, codec)

    def bcast_(self, x, root, algorithm=None):
        return bcast_(self._ctx, x, root, algorithm)

    def reduce_(self, x, op, root, algorithm=None):
        return reduce_(self._ctx, x, op, root, algorithm)

    def gather(self, x, gatheraxis, root):
        return gather(self._ctx, x, gatheraxis, root)

    def allgather(self, x, gatheraxis):
        return allgather(self._ctx, x, gatheraxis)

    def reduce_scatter(self, x, op, scatteraxis):
        return reduce_scatter(self._ctx, x, op, scatteraxis)

    def scatter(self, x, scatteraxis, numelem, root):
        return scatter(self._ctx, x, scatteraxis, numelem, root)

    def alltoall(self, x, gatheraxis, scatteraxis, numelem):
        return alltoall(self._ctx, x, gatheraxis, scatteraxis, numelem)

    def isend(self, x, dest, tag):
        return isend(self._ctx, x, dest, tag)

    def irecv(self, x, source, tag):
        return irecv(self._ctx, x, source, tag)

    def wait(self, handle):
        # Split-phase collective handles share the Wait surface with the
        # p2p trio (one completion verb, like MPI_Wait): consult the
        # collective table first, fall through to the p2p machinery.
        out = collective_wait(self._ctx, handle)
        if out is not _NOT_COLL:
            return out
        return wait(self._ctx, handle)

    def allreduce_start(self, x, op, algorithm=None,
                        algorithm_explicit=False):
        return allreduce_start(self._ctx, x, op, algorithm,
                               algorithm_explicit=algorithm_explicit)

    def reduce_scatter_start(self, x, op, scatteraxis):
        return reduce_scatter_start(self._ctx, x, op, scatteraxis)

    def allgather_start(self, x, gatheraxis):
        return allgather_start(self._ctx, x, gatheraxis)


class _bind_spmd:
    def __init__(self, ctx: SpmdContext):
        self.ctx = ctx

    def __enter__(self):
        self.token = _SPMD_CTX.set(self.ctx)
        return self.ctx

    def __exit__(self, exc_type, *rest):
        _SPMD_CTX.reset(self.token)
        if exc_type is None and self.ctx.pending:
            leftover = ", ".join(
                f"{p.kind}(tag={p.tag}, {_perm_desc(p.perm)})"
                for p in self.ctx.pending
            )
            raise DeadlockError(
                f"trace-time deadlock: unmatched point-to-point operations "
                f"at the end of the SPMD region: {leftover} — every Isend "
                "needs a complementary Irecv with the same tag (under MPI "
                "this program would hang)"
            )
        if exc_type is None and self.ctx.coll_pending:
            leftover = ", ".join(
                f"{s.opname}_start" for s in self.ctx.coll_pending)
            raise DeadlockError(
                f"un-waited split-phase collective handle(s) at the end "
                f"of the SPMD region: {leftover} — every *_start needs a "
                "matching Wait (the result exists only at the Wait; "
                "dropping the handle silently discards the collective)"
            )
        return False


class TierStackBackend:
    """N-level communicator over N mesh axes, outermost (slowest
    interconnect) first — the topology-aware tier stack
    (``comm_from_mesh(mesh, ("pod", "host", "chip"))``): ranks are
    row-major over the axes, the LAST axis is the fastest tier (ICI
    within a slice/host), earlier axes progressively slower (DCN
    across pods).  The 2-axis member is :class:`HierMeshBackend` — the
    original hierarchical communicator, subsumed unchanged (2-axis
    stacks delegate to the identical ``hier_allreduce_2d`` lowering, so
    the StableHLO text cannot differ by construction).

    Allreduce-only by design: the staged per-tier schedule — innermost
    reduce-scatter, recursing outward, innermost all-gather (or the
    deterministic grouped-fold chain) — is what a multi-axis mesh buys;
    every other op needs a single-axis communicator (``comm_from_mesh``
    with one axis name) and raises a :class:`CommError` pointing
    there."""

    # The facade degrades scope-default codecs on backends without a
    # compressed pipeline (and raises for explicit ones) — see
    # comm.Allreduce.
    supports_compression = False
    # The registry's flat-world applicability gates don't apply here
    # (the tiers ARE the mesh axes): the facade skips them and this
    # backend enforces its own hier/ring contract — see comm.Allreduce.
    owns_algorithm_resolution = True

    # The backend-method surface this communicator deliberately does
    # NOT serve.  __getattr__ raises the informative CommError for
    # exactly these; everything else (dunders, hasattr probes, copy/
    # pickle protocol lookups) gets the protocol-correct
    # AttributeError.
    _UNSUPPORTED_OPS = frozenset({
        "bcast_", "reduce_", "gather", "allgather", "reduce_scatter",
        "scatter", "alltoall", "isend", "irecv", "wait",
        "allreduce_compressed", "allgather_compressed",
    })

    def __init__(self, axis_names: Tuple[str, ...],
                 axis_sizes: Tuple[int, ...]):
        names = tuple(axis_names)
        sizes = tuple(int(s) for s in axis_sizes)
        if len(names) < 2 or len(names) != len(sizes):
            raise CommError(
                "a tier-stack communicator takes >= 2 mesh axis names "
                f"(outermost first) with their sizes; got {names!r} / "
                f"{sizes!r}")
        self.axis_names = names
        self.axis_sizes = sizes

    @property
    def rank(self):
        r = lax.axis_index(self.axis_names[0])
        for nm, s in zip(self.axis_names[1:], self.axis_sizes[1:]):
            r = r * s + lax.axis_index(nm)
        return r

    @property
    def size(self) -> int:
        p = 1
        for s in self.axis_sizes:
            p *= s
        return p

    def allreduce(self, x, op, algorithm=None, algorithm_explicit=False):
        if len(self.axis_names) == 2:
            return hier_allreduce_2d(self, x, op, algorithm,
                                     explicit=algorithm_explicit)
        return tier_allreduce_nd(self, x, op, algorithm,
                                 explicit=algorithm_explicit)

    def __getattr__(self, name):
        if name in TierStackBackend._UNSUPPORTED_OPS:
            raise CommError(
                "tier-stack mesh communicators support Allreduce only "
                f"(the staged per-tier wire schedule); {name!r} needs "
                "a single-axis communicator — use "
                "comm_from_mesh(mesh, axis_name) with one axis")
        raise AttributeError(name)


class HierMeshBackend(TierStackBackend):
    """Two-tier communicator over TWO mesh axes ``(outer, inner)`` —
    the topology-aware form of the ``hier`` algorithm, keyed off the
    mesh axis sizes themselves (``comm_from_mesh(mesh, ("dp", "tp"))``):
    the 2-level member of :class:`TierStackBackend`, kept as a named
    class so 2-axis adoption, reshard's backend guard, and the original
    2-level contract stay exactly what they were."""

    def __init__(self, axis_names: Tuple[str, str],
                 axis_sizes: Tuple[int, int]):
        if len(tuple(axis_names)) != 2:
            raise CommError(
                "HierMeshBackend is the 2-axis tier stack; use "
                f"TierStackBackend for {len(tuple(axis_names))} axes")
        super().__init__(axis_names, axis_sizes)


def _torus2d_fwd_value(hb: HierMeshBackend, x, op: int):
    """The ``torus`` schedule on a real 2-axis mesh communicator: the
    payload halves stripe across the two mesh axes — half 0's grouped
    reduce-scatter/allreduce/all-gather channel leads with the inner
    axis, half 1's with the outer axis — one concurrent ring channel
    per axis, their first-stage collectives riding different ICI
    dimensions with no dependency between the halves.  Deterministic /
    non-native ops fold each half in its channel's fixed 2-level
    association (:func:`constants.reduce_torus` with ``inner`` = the
    inner axis extent — the eager oracle)."""
    outer, inner = hb.axis_names
    so, si = hb.axis_sizes
    if so * si == 1:
        return x
    if op in (C.MPI_MINLOC, C.MPI_MAXLOC):
        C.combine2(op, x, x)  # raises with explanation
    shape = x.shape
    flat = x.reshape(-1)
    total = flat.size
    m = C.multipath_split(total)
    h0, h1 = flat[:m], flat[m:]
    if op == C.MPI_SUM and not _config.deterministic_reductions():
        o0 = _grouped_sum_schedule(h0, si, (inner, None), (outer, None),
                                   (inner, None))
        o1 = (_grouped_sum_schedule(h1, so, (outer, None), (inner, None),
                                    (outer, None))
              if m < total else None)
    else:
        o0 = _grouped_ordered_fold(h0, op, si, so, (inner, None),
                                   (outer, None))
        o1 = (_grouped_ordered_fold(h1, op, so, si, (outer, None),
                                    (inner, None))
              if m < total else None)
    if o1 is None:
        return o0.reshape(shape)
    return jnp.concatenate([o0, o1]).reshape(shape)


def _hier2d_fwd_value(hb: HierMeshBackend, x, op: int, algorithm: str):
    outer, inner = hb.axis_names
    so, si = hb.axis_sizes
    if so * si == 1:
        return x
    if algorithm == "torus":
        return _torus2d_fwd_value(hb, x, op)
    det = _config.deterministic_reductions()
    if not det and op == C.MPI_SUM:
        if algorithm == "ring":
            return lax.psum(x, hb.axis_names)
        return _grouped_sum_schedule(x, si, (inner, None), (outer, None),
                                     (inner, None))
    if not det and op == C.MPI_MAX:
        return lax.pmax(x, hb.axis_names)
    if not det and op == C.MPI_MIN:
        return lax.pmin(x, hb.axis_names)
    if op in (C.MPI_MINLOC, C.MPI_MAXLOC):
        C.combine2(op, x, x)  # raises with explanation
    # Deterministic / non-native ops: grouped ordered fold — inner tier
    # first (ascending within the si-rank group), then ascending over
    # group partials: the association of constants.reduce_grouped with
    # group = the inner axis size.
    return _grouped_ordered_fold(x, op, si, so, (inner, None),
                                 (outer, None))


def hier_allreduce_2d(hb: HierMeshBackend, x, op: int, algorithm=None,
                      explicit: bool = False):
    """Differentiable 2-level allreduce over a 2-axis mesh communicator;
    the adjoint is the same 2-level collective on the cotangents.

    The facade's degrade/raise rule applies to algorithms this backend
    cannot lower (``rhd``/``tree``/``bidir`` need a single ring axis):
    an explicit request raises, a scope/process default yields to
    ``hier`` — the communicator's own topology-native schedule.  Auto
    selection grows the bandwidth tier here too: at/above the measured
    ``config.bandwidth_crossover_bytes`` (outside deterministic mode)
    it picks ``torus`` — the per-axis multipath striping — instead of
    the staged 2-level ``hier``."""
    if algorithm in (None, "auto"):
        algorithm = "hier"
        bw = _config.bandwidth_crossover_bytes()
        if bw is not None and not _config.deterministic_reductions():
            xa = jnp.asarray(x)
            if xa.size * xa.dtype.itemsize >= bw:
                algorithm = "torus"
    if algorithm not in ("hier", "ring", "torus"):
        if not explicit:
            algorithm = "hier"
        else:
            raise CommError(
                f"a 2-axis mesh communicator lowers algorithm 'hier' "
                f"(the staged 2-level schedule), 'torus' (per-axis "
                f"multipath striping), or 'ring' (flat psum over both "
                f"axes); got {algorithm!r} — rhd/tree/bidir need a "
                "single-axis communicator")

    @jax.custom_vjp
    def f(v):
        return _hier2d_fwd_value(hb, v, op, algorithm)

    def bwd(_, g):
        if op != C.MPI_SUM:
            raise RuntimeError(
                f"Backward pass for Allreduce with {C.op_name(op)} is not "
                "implemented — only MPI_SUM is differentiable (reference: "
                "MPIUnimplementedNode, csrc/extension.cpp:194-202)"
            )
        with _bwd_scope("Allreduce"):
            return (_hier2d_fwd_value(hb, g, C.MPI_SUM, algorithm),)

    f.defvjp(lambda v: (_hier2d_fwd_value(hb, v, op, algorithm), None),
             bwd)
    return f(x)


def _tier_sum_schedule(x, names, sizes):
    """The N-level native SUM allreduce: grouped reduce-scatter over
    the innermost (fastest) axis, the remaining axes' allreduce on the
    shard, grouped all-gather back — the recursive generalization of
    :func:`_grouped_sum_schedule` (whose 2-level body is exactly one
    unrolling of this recursion).  Each level the payload shrinks by
    that tier's factor before crossing the next (slower) tier —
    the whole point of the stack: outer-tier bytes drop by the product
    of every inner factor."""
    if len(names) == 1:
        return lax.psum(x, names[0])
    inner_name, inner_size = names[-1], sizes[-1]
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    total = flat.size
    seg = -(-total // inner_size)
    if seg * inner_size != total:
        flat = jnp.concatenate(
            [flat, jnp.zeros(seg * inner_size - total, dtype)])
    xc = flat.reshape(inner_size, seg)
    part = lax.psum_scatter(xc, inner_name, scatter_dimension=0,
                            tiled=True)
    part = _tier_sum_schedule(part, names[:-1], sizes[:-1])
    out = lax.all_gather(part, inner_name, axis=0, tiled=True)
    return out.reshape(-1)[:total].reshape(shape)


def _tier_ordered_fold(x, op: int, names, sizes):
    """Deterministic N-level grouped fold: one all-gather + ascending
    fold per tier, innermost first — the chained form of
    :func:`_grouped_ordered_fold` (whose 2-level body is exactly two
    links of this chain), and the mesh-axis twin of the flat-world
    ``level_fold`` chain (csched ``fold_program``): the association is
    identical per tier, so Mode A/B parity per tier is the same
    single-sourced contract."""
    for nm, s in zip(reversed(names), reversed(sizes)):
        stacked = lax.all_gather(x, nm, axis=0, tiled=False)
        out = stacked[0]
        for i in range(1, s):
            out = C.combine2(op, out, stacked[i])
        x = out
    return x


def _tier_fwd_value(tb: TierStackBackend, x, op: int, algorithm: str):
    names, sizes = tb.axis_names, tb.axis_sizes
    if tb.size == 1:
        return x
    det = _config.deterministic_reductions()
    if not det and op == C.MPI_SUM:
        if algorithm == "ring":
            return lax.psum(x, names)
        return _tier_sum_schedule(x, names, sizes)
    if not det and op == C.MPI_MAX:
        return lax.pmax(x, names)
    if not det and op == C.MPI_MIN:
        return lax.pmin(x, names)
    if op in (C.MPI_MINLOC, C.MPI_MAXLOC):
        C.combine2(op, x, x)  # raises with explanation
    return _tier_ordered_fold(x, op, names, sizes)


def tier_allreduce_nd(tb: TierStackBackend, x, op: int, algorithm=None,
                      explicit: bool = False):
    """Differentiable N-level allreduce over an N-axis tier stack
    (N > 2; the 2-axis member routes through :func:`hier_allreduce_2d`
    unchanged).  Same degrade/raise rule as the 2-axis form: explicit
    single-ring-axis algorithms raise, scope defaults yield to ``hier``
    — the stack's own staged schedule; ``torus`` needs exactly two
    axes, so here it degrades/raises like the rest."""
    if algorithm in (None, "auto"):
        algorithm = "hier"
    if algorithm not in ("hier", "ring"):
        if not explicit:
            algorithm = "hier"
        else:
            raise CommError(
                f"an N-axis tier-stack communicator lowers algorithm "
                f"'hier' (the staged per-tier schedule) or 'ring' "
                f"(flat psum over all axes); got {algorithm!r} — "
                "'torus' stripes over exactly two axes, and "
                "rhd/tree/bidir need a single-axis communicator")

    @jax.custom_vjp
    def f(v):
        return _tier_fwd_value(tb, v, op, algorithm)

    def bwd(_, g):
        if op != C.MPI_SUM:
            raise RuntimeError(
                f"Backward pass for Allreduce with {C.op_name(op)} is not "
                "implemented — only MPI_SUM is differentiable (reference: "
                "MPIUnimplementedNode, csrc/extension.cpp:194-202)"
            )
        with _bwd_scope("Allreduce"):
            return (_tier_fwd_value(tb, g, C.MPI_SUM, algorithm),)

    f.defvjp(lambda v: (_tier_fwd_value(tb, v, op, algorithm), None),
             bwd)
    return f(x)


def comm_from_mesh(mesh, axis_name):
    """Adopt a mesh axis as a communicator for use inside the caller's own
    ``shard_map``/``pjit`` region — the TPU-native analogue of the
    reference's foreign-communicator interop (csrc/extension.cpp:168-171,
    src/__init__.py:247-261).

    A TUPLE of axis names (outermost/slowest first) adopts them as a
    tier-stack communicator: two names build the two-tier
    :class:`HierMeshBackend` — ``Allreduce`` runs the 2-level ``hier``
    schedule keyed off the mesh axis sizes (intra-``inner``
    reduce-scatter, inter-``outer`` allreduce, intra-``inner``
    all-gather) — and three or more build the N-level
    :class:`TierStackBackend`, the same schedule staged per tier."""
    from ..comm import MPI_Communicator

    if isinstance(axis_name, (tuple, list)):
        names = tuple(axis_name)
        if len(names) < 2:
            raise CommError(
                "a tier-stack communicator takes two or more axis "
                f"names (outermost first); got {names!r} — for one "
                "axis pass the bare name")
        for nm in names:
            if nm not in mesh.axis_names:
                raise CommError(
                    f"axis {nm!r} not in mesh axes {mesh.axis_names}")
        sizes = tuple(mesh.shape[nm] for nm in names)
        backend = (HierMeshBackend(names, sizes) if len(names) == 2
                   else TierStackBackend(names, sizes))
        comm = MPI_Communicator(lambda: backend)
        comm._hier_axes = (names, sizes)
        return comm

    if axis_name not in mesh.axis_names:
        raise CommError(
            f"axis {axis_name!r} not in mesh axes {mesh.axis_names}"
        )
    size = mesh.shape[axis_name]

    # One shared SpmdContext per trace region, so Isend/Irecv posted by
    # different op calls inside the same user-managed shard_map can match
    # into a collective_permute.  Keyed weakly on the active trace object:
    # entries die with their trace, and tracer-id handle state can never
    # leak across traces.
    import weakref
    trace_contexts: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()

    def _warn_if_pending(ctx: SpmdContext):
        # A user-managed shard_map region has no exit hook where we could
        # raise (run_spmd does); when the trace dies with unmatched p2p ops
        # we cannot throw from a finalizer, so emit a loud warning instead.
        if ctx.pending:
            import sys
            leftover = ", ".join(
                f"{p.kind}(tag={p.tag}, {_perm_desc(p.perm)})"
                for p in ctx.pending
            )
            print(
                "mpi4torch_tpu WARNING: SPMD trace region ended with "
                f"unmatched point-to-point operations: {leftover} — the "
                "message was silently dropped; every Isend needs a "
                "complementary Irecv with the same tag (under MPI this "
                "program would hang)",
                file=sys.stderr,
            )
        if ctx.coll_pending:
            import sys
            leftover = ", ".join(
                f"{s.opname}_start" for s in ctx.coll_pending)
            print(
                "mpi4torch_tpu WARNING: SPMD trace region ended with "
                f"un-waited split-phase collective handle(s): {leftover} "
                "— every *_start needs a matching Wait (the result "
                "exists only at the Wait)",
                file=sys.stderr,
            )

    def resolver():
        ctx = current_spmd_context()
        # Size must match too: two meshes can reuse an axis *name* with
        # different extents, and adopting the other mesh's context would
        # silently misroute ring arithmetic.
        if (ctx is not None and ctx.axis_name == axis_name
                and ctx.size == size):
            return SpmdBackend(ctx)
        # Public re-export (jax.core, no private-module import): the
        # active trace keys the per-region context.
        # jax.core.get_opaque_trace_state() wraps the same object but
        # hides it behind an opaque unhashable type, so the trace itself
        # stays the weak key here.
        trace = jax.core.trace_ctx.trace
        ctx = trace_contexts.get(trace)
        if ctx is None:
            ctx = SpmdContext(axis_name=axis_name, size=size)
            try:
                trace_contexts[trace] = ctx
                import weakref as _wr
                _wr.finalize(trace, _warn_if_pending, ctx)
            except TypeError:
                pass  # non-weakrefable trace: fall back to per-call context
        return SpmdBackend(ctx)

    comm = MPI_Communicator(resolver)
    comm._spmd_axis = (axis_name, size)
    return comm


@contextlib.contextmanager
def p2p_scope(comm):
    """Raising p2p-matching scope for *user-managed* ``shard_map`` regions.

    ``run_spmd`` raises :class:`DeadlockError` when a region ends with
    unmatched Isend/Irecv; a user-managed region has no exit hook, so by
    default the mesh communicator can only print a finalizer warning when
    the trace dies.  Wrapping the communication in ``with
    p2p_scope(comm):`` restores the hard guarantee — unmatched
    point-to-point operations raise at scope exit, at trace time::

        def body(x):
            with mpi.p2p_scope(comm):
                h = comm.Isend(x, dst, tag=0)
                y = comm.Recv(jnp.zeros_like(x), src, tag=0)
                comm.Wait(h)
            return y
        jax.jit(shard_map(body, mesh=mesh, ...))(x)
    """
    axis = getattr(comm, "_spmd_axis", None)
    if axis is None:
        raise CommError(
            "p2p_scope requires a mesh-derived communicator "
            "(comm_from_mesh); COMM_WORLD inside run_spmd already has a "
            "raising scope")
    ctx = SpmdContext(axis_name=axis[0], size=axis[1])
    with _bind_spmd(ctx):
        yield comm


DEFAULT_AXIS = "mpi"


def run_spmd(fn, nranks: Optional[int] = None, mesh=None,
             axis_name: str = DEFAULT_AXIS, jit: bool = True,
             donate_argnums=None):
    """Run ``fn`` SPMD over a mesh axis — the traced/compiled counterpart of
    :func:`mpi4torch_tpu.run_ranks`.

    ``fn(*args)`` is traced ONCE for all ranks (inputs replicated to every
    rank; derive rank-local data from ``COMM_WORLD.rank``).  Each of its
    outputs gains a leading ``nranks`` axis holding the per-rank results.
    Differentiable end-to-end: ``jax.grad`` of (a reduction of) the stacked
    outputs sums cotangents over ranks, exactly like executing ``backward()``
    on every MPI rank (SURVEY.md §3.3).

    ``donate_argnums`` (positions among ``fn``'s arguments; default none)
    hands those arguments' buffers to the compiled program, as
    ``jax.jit``'s option of that name does: state that goes in stacked
    ``(nranks, ...)`` and comes out so can then be updated in place.  The
    caller's arrays are deleted by the call.  Needs ``jit=True``.
    """
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    if mesh is None:
        devs = jax.devices()
        n = nranks or len(devs)
        if n > len(devs):
            raise CommError(
                f"requested {n} ranks but only {len(devs)} devices are "
                "available (set --xla_force_host_platform_device_count)"
            )
        import numpy as np
        mesh = Mesh(np.asarray(devs[:n]), (axis_name,))
    size = mesh.shape[axis_name]

    def wrapped(det, comp, bb, algo, ovl, _tune_key, *args):
        # _tune_key (thresholds fingerprint + tune cache generation) is
        # jit-cache-key-only: the values are read inside the trace via
        # config/tune, the static arg just forces a retrace when they
        # change.
        ctx = SpmdContext(axis_name=axis_name, size=size)
        with _bind_spmd(ctx), _config.deterministic_mode(det), \
                _config.compression_scope(comp), \
                _config.fusion_scope(bb), _config.algorithm_scope(algo), \
                _config.overlap_scope(ovl):
            out = fn(*args)
        return jax.tree.map(lambda y: jnp.expand_dims(y, 0), out)

    def sm(det, comp, bb, algo, ovl, tk, *args):
        return shard_map(
            lambda *a: wrapped(det, comp, bb, algo, ovl, tk, *a),
            mesh=mesh, in_specs=P(), out_specs=P(axis_name),
            check_vma=False)(*args)

    if donate_argnums is not None and not jit:
        raise ValueError("run_spmd: donate_argnums needs jit=True (the "
                         "caller's own jit donates when jit=False)")
    if jit:
        # The call below puts six static arguments before fn's own.
        jitted = jax.jit(
            sm, static_argnums=(0, 1, 2, 3, 4, 5),
            donate_argnums=tuple(6 + int(i) for i in donate_argnums or ()))
    else:
        jitted = sm

    def keyed():
        # The deterministic-reductions flag, the compression default,
        # the fusion bucket size, the algorithm default, the overlap
        # policy, and the schedule thresholds + tune-cache generation
        # are read at *call* time and made part of the jit cache key
        # (static args), so toggling any of them — or the autotuner
        # recording a new winner — retraces instead of silently reusing
        # the old lowering.
        from .. import tune as _tune

        return (_config.deterministic_reductions(),
                _config.default_compression(),
                _config.default_bucket_bytes(),
                _config.default_algorithm(),
                _config.default_overlap(),
                (_config.thresholds_fingerprint(), _tune.generation()))

    def call(*args):
        return jitted(*keyed(), *args)

    if jit:
        # The very program a call with these arguments runs, lowered:
        # for a caller that reads its compiled text.  (Not named
        # ``lower``: ``jax.jit(call)`` copies ``call``'s attributes onto
        # its own wrapper, whose ``lower`` this would then replace.)
        call.lower_as_called = lambda *args: jitted.lower(*keyed(), *args)
    return call
