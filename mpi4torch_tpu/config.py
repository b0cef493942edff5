"""Framework configuration flags.

The reference has no config system (SURVEY.md §5: three compile-time toggles
total).  This framework adds two semantic knobs:

``deterministic_reductions`` — when True, SPMD-mode SUM reductions are
computed as an all-gather followed by a fixed ascending-rank-order fold,
which is bit-identical to the eager thread-SPMD oracle (the 'MPI linear
order' reference) at the cost of bandwidth; when False (default), they lower
to ``lax.psum`` — the XLA/ICI-native reduction, fastest but with
compiler-chosen combining order (ulp-level differences possible).

``default_compression`` — the wire-compression codec applied by default to
``Allreduce``/``Allgather`` calls that do not pass an explicit
``compression=`` argument (mpi4torch_tpu.compress; None = exact fp wire).
Set it process-wide with :func:`set_default_compression` or lexically with
the :func:`compression_scope` context manager.  Like the deterministic
flag, the value is read at *trace* time: ``run_spmd`` makes it part of the
jit cache key so toggling retraces, but a user-managed ``jax.jit`` that
already traced keeps its lowering until it retraces.

``default_bucket_bytes`` — the target flat-bucket size of the fused tree
collectives (mpi4torch_tpu.fuse; the per-leaf→per-bucket launch
reduction).  ~4 MiB default, the production-stack sweet spot between
launch amortization and overlap granularity.  Set process-wide with
:func:`set_default_bucket_bytes` or lexically with :func:`fusion_scope`;
``fusion_scope(0)`` disables fusion (per-leaf collectives) for the
block.  Read at trace time like the other knobs; ``run_spmd`` keys its
jit cache on it.
"""

from __future__ import annotations

import os
import threading
from contextlib import contextmanager

_state = threading.local()


def deterministic_reductions() -> bool:
    return getattr(_state, "deterministic", False)


def set_deterministic_reductions(value: bool) -> None:
    _state.deterministic = bool(value)


@contextmanager
def deterministic_mode(value: bool = True):
    prev = deterministic_reductions()
    set_deterministic_reductions(value)
    try:
        yield
    finally:
        set_deterministic_reductions(prev)


# Sentinel distinguishing "no scope active on this thread" from an explicit
# compression_scope(None) (which forces exact transfers within the block).
_UNSET = object()
_process_default = None


def default_compression():
    """The codec (object or registered name) facade ops use when
    ``compression=None`` is passed: the innermost active
    :func:`compression_scope` on this thread, else the process-wide
    :func:`set_default_compression` value (None = no compression)."""
    scoped = getattr(_state, "compression", _UNSET)
    return _process_default if scoped is _UNSET else scoped


def _validated(codec):
    if codec is None:
        return None
    from .compress import get_codec

    return get_codec(codec)  # resolve names; ad-hoc codec objects pass


def set_default_compression(codec) -> None:
    """Set the process-wide default wire-compression codec (a registered
    name, a Codec object, or None to disable).  Visible on every thread —
    including ``run_ranks`` rank-threads — unless a thread's own
    :func:`compression_scope` overrides it."""
    global _process_default
    _process_default = _validated(codec)


# Fused-collective bucket size (mpi4torch_tpu.fuse).  4 MiB: large enough
# to amortize per-collective launch + ring latency over hundreds of tiny
# leaves, small enough that a grad tree still splits into several buckets
# whose transfers the overlap scheduler can keep in flight concurrently.
DEFAULT_BUCKET_BYTES = 4 * 1024 * 1024
_process_bucket_bytes = DEFAULT_BUCKET_BYTES


def default_bucket_bytes() -> int:
    """Bucket size (bytes) the fused tree collectives use when no
    explicit ``bucket_bytes=`` is passed: the innermost active
    :func:`fusion_scope` on this thread, else the process-wide
    :func:`set_default_bucket_bytes` value.  ``0`` disables fusion
    (per-leaf collectives)."""
    scoped = getattr(_state, "bucket_bytes", _UNSET)
    return _process_bucket_bytes if scoped is _UNSET else scoped


def _validated_bucket_bytes(nbytes) -> int:
    if nbytes is False:
        return 0
    nbytes = int(nbytes)
    if nbytes < 0:
        raise ValueError(f"bucket_bytes must be >= 0, got {nbytes}")
    return nbytes


def set_default_bucket_bytes(nbytes) -> None:
    """Set the process-wide fused-collective bucket size in bytes
    (``0``/``False`` = fusion off → per-leaf collectives)."""
    global _process_bucket_bytes
    _process_bucket_bytes = _validated_bucket_bytes(nbytes)


@contextmanager
def fusion_scope(bucket_bytes):
    """Lexically scoped bucket size for the fused tree collectives::

        with mpi.config.fusion_scope(1 << 20):   # 1 MiB buckets
            grads = comm.Allreduce_tree(grads, mpi.MPI_SUM, mean=True)

        with mpi.config.fusion_scope(0):         # per-leaf, unfused
            ...

    Per-thread like :func:`compression_scope` (a scope opened before
    ``run_ranks`` is not seen by the rank-threads — use
    :func:`set_default_bucket_bytes` or open the scope inside the rank
    body).  ``run_spmd`` re-reads the value at call time and makes it
    part of its jit cache key, so toggling retraces."""
    prev = getattr(_state, "bucket_bytes", _UNSET)
    _state.bucket_bytes = _validated_bucket_bytes(bucket_bytes)
    try:
        yield
    finally:
        if prev is _UNSET:
            del _state.bucket_bytes
        else:
            _state.bucket_bytes = prev


# ---------------------------------------------------------------------------
# Split-phase overlap (mpi4torch_tpu.overlap)
# ---------------------------------------------------------------------------

_process_overlap = None


def default_overlap():
    """The overlap policy facade tree collectives and the parallel/
    helpers use when no explicit ``overlap=`` is passed: the innermost
    active :func:`overlap_scope` on this thread, else the process-wide
    :func:`set_default_overlap` value.

    ``None`` (default) is the blocking schedule on both backends (one
    whole collective a bucket, nothing staged between buckets);
    ``True`` enables the split-phase overlap scheduler
    (:mod:`mpi4torch_tpu.overlap`) with the default prefetch depth of
    2; an ``int >= 1`` enables it with that many collectives in
    flight; ``False`` forces fully blocking schedules."""
    scoped = getattr(_state, "overlap", _UNSET)
    return _process_overlap if scoped is _UNSET else scoped


def _validated_overlap(value):
    if value is None or value is False:
        return value
    if value is True:
        return True
    try:
        depth = int(value)
    except (TypeError, ValueError):
        raise ValueError(
            f"overlap must be None, a bool, or a prefetch depth >= 1; "
            f"got {value!r}") from None
    if depth < 1:
        raise ValueError(
            f"overlap prefetch depth must be >= 1, got {depth}")
    return depth


def set_default_overlap(value) -> None:
    """Set the process-wide overlap policy (``None``/``True``/``False``
    or an integer prefetch depth — see :func:`default_overlap`)."""
    global _process_overlap
    _process_overlap = _validated_overlap(value)


@contextmanager
def overlap_scope(value):
    """Lexically scoped overlap policy for the split-phase scheduler::

        with mpi.config.overlap_scope(True):      # 2 buckets in flight
            grads = comm.Allreduce_tree(grads, mpi.MPI_SUM, mean=True)

        with mpi.config.overlap_scope(3):          # deeper prefetch
            params = mpi.parallel.zero.zero3_params(comm, shards, tmpl)

    Per-thread like :func:`compression_scope`; ``run_spmd`` re-reads the
    value at call time and makes it part of its jit cache key, so
    toggling retraces.  A scope default is a *preference*: buckets it
    cannot legally serve (e.g. a compressed bucket — the codec pipeline
    is a fused multi-step collective with no split form) degrade to the
    blocking path; an explicit ``overlap=`` plus an explicit conflicting
    argument raises instead, exactly like the compression scope's
    degrade/raise rule."""
    prev = getattr(_state, "overlap", _UNSET)
    _state.overlap = _validated_overlap(value)
    try:
        yield
    finally:
        if prev is _UNSET:
            del _state.overlap
        else:
            _state.overlap = prev


# ---------------------------------------------------------------------------
# Collective-algorithm selection (mpi4torch_tpu.tune)
# ---------------------------------------------------------------------------

_process_algorithm = None


def default_algorithm():
    """The collective algorithm facade ops use when no explicit
    ``algorithm=`` is passed: the innermost active :func:`algorithm_scope`
    on this thread, else the process-wide :func:`set_default_algorithm`
    value.  ``None``/``"auto"`` defer to the :mod:`mpi4torch_tpu.tune`
    selector (measured cache winner where one exists, else ``ring``)."""
    scoped = getattr(_state, "algorithm", _UNSET)
    return _process_algorithm if scoped is _UNSET else scoped


def _validated_algorithm(name):
    if name is None or name == "auto":
        return None
    from .tune import get_algorithm

    return get_algorithm(name).name  # raises on unknown names


def set_default_algorithm(name) -> None:
    """Set the process-wide default collective algorithm (a registered
    algorithm name — ``ring``/``rhd``/``tree``/``hier`` — or
    ``None``/``"auto"`` for selector-driven choice).  A scope/process
    default is a *preference*: collectives it cannot legally serve
    (e.g. ``rhd`` on a non-power-of-two world, or a compressed transfer
    whose codec is ring-only) silently fall back to auto selection,
    exactly like the compression scope's degrade rule; an explicit
    per-call ``algorithm=`` raises instead."""
    global _process_algorithm
    _process_algorithm = _validated_algorithm(name)


@contextmanager
def algorithm_scope(name):
    """Lexically scoped collective-algorithm default::

        with mpi.config.algorithm_scope("rhd"):
            y = comm.Allreduce(x, mpi.MPI_SUM)   # latency-optimal wire

    Per-thread like :func:`compression_scope`; ``run_spmd`` re-reads the
    value at call time and makes it part of its jit cache key, so
    toggling retraces."""
    prev = getattr(_state, "algorithm", _UNSET)
    _state.algorithm = _validated_algorithm(name)
    try:
        yield
    finally:
        if prev is _UNSET:
            del _state.algorithm
        else:
            _state.algorithm = prev


# ---------------------------------------------------------------------------
# Collective schedule thresholds (promoted from ops/spmd.py constants;
# ISSUE 3 satellite).  Process-wide, validated, and overridable from
# measurement by the mpi4torch_tpu.tune autotuner.
# ---------------------------------------------------------------------------

# The all-gather+fold form of the ordered reduction materializes size× the
# tensor per rank; below this many *gathered* bytes (payload × ranks) its
# latency advantage wins.  Above it, the chunked ring fold caps peak extra
# memory at ≈2× the tensor.  Both paths are bit-identical, so the switch
# is safe at any value.
DEFAULT_ORDERED_FOLD_GATHER_MAX_BYTES = 4 * 1024 * 1024
# Pipeline granularity of the deterministic ring fold.
DEFAULT_ORDERED_RING_CHUNK_BYTES = 8 * 1024 * 1024
# Payloads at or below this take the binomial-tree broadcast (log2(N)
# sequential full-payload hops); larger ones the root-masked psum (see
# ops/spmd.py _bcast_value for the wire accounting).
DEFAULT_BCAST_TREE_MAX_BYTES = 256 * 1024

_ordered_fold_gather_max_bytes = DEFAULT_ORDERED_FOLD_GATHER_MAX_BYTES
_ordered_ring_chunk_bytes = DEFAULT_ORDERED_RING_CHUNK_BYTES
_bcast_tree_max_bytes = DEFAULT_BCAST_TREE_MAX_BYTES


def _validated_threshold(nbytes, what: str, minimum: int = 0,
                         unit: str = "byte count") -> int:
    try:
        nbytes = int(nbytes)
    except (TypeError, ValueError):
        raise ValueError(f"{what} must be an integer {unit}, got "
                         f"{nbytes!r}") from None
    if nbytes < minimum:
        raise ValueError(f"{what} must be >= {minimum}, got {nbytes}")
    return nbytes


def ordered_fold_gather_max_bytes() -> int:
    """Gathered-bytes ceiling (payload × ranks) below which the
    deterministic ordered fold uses the all-gather+fold form instead of
    the chunked ring (ops/spmd.py)."""
    return _ordered_fold_gather_max_bytes


def set_ordered_fold_gather_max_bytes(nbytes) -> None:
    global _ordered_fold_gather_max_bytes
    _ordered_fold_gather_max_bytes = _validated_threshold(
        nbytes, "ordered_fold_gather_max_bytes")


def ordered_ring_chunk_bytes() -> int:
    """Chunk size of the deterministic ring-fold pipeline
    (ops/spmd.py)."""
    return _ordered_ring_chunk_bytes


def set_ordered_ring_chunk_bytes(nbytes) -> None:
    global _ordered_ring_chunk_bytes
    _ordered_ring_chunk_bytes = _validated_threshold(
        nbytes, "ordered_ring_chunk_bytes", minimum=1)


def bcast_tree_max_bytes() -> int:
    """Payload-bytes ceiling below which ``Bcast_`` takes the
    binomial-tree lowering instead of the root-masked psum
    (ops/spmd.py)."""
    return _bcast_tree_max_bytes


def set_bcast_tree_max_bytes(nbytes) -> None:
    global _bcast_tree_max_bytes
    _bcast_tree_max_bytes = _validated_threshold(
        nbytes, "bcast_tree_max_bytes")


# Measured latency/bandwidth crossover for allreduce algorithm selection.
# None = not measured: the selector never switches algorithms on a
# heuristic alone — it deviates from `ring` only on evidence (a cached
# per-key winner, or this crossover once the autotuner has measured it).
_latency_crossover_bytes = None


def latency_crossover_bytes():
    """Payload-bytes ceiling below which the tune selector prefers a
    latency-optimal algorithm (``rhd``, else ``tree``) for auto-selected
    allreduces.  ``None`` (default) = unmeasured: auto-selection stays
    on ``ring`` except where the autotuner cache names a winner.  Set
    from measurement by :func:`mpi4torch_tpu.tune.autotune_allreduce`
    or explicitly here."""
    return _latency_crossover_bytes


def set_latency_crossover_bytes(nbytes) -> None:
    global _latency_crossover_bytes
    _latency_crossover_bytes = (
        None if nbytes is None
        else _validated_threshold(nbytes, "latency_crossover_bytes"))


# Measured ring/multipath crossover for allreduce algorithm selection —
# the upper edge of the three-tier auto selection (latency algorithms
# below latency_crossover_bytes, plain ring in the middle, a multipath
# bandwidth algorithm at/above this).  None = not measured: like the
# latency crossover, auto-selection deviates from `ring` only on
# evidence.
_bandwidth_crossover_bytes = None


def bandwidth_crossover_bytes():
    """Payload-bytes floor at/above which the tune selector prefers a
    bandwidth-tier multipath algorithm (``bidir``, the dual-ring) for
    auto-selected allreduces.  ``None`` (default) = unmeasured: auto
    selection stays on ``ring`` for large payloads except where the
    autotuner cache names a winner.  Set from measurement by
    :func:`mpi4torch_tpu.tune.autotune_allreduce` or explicitly here."""
    return _bandwidth_crossover_bytes


def set_bandwidth_crossover_bytes(nbytes) -> None:
    global _bandwidth_crossover_bytes
    _bandwidth_crossover_bytes = (
        None if nbytes is None
        else _validated_threshold(nbytes, "bandwidth_crossover_bytes"))


# Phase pipelining of the deterministic chunked ring fold (ops/spmd.py
# _ring_fold_allreduce): when True (default) a chunk whose ascending-rank
# fold has completed starts its all-gather relay around the ring while
# later chunks are still folding — one fused scan, no trailing
# full-payload broadcast barrier.  False restores the fold-then-tree-
# broadcast two-phase form (the pre-pipelining baseline, kept for
# head-to-head measurement).  Bits are identical either way: the fold
# association is untouched and the relay is pure data movement.
_phase_pipelined_ring = True


def phase_pipelined_ring() -> bool:
    """Whether the deterministic chunked ring fold overlaps its
    all-gather head with the reduce-scatter tail (see ops/spmd.py
    ``_ring_fold_allreduce``)."""
    return _phase_pipelined_ring


def set_phase_pipelined_ring(value: bool) -> None:
    global _phase_pipelined_ring
    _phase_pipelined_ring = bool(value)


# Worlds up to this size unroll the explicit directional ring chains of
# the `bidir` schedule hop-by-hop (distinct permute ops — maximal
# scheduling freedom and the HLO-census surface); larger worlds roll
# each phase into a lax.scan so the compiled program does not grow with
# the rank count (a 256-rank pod would otherwise emit ~1000 permute ops
# per bidir allreduce).  Promoted from the ops/spmd.py module constant
# _CHAIN_UNROLL_MAX (ISSUE 5 satellite), matching the ISSUE 3
# threshold-promotion pattern: validated setter, run_spmd jit-cache
# fingerprint coverage, overridable from measurement.
DEFAULT_CHAIN_UNROLL_MAX = 32

_chain_unroll_max = DEFAULT_CHAIN_UNROLL_MAX


def chain_unroll_max() -> int:
    """Rank-count ceiling up to which the ``bidir`` directional ring
    chains unroll hop-by-hop; larger worlds take the O(1)-program
    ``lax.scan`` form (ops/spmd.py ``_ring_allreduce_chain``; bits are
    identical either way)."""
    return _chain_unroll_max


def set_chain_unroll_max(n) -> None:
    global _chain_unroll_max
    _chain_unroll_max = _validated_threshold(
        n, "chain_unroll_max", minimum=1, unit="rank count")


# Implementation of the fused dequantize→accumulate→requantize hop of the
# in-schedule quantized collectives (ops/quant_kernels.py, EQuARX-style):
# "auto" runs the Pallas TPU kernel on TPU and the bit-identical jnp
# fallback elsewhere; "jnp" forces the fallback everywhere; "pallas"
# forces the kernel (interpreted off-TPU — the bit-equivalence test
# surface).  Part of the run_spmd jit fingerprint: toggling retraces.
_QUANT_HOP_IMPLS = ("auto", "jnp", "pallas")
_quant_hop_impl = "auto"


def quant_hop_impl() -> str:
    """Which implementation serves the fused quantized ring hop
    (``ops/quant_kernels.py``): ``"auto"`` (Pallas kernel on TPU, jnp
    fallback elsewhere — both bit-identical), ``"jnp"`` (fallback
    everywhere), or ``"pallas"`` (kernel forced; interpreted off-TPU)."""
    return _quant_hop_impl


def set_quant_hop_impl(impl: str) -> None:
    global _quant_hop_impl
    if impl not in _QUANT_HOP_IMPLS:
        raise ValueError(
            f"quant_hop_impl must be one of {_QUANT_HOP_IMPLS}, got "
            f"{impl!r}")
    _quant_hop_impl = impl


# Split count of the serving decode step's per-layer TP collectives
# (mpi4torch_tpu.serve): each tiny per-token allreduce payload is split
# into this many windowed split-phase chunk buckets so >= 2 transfers
# stay in flight (the overlap scheduler's window, applied WITHIN one
# collective site — decode has no independent second collective stream
# to pair with).  2 (default) is the double-buffered sweet spot for
# payloads this small; 1 degenerates to a single split-phase pair
# (start/wait with an empty window — censuses exposed).  Only read when
# the engine's overlap policy is on; part of the trace-time fingerprint.
DEFAULT_SERVE_DECODE_BUCKETS = 2

_serve_decode_buckets = DEFAULT_SERVE_DECODE_BUCKETS


def serve_decode_buckets() -> int:
    """How many windowed split-phase chunk buckets one serving decode
    collective is split into (:mod:`mpi4torch_tpu.serve`; >= 1)."""
    return _serve_decode_buckets


def set_serve_decode_buckets(n) -> None:
    global _serve_decode_buckets
    _serve_decode_buckets = _validated_threshold(
        n, "serve_decode_buckets", minimum=1, unit="bucket count")


# Default planning strategy of the resharding subsystem
# (mpi4torch_tpu.reshard): "auto" lets the planner walk its preference
# order (local < permute < allgather < alltoall < rounds — gather, the
# full-materialization baseline, only ever wins through a measured tune
# cache entry); a concrete name pins every plan to that strategy and
# raises where it cannot serve the transition.  Part of the trace-time
# fingerprint: run_spmd retraces when it changes.
_reshard_strategy = None


def default_reshard_strategy():
    """The plan strategy :func:`mpi4torch_tpu.reshard.plan_reshard`
    uses when no explicit ``strategy=`` is passed (``None``/``"auto"``
    = preference order + transition-keyed autotuner winner)."""
    return _reshard_strategy


def set_default_reshard_strategy(name) -> None:
    global _reshard_strategy
    if name in (None, "auto"):
        _reshard_strategy = None
        return
    from .reshard.plan import STRATEGIES

    if name not in STRATEGIES:
        raise ValueError(
            f"reshard strategy must be one of {STRATEGIES} or "
            f"None/'auto', got {name!r}")
    _reshard_strategy = name


# Intra-group size of the 2-level `hier` allreduce on a single mesh axis.
# None = derive: the minor axis extent when the communicator was adopted
# from a multi-axis mesh, else the divisor of nranks closest to sqrt.
_hier_group_size = None


def hier_group_size():
    """Intra-group size of the single-axis ``hier`` allreduce (must
    divide the communicator size, 1 < g < size).  ``None`` = derive from
    topology (see :mod:`mpi4torch_tpu.tune`)."""
    return _hier_group_size


def set_hier_group_size(g) -> None:
    global _hier_group_size
    if g is None:
        _hier_group_size = None
        return
    g = _validated_threshold(g, "hier_group_size", minimum=2)
    _hier_group_size = g


# N-level tier factorization of a single-axis communicator, innermost
# (fastest interconnect) first — e.g. (4, 2) = groups of 4 inside a pod,
# 2 pods.  Generalizes _hier_group_size: a 2-level stack (g, n // g) is
# exactly hier_group_size=g.  None = derive (hier_group_size, else the
# sqrt-divisor 2-level split).  See mpi4torch_tpu.tune.resolve_tier_stack.
_tier_stack = None
# Relative bandwidth of each tier's interconnect, aligned with the tier
# stack (innermost first) — e.g. (1.0, 0.05) for fast ICI under slow DCN.
# The weights of the bandwidth-weighted wire census (csched.weighted_cost,
# analyze.weighted_wire_cost); None = uniform.
_tier_bandwidths = None


def tier_stack():
    """The configured tier-stack factorization (innermost first), or
    None to derive.  Each factor must be >= 2 and the product must equal
    the communicator size (validated where it is resolved)."""
    return _tier_stack


def set_tier_stack(stack) -> None:
    global _tier_stack
    if stack is None:
        _tier_stack = None
        return
    try:
        stack = tuple(int(g) for g in stack)
    except (TypeError, ValueError):
        raise ValueError(
            f"tier_stack must be a tuple of ints >= 2 or None, got "
            f"{stack!r}") from None
    if not stack or any(g < 2 for g in stack):
        raise ValueError(
            f"tier_stack factors must all be >= 2, got {stack!r}")
    _tier_stack = stack


def tier_bandwidths():
    """Per-tier relative bandwidths (innermost first), or None for
    uniform weights.  Aligned with the resolved tier stack."""
    return _tier_bandwidths


def set_tier_bandwidths(bws) -> None:
    global _tier_bandwidths
    if bws is None:
        _tier_bandwidths = None
        return
    try:
        bws = tuple(float(b) for b in bws)
    except (TypeError, ValueError):
        raise ValueError(
            f"tier_bandwidths must be a tuple of positive numbers or "
            f"None, got {bws!r}") from None
    if not bws or any(b <= 0 for b in bws):
        raise ValueError(
            f"tier_bandwidths must all be > 0, got {bws!r}")
    _tier_bandwidths = bws


# ---------------------------------------------------------------------------
# Fault tolerance (mpi4torch_tpu.resilience; ISSUE 7)
# ---------------------------------------------------------------------------

# Transient-fault retry budget of the eager rendezvous/p2p layer: a
# barrier or receive that finds nothing within the base timeout gets
# this many extra patience windows, each of capped-exponential-backoff
# length, before declaring DeadlockError — a slow-but-alive rank (GC
# pause, noisy neighbor, fault-injected delay) completes the collective
# inside the extended window instead of tearing the world down.  0
# (default) keeps the historical single-timeout behavior.
_comm_retries = 0
# Base backoff in seconds; retry k waits min(backoff * 2**(k-1), 30s).
_comm_backoff = 0.05


def comm_retries() -> int:
    """Retry extensions granted to a timed-out rendezvous barrier or p2p
    receive before it raises (mpi4torch_tpu.resilience)."""
    return _comm_retries


def set_comm_retries(n) -> None:
    global _comm_retries
    _comm_retries = _validated_threshold(n, "comm_retries",
                                         unit="retry count")


def comm_backoff() -> float:
    """Base seconds of the capped exponential backoff between comm
    retries (retry k waits ``min(comm_backoff * 2**(k-1), 30s)``)."""
    return _comm_backoff


def set_comm_backoff(seconds) -> None:
    global _comm_backoff
    try:
        seconds = float(seconds)
    except (TypeError, ValueError):
        raise ValueError(
            f"comm_backoff must be a number of seconds, got "
            f"{seconds!r}") from None
    if seconds < 0:
        raise ValueError(f"comm_backoff must be >= 0, got {seconds}")
    _comm_backoff = seconds


# Non-finite payload guard of the collective layer: "off" (default —
# the lowering is bit-identical to a guard-less build, held by
# tests/test_resilience.py), "warn" (IntegrityWarning naming the
# offending rank(s) on the eager backend), or "raise" (IntegrityError).
_GUARD_MODES = ("off", "warn", "raise")
_comm_finite_guard = "off"


def comm_finite_guard() -> str:
    """Non-finite payload check mode of the collective ops
    (mpi4torch_tpu.resilience.guards): ``"off"``/``"warn"``/``"raise"``.
    Part of the trace-time fingerprint — toggling retraces Mode A."""
    return _comm_finite_guard


def set_comm_finite_guard(mode: str) -> None:
    global _comm_finite_guard
    if mode not in _GUARD_MODES:
        raise ValueError(
            f"comm_finite_guard must be one of {_GUARD_MODES}, got "
            f"{mode!r}")
    _comm_finite_guard = mode


# Checksum leg of the compressed rendezvous wire (compress/eager.py):
# when True, every encoded payload ships with a CRC of its wire bytes
# and decode verifies each rank's block, raising IntegrityError naming
# the corrupt contributor.  Off (default) keeps the wire format —
# and the Mode A lowering — bit-identical to a checksum-less build.
_comm_wire_checksum = False


def comm_wire_checksum() -> bool:
    """Whether the compressed eager wire carries a verified checksum
    (mpi4torch_tpu.resilience.guards.wire_checksum)."""
    return _comm_wire_checksum


def set_comm_wire_checksum(value: bool) -> None:
    global _comm_wire_checksum
    _comm_wire_checksum = bool(value)


# The active deterministic fault-injection plan
# (mpi4torch_tpu.resilience.faults.FaultPlan), or None (default: the
# zero-overhead fast path — one attribute read per rendezvous).
# PROCESS-wide, not thread-scoped: faults must be visible inside
# run_ranks rank-threads, which a thread-local scope opened outside
# them would miss; resilience.fault_scope() is the save/restore wrapper.
_fault_plan = None


def fault_plan():
    """The active fault-injection plan (or None).  See
    :mod:`mpi4torch_tpu.resilience`."""
    return _fault_plan


def set_fault_plan(plan) -> None:
    """Install a process-wide fault plan: a
    :class:`~mpi4torch_tpu.resilience.FaultPlan`, a sequence of
    :class:`~mpi4torch_tpu.resilience.FaultSpec`, or None to clear."""
    global _fault_plan
    if plan is None:
        _fault_plan = None
        return
    from .resilience.faults import as_plan

    _fault_plan = as_plan(plan)


# ---------------------------------------------------------------------------
# Mode B transport backend (mpi4torch_tpu.transport; ISSUE 16)
# ---------------------------------------------------------------------------

# Which registered transport serves run_ranks when no explicit
# ``backend=`` is passed: "thread" (N rank-threads in this process —
# the historical semantics and the tier-1 default) or "process" (N
# spawned worker processes over the pickle-framed socket wire — real
# parallelism, real SIGKILLs).  PROCESS-wide like the fault plan: the
# transport choice must be visible wherever run_ranks is called.
# Deliberately NOT part of thresholds_fingerprint(): the knob is Mode B
# (rendezvous wire) only and provably never moves a Mode A lowering —
# the _comm_wire_checksum precedent.
_comm_transport = os.environ.get("MPI4TORCH_TPU_TRANSPORT", "thread")


def comm_transport() -> str:
    """The default transport backend :func:`~mpi4torch_tpu.run_ranks`
    uses when no explicit ``backend=`` is passed (see
    :mod:`mpi4torch_tpu.transport`).  Initialized from the
    ``MPI4TORCH_TPU_TRANSPORT`` environment variable (``"thread"``
    when unset)."""
    return _comm_transport


def set_comm_transport(name) -> None:
    """Set the process-wide default transport backend (a name
    registered in :data:`mpi4torch_tpu.transport.TRANSPORTS`)."""
    global _comm_transport
    if name is None:
        name = "thread"
    from .transport import TRANSPORTS

    if name not in TRANSPORTS:
        raise ValueError(
            f"comm_transport must be one of {sorted(TRANSPORTS)}, got "
            f"{name!r}")
    _comm_transport = name


@contextmanager
def transport_scope(name):
    """Install a transport default for a ``with`` block (process-wide
    like :func:`set_fault_plan` — the choice must be visible to
    whatever thread calls ``run_ranks`` inside the block)::

        with mpi.config.transport_scope("process"):
            mpi.run_ranks(step, 8)      # real worker processes
    """
    global _comm_transport
    prev = _comm_transport
    set_comm_transport(name)
    try:
        yield
    finally:
        _comm_transport = prev


# Process-wide knobs a transport worker process must replicate so the
# rank body computes bit-identically to a rank-thread.  Thread-SCOPED
# state (deterministic_mode, compression_scope, ...) is deliberately
# absent: rank-threads spawned by run_ranks never see the launcher
# thread's scopes either, so shipping them would DIVERGE from the
# thread backend, not match it.
def snapshot_process_state() -> dict:
    """Picklable snapshot of every process-wide config knob that
    affects Mode B rank-body execution — what the process transport
    ships to its workers (mpi4torch_tpu.transport).  Codecs travel by
    registered name (an unregistered ad-hoc codec object travels as
    itself and must pickle)."""
    codec = _process_default
    if codec is not None:
        name = getattr(codec, "name", None)
        if name is not None:
            codec = name
    return {
        "compression": codec,
        "bucket_bytes": _process_bucket_bytes,
        "overlap": _process_overlap,
        "algorithm": _process_algorithm,
        "ordered_fold_gather_max_bytes": _ordered_fold_gather_max_bytes,
        "ordered_ring_chunk_bytes": _ordered_ring_chunk_bytes,
        "bcast_tree_max_bytes": _bcast_tree_max_bytes,
        "latency_crossover_bytes": _latency_crossover_bytes,
        "bandwidth_crossover_bytes": _bandwidth_crossover_bytes,
        "phase_pipelined_ring": _phase_pipelined_ring,
        "hier_group_size": _hier_group_size,
        "tier_stack": _tier_stack,
        "tier_bandwidths": _tier_bandwidths,
        "chain_unroll_max": _chain_unroll_max,
        "quant_hop_impl": _quant_hop_impl,
        "serve_decode_buckets": _serve_decode_buckets,
        "reshard_strategy": _reshard_strategy,
        "comm_retries": _comm_retries,
        "comm_backoff": _comm_backoff,
        "comm_finite_guard": _comm_finite_guard,
        "comm_wire_checksum": _comm_wire_checksum,
        "ctl_enabled": _ctl_enabled,
        "ctl_halflife": _ctl_halflife,
        "ctl_drift_thresholds": (_ctl_drift_low, _ctl_drift_high),
        "ctl_drift_patience": _ctl_drift_patience,
        "ctl_min_switch_epochs": _ctl_min_switch_epochs,
        "ctl_codec_crossover": _ctl_codec_crossover,
    }


def apply_process_state(state: dict) -> None:
    """Apply a :func:`snapshot_process_state` dict — the worker-process
    half of the config shipping contract."""
    set_default_compression(state["compression"])
    set_default_bucket_bytes(state["bucket_bytes"])
    set_default_overlap(state["overlap"])
    set_default_algorithm(state["algorithm"])
    set_ordered_fold_gather_max_bytes(
        state["ordered_fold_gather_max_bytes"])
    set_ordered_ring_chunk_bytes(state["ordered_ring_chunk_bytes"])
    set_bcast_tree_max_bytes(state["bcast_tree_max_bytes"])
    set_latency_crossover_bytes(state["latency_crossover_bytes"])
    set_bandwidth_crossover_bytes(state["bandwidth_crossover_bytes"])
    set_phase_pipelined_ring(state["phase_pipelined_ring"])
    set_hier_group_size(state["hier_group_size"])
    set_tier_stack(state["tier_stack"])
    set_tier_bandwidths(state["tier_bandwidths"])
    set_chain_unroll_max(state["chain_unroll_max"])
    set_quant_hop_impl(state["quant_hop_impl"])
    set_serve_decode_buckets(state["serve_decode_buckets"])
    set_default_reshard_strategy(state["reshard_strategy"])
    set_comm_retries(state["comm_retries"])
    set_comm_backoff(state["comm_backoff"])
    set_comm_finite_guard(state["comm_finite_guard"])
    set_comm_wire_checksum(state["comm_wire_checksum"])
    set_ctl_enabled(state["ctl_enabled"])
    set_ctl_halflife(state["ctl_halflife"])
    set_ctl_drift_thresholds(*state["ctl_drift_thresholds"])
    set_ctl_drift_patience(state["ctl_drift_patience"])
    set_ctl_min_switch_epochs(state["ctl_min_switch_epochs"])
    set_ctl_codec_crossover(state["ctl_codec_crossover"])


# ---------------------------------------------------------------------------
# Runtime observability (mpi4torch_tpu.obs; ISSUE 12)
# ---------------------------------------------------------------------------

# The active comm tracer (mpi4torch_tpu.obs.CommTracer), or None
# (default: the zero-overhead fast path — one attribute read per
# chokepoint, the fault-plan discipline).  PROCESS-wide like the fault
# plan: events must flow from run_ranks rank-threads, which a
# thread-local scope opened outside them would miss; obs.trace() is the
# save/restore wrapper.
_comm_tracer = None


def comm_tracer():
    """The active comm tracer (or None).  See
    :mod:`mpi4torch_tpu.obs`."""
    return _comm_tracer


def set_comm_tracer(tracer) -> None:
    """Install a process-wide comm tracer (an
    :class:`~mpi4torch_tpu.obs.CommTracer`, or None to disable).  With
    ``tracer.mode_a`` set, Mode A lowerings gain the step-event host
    callback — the flag rides :func:`thresholds_fingerprint`, so
    installing/removing such a tracer retraces instead of reusing the
    uninstrumented lowering."""
    global _comm_tracer
    _comm_tracer = tracer


def thresholds_fingerprint():
    """Hashable snapshot of every trace-time threshold/selection knob —
    ``run_spmd`` folds it into its jit cache key so overriding a
    threshold (or the autotuner writing a measured crossover) retraces
    instead of silently reusing the old lowering."""
    # _comm_wire_checksum is deliberately NOT here: it is a Mode B
    # (rendezvous wire) leg only and provably never moves the Mode A
    # lowering (censused in tests/test_resilience.py) — keying it in
    # would force a full
    # retrace/recompile for zero semantic effect.
    # The obs tracer keys in only as "does Mode A get the step-event
    # callback": a Mode B-only tracer (mode_a=False, the default) never
    # moves the lowering, so it must not force a retrace either —
    # censused in tests/test_obs.py, like _comm_wire_checksum.
    # The ctl knobs ride along even though they never move a lowering
    # directly: the controller's thresholds decide which winners get
    # INSTALLED (tune.record bumps the selection generation), so a
    # lowering's cache identity should be keyed to the policy that
    # selected it — and the ISSUE 19 process-shipping contract wants
    # one fingerprint covering the whole selection surface.
    return (_ordered_fold_gather_max_bytes, _ordered_ring_chunk_bytes,
            _bcast_tree_max_bytes, _latency_crossover_bytes,
            _bandwidth_crossover_bytes, _phase_pipelined_ring,
            _hier_group_size, _tier_stack, _tier_bandwidths,
            _chain_unroll_max, _quant_hop_impl,
            _comm_finite_guard, _reshard_strategy,
            _serve_decode_buckets,
            _ctl_enabled, _ctl_halflife,
            (_ctl_drift_low, _ctl_drift_high), _ctl_drift_patience,
            _ctl_min_switch_epochs, _ctl_codec_crossover,
            # The mode_a tracer flag stays LAST (tests/test_obs.py
            # reads it as fingerprint[-1]).
            bool(_comm_tracer is not None
                 and getattr(_comm_tracer, "mode_a", False)))


@contextmanager
def compression_scope(codec):
    """Lexically scoped compression default::

        with mpi.config.compression_scope("q8"):
            y = comm.Allreduce(g, mpi.MPI_SUM)   # rides the wire as int8

    ``compression_scope(None)`` forces exact transfers for the block even
    when a process default is set.  The scope itself is per-thread (like
    ``deterministic_mode``): a scope opened before ``run_ranks`` is not
    seen by the rank-threads — use :func:`set_default_compression`, open
    the scope inside the rank body, or pass ``compression=`` explicitly
    for collective agreement there.  ``run_spmd`` re-reads the value at
    call time and makes it part of its jit cache key, so toggling
    retraces."""
    prev = getattr(_state, "compression", _UNSET)
    _state.compression = _validated(codec)
    try:
        yield
    finally:
        if prev is _UNSET:
            del _state.compression
        else:
            _state.compression = prev


# ---------------------------------------------------------------------------
# Online self-tuning controller (mpi4torch_tpu.ctl; ISSUE 19)
# ---------------------------------------------------------------------------

# Master switch: False (default) keeps SelfTuningController.poll to ONE
# knob read and guarantees the controller changes nothing — the
# fault-plan/obs off-path discipline, censused in tests/test_ctl.py.
_ctl_enabled = False
# EWMA half-life of the bandwidth estimates, in SAMPLES (after this
# many events a value's weight has decayed to 1/2) — a deterministic
# unit: the smoke/test cells drive the estimator with known event
# counts, never wall-clock.
_ctl_halflife = 4.0
# Hysteresis watermarks on the live/baseline per-tier ratio: a tier
# degrades below `low`, recovers above `high`, and the band between
# them resets both patience counters — scheduler noise oscillating
# inside the band can never flap a switch.
_ctl_drift_low = 0.5
_ctl_drift_high = 0.8
# Consecutive monitor checks past a watermark before the state flips.
_ctl_drift_patience = 2
# Minimum consensus epochs between ratified switches (a second
# anti-flap leg, counted in the currency switches themselves advance).
_ctl_min_switch_epochs = 1
# Ratio below which the escalation is a CODEC escalation (exact ->
# compressed wire, the EQuARX regime) rather than an exact re-rank: at
# a quarter of baseline bandwidth the ~4x smaller q8 wire breaks even
# on the sagged tier.
_ctl_codec_crossover = 0.25


def ctl_enabled() -> bool:
    """Whether the online self-tuning controller acts
    (:mod:`mpi4torch_tpu.ctl`).  Off (default): ``poll`` is one
    attribute read and the build is bit-identical to a controller-less
    one."""
    return _ctl_enabled


def set_ctl_enabled(value: bool) -> None:
    global _ctl_enabled
    _ctl_enabled = bool(value)


def ctl_halflife() -> float:
    """EWMA half-life (in samples) of the controller's live bandwidth
    estimates (ctl.estimate)."""
    return _ctl_halflife


def set_ctl_halflife(halflife) -> None:
    global _ctl_halflife
    try:
        halflife = float(halflife)
    except (TypeError, ValueError):
        raise ValueError(
            f"ctl_halflife must be a number of samples, got "
            f"{halflife!r}") from None
    if not halflife > 0:
        raise ValueError(f"ctl_halflife must be > 0, got {halflife}")
    _ctl_halflife = halflife


def ctl_drift_thresholds():
    """The ``(low, high)`` hysteresis watermarks on the live/baseline
    bandwidth ratio (ctl.drift): degrade below ``low``, recover above
    ``high``, never flap inside the band."""
    return (_ctl_drift_low, _ctl_drift_high)


def set_ctl_drift_thresholds(low, high) -> None:
    global _ctl_drift_low, _ctl_drift_high
    try:
        low, high = float(low), float(high)
    except (TypeError, ValueError):
        raise ValueError(
            f"ctl_drift_thresholds must be numbers, got "
            f"({low!r}, {high!r})") from None
    if not (0.0 < low < high):
        raise ValueError(
            f"ctl_drift_thresholds need 0 < low < high, got "
            f"({low}, {high})")
    _ctl_drift_low, _ctl_drift_high = low, high


def ctl_drift_patience() -> int:
    """Consecutive monitor checks past a watermark before a tier's
    drift state flips (ctl.drift)."""
    return _ctl_drift_patience


def set_ctl_drift_patience(n) -> None:
    global _ctl_drift_patience
    _ctl_drift_patience = _validated_threshold(
        n, "ctl_drift_patience", minimum=1, unit="check count")


def ctl_min_switch_epochs() -> int:
    """Minimum consensus epochs between ratified controller switches
    (ctl.controller) — the anti-flap leg counted in epochs."""
    return _ctl_min_switch_epochs


def set_ctl_min_switch_epochs(n) -> None:
    global _ctl_min_switch_epochs
    _ctl_min_switch_epochs = _validated_threshold(
        n, "ctl_min_switch_epochs", minimum=0, unit="epoch count")


def ctl_codec_crossover() -> float:
    """Live/baseline ratio below which the controller escalates the
    CODEC (exact -> q8) instead of only re-ranking the exact winner
    (ctl.controller)."""
    return _ctl_codec_crossover


def set_ctl_codec_crossover(ratio) -> None:
    global _ctl_codec_crossover
    try:
        ratio = float(ratio)
    except (TypeError, ValueError):
        raise ValueError(
            f"ctl_codec_crossover must be a ratio in (0, 1], got "
            f"{ratio!r}") from None
    if not (0.0 < ratio <= 1.0):
        raise ValueError(
            f"ctl_codec_crossover must be in (0, 1], got {ratio}")
    _ctl_codec_crossover = ratio
