"""Compressed collectives: AD-transparent block-scaled quantized wire.

The dominant cost of collectives at scale is bytes over ICI/DCN; this
package cuts them with wire-compression codecs while preserving the
framework's core invariant — the backward pass of every compressed
collective is itself a compressed collective (the paper's
adjoint-is-a-collective property, on a quantized wire).  Design
references: EQuARX (arxiv 2506.17615, block-scaled quantized AllReduce
native to XLA) and "The Big Send-off" (arxiv 2504.18658, per-topology
tunability — hence the codec registry, which later topology-aware
autotuning plugs into).

Usage — pick a codec per call, per scope, or process-wide::

    y = comm.Allreduce(g, mpi.MPI_SUM, compression="q8")

    with mpi.config.compression_scope("q8_ef"):
        y = comm.Allreduce(g, mpi.MPI_SUM)          # scope default

    mpi.config.set_default_compression("bf16")      # process default

Both backends honor the same argument: under ``run_spmd``/``shard_map``
(Mode A) the op lowers to the quantized ring reduce-scatter + encoded
all-gather pipeline (compress/spmd.py, int8-width transfers visible in
the lowered HLO and in profiler traces as ``mpi4torch.Allreduce.q8``
spans); under ``run_ranks`` (Mode B) the codec runs at the rendezvous
(compress/eager.py), so parity tests cover the same codec code path.

Modules: :mod:`.codecs` (registry + q8/bf16/bf16r/q8_ef),
:mod:`.spmd` (Mode A pipeline), :mod:`.eager` (Mode B rendezvous codec),
:mod:`.ef` (cross-step error-feedback state for training loops).
"""

from __future__ import annotations

from ..config import (compression_scope, default_compression,
                      set_default_compression)
from .codecs import (BF16Codec, BF16StochasticCodec, BlockQ8Codec, Codec,
                     ErrorFeedbackCodec, HopEFQ8Codec, available_codecs,
                     get_codec, register_codec)
from .ef import ef_allreduce, ef_init


def codec_rides_algorithm(codec, algorithm) -> bool:
    """THE codec/algorithm composition predicate: True when ``codec``
    may ride wire algorithm ``algorithm``.  Consulted dynamically on
    BOTH sides — the codec's own declaration (``Codec.algorithms``: the
    block-q8 family declares ring/bidir/torus, the bf16 family is
    ring-only) and the registry's (``AlgorithmSpec.codec_capable``:
    only the ring-shaped schedules can host a per-hop requantizing
    pipeline) — so registering a new codec or algorithm extends or
    restricts composition without touching this gate.  One shared rule
    for the facade reconcile (comm._reconcile_codec_algorithm), the
    tune selector, and the fused per-bucket picker."""
    if codec is None:
        return False
    from ..tune import codec_algorithms, get_algorithm

    if algorithm not in codec_algorithms(codec):
        return False
    return get_algorithm(algorithm).codec_capable


def codec_applicable(codec, dtype, algorithm=None) -> bool:
    """True when ``codec`` may legally touch a tensor of ``dtype`` (and,
    when ``algorithm`` is given, ride that wire algorithm).

    Quantizing integer/bool payloads (counts, masks, descriptors) would
    silently truncate rather than approximate, so only floating tensors
    are compressible.  This is THE dtype gate — the facade applies it
    per tensor (comm.py ``_codec_for``) and the fused bucketed
    collectives per dtype-homogeneous bucket (fuse/collectives.py), so
    the degrade/raise behavior cannot drift between the two paths.

    The ``algorithm`` leg is :func:`codec_rides_algorithm` — the
    codec's declared set × the registry's ``codec_capable`` gate,
    consulted dynamically: the tune selector respects it when
    auto-choosing an algorithm under an active compression scope (so
    ``auto`` can pick the compressed ``bidir`` past the bandwidth
    crossover), and the fused per-bucket picker uses it to keep each
    compressed bucket on an algorithm its codec declares while exact
    tail buckets take the latency algorithm."""
    import jax.numpy as jnp

    if codec is None or not jnp.issubdtype(jnp.dtype(dtype),
                                           jnp.floating):
        return False
    if algorithm is not None:
        return codec_rides_algorithm(codec, algorithm)
    return True


def int8_rotation_census(lowered: str, nranks: int):
    """Both-rotations census of a lowered q8 dual-ring program: returns
    ``(seen, fwd, bwd)`` where ``seen`` is the set of
    ``source_target_pairs`` tables appearing on int8-typed
    ``collective_permute`` ops in ``lowered`` and ``fwd``/``bwd`` are
    the forward/backward full-ring tables for ``nranks`` (all
    whitespace-normalized, so ``fwd in seen and bwd in seen`` is the
    tentpole's census criterion).  ONE matcher shared by the test census
    matrix (tests/test_tune.py) and the ``make quant-smoke`` lane
    (compress/__main__.py) — the StableHLO pattern cannot drift between
    CI and the smoke lane."""
    import re

    seen = set()
    for m in re.finditer(
            r'stablehlo\.collective_permute.*?'
            r'source_target_pairs\s*=\s*dense<(\[\[.*?\]\])>'
            r'.*?:\s*\(tensor<[^>]*i8>', lowered):
        seen.add(m.group(1).replace(" ", ""))
    fwd = str([[i, (i + 1) % nranks]
               for i in range(nranks)]).replace(" ", "")
    bwd = str([[i, (i - 1) % nranks]
               for i in range(nranks)]).replace(" ", "")
    return seen, fwd, bwd


__all__ = [
    "codec_applicable",
    "codec_rides_algorithm",
    "int8_rotation_census",
    "HopEFQ8Codec",
    "Codec",
    "BlockQ8Codec",
    "BF16Codec",
    "BF16StochasticCodec",
    "ErrorFeedbackCodec",
    "available_codecs",
    "get_codec",
    "register_codec",
    "compression_scope",
    "default_compression",
    "set_default_compression",
    "ef_init",
    "ef_allreduce",
]
