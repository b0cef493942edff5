"""Differentiable communication ops.

Two backends implement the same op table (SURVEY.md §2.2):

* :mod:`mpi4torch_tpu.ops.eager` — thread-SPMD eager execution with concrete
  per-rank shapes/ranks (the ``mpirun`` parity harness, Mode B).
* :mod:`mpi4torch_tpu.ops.spmd` — single-trace SPMD over a named mesh axis,
  lowering to XLA collectives over ICI/DCN (the TPU performance path, Mode A).

:mod:`mpi4torch_tpu.ops.flash` provides the fused (Pallas) block-attention
kernel that :func:`mpi4torch_tpu.parallel.ring_attention` composes over the
ring, with a jnp fallback for ineligible shapes/platforms.
:mod:`mpi4torch_tpu.ops.kda` is the gated delta rule of Kimi Delta
Attention, chunked (what the model runs) and token by token (its oracle).
"""

from .flash import flash_attention, flash_block_attention, merge_partials
from .kda import kda_chunked, kda_recurrent
from .ragged import (block_gather, block_scatter, ragged_allgather,
                     ragged_alltoall, ragged_gather, ragged_scatter,
                     segment_mask)

__all__ = [
    "flash_attention",
    "flash_block_attention",
    "merge_partials",
    "kda_chunked",
    "kda_recurrent",
    "block_gather",
    "block_scatter",
    "ragged_allgather",
    "ragged_alltoall",
    "ragged_gather",
    "ragged_scatter",
    "segment_mask",
]
