"""Parallelism strategies built on the differentiable op surface.

The reference ships the *primitives* for every strategy but no strategy
engines (SURVEY.md §2.5): its docs demonstrate DP, its axis-aware
Gather/Scatter are the TP glue, its Isend/Irecv ring is the CP transport,
and its Alltoall is the Ulysses SP reshuffle.  This package provides those
strategies as first-class, AD-transparent library code — every distributed
movement goes through the ``MPI_Communicator`` op table, so each strategy
runs unchanged on the eager thread-SPMD runtime (concrete ranks, the
``mpirun`` analogue) and on the SPMD mesh backend (XLA collectives over
ICI/DCN).

    dp         — data parallelism (the reference's two-Allreduce recipe)
    ring       — differentiable ring shifts and halo exchange (Isend/Irecv)
    attention  — long-context attention: ring attention (CP) and Ulysses
                 all-to-all head/sequence attention (SP)
    tp         — tensor parallelism: column/row-parallel layers
    moe        — expert parallelism: capacity-based MoE over Alltoall
    pp         — pipeline parallelism: GPipe fill-drain over Isend/Irecv
"""

from . import attention, dp, moe, pp, ring, tp, zero

from .dp import all_average_tree, dp_value_and_grad, replicated_tree
from .ring import halo_exchange, ring_shift
from .attention import (dense_attention, ring_attention,
                        ulysses_attention, zigzag_positions, zigzag_slice,
                        zigzag_ring_attention)
from .tp import (
    column_parallel_linear,
    row_parallel_linear,
    shard_axis,
    tp_attention,
    tp_mlp,
)
from .moe import (Experts, balanced_assignment, held_experts_ffn,
                  init_experts, init_moe, moe_ffn, moe_ffn_dense,
                  rebalance_experts, route_topk, top1_route)
from .zero import (shard_global_norm, zero3_init, zero3_params,
                   zero3_shard_params, zero3_step, zero3_to_tp,
                   zero_init, zero_step)
from .pp import (pipeline_spmd, pipeline_step, pipeline_step_1f1b,
                 pipeline_step_interleaved,
                 recv_activation, schedule_1f1b, send_activation)

__all__ = [
    "pipeline_step_interleaved",
    "shard_global_norm",
    "zero_init",
    "zero_step",
    "zero3_init",
    "zero3_params",
    "zero3_shard_params",
    "zero3_step",
    "zero3_to_tp",
    "attention",
    "dp",
    "moe",
    "ring",
    "tp",
    "all_average_tree",
    "replicated_tree",
    "dp_value_and_grad",
    "halo_exchange",
    "ring_shift",
    "dense_attention",
    "ring_attention",
    "ulysses_attention",
    "zigzag_positions",
    "zigzag_slice",
    "zigzag_ring_attention",
    "column_parallel_linear",
    "row_parallel_linear",
    "shard_axis",
    "tp_attention",
    "tp_mlp",
    "Experts",
    "held_experts_ffn",
    "init_experts",
    "route_topk",
    "init_moe",
    "moe_ffn",
    "moe_ffn_dense",
    "balanced_assignment",
    "rebalance_experts",
    "top1_route",
    "pipeline_spmd",
    "pipeline_step",
    "pipeline_step_1f1b",
    "schedule_1f1b",
    "recv_activation",
    "send_activation",
]
