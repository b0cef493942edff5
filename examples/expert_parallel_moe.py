"""Expert-parallel MoE training over the differentiable Alltoall.

The EP demo completing the §2.5 strategy-example matrix: each rank owns
``n_experts/size`` experts and a shard of the tokens; ``moe_ffn``
dispatches tokens to their routed expert's rank over the differentiable
``Alltoall`` (the reference's per-rank-varying-count primitive is
exactly this token exchange, SURVEY.md §2.5 EP row), computes the local
experts, and combines the outputs back — with gradients riding the
reverse Alltoall.

The script trains a one-layer MoE regressor and checks, at every step,
that the distributed loss equals the single-device oracle
(``moe_ffn_dense``: identical routing/capacity semantics, all experts
local) on the full batch — token-for-token EP correctness while the
router itself is learning.

Run:  python examples/expert_parallel_moe.py [nranks]
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax
jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

import mpi4torch_tpu as mpi
from mpi4torch_tpu.parallel import init_moe, moe_ffn, moe_ffn_dense

comm = mpi.COMM_WORLD

D, D_FF, T_LOCAL, N_EXP_PER_RANK = 8, 16, 16, 2
CAPACITY, N_STEPS, LR, AUX = 24, 25, 0.05, 0.01


def make_problem(size: int, seed=0):
    n_experts = N_EXP_PER_RANK * size
    params = init_moe(jax.random.PRNGKey(seed), n_experts, D, D_FF,
                       dtype=jnp.float64)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((size * T_LOCAL, D)))
    y = jnp.asarray(np.tanh(rng.standard_normal((size * T_LOCAL, D))))
    return params, x, y


def main():
    rank, size = int(comm.rank), comm.size
    params, x, y = make_problem(size)
    lo = rank * T_LOCAL
    xs, ys = x[lo:lo + T_LOCAL], y[lo:lo + T_LOCAL]

    def dense_loss(p):
        # The EP capacity contract is PER SOURCE RANK (each rank's token
        # shard routes into its own C slots per expert — tests/
        # test_moe.py), so the oracle applies the dense layer to each
        # shard independently and averages the per-shard aux losses.
        total = 0.0
        aux_sum = 0.0
        for r in range(size):
            xr = x[r * T_LOCAL:(r + 1) * T_LOCAL]
            yr = y[r * T_LOCAL:(r + 1) * T_LOCAL]
            out, aux = moe_ffn_dense(xr, p, CAPACITY)
            total = total + jnp.sum((out + xr - yr) ** 2)
            aux_sum = aux_sum + aux
        return total / x.shape[0] + AUX * aux_sum / size

    def ep_loss(p):
        # Token shard in, replicated global loss out: residual sums and
        # the shard-local aux are both Allreduce'd, mirroring the oracle.
        out, aux = moe_ffn(comm, xs, p, CAPACITY)
        local = jnp.sum((out + xs - ys) ** 2)
        total = comm.Allreduce(local, mpi.MPI_SUM) / x.shape[0]
        aux_mean = comm.Allreduce(aux, mpi.MPI_SUM) / size
        return total + AUX * aux_mean

    losses = []
    for step in range(N_STEPS):
        ref_l, ref_g = jax.value_and_grad(dense_loss)(params)
        l, g = jax.value_and_grad(ep_loss)(params)
        np.testing.assert_allclose(float(l), float(ref_l), rtol=1e-10,
                                   atol=1e-12)
        # Sum-over-ranks semantics: every rank seeds 1, so the program
        # differentiates size x loss — and expert leaves are sharded
        # inside moe_ffn, so each rank's grad covers only ITS experts'
        # slice (the gate, used by every rank, arrives complete).  The
        # uniform identity (same as the driver dryrun's): summing raw
        # grads over ranks gives size x the oracle gradient for EVERY
        # leaf, so one Allreduce + /size recovers the exact dense
        # gradient, replicated.
        g = jax.tree.map(
            lambda a: comm.Allreduce(a, mpi.MPI_SUM) / size, g)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-8, atol=1e-10),
            g, ref_g)
        params = jax.tree.map(lambda a, b: a - LR * b, params, g)
        losses.append(float(l))
    assert losses[-1] < 0.9 * losses[0], (losses[0], losses[-1])
    if rank == 0:
        print(f"rank 0: EP == dense oracle each step; loss "
              f"{losses[0]:.4f} -> {losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    nranks = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    outs = mpi.run_ranks(main, nranks)
    assert all(o == outs[0] for o in outs)
    print(f"OK: {nranks} ranks, loss {outs[0][0]:.4f} -> {outs[0][-1]:.4f}")
