"""Tensor-parallel MLP training: Megatron column->row sharding.

The TP demo completing the strategy-example matrix (DP:
simple_linear_regression / resnet_cifar_dp, p2p: isend_recv_wait, CP/SP:
ring_attention_longcontext, PP: pipeline_training, stencil:
halo_exchange_stencil).  The reference ships TP only as primitives —
its axis-aware Gather/Allgather/Scatter are the column/row-parallel glue
(SURVEY.md §2.5 TP row) — and this framework packages the pattern:

* ``w1`` column-sharded, ``w2`` row-sharded (``shard_axis``);
* one ``Allreduce`` forward per MLP (``tp_mlp``), its adjoint the one
  backward collective;
* per-rank grads are exact shard grads, so a plain SGD step per rank
  trains the sharded model in lock-step with the single-device oracle
  (asserted each step at near machine precision).

Run:  python examples/tensor_parallel_mlp.py [nranks]
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
from mpi4torch_tpu.parallel import shard_axis, tp_mlp

comm = mpi.COMM_WORLD

D_IN, D_FF, B, N_STEPS, LR = 8, 32, 16, 15, 0.1


def make_problem(seed=0):
    rng = np.random.default_rng(seed)
    params = {
        "w1": jnp.asarray(rng.standard_normal((D_IN, D_FF)) / np.sqrt(D_IN)),
        "b1": jnp.zeros((D_FF,)),
        "w2": jnp.asarray(rng.standard_normal((D_FF, D_IN)) / np.sqrt(D_FF)),
        "b2": jnp.zeros((D_IN,)),
    }
    x = jnp.asarray(rng.standard_normal((B, D_IN)))
    y = jnp.asarray(np.tanh(rng.standard_normal((B, D_IN))))
    return params, x, y


def dense_loss(params, x, y):
    h = jax.nn.gelu(x @ params["w1"] + params["b1"])
    return jnp.mean((h @ params["w2"] + params["b2"] - y) ** 2)


def main():
    params, x, y = make_problem()

    # Single-device oracle trajectory.
    ref = params
    ref_losses = []
    for _ in range(N_STEPS):
        l, g = jax.value_and_grad(dense_loss)(ref, x, y)
        ref = jax.tree.map(lambda a, b: a - LR * b, ref, g)
        ref_losses.append(float(l))

    # Tensor-parallel run: every rank owns a feature shard of w1/b1/w2
    # and the replicated b2.
    local = {
        "w1": shard_axis(comm, params["w1"], 1),
        "b1": shard_axis(comm, params["b1"], 0),
        "w2": shard_axis(comm, params["w2"], 0),
        "b2": params["b2"],
    }

    def tp_loss(p):
        out = tp_mlp(comm, x, p["w1"], p["b1"], p["w2"], p["b2"])
        return jnp.mean((out - y) ** 2)

    losses = []
    n = comm.size
    for step in range(N_STEPS):
        l, g = jax.value_and_grad(tp_loss)(local)
        # Gradient semantics (the reference's "pure sums over ranks"
        # discipline, doc/examples.rst:46-65): every rank's backward
        # seeds 1, so the program differentiates n x loss.  Shard params
        # (w1/b1/w2) sit upstream of the row-parallel Allreduce, whose
        # adjoint sums the n identical cotangents -> their grads arrive
        # n x already; the replicated b2 sits after it, so each rank
        # holds only its replica's partial -> Allreduce completes the
        # sum.  One uniform LR/n then reproduces the single-device
        # trajectory exactly (asserted below every step).
        g = dict(g, b2=comm.Allreduce(g["b2"], mpi.MPI_SUM))
        local = jax.tree.map(lambda a, b: a - (LR / n) * b, local, g)
        losses.append(float(l))
        np.testing.assert_allclose(float(l), ref_losses[step],
                                   rtol=1e-10, atol=1e-12)

    # Final sharded params equal the oracle's corresponding shards —
    # every leaf: both feature shards, the sharded bias, and the
    # replicated bias.
    r = int(comm.rank)
    f_lo = r * (D_FF // n)
    sl = slice(f_lo, f_lo + D_FF // n)
    np.testing.assert_allclose(np.asarray(local["w1"]),
                               np.asarray(ref["w1"][:, sl]), rtol=1e-10)
    np.testing.assert_allclose(np.asarray(local["b1"]),
                               np.asarray(ref["b1"][sl]), rtol=1e-10)
    np.testing.assert_allclose(np.asarray(local["w2"]),
                               np.asarray(ref["w2"][sl, :]), rtol=1e-10)
    np.testing.assert_allclose(np.asarray(local["b2"]),
                               np.asarray(ref["b2"]), rtol=1e-10)
    if r == 0:
        print(f"rank 0: TP trajectory matches the single-device oracle; "
              f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    nranks = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    outs = mpi.run_ranks(main, nranks)
    assert all(o == outs[0] for o in outs)
    print(f"OK: {nranks} ranks, loss {outs[0][0]:.4f} -> {outs[0][-1]:.4f}")
