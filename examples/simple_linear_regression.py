"""Data-parallel polynomial regression with L-BFGS.

The TPU-native port of the reference's canonical example (reference:
examples/simple_linear_regression.py): each rank holds a chunk of the data;
the loss function contains exactly two communication calls —

  1. ``Allreduce(params, MPI_SUM) / size`` — averages the (replicated)
     parameters so every rank's optimizer instance stays arithmetically
     identical; its adjoint divides by size again, making the total
     gradients pure sums and the run rank-count-invariant (the subtlety
     documented at reference doc/examples.rst:46-65).
  2. ``Allreduce(localloss, MPI_SUM)`` — the global loss.

Run:  python examples/simple_linear_regression.py [nranks]
(the thread-SPMD launcher replaces ``mpirun -np N``)
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
from mpi4torch_tpu.utils import LBFGS

comm = mpi.COMM_WORLD


def some_parametrized_function(inp, params):
    return (params[2] * inp + params[1]) * inp + params[0]


def main():
    rng = np.random.default_rng(42)

    num_points = 10000
    chunk_size = num_points // comm.size
    rest = num_points % comm.size
    if comm.rank < rest:
        chunk_size += 1
        offset = chunk_size * comm.rank
    else:
        offset = chunk_size * comm.rank + rest

    xinput = jnp.asarray(
        2.0 * rng.random(num_points)[offset:offset + chunk_size])

    gen_params = jnp.asarray([0.1, 1.0, -2.0])
    youtput = some_parametrized_function(xinput, gen_params)

    def lossfunction(params):
        # average initial params to bring all ranks on the same page
        params = comm.Allreduce(params, mpi.MPI_SUM) / comm.size

        # compute local loss
        localloss = jnp.sum(jnp.square(
            youtput - some_parametrized_function(xinput, params)))

        # sum up the loss among all ranks
        return comm.Allreduce(localloss, mpi.MPI_SUM)

    params = jnp.arange(3, dtype=jnp.float64)

    # L-BFGS needs only one outer step for so few parameters
    optimizer = LBFGS(max_iter=30)
    params, loss = optimizer.step(lossfunction, params)

    # only print output on rank 0
    if comm.rank == 0:
        print("Loss  : ", float(loss))
        print("Final parameters: ", np.asarray(params))
    return np.asarray(params), float(loss)


if __name__ == "__main__":
    nranks = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    results = mpi.run_ranks(main, nranks)
    params0, loss0 = results[0]
    assert all(np.array_equal(params0, p) for p, _ in results), \
        "ranks diverged"
    assert np.allclose(params0, [0.1, 1.0, -2.0], atol=1e-5), params0
    print(f"OK: {nranks} ranks converged identically to the generating "
          "parameters")
