"""Distributed 2D stencil PDE loss via differentiable halo exchange.

BASELINE.md parity config #5: a 5-point-Laplacian residual loss on a 2D
periodic grid, row-partitioned across ranks.  Each evaluation exchanges
one-row halos with both neighbors over the differentiable Isend/Irecv/Wait
ring (:func:`mpi4torch_tpu.parallel.halo_exchange` — under the SPMD mesh
backend each matched send/recv pair lowers to one ``collective_permute``
riding the ICI torus), applies the stencil locally, and Allreduces the
squared residual.  Gradient descent on the field then drives
``lap(u) = g``: boundary-row gradients physically travel the reverse ring
(reference: csrc/extension.cpp:1159-1218 — the backward of a p2p pipeline
is the mirror-image pipeline).

The run is rank-count invariant up to floating-point summation order: the
globally-reduced loss/line-search scalars make N ranks follow the
single-rank trajectory (tests/test_examples.py asserts the solved fields
agree to 1e-8; the Allreduce groups partial sums differently, so low bits
may differ).

Run:  python examples/halo_exchange_stencil.py [nranks] [steps]
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
from mpi4torch_tpu.parallel import halo_exchange
from mpi4torch_tpu.utils import LBFGS

comm = mpi.COMM_WORLD

GRID_N = 32  # global rows (divisible by any nranks used here)
GRID_M = 16  # columns


def source_term(n=GRID_N, m=GRID_M):
    """A smooth zero-mean RHS g with periodic structure."""
    i = jnp.arange(n)[:, None]
    j = jnp.arange(m)[None, :]
    return (jnp.sin(2 * jnp.pi * i / n) * jnp.cos(2 * jnp.pi * j / m)
            + 0.5 * jnp.sin(4 * jnp.pi * (i / n + j / m)))


def local_laplacian(u_local):
    """5-point periodic Laplacian of this rank's row block; the row
    neighbors come from the halo exchange, the column neighbors from a
    local roll."""
    padded = halo_exchange(comm, u_local, halo=1, axis=0)
    up, center, down = padded[:-2], padded[1:-1], padded[2:]
    left = jnp.roll(u_local, 1, axis=1)
    right = jnp.roll(u_local, -1, axis=1)
    return up + down + left + right - 4.0 * center


def residual_loss(u_local, g_local):
    res = local_laplacian(u_local) - g_local
    return comm.Allreduce(jnp.sum(res * res), mpi.MPI_SUM)


def main(steps: int = 80):
    """Solve ``lap(u) = g`` by L-BFGS on the distributed residual loss
    (the reference example's optimizer loop, scaled from 3 parameters to a
    whole field — examples/simple_linear_regression.py:42-53)."""
    if GRID_N % comm.size != 0:
        raise ValueError(
            f"GRID_N={GRID_N} rows must divide evenly over {comm.size} "
            "ranks (an uneven split would silently solve a truncated grid)")
    rows = GRID_N // comm.size
    start = jnp.asarray(comm.rank) * rows
    g_local = jax.lax.dynamic_slice_in_dim(source_term(), start, rows, 0)
    u = jnp.zeros((rows, GRID_M), jnp.float64)

    loss0 = float(residual_loss(u, g_local))
    # comm: u is domain-decomposed (each rank owns its row block), so the
    # line-search scalars must be global reductions to stay in lock-step.
    opt = LBFGS(max_iter=steps, comm=comm)
    u, loss = opt.step(lambda v: residual_loss(v, g_local), u)
    losses = [loss0, float(loss)]

    if comm.rank == 0:
        print(f"residual^2: {losses[0]:.6f} -> {losses[-1]:.3e} "
              f"(<= {steps} L-BFGS iters on {comm.size} rank(s))")
    return losses, np.asarray(u)


if __name__ == "__main__":
    nranks = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 80
    results = mpi.run_ranks(lambda: main(steps), nranks)
    losses0 = results[0][0]
    full = np.concatenate([u for _, u in results], axis=0)
    assert losses0[-1] < 1e-2 * losses0[0], losses0[-1]
    # The solution of lap(u)=g is unique only up to a constant on a
    # periodic domain; the zero-init gradient flow keeps the mean at 0.
    assert abs(full.mean()) < 1e-8
    print(f"OK: {nranks}-rank stencil converged, grid reassembled "
          f"{full.shape}, mean {full.mean():.2e}")
