"""ZeRO-1 and ZeRO-3: data-parallel training with sharded state.

Plain DP replicates Adam's two moment tensors on every rank — 2x the
parameter bytes of pure redundancy.  ZeRO stage 1 shards them: each
rank's un-reduced local gradients are ``Reduce_scatter``'d (the native
``psum_scatter`` under SPMD — half an allreduce on the wire), each rank
updates only its 1/N parameter shard, and an ``Allgather``
re-replicates the parameters.  Per-step wire cost equals ONE gradient
allreduce (its two halves), while optimizer HBM drops by the rank
count — and because element-wise optimizers act per-parameter, the
final parameters are EXACTLY the plain replicated-DP result, verified
here against a single-process oracle on every rank and leaf.

Stage 3 additionally shards the PARAMETERS between steps: each rank
persists only a 1/N flat shard, the forward gathers on use, and the
gradient comes back sharded through the Allgather ADJOINT (its
reduce-scatter) — no explicit DP reduction anywhere in the program.
Same oracle, same exactness, parameter + optimizer HBM both 1/N.

Run:  python examples/zero_sharded_optimizer.py [nranks]
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
import optax

import mpi4torch_tpu as mpi
from mpi4torch_tpu.parallel import (zero3_init, zero3_params, zero3_step,
                                    zero_init, zero_step)

N, D, STEPS, LR = 64, 8, 30, 1e-1


def make_problem():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((N, D)))
    w_true = jnp.asarray(rng.standard_normal((D,)))
    y = x @ w_true + 0.05 * jnp.asarray(rng.standard_normal((N,)))
    return x, y


def local_loss(p, xl, yl):
    return jnp.sum((yl - xl @ p["w"] - p["b"]) ** 2)


def main(nranks: int = 4):
    if N % nranks != 0:
        raise SystemExit(
            f"nranks must divide the dataset size {N}, got {nranks}")
    x, y = make_problem()
    params0 = {"w": jnp.zeros((D,)), "b": jnp.zeros(())}
    opt = optax.adam(LR)
    shard = N // nranks

    # Single-process oracle: Adam on the rank-mean loss.
    ref_p, ref_s = params0, opt.init(params0)
    for _ in range(STEPS):
        g = jax.grad(lambda p: local_loss(p, x, y) / nranks)(ref_p)
        u, ref_s = opt.update(g, ref_s, ref_p)
        ref_p = jax.tree.map(jnp.add, ref_p, u)

    def body():
        comm = mpi.COMM_WORLD
        xl = x[comm.rank * shard:(comm.rank + 1) * shard]
        yl = y[comm.rank * shard:(comm.rank + 1) * shard]
        params = params0
        state = zero_init(comm, opt, params)   # 1/N of the Adam moments
        for _ in range(STEPS):
            g = jax.grad(lambda p: local_loss(p, xl, yl))(params)
            params, state = zero_step(comm, opt, params, g, state)
        return params

    outs = mpi.run_ranks(body, nranks)
    for r, got in enumerate(outs):
        # Every leaf, every rank — "b" is the scalar leaf that exercises
        # the shard zero-padding path (() padded to nranks slots).
        for k in ("w", "b"):
            assert np.allclose(np.asarray(got[k]), np.asarray(ref_p[k]),
                               rtol=1e-9), \
                f"rank {r} leaf {k} diverged from oracle"
    print(f"{nranks} ranks, Adam state sharded 1/{nranks}: final params "
          f"match the replicated-DP oracle on every rank")

    # ZeRO-3: the same training run with the parameters themselves
    # sharded between steps — note there is NO collective in this loop
    # body besides the gather inside zero3_step (the reduction is its
    # adjoint).
    def body3():
        comm = mpi.COMM_WORLD
        xl = x[comm.rank * shard:(comm.rank + 1) * shard]
        yl = y[comm.rank * shard:(comm.rank + 1) * shard]
        p_shards, state = zero3_init(comm, opt, params0)
        for _ in range(STEPS):
            _, p_shards, state = zero3_step(
                comm, opt, p_shards, params0,
                lambda p: local_loss(p, xl, yl), state)
        return zero3_params(comm, p_shards, params0)

    outs3 = mpi.run_ranks(body3, nranks)
    for r, got in enumerate(outs3):
        for k in ("w", "b"):
            assert np.allclose(np.asarray(got[k]), np.asarray(ref_p[k]),
                               rtol=1e-9), \
                f"zero3: rank {r} leaf {k} diverged from oracle"
    print(f"ZeRO-3: params sharded 1/{nranks} between steps — same "
          f"oracle-exact result")
    print(f"w = {np.asarray(outs[0]['w']).round(3)}")
    return outs[0], ref_p


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 4)
