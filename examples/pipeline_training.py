"""Pipeline-parallel training: GPipe and 1F1B over the p2p substrate.

Stage ``r`` of a small MLP chain lives on rank ``r``; microbatch
activations travel rank-to-rank over the differentiable
``Isend/Irecv/Wait`` transport.  Two schedules, same gradients:

* ``pipeline_step`` (GPipe fill-drain) — AD-transparent: ONE
  ``jax.value_and_grad`` over the whole rank program; the reverse
  pipeline (cotangents hopping rank r+1 -> r) is generated by autodiff,
  the reference's signature capability (reference:
  doc/basic_usage.rst:194-457, csrc/extension.cpp:1048-1265).
* ``pipeline_step_1f1b`` (PipeDream-flush) — hand-scheduled
  per-microbatch ``jax.vjp`` pullbacks, at most ``size - rank``
  activation stashes live per stage (GPipe keeps all ``n_mb``).

The script asserts the two schedules produce identical losses and
gradients on step 1, then trains with 1F1B and checks the loss drops.

Run:  python examples/pipeline_training.py [nranks]
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
from mpi4torch_tpu.parallel import pipeline_step, pipeline_step_1f1b

comm = mpi.COMM_WORLD

D, B, N_MB, N_STEPS, LR = 8, 4, 6, 30, 0.05


def make_problem(size: int, seed=0):
    """Per-stage params + microbatches + regression targets (the last
    stage's loss pulls the chain's output toward a fixed random map)."""
    rng = np.random.default_rng(seed)
    stages = [{
        "w": jnp.asarray(rng.standard_normal((D, D)) / np.sqrt(D)),
        "b": jnp.zeros((D,)),
    } for _ in range(size)]
    mbs = [jnp.asarray(rng.standard_normal((B, D))) for _ in range(N_MB)]
    targets = [jnp.asarray(np.tanh(rng.standard_normal((B, D))))
               for _ in range(N_MB)]
    return stages, mbs, targets


def apply_stage(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def main():
    rank, size = int(comm.rank), comm.size
    stages, mbs, targets = make_problem(size)

    def loss_fn(y, i):
        # i may index Python lists here: both schedules call it with a
        # concrete int (the SPMD variant would hand it a traced index).
        return jnp.mean((y - targets[i]) ** 2)

    recv_like = jnp.zeros((B, D)) if rank > 0 else None
    p = stages[rank]

    # Step 1 under both schedules: bit-identical loss, same grads.
    l_g, g_g = pipeline_step(comm, apply_stage, p, mbs, loss_fn,
                             recv_like=recv_like)
    l_f, g_f = pipeline_step_1f1b(comm, apply_stage, p, mbs, loss_fn,
                                  recv_like=recv_like)
    np.testing.assert_allclose(float(l_g), float(l_f), rtol=1e-12)
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(g_g[k]), np.asarray(g_f[k]),
                                   rtol=1e-9, atol=1e-12)

    # Train with the memory-bounded schedule.
    losses = []
    for _ in range(N_STEPS):
        loss, g = pipeline_step_1f1b(comm, apply_stage, p, mbs, loss_fn,
                                     recv_like=recv_like)
        p = jax.tree.map(lambda a, b: a - LR * b, p, g)
        losses.append(float(loss))
    assert losses[-1] < 0.7 * losses[0], (losses[0], losses[-1])
    if rank == 0:
        print(f"rank 0: schedules agree; 1F1B training "
              f"{losses[0]:.4f} -> {losses[-1]:.4f} over {N_STEPS} steps")
    return losses


if __name__ == "__main__":
    nranks = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    outs = mpi.run_ranks(main, nranks)
    assert all(o == outs[0] for o in outs)
    print(f"OK: {nranks} ranks, loss {outs[0][0]:.4f} -> {outs[0][-1]:.4f}")
