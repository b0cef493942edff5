"""Crash-safe data-parallel training with checkpoint/resume.

Composes the framework's two persistence layers on the DP recipe of the
canonical regression example (reference: examples/
simple_linear_regression.py — the reference itself has no training-state
checkpointing, SURVEY.md §5):

* ``utils.CheckpointManager`` — step-numbered orbax checkpoints of the
  full train state (params + SGD momentum + step), atomic on disk;
* resume: a fresh process discovers ``latest_step()`` and continues; the
  resumed run is bit-identical to an uninterrupted one (asserted below).

Run:  python examples/checkpoint_resume.py [nranks] [workdir]
"""

import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax
jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

import mpi4torch_tpu as mpi
from mpi4torch_tpu.utils import CheckpointManager

comm = mpi.COMM_WORLD

N_STEPS = 8
CRASH_AFTER = 3          # simulated preemption point
LR, MOMENTUM = 0.002, 0.9


def make_data(rank: int, size: int):
    xs = jnp.linspace(0.0, 1.0, 64 * size)
    ys = 3.0 * xs + 0.5
    lo = rank * 64
    return xs[lo:lo + 64], ys[lo:lo + 64]


def loss_fn(params, x, y):
    params = comm.Allreduce(params, mpi.MPI_SUM) / comm.size
    pred = params[0] * x + params[1]
    local = jnp.sum((pred - y) ** 2)
    return comm.Allreduce(local, mpi.MPI_SUM)


def train_step(state, x, y):
    loss, g = jax.value_and_grad(loss_fn)(state["params"], x, y)
    vel = MOMENTUM * state["vel"] + g
    return {"params": state["params"] - LR * vel, "vel": vel,
            "step": state["step"] + 1}, loss


def init_state():
    return {"params": jnp.zeros(2), "vel": jnp.zeros(2),
            "step": jnp.asarray(0, jnp.int32)}


def run(workdir: str, stop_after=None):
    """Train, checkpointing every step; resume from the latest step if
    checkpoints exist.  Only rank 0 touches disk (the eager world is
    threads in ONE process; a multi-process launch would checkpoint
    collectively instead)."""
    rank = int(comm.rank)
    x, y = make_data(rank, comm.size)
    state = init_state()
    mgr = CheckpointManager(workdir, max_to_keep=2) if rank == 0 else None
    start = 0
    if rank == 0 and mgr.latest_step() is not None:
        start = int(mgr.latest_step()) + 1
        state = mgr.restore(mgr.latest_step(), template=state)
    # Every rank resumes from the same state: broadcast rank 0's restore.
    state = jax.tree.map(lambda a: comm.Bcast_(a, 0), state)
    start = int(comm.Bcast_(jnp.asarray(start), 0))

    losses = []
    for step in range(start, N_STEPS):
        state, loss = train_step(state, x, y)
        losses.append(float(loss))
        if rank == 0:
            mgr.save(step, state)
        if stop_after is not None and step + 1 - start >= stop_after:
            break
    if rank == 0:
        mgr.wait_until_finished()
        mgr.close()
    return state, losses


def main(workdir=None):
    rank = int(comm.rank)
    if workdir is None and len(sys.argv) > 2:
        workdir = sys.argv[2]
    cleanup = False
    if workdir is None and rank == 0:
        # One scratch dir per invocation, chosen once on rank 0 — rank 0
        # is the only rank that touches disk (see run()), so the other
        # rank threads can keep workdir=None.  Cleaned up below.
        workdir = tempfile.mkdtemp(prefix="mpi4torch_tpu_ckpt_")
        cleanup = True

    # Uninterrupted reference run (separate directory).
    ref_state, ref_losses = run(f"{workdir}_ref" if workdir else None)

    # "Preempted" run: train CRASH_AFTER steps, drop everything, resume.
    run(workdir, stop_after=CRASH_AFTER)
    state, tail = run(workdir)

    np.testing.assert_array_equal(np.asarray(state["params"]),
                                  np.asarray(ref_state["params"]))
    assert int(state["step"]) == N_STEPS
    if rank == 0:
        # tail is empty when the workdir already held a completed run
        # (the example re-invoked on a persistent directory).
        last = (f"final loss {tail[-1]:.6f}" if tail
                else "checkpointed run already complete")
        print(f"rank 0: resumed run matches uninterrupted run "
              f"bit-for-bit at step {N_STEPS}; {last}")
        if cleanup:
            import shutil

            shutil.rmtree(workdir, ignore_errors=True)
            shutil.rmtree(f"{workdir}_ref", ignore_errors=True)
    return np.asarray(state["params"])


if __name__ == "__main__":
    nranks = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    outs = mpi.run_ranks(main, nranks)
    assert all(np.array_equal(outs[0], o) for o in outs)
    print(f"OK: {nranks} ranks, params {outs[0]}")
