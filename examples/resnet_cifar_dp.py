"""Data-parallel ResNet-18 on CIFAR-10-shaped data (BASELINE.md config #4).

The classic DDP recipe over mpi4torch_tpu's differentiable Allreduce: each
rank computes a local backward on its batch shard, then every parameter
gradient is averaged with one ``Allreduce(g, MPI_SUM) / size`` — the
per-param-grad pattern the reference enables but leaves to the user
(reference: README.md:34-46).  The whole step (forward, backward, N
gradient Allreduces, SGD update) is ONE jitted XLA program per rank; under
the SPMD mesh backend the Allreduces lower to ``psum`` over ICI.

Data is synthetic CIFAR-10-shaped (32x32x3, 10 classes) so the example runs
hermetically; swap ``make_synthetic_cifar`` for real numpy CIFAR batches and
nothing else changes.

Run:  python examples/resnet_cifar_dp.py [nranks] [steps]
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

import jax.numpy as jnp
import numpy as np

import mpi4torch_tpu as mpi
from mpi4torch_tpu.models import resnet as R

comm = mpi.COMM_WORLD

CFG = R.ResNetConfig(num_classes=10)
BATCH_PER_RANK = 8
IMAGE_HW = 32


def make_synthetic_cifar(seed, n, hw, num_classes):
    rng = np.random.default_rng(seed)
    images = rng.standard_normal((n, hw, hw, 3)).astype(np.float32)
    labels = rng.integers(0, num_classes, n).astype(np.int32)
    return jnp.asarray(images), jnp.asarray(labels)


def main(steps: int = 3, cfg: R.ResNetConfig = CFG, hw: int = IMAGE_HW,
         batch_per_rank: int = BATCH_PER_RANK):
    params, state = R.init_resnet(jax.random.PRNGKey(0), cfg)

    # Every rank holds the full (here: synthetic) dataset and derives
    # its shard through the input pipeline: one seeded epoch
    # permutation shared by construction (no coordination collective),
    # static per-step shapes, and the next shard's host->device copy
    # prefetched behind the current step's compute.
    from mpi4torch_tpu.utils import prefetch_to_device, shard_batches_comm

    images, labels = make_synthetic_cifar(
        7, comm.size * batch_per_rank, hw, cfg.num_classes)
    data = (np.asarray(images), np.asarray(labels))

    def epochs():
        # One global batch per epoch: each epoch re-visits the same
        # example set under a fresh (seed, epoch) permutation, so the
        # global loss descends like plain repeated-batch GD while the
        # pipeline's reshuffle + rank partition are genuinely exercised.
        for epoch in range(steps):
            yield from shard_batches_comm(data, batch_per_rank, comm,
                                          seed=7, epoch=epoch)

    losses = []
    for batch in prefetch_to_device(epochs()):
        loss, params, state = R.dp_grad_train_step(
            comm, cfg, params, state, batch, lr=0.05)
        losses.append(float(loss))

    if comm.rank == 0:
        for i, l in enumerate(losses):
            print(f"step {i}: global loss {l:.4f}")
    head_w = np.asarray(params["head"]["w"])
    return losses, head_w


if __name__ == "__main__":
    nranks = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    results = mpi.run_ranks(lambda: main(steps), nranks)
    losses0, head0 = results[0]
    assert all(np.array_equal(head0, h) for _, h in results), "ranks diverged"
    assert losses0[-1] < losses0[0], losses0
    print(f"OK: {nranks}-rank DP ResNet-18 stayed in lock-step and the loss "
          f"fell {losses0[0]:.4f} -> {losses0[-1]:.4f}")
