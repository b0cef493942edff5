"""ViT with DP training plus patch-parallel inference.

Two phases over the communicator surface:

1. **DP training** — each rank trains the ViT on its batch shard with
   the classic per-gradient `Allreduce(g, MPI_SUM)/size` recipe
   (`models.vit.dp_grad_train_step`), through the deterministic input
   pipeline (`utils.shard_batches_comm` + `prefetch_to_device`).
2. **Patch-parallel inference** — the trained model classifies a batch
   with its PATCH axis sharded over the same ranks: each block's
   attention runs as NON-causal ring attention (every query attends
   every key through circulating KV shards — context parallelism
   without a causal cut), and the result must match the single-process
   forward exactly.

Run:  python examples/vit_patch_parallel.py [nranks] [steps]
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

import jax.numpy as jnp
import numpy as np

import mpi4torch_tpu as mpi
from mpi4torch_tpu import COMM_WORLD as comm
from mpi4torch_tpu.models import vit as V
from mpi4torch_tpu.utils import prefetch_to_device, shard_batches_comm

CFG = V.ViTConfig(image_hw=16, patch=4, d_model=32, n_heads=4,
                  n_layers=2, d_ff=64, num_classes=10)


def synthetic_images(seed, n, cfg):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(
        (n, cfg.image_hw, cfg.image_hw, cfg.channels)).astype(np.float32)
    y = rng.integers(0, cfg.num_classes, n).astype(np.int32)
    return x, y


def main(steps: int = 3, cfg: V.ViTConfig = CFG, batch_per_rank: int = 2):
    params = V.init_vit(jax.random.PRNGKey(0), cfg)
    data = synthetic_images(7, comm.size * batch_per_rank, cfg)

    def epochs():
        for epoch in range(steps):
            yield from shard_batches_comm(data, batch_per_rank, comm,
                                          seed=7, epoch=epoch)

    losses = []
    for batch in prefetch_to_device(epochs()):
        loss, params = V.dp_grad_train_step(comm, cfg, params, batch,
                                            lr=0.05)
        losses.append(float(loss))

    # Phase 2: classify with the patch axis sharded over the ranks.
    if cfg.n_patches % comm.size != 0:
        raise ValueError(
            f"patch parallelism needs the {cfg.n_patches} patches to "
            f"split evenly over {comm.size} ranks — run with a divisor "
            "rank count (ring attention's equal-shard layout)")
    images = jnp.asarray(data[0][:batch_per_rank])
    patches = V.patchify(cfg, images)
    sl = cfg.n_patches // comm.size
    local = patches[:, comm.rank * sl:(comm.rank + 1) * sl]
    sharded_logits = V.forward_patches(cfg, params, local, comm_sp=comm)
    single_logits = V.forward(cfg, params, images)

    if comm.rank == 0:
        for i, l in enumerate(losses):
            print(f"step {i}: global loss {l:.4f}")
    return (losses, np.asarray(params["head"]),
            np.asarray(sharded_logits), np.asarray(single_logits))


if __name__ == "__main__":
    nranks = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    results = mpi.run_ranks(lambda: main(steps), nranks)
    losses0, head0, shard0, single0 = results[0]
    assert all(np.array_equal(head0, h) for _, h, _, _ in results), \
        "ranks diverged"
    assert losses0[-1] < losses0[0], losses0
    for _, _, sh, si in results:
        np.testing.assert_allclose(sh, si, rtol=1e-5, atol=1e-6)
    print(f"OK: {nranks}-rank DP ViT trained in lock-step "
          f"({losses0[0]:.3f} -> {losses0[-1]:.3f}) and patch-parallel "
          f"ring-attention inference matched the single-process forward")
