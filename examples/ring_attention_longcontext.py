"""Long-context attention via ring (CP) and Ulysses (SP) parallelism.

The SURVEY.md §2.5 sequence-parallel demo: a sequence too long to attend
on one device is sharded across ranks; two strategies compute exact dense
attention over the full context from the reference's own primitive set:

* **ring** — K/V blocks circulate the differentiable Isend/Irecv ring
  (one ``collective_permute`` per hop under SPMD), merged by online
  softmax; per-rank memory is O(seq/ranks).  The per-block compute is the
  fused Pallas kernel on eligible TPU shapes.
* **ulysses** — two ``Alltoall`` calls reshuffle sequence<->head shards
  around fully-local per-head attention (the reference's
  ``Alltoall(gatheraxis != scatteraxis)`` is exactly this exchange,
  csrc/extension.cpp:917-987).

Both match the single-device oracle in values AND gradients — gradients
travel the reverse ring / inverse reshuffle.  Attention is causal, as in
a decoder.

Run:  python examples/ring_attention_longcontext.py [nranks] [seq_per_rank]
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
from mpi4torch_tpu.parallel import (
    dense_attention,
    ring_attention,
    ulysses_attention,
)

comm = mpi.COMM_WORLD

BATCH, HEADS, HEAD_DIM = 2, 4, 16


def make_qkv(seq_total, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal((BATCH, seq_total, HEADS, HEAD_DIM)))
        for _ in range(3))


def main(seq_per_rank: int = 16, attn: str = "ring"):
    """Each rank attends its sequence shard against the FULL context;
    returns (local output, local dq) for reassembly by the caller."""
    seq_total = comm.size * seq_per_rank
    q, k, v = make_qkv(seq_total)
    r = jnp.asarray(comm.rank)
    ql, kl, vl = (
        jax.lax.dynamic_slice_in_dim(t, r * seq_per_rank, seq_per_rank, 1)
        for t in (q, k, v))

    fn = ring_attention if attn == "ring" else ulysses_attention

    def f(ql):
        out = fn(comm, ql, kl, vl, causal=True)
        return jnp.sum(out ** 2), out

    (loss, out), dq = jax.value_and_grad(f, has_aux=True)(ql)
    return np.asarray(out), np.asarray(dq)


if __name__ == "__main__":
    nranks = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    seq_per_rank = int(sys.argv[2]) if len(sys.argv) > 2 else 16

    # Single-device oracle over the full context.
    q, k, v = make_qkv(nranks * seq_per_rank)
    ref_out = dense_attention(q, k, v, causal=True)
    ref_dq = jax.grad(
        lambda q: jnp.sum(dense_attention(q, k, v, causal=True) ** 2))(q)

    for attn in ("ring", "ulysses"):
        if attn == "ulysses" and HEADS % nranks != 0:
            print(f"skip ulysses: {HEADS} heads not divisible by {nranks}")
            continue
        results = mpi.run_ranks(lambda: main(seq_per_rank, attn), nranks)
        out = np.concatenate([o for o, _ in results], axis=1)
        dq = np.concatenate([g for _, g in results], axis=1)
        np.testing.assert_allclose(out, np.asarray(ref_out), rtol=1e-9,
                                   atol=1e-11)
        np.testing.assert_allclose(dq, np.asarray(ref_dq), rtol=1e-9,
                                   atol=1e-11)
        print(f"OK: {attn} attention on {nranks} ranks x {seq_per_rank} "
              f"tokens == dense oracle over {nranks * seq_per_rank} tokens "
              "(values + gradients)")
