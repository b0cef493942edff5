"""Train a tiny character LM data-parallel, then decode with a KV cache.

End-to-end inference demo for the flagship transformer: the model is
trained for a few steps with the reference's two-Allreduce DP recipe
(Allreduce parameter averaging + Allreduce'd loss — the adjoint keeps
every rank's optimizer in lock-step, reference doc/examples.rst:24-65)
on a memorizable token pattern, then text is generated two ways:

* ``models.transformer.generate`` — batched one-pass prefill + a single
  compiled ``lax.scan`` of KV-cache ``decode_step`` calls (the serving
  path: under GQA the cache holds only ``n_kv_heads`` heads, and
  ``attn_window`` bounds each step's attention reach);
* a repeated-full-forward greedy loop (the oracle).

Both must emit identical tokens — the same teacher-forcing-equivalence
property tests/test_transformer.py::TestDecoding asserts.

Run:  python examples/generate_kv_cache.py [nranks]
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax
import jax.numpy as jnp
import numpy as np

import mpi4torch_tpu as mpi
from mpi4torch_tpu.models import transformer as T
from mpi4torch_tpu.parallel import all_average_tree

CFG = T.TransformerConfig(vocab=16, d_model=32, n_heads=4, n_layers=2,
                          d_ff=64, max_seq=32, n_kv_heads=2, attn_window=8)
STEPS, BATCH, LR = 150, 8, 3e-2


def make_data(key):
    # A deterministic repeating pattern: next token = (tok + 1) % 8 — easy
    # to memorize, and verifiably learned when generation continues it.
    start = jax.random.randint(key, (BATCH, 1), 0, 8)
    ramp = jnp.arange(CFG.max_seq, dtype=jnp.int32)[None, :]
    return ((start + ramp) % 8).astype(jnp.int32)


def train(nranks: int):
    """DP training: each rank holds a batch shard; the two-Allreduce
    recipe keeps per-rank SGD trajectories bit-identical."""
    tokens = make_data(jax.random.PRNGKey(1))
    params0 = T.init_transformer(jax.random.PRNGKey(0), CFG,
                                 dtype=jnp.float64)
    shard = BATCH // nranks

    def body():
        comm = mpi.COMM_WORLD
        local = tokens[comm.rank * shard:(comm.rank + 1) * shard]
        params = params0

        def loss_fn(p):
            p = all_average_tree(comm, p) if comm.size > 1 else p
            loss = T.lm_loss(CFG, p, local)
            return comm.Allreduce(loss, mpi.MPI_SUM) / comm.size \
                if comm.size > 1 else loss

        for _ in range(STEPS):
            loss, grads = jax.value_and_grad(loss_fn)(params)
            params = jax.tree.map(lambda p, g: p - LR * g, params, grads)
        return float(loss), params

    results = mpi.run_ranks(body, nranks)
    loss, params = results[0]
    for other_loss, other in results[1:]:
        assert other_loss == loss, "DP ranks diverged"
    return loss, params


def main(nranks: int = 4):
    loss, params = train(nranks)
    print(f"trained {STEPS} steps on {nranks} ranks: loss {loss:.4f}")

    prompt = jnp.asarray([[3, 4, 5, 6]], jnp.int32)
    out = T.generate(CFG, params, prompt, n_new=12, dtype=jnp.float64)

    # Oracle: repeated full forwards.
    seq = prompt
    for _ in range(12):
        nxt = jnp.argmax(T.forward(CFG, params, seq)[:, -1], axis=-1)
        seq = jnp.concatenate([seq, nxt[:, None].astype(seq.dtype)], axis=1)
    assert (np.asarray(out) == np.asarray(seq)).all(), \
        "KV-cache decode diverged from the full-forward oracle"

    gen = np.asarray(out[0, 4:])
    want = (np.asarray(prompt[0, -1]) + 1 + np.arange(12)) % 8
    learned = (gen == want).mean()
    print(f"prompt {np.asarray(prompt[0])} -> generated {gen}")
    print(f"pattern continuation accuracy: {learned:.0%}")
    return gen, want


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 4)
