"""Variable-length token exchange: butterfly p2p + ragged Alltoall on the
compiled mesh backend.

Two capabilities the reference exposes through raw MPI that this example
exercises TPU-natively under ONE compiled SPMD program:

1. **Arbitrary static p2p permutations** (reference: any dest/source
   rank, csrc/extension.cpp:1071-1157): a butterfly exchange
   ``dest = rank ^ 1`` — the classic recursive-doubling building block —
   written with the same Isend/JoinDummies/Recv/Wait token discipline as
   the ring example, lowering to exactly one ``collective_permute``.
2. **Per-rank-varying segment sizes on the dense collectives**
   (reference: MPI_Alltoallv-style varying ``numelem``,
   csrc/extension.cpp:947-979): every rank holds a *different* number of
   valid tokens (static per-rank counts over a capacity-padded buffer)
   and redistributes them into equal-ish contiguous spans via
   ``Alltoall(..., numelem=new_counts, current_numelem=old_counts)`` —
   the load-balancing step of an expert-parallel dispatch.

Differentiability is asserted end to end: the loss pulls gradients back
through the redistribution AND the butterfly (padding slots provably get
zero gradient).

Run:  python examples/variable_token_exchange.py [nranks]
      (nranks must be even: the ``rank ^ 1`` butterfly pairs ranks)
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

comm = mpi.COMM_WORLD

D = 4  # token feature width


def balanced_counts(old):
    """Rebalance a lopsided partition into spans differing by <= 1."""
    total, n = sum(old), len(old)
    base, extra = divmod(total, n)
    return tuple(base + (1 if r < extra else 0) for r in range(n))


def exchange(x0, old_counts, new_counts, cap):
    """One compiled step: butterfly-mix each rank's valid tokens with its
    partner, then repartition the global token axis to ``new_counts``."""
    # Rank-stamped tokens: row i of rank r = (global token id, r, ...).
    offs = np.concatenate([[0], np.cumsum(old_counts)])
    gids = jnp.take(jnp.asarray(offs[:-1], jnp.float64),
                    jnp.asarray(comm.rank + 0)) + jnp.arange(cap)
    tokens = (gids[:, None] + jnp.zeros((cap, D))) * x0

    # 1. Butterfly: swap token blocks with partner rank ^ 1 (capacity-
    #    uniform on the wire; validity travels with the counts below).
    h = comm.Isend(tokens, comm.rank ^ 1, 0)
    mixed = comm.Recv(mpi.JoinDummies(jnp.empty_like(tokens), [h.dummy]),
                      comm.rank ^ 1, 0)
    mixed = mpi.JoinDummies(mixed, [comm.Wait(h)])
    # After the swap, rank r holds its PARTNER's tokens — and therefore
    # the partner's valid count.
    swapped = tuple(old_counts[r ^ 1] for r in range(len(old_counts)))

    # 2. Ragged repartition of the global token axis to the balanced
    #    spans (MPI_Alltoallv analogue; static count tuples, one program).
    spans = comm.Alltoall(mixed, 0, 0, new_counts,
                          current_numelem=swapped)
    return tokens, spans


def main():
    n = comm.size
    old = tuple(((3 * r + 1) % (n + 2)) + 1 for r in range(n))  # lopsided
    new = balanced_counts(old)
    cap = max(max(old), max(new))

    def fwd(x0):
        return exchange(x0, old, new, cap)

    tokens, spans = fwd(jnp.ones(()))

    # Gradient through butterfly + repartition: every VALID token in the
    # global axis contributes exactly once to sum(spans); padding never.
    g = jax.grad(lambda x0: fwd(x0)[1].sum())(jnp.ones(()))
    return tokens, spans, g


if __name__ == "__main__":
    nranks = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    if nranks % 2:
        sys.exit(f"nranks must be even (the rank ^ 1 butterfly pairs "
                 f"ranks); got {nranks}")
    tokens, spans, grads = mpi.run_spmd(main, nranks=nranks)()
    # Recompute the static metadata for the assertions.
    old = tuple(((3 * r + 1) % (nranks + 2)) + 1 for r in range(nranks))
    new = balanced_counts(old)
    offs = np.concatenate([[0], np.cumsum(new)])
    swapped_order = []   # global ids in post-butterfly axis order
    oo = np.concatenate([[0], np.cumsum(old)])
    for r in range(nranks):
        p = r ^ 1
        swapped_order.extend(range(oo[p], oo[p] + old[p]))
    for r in range(nranks):
        span = np.asarray(spans)[r, :new[r], 0]
        want = np.asarray(swapped_order[offs[r]:offs[r + 1]], float)
        np.testing.assert_array_equal(span, want)
        assert (np.asarray(spans)[r, new[r]:] == 0).all()
        # Per-rank gradient oracle: rank r's x0 feeds its own valid
        # tokens (ids oo[r]..oo[r]+old[r]-1), each reaching exactly one
        # valid span slot somewhere — so dL/dx0_r = D * sum(those ids),
        # delivered back through the adjoint repartition AND the reverse
        # butterfly.  Padding contributes exactly nothing.
        ids = range(oo[r], oo[r] + old[r])
        np.testing.assert_allclose(np.asarray(grads)[r], D * sum(ids))
    print(f"OK: {nranks} ranks, counts {old} -> {new}, "
          f"butterfly+ragged repartition verified; per-rank grads match "
          f"the token-id oracle")
