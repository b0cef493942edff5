"""SPMD mesh-backend tests (Mode A): the reference oracles re-expressed over
an 8-virtual-device CPU mesh — the analogue of the reference CI's
oversubscribed `mpirun` (SURVEY.md §4), but single-trace SPMD with XLA
collectives.  Includes the cross-backend equivalence checks that play the
role of the reference's TorchScript-parity tests
(tests/test_collectives.py:14-21): the same program must give identical
results eagerly (thread-SPMD) and traced (mesh SPMD)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4torch_tpu as mpi
from mpi4torch_tpu import COMM_WORLD as comm

NR = 8


def run(fn, **kw):
    return mpi.run_spmd(fn, nranks=NR, **kw)


class TestAllreduceSpmd:
    def test_forward_and_grad(self):
        def fn(x):
            return comm.Allreduce(x * (comm.rank + 1), mpi.MPI_SUM)

        out = run(fn)(jnp.ones(4))
        assert out.shape == (NR, 4)
        expect = NR * (NR + 1) / 2
        assert (np.asarray(out) == expect).all()
        g = jax.grad(lambda x: run(fn)(x).sum())(jnp.ones(4))
        assert (np.asarray(g) == NR * expect).all()

    def test_jit_compatible(self):
        # The traced path *is* the compiled path — the analogue of the
        # reference's TorchScript test (tests/test_collectives.py:14-21).
        fn = run(lambda x: comm.Allreduce(x, mpi.MPI_SUM), jit=True)
        out1 = fn(jnp.ones(3))
        out2 = fn(jnp.ones(3) * 2)
        assert (np.asarray(out1) == NR).all()
        assert (np.asarray(out2) == 2 * NR).all()

    def test_max_forward_ok_backward_raises(self):
        def fn(x):
            return comm.Allreduce(x * (comm.rank + 1), mpi.MPI_MAX)

        out = run(fn)(jnp.ones(3))
        assert (np.asarray(out) == NR).all()
        with pytest.raises(RuntimeError, match="MPI_MAX"):
            jax.grad(lambda x: run(fn)(x).sum())(jnp.ones(3))

    def test_prod_and_bitwise_forward(self):
        out = run(lambda x: comm.Allreduce(x * 2, mpi.MPI_PROD))(jnp.ones(2))
        assert (np.asarray(out) == 2.0 ** NR).all()

        def bor(x):
            t = (x * 0 + (comm.rank + 0)).astype(jnp.int32)
            return comm.Allreduce(1 << t, mpi.MPI_BOR)

        out = run(bor)(jnp.zeros(2))
        assert (np.asarray(out) == (1 << NR) - 1).all()

    def test_deterministic_mode_matches_eager_oracle(self):
        # BASELINE.md north star: gradients bit-exact vs. the MPI-linear-
        # order reference.  The eager runtime reduces in ascending rank
        # order; deterministic SPMD mode must match it bit for bit.
        rng = np.random.default_rng(3)
        data = jnp.asarray(rng.standard_normal((NR, 513)).astype(np.float32))

        def spmd_fn(x):
            t = jax.lax.dynamic_index_in_dim(x, jnp.asarray(comm.rank + 0),
                                             0, keepdims=False)
            return comm.Allreduce(t, mpi.MPI_SUM)

        with mpi.config.deterministic_mode(True):
            det = np.asarray(run(spmd_fn)(data))

        def eager_body(rank):
            return np.asarray(comm.Allreduce(data[rank], mpi.MPI_SUM))

        eager = mpi.run_ranks(eager_body, NR)
        for r in range(NR):
            np.testing.assert_array_equal(det[r], eager[r])

    def test_ring_fold_bit_identical_to_gather_fold(self, monkeypatch):
        # The O(1)-memory chunked ring fold (VERDICT r4 item 3) must
        # produce the very bits of the all-gather+fold and of the eager
        # MPI-linear-order oracle.  Force the ring path at test size and
        # a tiny chunk so the pipeline runs multi-chunk WITH padding
        # (513 f32 elems / 16-elem chunks = 33 chunks, last one padded).
        from mpi4torch_tpu.ops import spmd as spmd_mod
        rng = np.random.default_rng(7)
        data = jnp.asarray(rng.standard_normal((NR, 513)).astype(np.float32))

        def spmd_fn(x):
            t = jax.lax.dynamic_index_in_dim(x, jnp.asarray(comm.rank + 0),
                                             0, keepdims=False)
            return comm.Allreduce(t, mpi.MPI_SUM)

        with mpi.config.deterministic_mode(True):
            gather_path = np.asarray(run(spmd_fn)(data))
            monkeypatch.setattr(mpi.config,
                                "_ordered_fold_gather_max_bytes", 0)
            monkeypatch.setattr(mpi.config, "_ordered_ring_chunk_bytes", 64)
            ring_path = np.asarray(run(spmd_fn)(data))

        np.testing.assert_array_equal(ring_path, gather_path)

        def eager_body(rank):
            return np.asarray(comm.Allreduce(data[rank], mpi.MPI_SUM))

        eager = mpi.run_ranks(eager_body, NR)
        for r in range(NR):
            np.testing.assert_array_equal(ring_path[r], eager[r])

    def test_ring_fold_single_chunk_and_exact_multiple(self, monkeypatch):
        # Degenerate pipeline shapes: one chunk (no pipelining) and an
        # exact chunk multiple (no padding).
        from mpi4torch_tpu.ops import spmd as spmd_mod
        rng = np.random.default_rng(11)
        data = jnp.asarray(rng.standard_normal((NR, 64)).astype(np.float32))

        def spmd_fn(x):
            t = jax.lax.dynamic_index_in_dim(x, jnp.asarray(comm.rank + 0),
                                             0, keepdims=False)
            return comm.Allreduce(t, mpi.MPI_SUM)

        with mpi.config.deterministic_mode(True):
            want = np.asarray(run(spmd_fn)(data))
            monkeypatch.setattr(mpi.config,
                                "_ordered_fold_gather_max_bytes", 0)
            for chunk_bytes in (64 * 4, 16 * 4):   # 1 chunk; 4 exact chunks
                monkeypatch.setattr(mpi.config, "_ordered_ring_chunk_bytes",
                                    chunk_bytes)
                got = np.asarray(run(spmd_fn)(data))
                np.testing.assert_array_equal(got, want)

    @pytest.mark.slow  # heavyweight compile/run; TPU-manual lane (tier-1 budget)
    def test_ring_fold_reduce_scatter_matches(self, monkeypatch):
        # reduce_scatter's large-payload deterministic path is the
        # relay-routed ring fold (segment s delivered straight to rank s);
        # must equal the slice-before-fold bits.  Shapes cover: exact
        # chunk multiple, padded last chunk, single-chunk segments, and a
        # non-leading scatter axis (moveaxis round-trip).
        from mpi4torch_tpu.ops import spmd as spmd_mod
        rng = np.random.default_rng(13)
        cases = [
            ((NR * 8,), 0, 32),       # 4 exact chunks per segment
            ((NR * 9,), 0, 32),       # padded last chunk (9 f32 per seg)
            ((NR * 8,), 0, 8 * 4),    # one chunk per segment
            ((3, NR * 4, 2), 1, 32),  # non-leading axis, rest dims
        ]
        for shape, axis, chunk_bytes in cases:
            data = jnp.asarray(
                rng.standard_normal((NR,) + shape).astype(np.float32))

            def spmd_fn(x):
                t = jax.lax.dynamic_index_in_dim(
                    x, jnp.asarray(comm.rank + 0), 0, keepdims=False)
                return comm.Reduce_scatter(t, mpi.MPI_SUM, axis)

            with mpi.config.deterministic_mode(True):
                want = np.asarray(run(spmd_fn)(data))
                monkeypatch.setattr(
                    mpi.config, "_ordered_fold_gather_max_bytes", 0)
                monkeypatch.setattr(
                    mpi.config, "_ordered_ring_chunk_bytes", chunk_bytes)
                got = np.asarray(run(spmd_fn)(data))
                monkeypatch.setattr(
                    mpi.config, "_ordered_fold_gather_max_bytes",
                    4 * 1024 * 1024)
            np.testing.assert_array_equal(got, want, err_msg=str(
                (shape, axis, chunk_bytes)))


class TestReduceScatterSpmd:
    def test_forward_and_identity(self):
        def fn(x):
            rs = comm.Reduce_scatter(x * (comm.rank + 1), mpi.MPI_SUM, 0)
            ag = comm.Allgather(rs, 0)
            ar = comm.Allreduce(x * (comm.rank + 1), mpi.MPI_SUM)
            return rs, ag - ar

        rs, diff = run(fn)(jnp.ones((NR * 2,)))
        assert rs.shape == (NR, 2)
        assert (np.asarray(rs) == NR * (NR + 1) / 2).all()
        assert (np.asarray(diff) == 0).all()

    def test_grad_is_allgather(self):
        # Per-rank loss weights its shard by rank+1; summing the
        # per-rank backward seeds gives the concatenated weights.
        def fn(x):
            rs = comm.Reduce_scatter(x, mpi.MPI_SUM, 0)
            w = jnp.asarray(comm.rank + 1, rs.dtype)
            return jnp.sum(w * rs)

        g = jax.grad(lambda x: run(fn)(x).sum())(jnp.ones((NR * 2,)))
        want = np.repeat(np.arange(1, NR + 1, dtype=float), 2) * NR
        np.testing.assert_array_equal(np.asarray(g), want)

    def test_non_sum_forward_ok_backward_raises(self):
        def fn(x):
            return comm.Reduce_scatter(x * (comm.rank + 1), mpi.MPI_MAX, 0)

        out = run(fn)(jnp.ones((NR,)))
        assert (np.asarray(out) == NR).all()
        with pytest.raises(RuntimeError, match="MPI_MAX"):
            jax.grad(lambda x: run(fn)(x).sum())(jnp.ones((NR,)))

    def test_deterministic_mode_matches_eager_order(self):
        # Under deterministic reductions the lowering is ordered-fold +
        # slice; values must still satisfy the allreduce identity.
        def fn(x):
            rs = comm.Reduce_scatter(x * (comm.rank + 1), mpi.MPI_SUM, 0)
            ar = comm.Allreduce(x * (comm.rank + 1), mpi.MPI_SUM)
            return comm.Allgather(rs, 0) - ar

        with mpi.config.deterministic_mode(True):
            diff = run(fn)(jnp.ones((NR * 2,)))
        assert (np.asarray(diff) == 0).all()

    def test_indivisible_axis_raises(self):
        with pytest.raises(mpi.CommError, match="divisible"):
            run(lambda x: comm.Reduce_scatter(x, mpi.MPI_SUM, 0))(
                jnp.ones((NR + 1,)))


class TestBcastReduceSpmd:
    def test_bcast_forward_and_grad(self):
        def fn(x):
            return comm.Bcast_(x * (comm.rank + 1), 2)

        out = np.asarray(run(fn)(jnp.ones(3)))
        assert (out == 3.0).all()  # root 2 holds x*3, broadcast everywhere

        # grad w.r.t. replicated x: every rank's output is x*(root+1);
        # d/dx sum over ranks = NR * 3
        g = jax.grad(lambda x: run(fn)(x).sum())(jnp.ones(3))
        assert (np.asarray(g) == NR * 3.0).all()

    def test_reduce_zeroes_nonroot(self):
        def fn(x):
            return comm.Reduce_(x * (comm.rank + 1), mpi.MPI_SUM, 0)

        out = np.asarray(run(fn)(jnp.ones(3)))
        assert (out[0] == NR * (NR + 1) / 2).all()
        assert (out[1:] == 0).all()

    def test_bcast_reduce_adjoint_pair(self):
        # Reduce_ grad == Bcast of upstream root gradient; exercised via a
        # root-weighted loss.
        def fn(x):
            return comm.Reduce_(x, mpi.MPI_SUM, 0)

        g = jax.grad(lambda x: run(fn)(x).sum())(jnp.ones(3))
        # each rank's input contributes only to root output; upstream grad
        # at root is 1 per element summed over... stacked loss sums all
        # ranks' outputs; only root row nonzero => grad = NR? No: root row
        # = sum of all ranks' x => d/dx (replicated) = NR * 1
        assert (np.asarray(g) == NR).all()


class TestShardOpsSpmd:
    def test_allgather_roundtrip_and_grad(self):
        def fn(x):
            t = x * (comm.rank + 1)
            return comm.Allgather(t, 0)

        out = np.asarray(run(fn)(jnp.ones((2, 3))))
        assert out.shape == (NR, 2 * NR, 3)
        for r in range(NR):
            for k in range(NR):
                assert (out[r, 2 * k:2 * k + 2] == k + 1).all()
        g = jax.grad(lambda x: run(fn)(x).sum())(jnp.ones((2, 3)))
        # every rank's t appears in every rank's output: sum_r sum_k (k+1)
        assert (np.asarray(g) == NR * NR * (NR + 1) / 2).all()

    def test_gather_root_only(self):
        def fn(x):
            return comm.Gather(x * (comm.rank + 1), 0, 3)

        out = np.asarray(run(fn)(jnp.ones((1, 2))))
        assert out.shape == (NR, NR, 2)
        for k in range(NR):
            assert (out[3, k] == k + 1).all()
        assert (out[np.arange(NR) != 3] == 0).all()

    def test_gather_grad_is_ones(self):
        # reference oracle (tests/test_collectives.py:58-63): grad of
        # Gather(...).sum() is ones on every rank.
        def fn(x):
            t = x * (comm.rank + 1)
            return comm.Gather(t, 0, 0)

        g = jax.grad(lambda x: run(fn)(x).sum())(jnp.ones((1, 2)))
        # d/dx: rank r's t = x*(r+1) lands once in root's gather =>
        # sum_r (r+1)
        assert (np.asarray(g) == NR * (NR + 1) / 2).all()

    def test_scatter_gather_identity(self):
        def fn(x):
            t = x * (comm.rank + 1)
            full = comm.Allgather(t, 0)
            back = comm.Scatter(full, 0, 2, 0)
            return back - t

        out = np.asarray(run(fn)(jnp.ones((2, 3))))
        assert (out == 0).all()

    def test_scatter_numelem_validation(self):
        def fn(x):
            return comm.Scatter(x, 0, 3, 0)

        with pytest.raises(ValueError, match="numelem"):
            run(fn)(jnp.ones((NR * 2, 2)))

    def test_alltoall_involution_and_grad(self):
        # reference identities (tests/test_collectives.py:137-147)
        def fn(x):
            t = x * (comm.rank + 1)
            y = comm.Alltoall(t, 0, 1, 1)
            z = comm.Alltoall(y, 1, 0, 2)
            return z - t

        out = np.asarray(run(fn)(jnp.ones((2, NR))))
        assert (out == 0).all()

        def fn2(x):
            return comm.Alltoall(x * (comm.rank + 1), 0, 1, 1)

        g = jax.grad(lambda x: run(fn2)(x).sum())(jnp.ones((2, NR)))
        assert (np.asarray(g) == NR * (NR + 1) / 2).all()


class TestP2PSpmd:
    def test_ring_three_orderings(self):
        # reference: tests/test_nonblocking.py:8-35, all three orderings.
        def ring_isendirecv(a0):
            a = a0 * (1.0 + comm.rank)
            req = comm.Isend(a, (comm.rank + 1) % comm.size, 0)
            req2 = comm.Irecv(mpi.JoinDummies(jnp.empty_like(a), [req.dummy]),
                              (comm.rank + comm.size - 1) % comm.size, 0)
            res = comm.Wait(mpi.JoinDummiesHandle(req, [req2.dummy]))
            res2 = comm.Wait(mpi.JoinDummiesHandle(req2, [res]))
            return res2 * comm.rank

        def ring_isendrecv(a0):
            a = a0 * (1.0 + comm.rank)
            req = comm.Isend(a, (comm.rank + 1) % comm.size, 0)
            res = comm.Recv(mpi.JoinDummies(jnp.empty_like(a), [req.dummy]),
                            (comm.rank + comm.size - 1) % comm.size, 0)
            res2 = comm.Wait(mpi.JoinDummiesHandle(req, [res]))
            return mpi.JoinDummies(res, [res2]) * comm.rank

        def ring_irecvsend(a0):
            a = a0 * (1.0 + comm.rank)
            req = comm.Irecv(mpi.JoinDummies(jnp.empty_like(a), [a]),
                             (comm.rank + comm.size - 1) % comm.size, 0)
            res = comm.Send(a, (comm.rank + 1) % comm.size, 0)
            res2 = comm.Wait(mpi.JoinDummiesHandle(req, [res]))
            return res2 * comm.rank

        for prog in (ring_isendirecv, ring_isendrecv, ring_irecvsend):
            out = np.asarray(run(prog)(jnp.ones(2)))
            for r in range(NR):
                left = (r - 1 + NR) % NR
                assert (out[r] == (1.0 + left) * r).all(), prog.__name__
            # gradient: rank r's a reaches rank (r+1)'s output scaled by
            # (r+1)%NR; loss sums all ranks → d/dx sum_r (1+r)*((r+1)%NR)
            g = jax.grad(lambda x: run(prog)(x).sum())(jnp.ones(2))
            expect = sum((1 + r) * ((r + 1) % NR) for r in range(NR))
            assert (np.asarray(g) == expect).all(), prog.__name__

    def test_longer_shift(self):
        def prog(a0):
            a = a0 * (1.0 + comm.rank)
            h = comm.Isend(a, (comm.rank + 3) % comm.size, 7)
            b = comm.Recv(mpi.JoinDummies(jnp.empty_like(a), [h.dummy]),
                          (comm.rank - 3) % comm.size, 7)
            comm.Wait(mpi.JoinDummiesHandle(h, [b]))
            return b

        out = np.asarray(run(prog)(jnp.ones(1)))
        for r in range(NR):
            assert out[r, 0] == 1.0 + (r - 3) % NR

    def test_unmatched_send_trace_time_deadlock(self):
        def prog(a):
            comm.Isend(a, (comm.rank + 1) % comm.size, 0)
            return a

        with pytest.raises(mpi.DeadlockError, match="unmatched"):
            run(prog)(jnp.ones(1))

    def test_wait_unmatched_recv_raises(self):
        def prog(a):
            h = comm.Irecv(jnp.empty_like(a), (comm.rank - 1) % comm.size, 0)
            return comm.Wait(h)

        with pytest.raises(mpi.DeadlockError, match="before the matching"):
            run(prog)(jnp.ones(1))

    def test_blocking_send_recv_ring(self):
        # Blocking Send = Isend+Wait: the Wait on a buffered send completes
        # locally even though the matching Recv appears later in the
        # program (fixed: an eager wait must not be a false deadlock).
        def prog(a0):
            a = a0 * (1.0 + comm.rank)
            comm.Send(a, (comm.rank + 1) % comm.size, 0)
            return comm.Recv(jnp.empty_like(a), (comm.rank - 1) % comm.size, 0)

        out = np.asarray(run(prog)(jnp.ones(2)))
        for r in range(NR):
            assert (out[r] == 1.0 + (r - 1) % NR).all()

    def test_unwrapped_destination_rejected(self):
        # `comm.rank + 1` without `% size` is out of range on the last rank;
        # silent ring-wrapping would mask the bug the eager backend reports.
        def prog(a):
            h = comm.Isend(a, comm.rank + 1, 0)
            return comm.Wait(h)

        with pytest.raises(mpi.CommError, match="out of range"):
            run(prog)(jnp.ones(1))

    def test_rankexpr_arith_after_wrap_materializes(self):
        # ((rank+1) % size) + 1 must wrap before the +1: on the last of 8
        # ranks the value is 0+1=1, not 9.
        def prog(x):
            return x * ((((comm.rank + 1) % comm.size) + 1))

        out = np.asarray(run(prog)(jnp.ones(1)))
        assert out.ravel().tolist() == [(r + 1) % NR + 1 for r in range(NR)]


class TestGeneralPermutationsP2P:
    """Arbitrary static bijections on the SPMD p2p path (reference contract:
    any dest/source rank, csrc/extension.cpp:1071-1157).  Ring shifts remain
    the common case; butterfly (rank ^ k), explicit permutation tables, and
    self-sends all lower to at most one collective_permute."""

    def test_butterfly_xor(self):
        # dest = rank ^ 1: pairwise exchange, its own inverse.
        def prog(a0):
            a = a0 * (1.0 + comm.rank)
            h = comm.Isend(a, comm.rank ^ 1, 0)
            b = comm.Recv(mpi.JoinDummies(jnp.empty_like(a), [h.dummy]),
                          comm.rank ^ 1, 0)
            comm.Wait(mpi.JoinDummiesHandle(h, [b]))
            return b

        out = np.asarray(run(prog)(jnp.ones(2)))
        for r in range(NR):
            assert (out[r] == 1.0 + (r ^ 1)).all()

    def test_butterfly_gradient_crosschecked_with_eager(self):
        # Gradient must travel the butterfly backwards; the eager runtime
        # (arbitrary concrete destinations) is the oracle.
        def prog(a0):
            a = a0 * (1.0 + comm.rank)
            h = comm.Isend(a, comm.rank ^ 2, 3)
            b = comm.Recv(mpi.JoinDummies(jnp.empty_like(a), [h.dummy]),
                          comm.rank ^ 2, 3)
            comm.Wait(mpi.JoinDummiesHandle(h, [b]))
            return b * (1.0 + comm.rank)

        g_spmd = np.asarray(
            jax.grad(lambda x: run(prog)(x).sum())(jnp.ones(2)))

        per_rank = {}

        def body():
            def eager_prog(a0):
                a = a0 * (1.0 + comm.rank)
                h = comm.Isend(a, comm.rank ^ 2, 3)
                b = comm.Recv(mpi.JoinDummies(jnp.empty_like(a), [h.dummy]),
                              comm.rank ^ 2, 3)
                comm.Wait(mpi.JoinDummiesHandle(h, [b]))
                return (b * (1.0 + comm.rank)).sum()

            per_rank[comm.rank] = np.asarray(jax.grad(eager_prog)(jnp.ones(2)))

        mpi.run_ranks(body, NR)
        g_eager = sum(per_rank[r] for r in range(NR))
        np.testing.assert_array_equal(g_spmd, g_eager)

    def test_explicit_table_reversal(self):
        # dest table r -> NR-1-r (an involution that is NOT a ring shift).
        table = [NR - 1 - r for r in range(NR)]

        def prog(a0):
            a = a0 * (1.0 + comm.rank)
            h = comm.Isend(a, table, 0)
            b = comm.Recv(mpi.JoinDummies(jnp.empty_like(a), [h.dummy]),
                          table, 0)
            comm.Wait(mpi.JoinDummiesHandle(h, [b]))
            return b

        out = np.asarray(run(prog)(jnp.ones(1)))
        for r in range(NR):
            assert out[r, 0] == 1.0 + (NR - 1 - r)

    def test_non_involution_table(self):
        # A 3-cycle embedded in the identity: recv source is the inverse
        # table, exercising _invert_perm on an asymmetric permutation.
        dest = list(range(NR))
        dest[0], dest[1], dest[2] = 1, 2, 0          # 0->1->2->0
        src = [0] * NR
        for r, d in enumerate(dest):
            src[d] = r

        def prog(a0):
            a = a0 * (1.0 + comm.rank)
            h = comm.Isend(a, dest, 0)
            b = comm.Recv(mpi.JoinDummies(jnp.empty_like(a), [h.dummy]),
                          src, 0)
            comm.Wait(mpi.JoinDummiesHandle(h, [b]))
            return b

        out = np.asarray(run(prog)(jnp.ones(1)))
        for r in range(NR):
            assert out[r, 0] == 1.0 + src[r]

    def test_self_send(self):
        # MPI permits Isend(dest=rank); a local hand-off, no collective.
        def prog(a0):
            a = a0 * (1.0 + comm.rank)
            h = comm.Isend(a, comm.rank, 0)
            b = comm.Recv(mpi.JoinDummies(jnp.empty_like(a), [h.dummy]),
                          comm.rank, 0)
            comm.Wait(mpi.JoinDummiesHandle(h, [b]))
            return b

        out = np.asarray(run(prog)(jnp.ones(2)))
        for r in range(NR):
            assert (out[r] == 1.0 + r).all()

    def test_self_send_ring_shift_zero(self):
        # (comm.rank + 0) % comm.size spells self-send through RankExpr.
        def prog(a0):
            a = a0 * (1.0 + comm.rank)
            h = comm.Isend(a, (comm.rank + comm.size) % comm.size, 0)
            b = comm.Recv(mpi.JoinDummies(jnp.empty_like(a), [h.dummy]),
                          (comm.rank + comm.size) % comm.size, 0)
            comm.Wait(mpi.JoinDummiesHandle(h, [b]))
            return b

        out = np.asarray(run(prog)(jnp.ones(2)))
        for r in range(NR):
            assert (out[r] == 1.0 + r).all()

    def test_non_bijection_table_rejected(self):
        bad = [0] * NR

        def prog(a):
            h = comm.Isend(a, bad, 0)
            return comm.Wait(h)

        with pytest.raises(mpi.CommError, match="not a permutation"):
            run(prog)(jnp.ones(1))

    def test_xor_out_of_range_rejected(self):
        def prog(a):
            h = comm.Isend(a, comm.rank ^ (NR + 1), 0)
            return comm.Wait(h)

        with pytest.raises(mpi.CommError, match="leaves"):
            run(prog)(jnp.ones(1))

    def test_ring_and_butterfly_do_not_cross_match(self):
        # Same tag, different permutations: must stay unmatched and raise
        # at region close, not silently pair up.
        def prog(a):
            comm.Isend(a, (comm.rank + 1) % comm.size, 0)
            h = comm.Irecv(jnp.empty_like(a), comm.rank ^ 1, 0)
            return a

        with pytest.raises(mpi.DeadlockError, match="unmatched"):
            run(prog)(jnp.ones(1))


class TestEagerPeerTables:
    def test_table_program_runs_on_both_backends(self):
        # The SPMD backends' portable permutation-table form must run
        # unchanged on the eager backend (each rank takes its entry).
        table = [NR - 1 - r for r in range(NR)]

        def prog(a0):
            a = a0 * (1.0 + comm.rank)
            h = comm.Isend(a, table, 2)
            b = comm.Recv(mpi.JoinDummies(jnp.empty_like(a), [h.dummy]),
                          table, 2)
            comm.Wait(mpi.JoinDummiesHandle(h, [b]))
            return b

        spmd = np.asarray(run(prog)(jnp.ones(2)))
        eager = {}

        def body():
            eager[comm.rank] = np.asarray(prog(jnp.ones(2)))

        mpi.run_ranks(body, NR)
        for r in range(NR):
            np.testing.assert_array_equal(eager[r], spmd[r])

    def test_wrong_length_table_rejected_eager(self):
        def body():
            with pytest.raises(mpi.CommError, match="entries"):
                comm.Isend(jnp.ones(1), [0] * (4 + 1), 0)

        mpi.run_ranks(body, 4)


class TestEagerSelfSend:
    def test_self_send_eager(self):
        # MPI semantics: Isend(dest=rank) + Recv(source=rank) completes
        # locally on the eager (mailbox) runtime too.
        def body():
            a = jnp.ones(2) * (1.0 + comm.rank)
            h = comm.Isend(a, comm.rank, 0)
            b = comm.Recv(mpi.JoinDummies(jnp.empty_like(a), [h.dummy]),
                          comm.rank, 0)
            comm.Wait(mpi.JoinDummiesHandle(h, [b]))
            assert (np.asarray(b) == 1.0 + comm.rank).all()

        mpi.run_ranks(body, 4)


class TestDeterministicToggle:
    def test_toggle_after_first_call_retraces(self):
        # The flag is part of the jit cache key: flipping it after the
        # first call must change the executed lowering, not silently reuse
        # the cached trace.
        rng = np.random.default_rng(5)
        data = jnp.asarray(rng.standard_normal((NR, 127)).astype(np.float32))

        def fn(x):
            t = jax.lax.dynamic_index_in_dim(x, jnp.asarray(comm.rank + 0),
                                             0, keepdims=False)
            return comm.Allreduce(t, mpi.MPI_SUM)

        f = run(fn)
        _ = f(data)  # traced with deterministic off
        with mpi.config.deterministic_mode(True):
            det = np.asarray(f(data))  # must retrace with the fold
        oracle = np.asarray(data)[0].copy()
        for r in range(1, NR):
            oracle = oracle + np.asarray(data)[r]
        np.testing.assert_array_equal(det[0], oracle)

    def test_double_wait_raises(self):
        def prog(a):
            h = comm.Isend(a, (comm.rank + 1) % comm.size, 0)
            b = comm.Recv(jnp.empty_like(a), (comm.rank - 1) % comm.size, 0)
            comm.Wait(h)
            comm.Wait(h)
            return b

        with pytest.raises(mpi.BifurcationError, match="already waited"):
            run(prog)(jnp.ones(1))

    def test_spliced_handle_raises(self):
        def prog(a):
            h = comm.Isend(a, (comm.rank + 1) % comm.size, 0)
            b = comm.Recv(jnp.empty_like(a), (comm.rank - 1) % comm.size, 0)
            franken = mpi.WaitHandle([h._handle[0], b, b])
            comm.Wait(franken)
            return b

        with pytest.raises(mpi.BifurcationError, match="bifurcation"):
            run(prog)(jnp.ones(1))

    def test_literal_destination_rejected(self):
        def prog(a):
            h = comm.Isend(a, 3, 0)
            return comm.Wait(h)

        with pytest.raises(mpi.CommError, match="static permutation"):
            run(prog)(jnp.ones(1))


class TestCrossBackendEquivalence:
    """The same per-rank program, executed eagerly (thread-SPMD) and traced
    (mesh SPMD), must agree — the moral equivalent of the reference's
    eager-vs-TorchScript parity tests."""

    def test_allreduce_program(self):
        rng = np.random.default_rng(11)
        data = rng.standard_normal((NR, 64))

        def spmd_fn(x):
            t = jax.lax.dynamic_index_in_dim(x, jnp.asarray(comm.rank + 0),
                                             0, keepdims=False)
            y = comm.Allreduce(t, mpi.MPI_SUM)
            return y * (comm.rank + 1)

        spmd_out = np.asarray(run(spmd_fn)(jnp.asarray(data)))

        def eager_body(rank):
            y = comm.Allreduce(jnp.asarray(data[rank]), mpi.MPI_SUM)
            return np.asarray(y * (comm.rank + 1))

        eager_out = mpi.run_ranks(eager_body, NR)
        for r in range(NR):
            np.testing.assert_allclose(spmd_out[r], eager_out[r], rtol=1e-12)

    def test_ring_program(self):
        def spmd_fn(x):
            a = x * (1.0 + comm.rank)
            h = comm.Isend(a, (comm.rank + 1) % comm.size, 0)
            b = comm.Recv(mpi.JoinDummies(jnp.empty_like(a), [h.dummy]),
                          (comm.rank - 1) % comm.size, 0)
            w = comm.Wait(mpi.JoinDummiesHandle(h, [b]))
            return mpi.JoinDummies(a + b, [w])

        spmd_out = np.asarray(run(spmd_fn)(jnp.ones(3)))

        def eager_body(rank):
            a = jnp.ones(3) * (1.0 + comm.rank)
            h = comm.Isend(a, (comm.rank + 1) % comm.size, 0)
            b = comm.Recv(mpi.JoinDummies(jnp.empty_like(a), [h.dummy]),
                          (comm.rank - 1 + comm.size) % comm.size, 0)
            w = comm.Wait(mpi.JoinDummiesHandle(h, [b]))
            return np.asarray(mpi.JoinDummies(a + b, [w]))

        eager_out = mpi.run_ranks(eager_body, NR)
        for r in range(NR):
            np.testing.assert_array_equal(spmd_out[r], eager_out[r])


class TestCommFromMesh:
    def test_user_managed_shard_map(self):
        # Foreign-mesh adoption (the mpi4py-interop analogue,
        # src/__init__.py:247-261): use the communicator inside a
        # user-managed shard_map over the user's own axis name.
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map

        devs = jax.devices()[:4]
        mesh = Mesh(np.asarray(devs), ("workers",))
        c = mpi.comm_from_mesh(mesh, "workers")
        assert c.size == 4

        def fn(x):
            return c.Allreduce(x, mpi.MPI_SUM)

        out = shard_map(fn, mesh=mesh, in_specs=P("workers"),
                        out_specs=P("workers"), check_vma=False)(
            jnp.arange(8.0))
        # shards [0,1],[2,3],[4,5],[6,7]; psum over shards: [12, 16] each
        assert (np.asarray(out) == np.tile([12.0, 16.0], 4)).all()

    def test_p2p_in_user_managed_shard_map(self):
        # Regression: Isend/Irecv posted through a comm_from_mesh
        # communicator must share one trace-region context so the pair can
        # fuse into a collective_permute (a fresh context per op call would
        # produce a spurious trace-time DeadlockError).
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map

        mesh = Mesh(np.asarray(jax.devices()), ("w",))
        c = mpi.comm_from_mesh(mesh, "w")

        def ring(a):
            h = c.Isend(a, (c.rank + 1) % c.size, 0)
            b = c.Recv(mpi.JoinDummies(jnp.empty_like(a), [h.dummy]),
                       (c.rank - 1) % c.size, 0)
            w = c.Wait(mpi.JoinDummiesHandle(h, [b]))
            return mpi.JoinDummies(b, [w])

        out = shard_map(ring, mesh=mesh, in_specs=P("w"), out_specs=P("w"),
                        check_vma=False)(jnp.arange(8.0))
        assert (np.asarray(out) == np.asarray(
            [7., 0., 1., 2., 3., 4., 5., 6.])).all()

    def test_bad_axis_rejected(self):
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("w",))
        with pytest.raises(mpi.CommError, match="axis"):
            mpi.comm_from_mesh(mesh, "nope")

    def test_p2p_scope_matches_and_returns_values(self):
        # Inside an explicit scope the ring still fuses and computes.
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map

        mesh = Mesh(np.asarray(jax.devices()), ("w",))
        c = mpi.comm_from_mesh(mesh, "w")

        def ring(a):
            with mpi.p2p_scope(c):
                h = c.Isend(a, (c.rank + 1) % c.size, 0)
                b = c.Recv(mpi.JoinDummies(jnp.empty_like(a), [h.dummy]),
                           (c.rank - 1) % c.size, 0)
                w = c.Wait(mpi.JoinDummiesHandle(h, [b]))
            return mpi.JoinDummies(b, [w])

        out = shard_map(ring, mesh=mesh, in_specs=P("w"), out_specs=P("w"),
                        check_vma=False)(jnp.arange(8.0))
        assert (np.asarray(out) == np.asarray(
            [7., 0., 1., 2., 3., 4., 5., 6.])).all()

    def test_p2p_scope_raises_on_unmatched_send(self):
        # A user-managed region has no exit hook, so unmatched p2p there
        # normally only warns from a finalizer; the explicit scope
        # restores run_spmd's hard trace-time DeadlockError.
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map

        mesh = Mesh(np.asarray(jax.devices()), ("w",))
        c = mpi.comm_from_mesh(mesh, "w")

        def lonely_send(a):
            with mpi.p2p_scope(c):
                h = c.Isend(a, (c.rank + 1) % c.size, 0)
            return mpi.JoinDummies(a, [h.dummy])

        with pytest.raises(mpi.DeadlockError, match="unmatched"):
            shard_map(lonely_send, mesh=mesh, in_specs=P("w"),
                      out_specs=P("w"), check_vma=False)(jnp.arange(8.0))

    def test_p2p_scope_rejects_non_mesh_comm(self):
        with pytest.raises(mpi.CommError, match="mesh-derived"):
            with mpi.p2p_scope(mpi.COMM_WORLD):
                pass


class TestAlltoallCrossModeParity:
    """ISSUE 9 satellite: Alltoall was the one facade collective with no
    cross-mode bitwise-parity matrix (the reduction family has one in
    TestDeterministic* above) — and the reshard executor leans on it.
    Mode A (compiled all_to_all) and Mode B (rendezvous gather+scatter)
    must agree BITWISE on general float data, forward and backward, on
    (3,), (8,) and the (2,4)-mesh worlds."""

    @staticmethod
    def _data(n, k=4):
        rng = np.random.default_rng(n)
        return rng.standard_normal((n, n * k)).astype(np.float64)

    @pytest.mark.parametrize("n", [3, 8])
    def test_forward_bitwise(self, n):
        data = self._data(n)

        def spmd_body():
            t = jnp.asarray(data)[jnp.asarray(comm.rank + 0)]
            t = t.reshape(n, -1)
            return comm.Alltoall(t, gatheraxis=1, scatteraxis=0,
                                 numelem=1)

        a = np.asarray(mpi.run_spmd(spmd_body, nranks=n)())

        def eager_body():
            t = jnp.asarray(data)[comm.rank].reshape(n, -1)
            return comm.Alltoall(t, gatheraxis=1, scatteraxis=0,
                                 numelem=1)

        b = mpi.run_ranks(eager_body, n)
        for r in range(n):
            assert np.array_equal(a[r], np.asarray(b[r])), r

    @pytest.mark.parametrize("n", [3, 8])
    def test_backward_bitwise(self, n):
        data = self._data(n)
        w = np.random.default_rng(n + 100).standard_normal(
            (n, n, self._data(n).shape[1] // n))

        def loss(c, t, wr):
            y = c.Alltoall(t, gatheraxis=1, scatteraxis=0, numelem=1)
            return jnp.vdot(y, wr)

        def spmd_body():
            t = jnp.asarray(data)[jnp.asarray(comm.rank + 0)]
            t = t.reshape(n, -1)
            wr = jnp.asarray(w)[jnp.asarray(comm.rank + 0)]
            return jax.grad(lambda v: loss(comm, v, wr))(t)

        a = np.asarray(mpi.run_spmd(spmd_body, nranks=n)())

        def eager_body():
            t = jnp.asarray(data)[comm.rank].reshape(n, -1)
            wr = jnp.asarray(w)[comm.rank]
            return jax.grad(lambda v: loss(comm, v, wr))(t)

        b = mpi.run_ranks(eager_body, n)
        for r in range(n):
            assert np.array_equal(a[r], np.asarray(b[r])), r

    def test_2d_mesh_per_axis_vs_local_oracle(self):
        # The (2,4) world: one Alltoall per mesh axis inside a 2D
        # shard_map, each checked against the local transpose oracle.
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map

        mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                    ("a", "b"))
        rng = np.random.default_rng(7)
        data = rng.standard_normal((2, 4, 4, 6)).astype(np.float64)

        for axis, size in (("a", 2), ("b", 4)):
            c = mpi.comm_from_mesh(mesh, axis)

            def body(x):
                ia = jax.lax.axis_index("a")
                ib = jax.lax.axis_index("b")
                t = jnp.asarray(data)[ia, ib].reshape(size, -1)
                y = c.Alltoall(t, gatheraxis=1, scatteraxis=0,
                               numelem=1)
                return jnp.expand_dims(jnp.expand_dims(y, 0), 0)

            out = jax.jit(shard_map(
                body, mesh=mesh, in_specs=P(),
                out_specs=P("a", "b"), check_vma=False))(
                    jnp.zeros(()))
            out = np.asarray(out)
            for ra in range(2):
                for rb in range(4):
                    me = (ra, rb)
                    group = [(i, rb) for i in range(2)] if axis == "a" \
                        else [(ra, j) for j in range(4)]
                    pos = group.index(me)
                    pieces = [
                        data[g].reshape(size, -1)[pos] for g in group]
                    want = np.concatenate(
                        [p.reshape(1, -1) for p in pieces], axis=1)
                    got = out[ra, rb]
                    assert np.array_equal(got.reshape(1, -1), want), \
                        (axis, ra, rb)


def test_no_private_jax_imports():
    # VERDICT round 1: `jax._src` is version-unstable; the package must
    # stick to public API (jax.core re-exports included).
    import pathlib

    pkg = pathlib.Path(mpi.__file__).parent
    offenders = [
        str(p) for p in pkg.rglob("*.py") if "jax._src" in p.read_text()
    ]
    assert offenders == []


class TestDonation:
    """``run_spmd(..., donate_argnums=...)`` (ISSUE 29): stacked state
    that goes in and comes out in one layout is handed to the program."""

    @staticmethod
    def bump(state, x):
        # state arrives stacked (NR, 3); each rank returns its own row.
        mine = jax.lax.dynamic_index_in_dim(
            state, jnp.asarray(comm.rank), 0, keepdims=False)
        return mine + comm.Allreduce(x, mpi.MPI_SUM)

    def test_donated_argument_is_taken_and_the_result_is_right(self):
        f = run(self.bump, donate_argnums=(0,))
        state, x = f(jnp.zeros((NR, 3)), jnp.ones(3)), jnp.ones(3)
        old = state
        state = f(state, x)
        assert old.is_deleted() and not x.is_deleted()
        np.testing.assert_array_equal(np.asarray(state),
                                      np.full((NR, 3), 2.0 * NR))

    def test_default_takes_nothing(self):
        f = run(self.bump)
        state = jnp.zeros((NR, 3))
        f(state, jnp.ones(3))
        assert not state.is_deleted()

    def test_donation_does_not_change_the_lowering(self):
        # The option is jit's: the traced program is the same text.
        args = (jnp.zeros((NR, 3)), jnp.ones(3))
        plain = jax.jit(run(self.bump)).lower(*args).as_text()
        taken = jax.jit(run(self.bump, donate_argnums=(0,))) \
            .lower(*args).as_text()
        assert plain == taken

    def test_needs_jit(self):
        with pytest.raises(ValueError, match="jit=True"):
            run(self.bump, jit=False, donate_argnums=(0,))
