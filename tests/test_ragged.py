"""Ragged collectives: capacity-padded + masked per-rank-varying exchange
(SURVEY.md §7 hard part 2 — the SPMD-compatible form of the reference's
Gatherv/Alltoallv semantics, csrc/extension.cpp:540-554, 947-979).

Oracles: explicit routing tables built in numpy; identical results on the
eager and SPMD backends; gradients route back through the exchange with
zero gradient into padding slots."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4torch_tpu as mpi
from mpi4torch_tpu import COMM_WORLD as comm
from mpi4torch_tpu.ops import (block_gather, block_scatter,
                               ragged_allgather, ragged_alltoall,
                               ragged_gather, ragged_scatter, segment_mask)
from mpi4torch_tpu.ops.ragged import position_onehot

NR = 4
CAP = 5
FEAT = 3

# counts[src][dst] = rows src sends to dst (varying, some zero).
COUNTS = np.array([[1, 2, 0, 3],
                   [4, 0, 1, 2],
                   [0, 5, 2, 1],
                   [2, 1, 3, 0]])


def payload(src):
    """Deterministic payload: row r of src's block for dst carries value
    100*src + 10*dst + r in every feature slot."""
    x = np.zeros((NR, CAP, FEAT))
    for dst in range(NR):
        for r in range(COUNTS[src][dst]):
            x[dst, r, :] = 100 * src + 10 * dst + r
    # Poison the padding so masking is actually load-bearing.
    for dst in range(NR):
        x[dst, COUNTS[src][dst]:, :] = -999.0
    return jnp.asarray(x)


def expected_recv(dst):
    r = np.zeros((NR, CAP, FEAT))
    for src in range(NR):
        for row in range(COUNTS[src][dst]):
            r[src, row, :] = 100 * src + 10 * dst + row
    return r


class TestRaggedAlltoall:
    def test_eager_matches_routing_oracle(self):
        def body():
            r = int(comm.rank)
            recv, rc = ragged_alltoall(comm, payload(r),
                                       jnp.asarray(COUNTS)[r])
            return np.asarray(recv), np.asarray(rc)

        outs = mpi.run_ranks(body, NR)
        for dst, (recv, rc) in enumerate(outs):
            np.testing.assert_array_equal(recv, expected_recv(dst))
            np.testing.assert_array_equal(rc, COUNTS[:, dst])

    def test_spmd_matches_eager(self):
        def body():
            rk = jnp.asarray(comm.rank)
            x = jnp.stack([payload(s) for s in range(NR)])[rk]
            cnt = jnp.asarray(COUNTS)[rk]
            return ragged_alltoall(comm, x, cnt)

        recv, rc = mpi.run_spmd(body, nranks=NR)()
        for dst in range(NR):
            np.testing.assert_array_equal(np.asarray(recv)[dst],
                                          expected_recv(dst))
            np.testing.assert_array_equal(np.asarray(rc)[dst],
                                          COUNTS[:, dst])

    def test_grads_route_back_and_padding_gets_zero(self):
        def body():
            r = int(comm.rank)
            x = payload(r)
            cnt = jnp.asarray(COUNTS)[r]

            def loss(x):
                recv, _ = ragged_alltoall(comm, x, cnt)
                return jnp.sum(recv)

            return np.asarray(jax.grad(loss)(x))

        grads = mpi.run_ranks(body, NR)
        for src, g in enumerate(grads):
            mask = np.zeros((NR, CAP, FEAT))
            for dst in range(NR):
                mask[dst, :COUNTS[src][dst], :] = 1.0
            # Valid slots got cotangent 1 (delivered across ranks); the
            # poisoned padding slots got exactly zero.
            np.testing.assert_array_equal(g, mask)

    def test_shape_validation(self):
        def body():
            with pytest.raises(ValueError, match="capacity"):
                ragged_alltoall(comm, jnp.zeros((2, CAP, 1)),
                                jnp.zeros((NR,), jnp.int32))
            with pytest.raises(ValueError, match="send_counts"):
                ragged_alltoall(comm, jnp.zeros((NR, CAP, 1)),
                                jnp.zeros((2,), jnp.int32))
            return True

        assert all(mpi.run_ranks(body, NR))


class TestRaggedAllgather:
    def test_reconstructs_allgatherv(self):
        lens = [2, 5, 1, 3]

        def body():
            r = int(comm.rank)
            x = np.full((CAP, FEAT), -999.0)
            x[:lens[r]] = 10 * r + np.arange(lens[r])[:, None]
            g, c = ragged_allgather(comm, jnp.asarray(x), lens[r])
            return np.asarray(g), np.asarray(c)

        outs = mpi.run_ranks(body, NR)
        want = np.concatenate([
            (10 * r + np.arange(lens[r])[:, None]) * np.ones((1, FEAT))
            for r in range(NR)])
        for g, c in outs:
            np.testing.assert_array_equal(c, lens)
            got = np.concatenate([g[r, :lens[r]] for r in range(NR)])
            np.testing.assert_array_equal(got, want)

    def test_spmd_backend(self):
        lens = jnp.asarray([2, 5, 1, 3])

        def body():
            r = jnp.asarray(comm.rank)
            base = jnp.arange(CAP, dtype=jnp.float64)[:, None] + 10.0 * r
            x = jnp.broadcast_to(base, (CAP, FEAT))
            return ragged_allgather(comm, x, lens[r])

        g, c = mpi.run_spmd(body, nranks=NR)()
        g, c = np.asarray(g), np.asarray(c)
        for dst in range(NR):
            np.testing.assert_array_equal(c[dst], [2, 5, 1, 3])
            for src in range(NR):
                valid = g[dst, src, :int(c[dst][src])]
                expect = (10.0 * src
                          + np.arange(int(c[dst][src]))[:, None]
                          ) * np.ones((1, FEAT))
                np.testing.assert_array_equal(valid, expect)
                np.testing.assert_array_equal(
                    g[dst, src, int(c[dst][src]):], 0.0)


class TestSegmentMask:
    def test_mask_shape_and_values(self):
        m = np.asarray(segment_mask(jnp.asarray([0, 2, 5]), 5))
        np.testing.assert_array_equal(m[0], np.zeros(5))
        np.testing.assert_array_equal(m[1], [1, 1, 0, 0, 0])
        np.testing.assert_array_equal(m[2], np.ones(5))

    def test_scalar_count_gives_1d_mask(self):
        m = np.asarray(segment_mask(jnp.asarray(3), 5))
        assert m.shape == (5,)
        np.testing.assert_array_equal(m, [1, 1, 1, 0, 0])


class TestRobustness:
    def test_over_capacity_counts_are_clamped(self):
        # A count > capacity must not transmit a recv_count larger than
        # the actual zero-padded valid data.
        def body():
            r = int(comm.rank)
            x = jnp.ones((NR, CAP, FEAT))
            cnt = jnp.full((NR,), CAP + 3)
            recv, rc = ragged_alltoall(comm, x, cnt)
            return np.asarray(rc)

        for rc in mpi.run_ranks(body, NR):
            np.testing.assert_array_equal(rc, np.full(NR, CAP))

    def test_allgather_rejects_vector_count(self):
        def body():
            with pytest.raises(ValueError, match="scalar"):
                ragged_allgather(comm, jnp.zeros((CAP, FEAT)),
                                 jnp.zeros((NR,), jnp.int32))
            return True

        assert all(mpi.run_ranks(body, NR))

    def test_nan_padding_does_not_leak(self):
        # Padding may hold NaN (e.g. masked-softmax leftovers); the
        # exchange must still deliver zeros in invalid slots.
        def body():
            r = int(comm.rank)
            x = jnp.where(jnp.isnan(jnp.full((NR, CAP, FEAT), jnp.nan)),
                          jnp.nan, 0.0)
            x = x.at[:, 0].set(1.0)
            recv, rc = ragged_alltoall(comm, x, jnp.ones((NR,), jnp.int32))
            return np.asarray(recv)

        for recv in mpi.run_ranks(body, NR):
            assert np.all(np.isfinite(recv))
            np.testing.assert_array_equal(recv[:, 0], 1.0)
            np.testing.assert_array_equal(recv[:, 1:], 0.0)

    def test_negative_counts_clamped_to_zero(self):
        def body():
            x = jnp.ones((NR, CAP, FEAT))
            recv, rc = ragged_alltoall(comm, x, jnp.full((NR,), -2))
            return np.asarray(rc), np.asarray(recv)

        for rc, recv in mpi.run_ranks(body, NR):
            np.testing.assert_array_equal(rc, 0)
            np.testing.assert_array_equal(recv, 0.0)

    def test_allgather_clamps_count(self):
        def body():
            g, c = ragged_allgather(comm, jnp.ones((CAP, FEAT)), CAP + 9)
            return np.asarray(c)

        for c in mpi.run_ranks(body, NR):
            np.testing.assert_array_equal(c, np.full(NR, CAP))


# Root-varying Gatherv/Scatterv (reference: varying ``numelem`` cases,
# tests/test_collectives.py:121-125, csrc/extension.cpp:540-577, 839-871).
GLENS = np.array([2, 0, 3, 1])          # per-rank valid lengths
ROOT = 2


def gv_payload(r):
    """Rank r's padded block: row i carries 10*r + i; padding poisoned."""
    x = np.full((CAP, FEAT), -999.0)
    for i in range(GLENS[r]):
        x[i, :] = 10 * r + i
    return jnp.asarray(x)


def gv_expected():
    g = np.zeros((NR, CAP, FEAT))
    for r in range(NR):
        for i in range(GLENS[r]):
            g[r, i, :] = 10 * r + i
    return g


def packed(gathered, counts):
    """MPI_Gatherv's packed result: concatenated valid prefixes."""
    return np.concatenate([np.asarray(gathered)[s, :c]
                           for s, c in enumerate(np.asarray(counts))])


class TestRaggedGatherScatter:
    def test_eager_gather_matches_oracle(self):
        def body():
            r = int(comm.rank)
            g, c = ragged_gather(comm, gv_payload(r),
                                 jnp.asarray(GLENS)[r], root=ROOT)
            return np.asarray(g), np.asarray(c)

        outs = mpi.run_ranks(body, NR)
        g_root, c_root = outs[ROOT]
        np.testing.assert_array_equal(g_root, gv_expected())
        np.testing.assert_array_equal(c_root, GLENS)
        ref_packed = np.concatenate(
            [np.asarray(gv_payload(r))[:GLENS[r]] for r in range(NR)])
        np.testing.assert_array_equal(packed(g_root, c_root), ref_packed)
        for r, (g, c) in enumerate(outs):
            if r != ROOT:
                np.testing.assert_array_equal(g, 0.0)   # zeroed non-root
                np.testing.assert_array_equal(c, 0)

    def test_spmd_gather_matches_eager(self):
        lens = jnp.asarray(GLENS)

        def body():
            r = jnp.asarray(comm.rank)
            x = jnp.where(
                jnp.arange(CAP)[:, None] < lens[r],
                (10.0 * r + jnp.arange(CAP))[:, None]
                * jnp.ones((CAP, FEAT)),
                -999.0)
            return ragged_gather(comm, x, lens[r], root=ROOT)

        g, c = mpi.run_spmd(body, nranks=NR)()
        np.testing.assert_array_equal(np.asarray(g)[ROOT], gv_expected())
        np.testing.assert_array_equal(np.asarray(c)[ROOT], GLENS)
        for r in range(NR):
            if r != ROOT:
                np.testing.assert_array_equal(np.asarray(g)[r], 0.0)

    @pytest.mark.parametrize("backend", ["eager", "spmd"])
    def test_scatter_inverts_gather_on_valid_prefixes(self, backend):
        # Scatterv(Gatherv(x)) == x on valid slots, zeros on padding —
        # the reference's Scatter∘Gather identity with varying numelem.
        lens = jnp.asarray(GLENS)

        def body():
            r = jnp.asarray(comm.rank)
            x = jnp.where(
                jnp.arange(CAP)[:, None] < lens[r],
                (10.0 * r + jnp.arange(CAP))[:, None]
                * jnp.ones((CAP, FEAT)),
                -999.0)
            g, c = ragged_gather(comm, x, lens[r], root=ROOT)
            recv, mc = ragged_scatter(comm, g, c, root=ROOT)
            return recv, mc

        if backend == "eager":
            outs = mpi.run_ranks(lambda: tuple(
                np.asarray(t) for t in body()), NR)
        else:
            recv, mc = mpi.run_spmd(body, nranks=NR)()
            outs = [(np.asarray(recv)[r], np.asarray(mc)[r])
                    for r in range(NR)]
        for r, (recv, mc) in enumerate(outs):
            np.testing.assert_array_equal(mc, GLENS[r])
            expect = np.zeros((CAP, FEAT))
            for i in range(GLENS[r]):
                expect[i, :] = 10 * r + i
            np.testing.assert_array_equal(recv, expect)

    def test_gather_grads_route_back_padding_zero(self):
        lens = jnp.asarray(GLENS)

        def body():
            r = int(comm.rank)

            def loss(x):
                g, _ = ragged_gather(comm, x, lens[r], root=ROOT)
                return jnp.sum(g * 2.0)

            return np.asarray(jax.grad(loss)(jnp.ones((CAP, FEAT))))

        for r, grad in enumerate(mpi.run_ranks(body, NR)):
            expect = np.zeros((CAP, FEAT))
            expect[:GLENS[r]] = 2.0       # valid slots see the cotangent
            np.testing.assert_array_equal(grad, expect)

    def test_scatter_grads_route_back_padding_zero(self):
        lens = jnp.asarray(GLENS)

        def body():
            r = int(comm.rank)

            def loss(x):
                recv, _ = ragged_scatter(comm, x, lens, root=ROOT)
                return jnp.sum(recv * 3.0)

            return np.asarray(jax.grad(loss)(jnp.ones((NR, CAP, FEAT))))

        grads = mpi.run_ranks(body, NR)
        expect_root = np.zeros((NR, CAP, FEAT))
        for r in range(NR):
            expect_root[r, :GLENS[r]] = 3.0
        np.testing.assert_array_equal(grads[ROOT], expect_root)
        for r, g in enumerate(grads):
            if r != ROOT:
                np.testing.assert_array_equal(g, 0.0)  # root-only input

    def test_shape_validation(self):
        def body():
            with pytest.raises(ValueError, match="capacity"):
                ragged_gather(comm, jnp.asarray(0.0), 1)
            with pytest.raises(ValueError, match="scalar"):
                ragged_gather(comm, jnp.zeros((CAP,)), jnp.zeros((2,)))
            with pytest.raises(ValueError, match="size"):
                ragged_scatter(comm, jnp.zeros((NR + 1, CAP)),
                               jnp.zeros((NR,)))
            with pytest.raises(ValueError, match="shape"):
                ragged_scatter(comm, jnp.zeros((NR, CAP)),
                               jnp.zeros((NR + 1,)))
            return True

        assert all(mpi.run_ranks(body, NR))


# ---------------------------------------------------------------------------
# Paged KV-pool primitives (ISSUE 17): block_gather / block_scatter.
# Pure single-device ops — the serving engine drives them through the
# block table; here they are pinned standalone.
# ---------------------------------------------------------------------------


def _pool(nb=5, bs=3, feat=(2,), dtype=np.float32, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((nb, bs) + feat).astype(dtype)


class TestBlockGather:
    def test_oracle_concat(self):
        pool = _pool()
        table = np.array([[2, 0], [4, 4]], np.int32)
        got = np.asarray(block_gather(pool, table))
        want = np.stack([np.concatenate([pool[2], pool[0]]),
                         np.concatenate([pool[4], pool[4]])])
        np.testing.assert_array_equal(got, want)

    def test_unmapped_tail_blocks_inert(self):
        # -1 entries (the engine's free convention) come back as ZERO
        # pages — even when the pool holds NaN poison elsewhere, the
        # padded tail must be inert, not plausible.
        pool = _pool()
        pool[3] = np.nan
        table = np.array([[1, -1, -1]], np.int32)
        got = np.asarray(block_gather(pool, table))
        np.testing.assert_array_equal(got[0, :3], pool[1])
        np.testing.assert_array_equal(got[0, 3:], 0.0)

    def test_dtype_preserved_bitwise(self):
        for dtype in (np.float16, np.float32, np.int32):
            pool = (np.arange(5 * 3 * 2).reshape(5, 3, 2) * 7 + 1) \
                .astype(dtype)
            got = np.asarray(block_gather(pool, np.array([[4, 2]])))
            assert got.dtype == dtype
            np.testing.assert_array_equal(
                got[0], np.concatenate([pool[4], pool[2]]))

    def test_table_is_data_not_structure(self):
        # One compiled program for EVERY table state — the no-retrace
        # contract the serving decode step rides on.
        pool = _pool()
        f = jax.jit(block_gather)
        t1 = np.array([[0, 1]], np.int32)
        t2 = np.array([[3, -1]], np.int32)
        np.testing.assert_array_equal(np.asarray(f(pool, t1)),
                                      np.asarray(block_gather(pool, t1)))
        np.testing.assert_array_equal(np.asarray(f(pool, t2)),
                                      np.asarray(block_gather(pool, t2)))
        assert f._cache_size() == 1

    def test_shape_validation(self):
        with pytest.raises(ValueError, match="pool"):
            block_gather(jnp.zeros((4,)), np.zeros((1, 1), np.int32))
        with pytest.raises(ValueError, match="table"):
            block_gather(jnp.zeros((4, 2)), np.zeros((3,), np.int32))


class TestBlockScatter:
    def test_one_hot_write_at_block_granularity(self):
        pool = _pool()
        out = np.asarray(block_scatter(
            pool, np.array([3, 1]), np.array([0, 2]),
            np.array([[10.0, 11.0], [20.0, 21.0]], np.float32)))
        want = pool.copy()
        want[3, 0] = [10.0, 11.0]
        want[1, 2] = [20.0, 21.0]
        np.testing.assert_array_equal(out, want)

    def test_negative_or_oob_targets_write_nothing(self):
        pool = _pool()
        vals = np.full((3, 2), 99.0, np.float32)
        out = np.asarray(block_scatter(
            pool, np.array([-1, 7, 2]), np.array([0, 1, 9]), vals))
        np.testing.assert_array_equal(out, pool)

    def test_active_mask_suppresses_writer(self):
        pool = _pool()
        vals = np.array([[1.0, 2.0], [3.0, 4.0]], np.float32)
        out = np.asarray(block_scatter(
            pool, np.array([0, 1]), np.array([0, 0]), vals,
            active=np.array([False, True])))
        want = pool.copy()
        want[1, 0] = [3.0, 4.0]
        np.testing.assert_array_equal(out, want)

    def test_untouched_cells_bitwise_unchanged(self):
        # `where`-routed, never summed: a write elsewhere must not
        # perturb (or de-NaN) any other cell by a single bit.
        pool = _pool()
        pool[0, 0] = -0.0
        pool[2, 1] = np.nan
        out = np.asarray(block_scatter(
            pool, np.array([4]), np.array([2]),
            np.array([[5.0, 6.0]], np.float32)))
        np.testing.assert_array_equal(out[4, 2], [5.0, 6.0])
        assert np.signbit(out[0, 0]).all()
        assert np.isnan(out[2, 1]).all()

    def test_dtype_cast_to_pool(self):
        pool = _pool(dtype=np.float16)
        out = block_scatter(pool, np.array([1]), np.array([1]),
                            jnp.asarray([[1.5, 2.5]], jnp.float32))
        assert out.dtype == jnp.float16
        np.testing.assert_array_equal(np.asarray(out[1, 1]), [1.5, 2.5])

    def test_feature_shape_validation(self):
        with pytest.raises(ValueError, match="feature"):
            block_scatter(jnp.zeros((4, 2, 3)), np.array([0]),
                          np.array([0]), jnp.zeros((1, 5)))

    def test_scatter_then_gather_roundtrip(self):
        # The decode step's exact composition: write one row per slot,
        # gather each slot's pages back — the written row must come
        # back bit-identical through the table.
        pool = _pool(nb=6, bs=2)
        table = np.array([[0, 3], [5, 1]], np.int32)
        vals = np.array([[7.0, 8.0], [9.0, 10.0]], np.float32)
        # slot 0 writes position 3 (page table[0,1]=3, offset 1);
        # slot 1 writes position 0 (page table[1,0]=5, offset 0).
        out = block_scatter(pool, np.array([3, 5]), np.array([1, 0]),
                            vals)
        g = np.asarray(block_gather(out, table))
        np.testing.assert_array_equal(g[0, 3], vals[0])
        np.testing.assert_array_equal(g[1, 0], vals[1])


def _onehot_scatter(pool, block_ids, offsets, values, active=None):
    """``block_scatter`` as it was before ISSUE 29, kept here as the
    reference: integer one-hot routing of each writer to its cell and a
    ``where`` over the whole pool."""
    pool, values = jnp.asarray(pool), jnp.asarray(values)
    nb, bs = pool.shape[0], pool.shape[1]
    b = jnp.asarray(block_ids, jnp.int32)
    o = jnp.asarray(offsets, jnp.int32)
    live = (b >= 0) & (b < nb) & (o >= 0) & (o < bs)
    if active is not None:
        live = live & (jnp.asarray(active).astype(bool))
    bmask = (jnp.arange(nb, dtype=jnp.int32)[None, :] == b[:, None]) \
        & live[:, None]
    omask = position_onehot(o, bs) != 0
    cell = bmask[:, :, None] & omask[:, None, :]
    hit = cell.any(axis=0)
    writer = jnp.einsum("wnb,w->nb", cell.astype(jnp.int32),
                        jnp.arange(b.shape[0], dtype=jnp.int32))
    src = jnp.take(values, writer.reshape(-1), axis=0).reshape(
        (nb, bs) + values.shape[1:])
    mask = hit.reshape((nb, bs) + (1,) * (pool.ndim - 2))
    return jnp.where(mask, src.astype(pool.dtype), pool)


class TestBlockScatterInPlace:
    """ISSUE 29: the write is a true scatter of ``writers`` rows, bit
    for bit what the one-hot formulation wrote, and in the pool's own
    buffer when the pool is donated."""

    # (block ids, offsets, active); pool of 6 pages of 4 rows.
    WRITERS = [
        pytest.param([3, 1, 5], [0, 2, 3], None, id="plain"),
        pytest.param([3, -1, 5], [0, 2, 3], None, id="free-slot-id"),
        pytest.param([-1, -1, -1], [0, 0, 0], None, id="all-free"),
        pytest.param([5, -1, -1], [3, 3, 3], None,
                     id="free-ids-beside-the-last-page"),
        pytest.param([3, 6, 600], [0, 2, 3], None, id="ids-past-the-pool"),
        pytest.param([3, 1, 5], [-1, 4, 3], None, id="offsets-out-of-range"),
        pytest.param([3, 1, 5], [0, 2, 3], [True, False, True],
                     id="inactive-writer"),
        pytest.param([3, 1, 5], [0, 2, 3], [0, 0, 0], id="all-inactive"),
        pytest.param([0, 0, 0], [0, 1, 2], None, id="one-page-three-rows"),
    ]

    @pytest.mark.parametrize("pool_dtype,value_dtype", [
        (np.float32, np.float32), (np.float32, np.float64),
        (jnp.bfloat16, np.float32), (np.int32, np.int32)],
        ids=["f32", "f64-into-f32", "f32-into-bf16", "i32"])
    @pytest.mark.parametrize("ids,offs,active", WRITERS)
    def test_bitwise_vs_one_hot_formulation(self, ids, offs, active,
                                            pool_dtype, value_dtype):
        rng = np.random.default_rng(29)
        pool = jnp.asarray(rng.standard_normal((6, 4, 2, 8)) * 9, pool_dtype)
        pool = pool.at[2, 1].set(jnp.asarray(-0.0, pool.dtype))
        vals = jnp.asarray(rng.standard_normal((3, 2, 8)) * 9, value_dtype)
        act = None if active is None else np.asarray(active)
        got = block_scatter(pool, np.array(ids), np.array(offs), vals, act)
        want = _onehot_scatter(pool, np.array(ids), np.array(offs), vals, act)
        assert got.dtype == pool.dtype
        np.testing.assert_array_equal(
            np.asarray(got).view(np.uint8), np.asarray(want).view(np.uint8))

    def test_nan_cells_elsewhere_keep_their_bits(self):
        pool = _pool(nb=6, bs=4)
        pool[5, 3] = np.nan
        got = np.asarray(block_scatter(
            pool, np.array([-1, 2]), np.array([3, 0]),
            np.ones((2, 2), np.float32)))
        want = np.asarray(_onehot_scatter(
            pool, np.array([-1, 2]), np.array([3, 0]),
            np.ones((2, 2), np.float32)))
        np.testing.assert_array_equal(got.view(np.uint32),
                                      want.view(np.uint32))
        assert np.isnan(got[5, 3]).all()          # -1 did not wrap to it

    def test_one_program_for_every_writer_state(self):
        f = jax.jit(block_scatter)
        pool = _pool(nb=6, bs=4)
        vals = np.ones((2, 2), np.float32)
        for ids, offs, act in (([1, 2], [0, 3], [True, True]),
                               ([-1, 5], [0, 0], [True, True]),
                               ([4, 4], [1, 2], [False, True])):
            np.testing.assert_array_equal(
                np.asarray(f(pool, np.array(ids), np.array(offs), vals,
                             np.array(act))),
                np.asarray(_onehot_scatter(pool, np.array(ids),
                                           np.array(offs), vals,
                                           np.array(act))))
        assert f._cache_size() == 1

    def test_lowers_to_a_scatter_and_nothing_of_the_pools_shape_beside(self):
        pool = jnp.zeros((6, 4, 2, 8), jnp.float32)
        text = jax.jit(block_scatter).lower(
            pool, np.array([1, -1]), np.array([0, 0]),
            jnp.ones((2, 2, 8)), np.array([True, True])).as_text()
        assert text.count('"stablehlo.scatter"(') == 1
        # No other instruction's result has the pool's type (the
        # scatter's own closes on a line without an op name).
        import re
        made = [m.group(1) for m in re.finditer(
            r"= \"?stablehlo\.(\w+)[^\n]*tensor<6x4x2x8xf32>\n", text)]
        assert made == [], made

    def test_a_donated_pool_is_written_in_its_own_buffer(self):
        f = jax.jit(block_scatter, donate_argnums=0)
        pool = jnp.asarray(_pool(nb=6, bs=4))
        want = np.asarray(_onehot_scatter(
            pool, np.array([3]), np.array([1]), np.ones((1, 2), np.float32)))
        where = pool.unsafe_buffer_pointer()
        out = f(pool, np.array([3]), np.array([1]),
                np.ones((1, 2), np.float32))
        assert pool.is_deleted()
        assert out.unsafe_buffer_pointer() == where
        np.testing.assert_array_equal(np.asarray(out), want)
