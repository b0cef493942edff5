"""HLO-level evidence for the SPMD lowerings (VERDICT round 1, item 7).

Every facade collective is lowered to StableHLO and its collective-op
census asserted — the compile-time counterpart of test_observability.py's
scope assertions.  These tests pin the claims made in ops/spmd.py's
docstrings: one op in the source program produces exactly the stated XLA
collectives, matched p2p pairs fuse into ONE collective_permute, adjoints
add exactly their stated collective, and the Bcast_ size dispatch picks
the documented strategy per payload class.

The matchers ride the shared StableHLO parse (mpi4torch_tpu.analyze):
``census()`` is :meth:`~mpi4torch_tpu.analyze.ParsedProgram.census`,
and the compressed-path assertions read payload dtypes and named-scope
labels off the typed :class:`~mpi4torch_tpu.analyze.CollectiveOp`
records instead of ad-hoc regexes over the text.  Assertion counts and
expected values are unchanged from the regex era.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import mpi4torch_tpu as mpi
from mpi4torch_tpu import analyze
from mpi4torch_tpu.ops import spmd as spmd_mod

NR = 4

COLLECTIVES = analyze.COLLECTIVE_KINDS


def census(fn, *args):
    """Map collective-op name -> occurrence count in the lowered StableHLO
    of ``fn`` wrapped in a shard_map over a fresh NR-device mesh."""
    mesh = Mesh(np.asarray(jax.devices()[:NR]), ("w",))
    comm = mpi.comm_from_mesh(mesh, "w")

    def body(*a):
        with mpi.p2p_scope(comm):
            return fn(comm, *a)

    wrapped = shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
    txt = jax.jit(wrapped).lower(*args).as_text()
    return analyze.parse_program(txt).census()


def only(**expected):
    out = {c: 0 for c in COLLECTIVES}
    out.update(expected)
    return out


SMALL = jnp.ones((16,))                      # tree-bcast regime
# > config.bcast_tree_max_bytes (f64 under the x64 test harness: 8 B/elem).
BIG = jnp.ones((mpi.config.bcast_tree_max_bytes() // 8 + 1024,))


class TestOrderedRingFoldCensus:
    def test_ring_fold_has_no_size_n_gather(self, monkeypatch):
        # VERDICT r4 item 3 "done" criterion: deterministic mode's large-
        # payload path must not materialize a size×-tensor buffer.  The
        # census shows zero all_gathers — only the scan's ring permute and
        # the tree broadcast's permutes remain.  (Thresholds live in
        # config since ISSUE 3; patch the backing globals.)
        monkeypatch.setattr(mpi.config, "_ordered_fold_gather_max_bytes", 0)
        monkeypatch.setattr(mpi.config, "_ordered_ring_chunk_bytes", 64)
        with mpi.config.deterministic_mode(True):
            got = census(lambda c, x: c.Allreduce(x, mpi.MPI_SUM),
                         jnp.ones((513,), jnp.float32))
        assert got["all_gather"] == 0
        assert got["all_reduce"] == 0
        assert got["collective_permute"] >= 1

    def test_small_payload_keeps_gather_fold(self):
        with mpi.config.deterministic_mode(True):
            got = census(lambda c, x: c.Allreduce(x, mpi.MPI_SUM),
                         jnp.ones((16,), jnp.float32))
        assert got["all_gather"] == 1


class TestForwardCensus:
    def test_allreduce_is_one_all_reduce(self):
        got = census(lambda c, x: c.Allreduce(x, mpi.MPI_SUM), SMALL)
        assert got == only(all_reduce=1)

    def test_reduce_scatter_is_one_native_collective(self):
        # The op's existence case: ONE stablehlo.reduce_scatter — half an
        # allreduce on the wire (the ZeRO gradient-sharding path).
        got = census(lambda c, x: c.Reduce_scatter(x, mpi.MPI_SUM, 0),
                     jnp.ones((NR * 4,)))
        assert got == only(reduce_scatter=1)

    def test_reduce_scatter_fwd_bwd_is_rs_plus_allgather(self):
        # value_and_grad keeps the forward live (plain grad would DCE the
        # psum_scatter: sum's cotangent is primal-independent).
        got = census(
            lambda c, x: jax.value_and_grad(lambda v: jnp.sum(
                c.Reduce_scatter(v, mpi.MPI_SUM, 0)))(x),
            jnp.ones((NR * 4,)))
        assert got == only(reduce_scatter=1, all_gather=1)

    def test_bcast_small_is_log2_permutes(self):
        got = census(lambda c, x: c.Bcast_(x, root=1), SMALL)
        assert got == only(collective_permute=math.ceil(math.log2(NR)))

    def test_bcast_large_is_one_all_reduce(self):
        got = census(lambda c, x: c.Bcast_(x, root=1), BIG)
        assert got == only(all_reduce=1)

    def test_reduce_is_one_all_reduce(self):
        # No reduce-to-one collective exists in StableHLO; masked
        # all-reduce is the documented lowering.
        got = census(lambda c, x: c.Reduce_(x, mpi.MPI_SUM, root=0), SMALL)
        assert got == only(all_reduce=1)

    def test_allgather_is_one_all_gather(self):
        got = census(lambda c, x: c.Allgather(x, gatheraxis=0), SMALL)
        assert got == only(all_gather=1)

    def test_gather_is_one_all_gather(self):
        # Documented cost: non-roots pay the all-gather too (see
        # ops/spmd.py gather docstring).
        got = census(lambda c, x: c.Gather(x, gatheraxis=0, root=0), SMALL)
        assert got == only(all_gather=1)

    def test_scatter_is_one_reduce_scatter(self):
        got = census(
            lambda c, x: c.Scatter(x, scatteraxis=0, numelem=4, root=0),
            jnp.ones((16,)))
        assert got == only(reduce_scatter=1)

    def test_alltoall_is_one_all_to_all(self):
        got = census(
            lambda c, x: c.Alltoall(x, gatheraxis=1, scatteraxis=0,
                                    numelem=1),
            jnp.ones((NR, 2)))
        assert got == only(all_to_all=1)

    def test_matched_p2p_pair_fuses_into_one_collective_permute(self):
        def ring(c, a):
            h = c.Isend(a, (c.rank + 1) % c.size, 0)
            b = c.Recv(mpi.JoinDummies(jnp.empty_like(a), [h.dummy]),
                       (c.rank - 1) % c.size, 0)
            w = c.Wait(mpi.JoinDummiesHandle(h, [b]))
            return mpi.JoinDummies(b, [w])

        got = census(ring, SMALL)
        assert got == only(collective_permute=1)

    def test_butterfly_pair_fuses_into_one_collective_permute(self):
        # General static permutations (rank ^ k) compile exactly like
        # ring shifts: one collective_permute per matched pair, also in
        # a user-managed shard_map region (comm_from_mesh + p2p_scope).
        def butterfly(c, a):
            h = c.Isend(a, c.rank ^ 1, 0)
            b = c.Recv(mpi.JoinDummies(jnp.empty_like(a), [h.dummy]),
                       c.rank ^ 1, 0)
            w = c.Wait(mpi.JoinDummiesHandle(h, [b]))
            return mpi.JoinDummies(b, [w])

        got = census(butterfly, SMALL)
        assert got == only(collective_permute=1)

    def test_self_send_emits_no_collective(self):
        # Identity permutation = local hand-off; nothing on the wire.
        def selfsend(c, a):
            h = c.Isend(a, c.rank, 0)
            b = c.Recv(mpi.JoinDummies(jnp.empty_like(a), [h.dummy]),
                       c.rank, 0)
            w = c.Wait(mpi.JoinDummiesHandle(h, [b]))
            return mpi.JoinDummies(b, [w])

        got = census(selfsend, SMALL)
        assert got == only()


class TestAdjointCensus:
    def test_allreduce_fwd_bwd_is_two_all_reduce(self):
        # The adjoint of psum is a second psum (SURVEY.md §3.3: backward
        # re-enters the network exactly once).
        def f(c, x):
            return jax.grad(
                lambda v: jnp.vdot(c.Allreduce(v, mpi.MPI_SUM), v))(x)

        got = census(f, SMALL)
        assert got == only(all_reduce=2)

    def test_allgather_bwd_is_one_reduce_scatter(self):
        def f(c, x):
            return jax.grad(
                lambda v: jnp.sum(c.Allgather(v, gatheraxis=0) ** 2))(x)

        got = census(f, SMALL)
        assert got == only(all_gather=1, reduce_scatter=1)

    def test_gather_bwd_is_one_reduce_scatter(self):
        def f(c, x):
            return jax.grad(
                lambda v: jnp.sum(c.Gather(v, gatheraxis=0, root=0) ** 2))(x)

        got = census(f, SMALL)
        assert got == only(all_gather=1, reduce_scatter=1)

    def test_scatter_bwd_is_one_all_gather(self):
        def f(c, x):
            return jax.grad(lambda v: jnp.sum(
                c.Scatter(v, scatteraxis=0, numelem=4, root=0) ** 2))(x)

        got = census(f, jnp.ones((16,)))
        assert got == only(reduce_scatter=1, all_gather=1)

    def test_bcast_small_bwd_adds_one_all_reduce(self):
        # Adjoint of Bcast_ is Reduce_(SUM, root) — a masked all-reduce —
        # regardless of which forward strategy the size dispatch chose.
        def f(c, x):
            return jax.grad(
                lambda v: jnp.sum(c.Bcast_(v, root=1) ** 2))(x)

        got = census(f, SMALL)
        assert got == only(
            collective_permute=math.ceil(math.log2(NR)), all_reduce=1)

    def test_alltoall_fwd_bwd_is_two_all_to_all(self):
        # ISSUE 9 satellite: Alltoall was the one facade collective with
        # no adjoint census — the reshard executor leans on it, so pin
        # it: the backward is the axes-swapped all-to-all, exactly one
        # more stablehlo.all_to_all (value_and_grad keeps the forward
        # live, as in the Reduce_scatter census above).
        got = census(
            lambda c, x: jax.value_and_grad(lambda v: jnp.sum(
                c.Alltoall(v, gatheraxis=1, scatteraxis=0,
                           numelem=1) ** 2))(x),
            jnp.ones((NR, 2)))
        assert got == only(all_to_all=2)

    def test_p2p_ring_fwd_bwd_is_two_collective_permutes(self):
        # Gradients ride the reverse ring: one fused permute per
        # direction (csrc/extension.cpp:1159-1218's tag+10 discipline,
        # compiler-scheduled here).
        def ring_loss(c, a):
            h = c.Isend(a, (c.rank + 1) % c.size, 0)
            b = c.Recv(mpi.JoinDummies(jnp.empty_like(a), [h.dummy]),
                       (c.rank - 1) % c.size, 0)
            w = c.Wait(mpi.JoinDummiesHandle(h, [b]))
            return jnp.sum(mpi.JoinDummies(a + b, [w]) ** 2)

        def f(c, a):
            return jax.grad(lambda v: ring_loss(c, v))(a)

        got = census(f, SMALL)
        assert got == only(collective_permute=2)


class TestTreeBcastExecution:
    """The size dispatch must be value-invisible: both strategies produce
    the root's values on every rank, with the same adjoint."""

    @pytest.mark.parametrize("shape", [(16,), (BIG.size,)])
    @pytest.mark.parametrize("root", [0, 2])
    def test_bcast_values_match_both_strategies(self, shape, root):
        def body():
            r = jnp.asarray(mpi.COMM_WORLD.rank)
            x = jnp.full(shape, 1.0) * (r + 1.0)
            return mpi.COMM_WORLD.Bcast_(x, root=root)

        out = np.asarray(mpi.run_spmd(body, nranks=NR)())
        for r in range(NR):
            np.testing.assert_array_equal(out[r], float(root + 1))

    def test_bcast_grads_match_both_strategies(self):
        # grad through Bcast_ is Reduce_(SUM, root): root rank accumulates
        # the cotangents of every rank, non-roots get zero.
        for shape in [(16,), (BIG.size,)]:
            def body():
                def loss(x):
                    return jnp.sum(mpi.COMM_WORLD.Bcast_(x, root=1))

                return jax.grad(loss)(jnp.ones(shape))

            g = np.asarray(mpi.run_spmd(body, nranks=NR)())
            np.testing.assert_array_equal(g[1], float(NR))
            for r in (0, 2, 3):
                np.testing.assert_array_equal(g[r], 0.0)

    def test_uneven_tree_sizes(self):
        # Non-power-of-two world: the last tree round has fewer pairs.
        for nr in (3, 5, 6):
            def body():
                r = jnp.asarray(mpi.COMM_WORLD.rank)
                x = jnp.arange(8.0) + 100.0 * r
                return mpi.COMM_WORLD.Bcast_(x, root=nr - 1)

            out = np.asarray(mpi.run_spmd(body, nranks=nr)())
            for r in range(nr):
                np.testing.assert_array_equal(
                    out[r], np.arange(8.0) + 100.0 * (nr - 1))


class TestStrategyCensus:
    """Wire counts of the composed strategies: the ring-attention loop
    must ship exactly 2*(size-1) hops (K and V per non-final step) — the
    comm/compute overlap reordering must not duplicate or drop any."""

    def test_ring_attention_wire_count(self):
        from mpi4torch_tpu.parallel import ring_attention

        q = jnp.ones((1, 8 * NR, 2, 8))

        def fn(comm, q):
            r = jnp.asarray(comm.rank)
            sl = jax.lax.dynamic_slice_in_dim(q, r * 8, 8, 1)
            return ring_attention(comm, sl, sl, sl, causal=True)

        got = census(fn, q)
        assert got == only(collective_permute=2 * (NR - 1)), got

    def test_ulysses_wire_count(self):
        # Ulysses = one all_to_all per q/k/v into head-sharding plus one
        # back for the output: exactly 4, independent of size.
        from mpi4torch_tpu.parallel import ulysses_attention

        q = jnp.ones((1, 8 * NR, NR, 8))

        def fn(comm, q):
            r = jnp.asarray(comm.rank)
            sl = jax.lax.dynamic_slice_in_dim(q, r * 8, 8, 1)
            return ulysses_attention(comm, sl, sl, sl, causal=True)

        got = census(fn, q)
        assert got == only(all_to_all=4), got


class TestCompressedCensus:
    """The quantized path's compile-time evidence (ISSUE 1 acceptance):
    int8-width transfer ops in the lowered program, no fp32 all_reduce on
    the compressed path, and codec-suffixed named scopes so profiler
    traces distinguish compressed transfers."""

    def _lowered(self, fn, *args, grad=False):
        mesh = Mesh(np.asarray(jax.devices()[:NR]), ("w",))
        comm = mpi.comm_from_mesh(mesh, "w")

        def body(*a):
            out = fn(comm, *a)
            return jnp.sum(out)

        prog = body
        if grad:
            prog = jax.grad(body)
        wrapped = shard_map(prog, mesh=mesh, in_specs=P(), out_specs=P(),
                            check_vma=False)
        return jax.jit(wrapped).lower(*args).as_text(debug_info=True)

    def test_q8_allreduce_ships_int8(self):
        txt = self._lowered(
            lambda c, x: c.Allreduce(x, mpi.MPI_SUM, compression="q8"),
            jnp.ones((512,), jnp.float32))
        parsed = analyze.parse_program(txt)
        # ring hops: collective_permute on int8 tensors
        assert parsed.ops("collective_permute", dtype="i8"), \
            "no int8-width collective_permute in the compressed lowering"
        # final stage: the encoded shards all_gather as int8
        assert parsed.ops("all_gather", dtype="i8"), \
            "no int8-width all_gather in the compressed lowering"
        # nothing rides the wire at full fp32 width
        assert parsed.census()["all_reduce"] == 0

    def test_q8_allreduce_wire_census(self):
        got = census(lambda c, x: c.Allreduce(x, mpi.MPI_SUM,
                                              compression="q8"),
                     jnp.ones((512,), jnp.float32))
        # n-1 ring hops x (int8 payload + scales) permutes, one encoded
        # all_gather pair, and no exact-path collectives.
        assert got["all_reduce"] == 0
        assert got["collective_permute"] == 2 * (NR - 1)
        assert got["all_gather"] == 2
        assert got["reduce_scatter"] == 0

    def test_q8_backward_is_compressed_too(self):
        # AD transparency on the wire: the adjoint must also ship int8 —
        # twice the forward's quantized collectives, no fp32 all_reduce.
        got = census(
            lambda c, x: jax.value_and_grad(lambda v: jnp.sum(
                c.Allreduce(v, mpi.MPI_SUM, compression="q8")))(x),
            jnp.ones((512,), jnp.float32))
        assert got["all_reduce"] == 0
        assert got["collective_permute"] == 2 * 2 * (NR - 1)
        assert got["all_gather"] == 2 * 2

    def test_q8_allgather_ships_int8(self):
        txt = self._lowered(
            lambda c, x: c.Allgather(x, 0, compression="q8"),
            jnp.ones((64,), jnp.float32))
        assert analyze.parse_program(txt).ops("all_gather", dtype="i8")

    def test_named_scope_carries_codec_suffix(self):
        # The codec suffix must sit on the WIRE ops' own scope paths —
        # the analyzer recovers each collective's label from the
        # debug-info loc table, so the assertion is per-op, not a
        # whole-text substring.
        txt = self._lowered(
            lambda c, x: c.Allreduce(x, mpi.MPI_SUM, compression="q8"),
            jnp.ones((64,), jnp.float32))
        parsed = analyze.parse_program(txt)
        assert any(op.label == "mpi4torch.Allreduce.q8"
                   for op in parsed.collectives)
        txt_bwd = self._lowered(
            lambda c, x: c.Allreduce(x, mpi.MPI_SUM, compression="q8"),
            jnp.ones((64,), jnp.float32), grad=True)
        parsed_bwd = analyze.parse_program(txt_bwd)
        assert any("mpi4torch.AllreduceBackward.q8" in op.scope
                   for op in parsed_bwd.collectives)

    def test_exact_path_untouched(self):
        # compression=None keeps the documented exact lowering.
        got = census(lambda c, x: c.Allreduce(x, mpi.MPI_SUM), SMALL)
        assert got == only(all_reduce=1)
