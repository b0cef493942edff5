"""Fused bucketed collectives (mpi4torch_tpu.fuse, ISSUE 2).

Four claims are pinned here:

1. **Launch census** — a 100-leaf fp32 pytree Allreduce lowers to exactly
   ONE all-reduce under SPMD when it fits one bucket, and to exactly
   ``ceil(total_bytes / bucket_bytes)`` of them when it does not (vs one
   all_reduce per leaf unfused).  Since PR 35 no bucket of the blocking
   path is a reduce-scatter + all-gather pair: on the chip that was an
   all-reduce and then an all-gather, with nothing in flight.
2. **Parity** — the fused path is bit-identical to the per-leaf path on
   the eager backend (same ascending-rank fold, concat changes nothing
   per element), including the Isend/Irecv overlap pipeline, and matches
   it to fp tolerance on the SPMD mesh.
3. **AD transparency** — gradients through fused (and fused+compressed)
   buckets equal the per-leaf gradients; the backward program is itself
   bucketed (census counts double, not per-leaf).
4. **DP lock-step** — ``all_average_tree``'s fused mean keeps gradients
   bitwise identical across ranks (the regression test of the
   single-post-fuse-scale change in parallel/dp.py).
"""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import mpi4torch_tpu as mpi
from mpi4torch_tpu import fuse
from jax import shard_map
from mpi4torch_tpu.fuse.bucketing import bucket_layout, flatten_buckets

NR = 4
comm = mpi.COMM_WORLD

COLLECTIVES = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all",
               "collective_permute")


def lowered_text(fn, *args):
    """``fn`` lowered in a shard_map over a fresh NR-device mesh (the
    test_hlo.py pattern)."""
    mesh = Mesh(np.asarray(jax.devices()[:NR]), ("w",))
    c = mpi.comm_from_mesh(mesh, "w")
    wrapped = shard_map(lambda *a: fn(c, *a), mesh=mesh, in_specs=P(),
                        out_specs=P(), check_vma=False)
    return jax.jit(wrapped).lower(*args).as_text()


def allreduce_operands(txt):
    """The operand type of every ``stablehlo.all_reduce`` in ``txt``."""
    return re.findall(r'"stablehlo\.all_reduce".*?:\s*\(tensor<([^>]+)>\)',
                      txt, flags=re.S)


def census(fn, *args):
    """Collective-op census of ``fn`` lowered so."""
    txt = lowered_text(fn, *args)
    return {k: txt.count(f"stablehlo.{k}") for k in COLLECTIVES}


def tree100():
    # 100 fp32 leaves, 6400 B total — far under one 4 MiB bucket.
    return {f"p{i}": jnp.full((16,), float(i + 1), jnp.float32)
            for i in range(100)}


def mixed_tree(scale=1.0):
    return {
        "a": jnp.arange(7, dtype=jnp.float32) * scale,
        "b": [jnp.ones((3, 5), jnp.float64) * 2.0 * scale,
              jnp.arange(4, dtype=jnp.int32)],
        "c": jnp.linspace(0.0, 1.0, 9, dtype=jnp.float64) * scale,
        "d": jnp.float32(scale),
    }


# ---------------------------------------------------------------------------
# Bucketing layout
# ---------------------------------------------------------------------------


class TestBucketing:
    def test_roundtrip_identity(self):
        t = mixed_tree(3.0)
        buckets, layout = flatten_buckets(t, 1 << 22)
        back = fuse.unflatten_buckets(buckets, layout)
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                       np.asarray(b)),
            t, back)

    def test_buckets_are_dtype_homogeneous(self):
        buckets, layout = flatten_buckets(mixed_tree(), 1 << 22)
        for b, dt in zip(buckets, layout.bucket_dtypes):
            assert b.dtype == dt
        # f32 leaves (a, d), f64 leaves (b0, c), i32 leaf (b1) — three
        # dtype classes, three buckets at this size.
        assert layout.num_buckets == 3

    def test_layout_cached_per_structure(self):
        t = tree100()
        l1 = bucket_layout(t, 1 << 22)
        l2 = bucket_layout(jax.tree.map(lambda x: x * 2.0, t), 1 << 22)
        assert l1 is l2          # lru_cache hit: same structure+avals
        l3 = bucket_layout(t, 1 << 20)
        assert l3 is not l1      # different bucket size, different plan

    def test_bucket_bytes_respected_and_oversize_leaf_isolated(self):
        t = {"small": [jnp.ones((64,), jnp.float32) for _ in range(8)],
             "big": jnp.ones((1024,), jnp.float32)}
        layout = bucket_layout(t, 1024)      # 256 B leaves, 4 KiB big leaf
        sizes = layout.bucket_sizes
        # 8 small leaves -> 4 elem/bucket... 64*4B=256B, 4 per 1 KiB
        # bucket -> 2 buckets of 256 elems; the big leaf overflows any
        # bucket and sits alone in its own.
        assert 1024 in sizes
        for sz, dt in zip(sizes, layout.bucket_dtypes):
            if sz != 1024:
                assert sz * jnp.dtype(dt).itemsize <= 1024


# ---------------------------------------------------------------------------
# HLO census: launches
# ---------------------------------------------------------------------------


class TestFusedCensus:
    def test_100_leaves_one_collective(self):
        # The ISSUE 2 acceptance bar: <= 4 MiB of fp32 leaves -> exactly
        # one fused collective, nothing else; since PR 35 one all-reduce.
        got = census(lambda c, t: c.Allreduce_tree(t, mpi.MPI_SUM),
                     tree100())
        assert got == {"all_reduce": 1, "all_gather": 0,
                       "reduce_scatter": 0, "all_to_all": 0,
                       "collective_permute": 0}

    def test_no_bucket_is_scattered_or_viewed_as_size_by_seg(self):
        # On the TPU a (size, seg) view of a flat bucket is a relayout
        # whose kernel took minutes to compile at 128 MiB and crashed
        # the compiler at 541M parameters on four chips (PR 22), and a
        # flat reduce-scatter compiles to an all-reduce and a slice
        # (PR 35): no bucket of the blocking path, of one leaf or of
        # several, forward or adjoint, is split over the ranks at all.
        t = {"w": jnp.ones((64, 48), jnp.float32),
             "a": jnp.ones((8, 6), jnp.float64),
             "b": jnp.ones((5,), jnp.float64)}
        for fn in (lambda c, tt: c.Allreduce_tree(tt, mpi.MPI_SUM),
                   lambda c, tt: jax.grad(lambda u: sum(
                       jnp.sum(v * v) for v in jax.tree.leaves(
                           c.Allreduce_tree(u, mpi.MPI_SUM, mean=True))))(
                               tt)):
            txt = lowered_text(fn, t)
            for gone in ("reduce_scatter", "all_gather", "dynamic_slice"):
                assert f"stablehlo.{gone}" not in txt, gone
            made = re.findall(r"stablehlo\.reshape.*?-> tensor<([^>]+)>", txt)
            assert made and not [m for m in made
                                 if m.startswith(f"{NR}x")], made

    def test_unfused_baseline_is_per_leaf(self):
        got = census(
            lambda c, t: jax.tree.map(
                lambda p: c.Allreduce(p, mpi.MPI_SUM), t),
            tree100())
        assert got["all_reduce"] == 100

    def test_bucket_count_matches_ceil_bound(self):
        # 100 leaves x 64 B; bucket_bytes=1024 packs exactly 16 leaves
        # per bucket -> ceil(6400/1024) = 7 all-reduces.
        t = tree100()
        total = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))
        bb = 1024
        expect = math.ceil(total / bb)
        got = census(
            lambda c, tt: c.Allreduce_tree(tt, mpi.MPI_SUM,
                                           bucket_bytes=bb), t)
        assert got["all_reduce"] == expect
        assert got["reduce_scatter"] == got["all_gather"] == 0

    def test_fusion_scope_zero_disables(self):
        def f(c, t):
            with mpi.config.fusion_scope(0):
                return c.Allreduce_tree(t, mpi.MPI_SUM)

        got = census(f, tree100())
        assert got["all_reduce"] == 100
        assert got["reduce_scatter"] == 0

    def test_fusion_scope_sets_bucket_size(self):
        def f(c, t):
            with mpi.config.fusion_scope(1024):
                return c.Allreduce_tree(t, mpi.MPI_SUM)

        got = census(f, tree100())
        assert got["all_reduce"] == 7
        # and the default is restored outside the scope
        assert mpi.config.default_bucket_bytes() \
            == mpi.config.DEFAULT_BUCKET_BYTES

    def test_backward_is_bucketed_too(self):
        # AD transparency at the launch level: fwd+bwd of one fused
        # bucket is two all-reduces, not 100 + 100 per-leaf collectives.
        def f(c, t):
            def loss(tt):
                y = c.Allreduce_tree(tt, mpi.MPI_SUM)
                return sum(jnp.vdot(v, v) for v in jax.tree.leaves(y))
            return jax.grad(loss)(t)

        got = census(f, tree100())
        assert got["all_reduce"] == 2
        assert got["reduce_scatter"] == got["all_gather"] == 0

    @pytest.mark.parametrize("overlap", [None, False])
    def test_buckets_are_not_staged_behind_one_another(self, overlap):
        # Seven buckets, forward and adjoint: the chain of
        # optimization_barriers that held bucket i's all-gather behind
        # bucket i+1's reduce-scatter went with the pair (PR 35), and
        # overlap=False, which used to switch it off, now says nothing.
        def f(c, t):
            return jax.grad(lambda tt: sum(
                jnp.vdot(v, v) for v in jax.tree.leaves(c.Allreduce_tree(
                    tt, mpi.MPI_SUM, bucket_bytes=1024, mean=True,
                    overlap=overlap))))(t)

        txt = lowered_text(f, tree100())
        assert txt.count("stablehlo.all_reduce") == 14
        assert "optimization_barrier" not in txt

    def test_one_leaf_bucket_travels_in_the_leafs_shape(self):
        # A leaf alone in its bucket (every matrix of a real model: the
        # buckets are 4 MiB) is reduced as it is and comes back as it
        # is.  Flat, the TPU pays a relayout of a tiled array each way
        # (PERF.md, PR 35); the small leaves beside it still share one
        # flat bucket.
        t = {"w": jnp.ones((64, 48), jnp.float32)}
        avg = lambda c, tt: c.Allreduce_tree(tt, mpi.MPI_SUM, mean=True)
        grad = lambda c, tt: jax.grad(
            lambda u: jnp.sum(avg(c, u)["w"] ** 2))(tt)
        for fn, n in ((avg, 1), (grad, 2)):
            txt = lowered_text(fn, t)
            assert "stablehlo.reshape" not in txt
            assert "stablehlo.slice" not in txt
            assert allreduce_operands(txt) == ["64x48xf32"] * n
        mixed = dict(t, b=jnp.ones((48,), jnp.float32),
                     s=jnp.ones((), jnp.float32))
        txt = lowered_text(
            lambda c, tt: c.Allreduce_tree(tt, mpi.MPI_SUM,
                                           bucket_bytes=4096), mixed)
        assert sorted(allreduce_operands(txt)) == ["49xf32", "64x48xf32"]

    def test_compressed_buckets_ship_int8(self):
        mesh = Mesh(np.asarray(jax.devices()[:NR]), ("w",))
        c = mpi.comm_from_mesh(mesh, "w")
        t = {f"p{i}": jnp.ones((64,), jnp.float32) for i in range(10)}

        def f(tt):
            return c.Allreduce_tree(tt, mpi.MPI_SUM, compression="q8")

        txt = jax.jit(shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                                check_vma=False)).lower(t).as_text()
        assert re.search(r"collective_permute.*xi8>", txt), \
            "fused q8 bucket did not ride the int8 ring"
        assert txt.count("stablehlo.all_reduce") == 0

    def test_zero3_regather_is_one_allgather_per_bucket(self):
        from mpi4torch_tpu.parallel import zero

        t = {f"w{i}": jnp.ones((8, 3), jnp.float64) for i in range(12)}

        def f(c, tt):
            shards = zero.zero3_shard_params(c, tt)
            return zero.zero3_params(c, shards, tt)

        got = census(f, t)
        assert got["all_gather"] == 1
        assert got["all_reduce"] == 0

    def test_zero_grad_shard_is_one_reduce_scatter_per_bucket(self):
        def f(c, tt):
            return fuse.fused_reduce_scatter_tree(c, tt, mpi.MPI_SUM,
                                                  mean=True)

        got = census(f, {f"g{i}": jnp.ones((10,), jnp.float64)
                         for i in range(12)})
        assert got["reduce_scatter"] == 1
        assert got["all_reduce"] == got["all_gather"] == 0


# ---------------------------------------------------------------------------
# Value / gradient parity
# ---------------------------------------------------------------------------


def _perleaf_allreduce(c, t, **kw):
    return jax.tree.map(lambda p: c.Allreduce(p, mpi.MPI_SUM, **kw), t)


class TestParity:
    def test_eager_fused_bitwise_equals_perleaf(self):
        def body():
            t = mixed_tree(float(comm.rank + 1))
            fused = comm.Allreduce_tree(t, mpi.MPI_SUM)
            ref = _perleaf_allreduce(comm, t)
            return jax.tree.map(np.asarray, (fused, ref))

        for fused, ref in mpi.run_ranks(body, NR):
            jax.tree.map(np.testing.assert_array_equal, fused, ref)

    def test_eager_overlap_pipeline_bitwise_equals_perleaf(self):
        def body():
            t = {"a": jnp.arange(13, dtype=jnp.float64) * (comm.rank + 1),
                 "b": jnp.ones((5, 3), jnp.float64) * (comm.rank - 1.5)}
            fused = fuse.fused_allreduce_tree(comm, t, mpi.MPI_SUM,
                                              overlap=True)
            ref = _perleaf_allreduce(comm, t)
            return jax.tree.map(np.asarray, (fused, ref))

        for fused, ref in mpi.run_ranks(body, NR):
            jax.tree.map(np.testing.assert_array_equal, fused, ref)

    def test_eager_overlap_pipeline_multibucket_and_grads(self):
        # Several buckets in flight (bucket_bytes forces 4 buckets of 2
        # leaves); values and gradients must both match the per-leaf
        # path bitwise.
        def body():
            t = {f"p{i}": jnp.arange(8, dtype=jnp.float64) + comm.rank + i
                 for i in range(8)}

            def loss_fused(tt):
                y = fuse.fused_allreduce_tree(comm, tt, mpi.MPI_SUM,
                                              bucket_bytes=128,
                                              overlap=True)
                return sum(jnp.vdot(v, v) for v in jax.tree.leaves(y))

            def loss_ref(tt):
                y = _perleaf_allreduce(comm, tt)
                return sum(jnp.vdot(v, v) for v in jax.tree.leaves(y))

            vf, gf = jax.value_and_grad(loss_fused)(t)
            vr, gr = jax.value_and_grad(loss_ref)(t)
            return np.asarray(vf), np.asarray(vr), \
                jax.tree.map(np.asarray, (gf, gr))

        for vf, vr, (gf, gr) in mpi.run_ranks(body, NR):
            np.testing.assert_array_equal(vf, vr)
            jax.tree.map(np.testing.assert_array_equal, gf, gr)

    def test_spmd_fused_matches_eager_oracle(self):
        data = {"a": np.linspace(-2.0, 3.0, 17),
                "c": np.sin(np.arange(33, dtype=np.float64))}

        def eager_body():
            t = jax.tree.map(lambda x: jnp.asarray(x) * (comm.rank + 1),
                             data)
            return jax.tree.map(np.asarray,
                                comm.Allreduce_tree(t, mpi.MPI_SUM))

        oracle = mpi.run_ranks(eager_body, NR)[0]

        def spmd_body():
            r = jnp.asarray(comm.rank + 0)
            t = jax.tree.map(lambda x: jnp.asarray(x) * (r + 1.0), data)
            return comm.Allreduce_tree(t, mpi.MPI_SUM)

        out = mpi.run_spmd(spmd_body, nranks=NR)()
        for rank in range(NR):
            jax.tree.map(
                lambda o, s: np.testing.assert_allclose(
                    o, np.asarray(s)[rank], rtol=1e-12, atol=1e-12),
                oracle, out)

    def test_spmd_deterministic_fused_bitwise_matches_eager(self):
        data = np.sin(np.arange(40, dtype=np.float32)).reshape(8, 5)

        def eager_body():
            t = {"x": jnp.asarray(data) * (comm.rank + 1)}
            return np.asarray(comm.Allreduce_tree(t, mpi.MPI_SUM)["x"])

        oracle = mpi.run_ranks(eager_body, NR)[0]

        def spmd_body():
            r = jnp.asarray(comm.rank + 0)
            t = {"x": jnp.asarray(data) * (r + 1.0).astype(jnp.float32)}
            return comm.Allreduce_tree(t, mpi.MPI_SUM)["x"]

        with mpi.config.deterministic_mode(True):
            out = np.asarray(mpi.run_spmd(spmd_body, nranks=NR)())
        for rank in range(NR):
            np.testing.assert_array_equal(out[rank], oracle)

    def test_spmd_fused_grads_match_perleaf(self):
        def body():
            r = jnp.asarray(comm.rank + 0)
            t = {"a": jnp.arange(7.0) * (r + 1.0),
                 "b": jnp.ones((3, 5)) * (r + 2.0)}

            def loss(fn, tt):
                y = fn(tt)
                return sum(jnp.vdot(v, v) for v in jax.tree.leaves(y))

            gf = jax.grad(lambda tt: loss(
                lambda u: comm.Allreduce_tree(u, mpi.MPI_SUM), tt))(t)
            gr = jax.grad(lambda tt: loss(
                lambda u: _perleaf_allreduce(comm, u), tt))(t)
            return gf, gr

        gf, gr = mpi.run_spmd(body, nranks=NR)()
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-12, atol=1e-12),
            gf, gr)

    @staticmethod
    def _small_and_large(r):
        # "w" and "e" overflow a 256 B bucket and travel alone, in their
        # own shapes; "b", "s" and "g" share flat buckets.
        rng = np.random.default_rng(5)
        t = {"w": rng.standard_normal((9, 7)), "b": rng.standard_normal(7),
             "s": rng.standard_normal(()), "e": rng.standard_normal((3, 4, 5)),
             "g": rng.standard_normal((2, 3))}
        return jax.tree.map(lambda x: jnp.asarray(x) * (r + 1.0), t)

    @staticmethod
    def _fused_and_perleaf(t, mean):
        """(value, grads) through 256 B buckets and through one
        ``Allreduce`` a leaf."""
        div = comm.size if mean else 1
        vg = lambda reduce: jax.value_and_grad(lambda tt: sum(
            jnp.vdot(v, v) for v in jax.tree.leaves(reduce(tt))))(t)
        return (vg(lambda tt: comm.Allreduce_tree(
                    tt, mpi.MPI_SUM, mean=mean, bucket_bytes=256)),
                vg(lambda tt: jax.tree.map(
                    lambda p: comm.Allreduce(p, mpi.MPI_SUM) / div, tt)))

    @pytest.mark.parametrize("mean", [False, True])
    def test_eager_small_and_large_leaves_bitwise_equal_perleaf(self, mean):
        def body():
            return jax.tree.map(np.asarray, self._fused_and_perleaf(
                self._small_and_large(float(comm.rank)), mean))

        for fused, ref in mpi.run_ranks(body, NR):
            jax.tree.map(np.testing.assert_array_equal, fused, ref)

    @pytest.mark.parametrize("mean", [False, True])
    def test_spmd_small_and_large_leaves_match_perleaf(self, mean):
        fused, ref = mpi.run_spmd(lambda: self._fused_and_perleaf(
            self._small_and_large(jnp.asarray(comm.rank + 0.0)), mean),
            nranks=NR)()
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-12, atol=1e-12),
            fused, ref)

    def test_nonsum_op_fused(self):
        def body():
            t = {"a": jnp.asarray([comm.rank, -comm.rank], jnp.float64),
                 "b": jnp.full((3,), float(comm.rank), jnp.float64)}
            got = comm.Allreduce_tree(t, mpi.MPI_MAX)
            ref = jax.tree.map(
                lambda p: comm.Allreduce(p, mpi.MPI_MAX), t)
            return jax.tree.map(np.asarray, (got, ref))

        for got, ref in mpi.run_ranks(body, NR):
            jax.tree.map(np.testing.assert_array_equal, got, ref)

    def test_mean_is_single_postfuse_scale(self):
        # mean=True equals per-leaf Allreduce / size bitwise in eager.
        def body():
            t = mixed_tree(float(comm.rank + 1))
            t = {"a": t["a"], "c": t["c"]}     # float leaves only
            got = comm.Allreduce_tree(t, mpi.MPI_SUM, mean=True)
            ref = jax.tree.map(
                lambda p: comm.Allreduce(p, mpi.MPI_SUM) / comm.size, t)
            return jax.tree.map(np.asarray, (got, ref))

        for got, ref in mpi.run_ranks(body, NR):
            jax.tree.map(np.testing.assert_array_equal, got, ref)

    def test_mean_with_nonsum_raises(self):
        with pytest.raises(mpi.CommError, match="mean"):
            comm.Allreduce_tree({"a": jnp.ones(3)}, mpi.MPI_MAX, mean=True)

    def test_eager_overlap_with_codec_or_nonsum_raises(self):
        # An explicit overlap=True must never silently degrade to the
        # blocking rendezvous path: the pipeline is exact-SUM-only.
        def body():
            t = {"a": jnp.ones(4)}
            got = []
            for kwargs in ({"compression": "q8"}, {}):
                try:
                    comm.Allreduce_tree(
                        t, mpi.MPI_MAX if not kwargs else mpi.MPI_SUM,
                        overlap=True, **kwargs)
                    got.append("no error")
                except mpi.CommError as e:
                    got.append("pipeline" in str(e))
            return got

        assert all(all(r) for r in mpi.run_ranks(body, NR))

    def test_stale_shard_tree_raises(self):
        # flatten_shard_rows must reject a shard tree that does not
        # belong to the template (the old per-leaf tree.map raised too).
        from mpi4torch_tpu.parallel import zero

        def body():
            t = {"w": jnp.ones((6,)), "b": jnp.ones((3,))}
            shards = zero.zero3_shard_params(comm, t)
            stale = {"w": shards["w"]}              # leaf removed
            try:
                zero.zero3_params(comm, stale, t)
            except ValueError as e:
                return "structure" in str(e)
            return False

        assert all(mpi.run_ranks(body, NR))


# ---------------------------------------------------------------------------
# Compression interaction
# ---------------------------------------------------------------------------


class TestCompressedBuckets:
    def test_fused_q8_grads_close_to_exact(self):
        # Gradient correctness through fused + compressed buckets: the
        # adjoint is a compressed bucketed collective; on rank-uniform
        # values q8's block scaling is tight.
        def body():
            t = {"a": jnp.full((32,), 2.0 + comm.rank, jnp.float32),
                 "b": jnp.full((16,), -1.0 - comm.rank, jnp.float32)}

            def loss(tt):
                y = comm.Allreduce_tree(tt, mpi.MPI_SUM, compression="q8")
                return sum(jnp.sum(v) for v in jax.tree.leaves(y))

            return jax.tree.map(np.asarray, jax.grad(loss)(t))

        for g in mpi.run_ranks(body, NR):
            # d(sum of AR(x)) / dx = size on every slot, through the
            # quantized wire (scales are exact powers-free but tight on
            # constants).
            jax.tree.map(
                lambda a: np.testing.assert_allclose(a, float(NR),
                                                     rtol=1e-2), g)

    def test_scope_default_degrades_int_leaves(self):
        def body():
            t = mixed_tree(float(comm.rank + 1))    # has an int32 leaf
            with mpi.config.compression_scope("q8"):
                got = comm.Allreduce_tree(t, mpi.MPI_SUM)
            ref = _perleaf_allreduce(comm, t, compression=False)
            # int leaf must be exact; float leaves carry q8 error
            np.testing.assert_array_equal(np.asarray(got["b"][1]),
                                          np.asarray(ref["b"][1]))
            np.testing.assert_allclose(np.asarray(got["a"]),
                                       np.asarray(ref["a"]), rtol=0.05,
                                       atol=0.05)
            return True

        assert all(mpi.run_ranks(body, NR))

    def test_explicit_codec_on_int_leaf_raises(self):
        def body():
            t = {"i": jnp.arange(4, dtype=jnp.int32)}
            try:
                comm.Allreduce_tree(t, mpi.MPI_SUM, compression="q8")
            except ValueError as e:
                return "requires a floating tensor" in str(e)
            return False

        assert all(mpi.run_ranks(body, NR))

    def test_explicit_false_overrides_scope_in_buckets(self):
        def body():
            t = {"a": jnp.full((8,), 1.0 + comm.rank, jnp.float64)}
            with mpi.config.compression_scope("q8"):
                got = comm.Allreduce_tree(t, mpi.MPI_SUM,
                                          compression=False)
            ref = _perleaf_allreduce(comm, t, compression=False)
            return jax.tree.map(np.asarray, (got, ref))

        for got, ref in mpi.run_ranks(body, NR):
            jax.tree.map(np.testing.assert_array_equal, got, ref)


# ---------------------------------------------------------------------------
# DP lock-step regression (parallel/dp.py single post-fuse scale)
# ---------------------------------------------------------------------------


class TestDPLockstep:
    def test_all_average_tree_bitwise_lockstep_across_ranks(self):
        from mpi4torch_tpu.parallel import all_average_tree

        def body():
            rng = np.random.default_rng(100 + comm.rank)
            t = {"w": jnp.asarray(rng.standard_normal((11, 3))),
                 "b": jnp.asarray(rng.standard_normal(7))}
            return jax.tree.map(np.asarray, all_average_tree(comm, t))

        outs = mpi.run_ranks(body, NR)
        for other in outs[1:]:
            jax.tree.map(np.testing.assert_array_equal, outs[0], other)

    def test_dp_grads_bitwise_lockstep_across_ranks(self):
        from mpi4torch_tpu.parallel import dp_value_and_grad

        rng = np.random.default_rng(7)
        X = jnp.asarray(rng.standard_normal((8 * NR, 3)))
        y = jnp.asarray(rng.standard_normal(8 * NR))
        w0 = jnp.asarray(rng.standard_normal(3))

        def local_loss(w, batch):
            xb, yb = batch
            return jnp.mean((xb @ w - yb) ** 2)

        def body():
            r = comm.rank
            batch = (X[r * 8:(r + 1) * 8], y[r * 8:(r + 1) * 8])
            val, grad = dp_value_and_grad(comm, local_loss)(w0, batch)
            return np.asarray(val), np.asarray(grad)

        outs = mpi.run_ranks(body, NR)
        for val, grad in outs[1:]:
            np.testing.assert_array_equal(val, outs[0][0])
            np.testing.assert_array_equal(grad, outs[0][1])


# ---------------------------------------------------------------------------
# Fused ZeRO building blocks
# ---------------------------------------------------------------------------


class TestZeroFused:
    def test_fused_reduce_scatter_tree_matches_perleaf(self):
        def body():
            rng = np.random.default_rng(comm.rank)
            t = {"w": jnp.asarray(rng.standard_normal((5, 3))),
                 "b": jnp.asarray(rng.standard_normal(9))}
            got = fuse.fused_reduce_scatter_tree(comm, t, mpi.MPI_SUM,
                                                 mean=True)

            def per_leaf(g):
                flat = jnp.asarray(g).reshape(-1)
                per = -(-flat.shape[0] // comm.size)
                padded = jnp.pad(flat,
                                 (0, per * comm.size - flat.shape[0]))
                return comm.Reduce_scatter(padded, mpi.MPI_SUM, 0) \
                    / comm.size

            ref = jax.tree.map(per_leaf, t)
            return jax.tree.map(np.asarray, (got, ref))

        for got, ref in mpi.run_ranks(body, NR):
            jax.tree.map(np.testing.assert_array_equal, got, ref)

    def test_fused_allgather_tree_roundtrip(self):
        from mpi4torch_tpu.parallel import zero

        def body():
            t = {"w": jnp.arange(13, dtype=jnp.float64).reshape(1, 13),
                 "b": jnp.linspace(-1.0, 1.0, 6)}
            shards = zero.zero3_shard_params(comm, t)
            back = zero.zero3_params(comm, shards, t)
            return jax.tree.map(np.asarray, (t, back))

        for t, back in mpi.run_ranks(body, NR):
            jax.tree.map(np.testing.assert_array_equal, t, back)
