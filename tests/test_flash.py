"""Fused block-attention kernel tests.

The Pallas kernel (run in interpret mode on the CPU host — the kernel-level
analogue of the CPU-mesh harness) must match the jnp reference path, which
itself must match the dense oracle; grads flow through the shared
custom_vjp backward.  Merging partials must reproduce un-blocked attention
exactly, because ring attention is built on it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4torch_tpu as mpi
from mpi4torch_tpu import COMM_WORLD as comm
from mpi4torch_tpu.ops import flash
from mpi4torch_tpu.parallel import dense_attention, ring_attention

B, S, H, D = 2, 16, 2, 8          # jnp-path shapes (D too small for pallas)
PB, PS, PH, PD = 1, 256, 2, 128   # pallas-eligible shapes


def qkv(shape, seed=0, dtype=jnp.float64):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal(shape), dtype)
                 for _ in range(3))


class TestJnpBlock:
    @pytest.mark.parametrize("causal", [False, True])
    def test_single_block_matches_dense(self, causal):
        q, k, v = qkv((B, S, H, D))
        out, _ = flash.flash_block_attention(q, k, v, causal=causal,
                                             impl="jnp")
        ref = dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-12, atol=1e-14)

    @pytest.mark.parametrize("causal", [False, True])
    def test_merge_matches_dense(self, causal):
        q, k, v = qkv((B, S, H, D))
        o1, l1 = flash.flash_block_attention(
            q, k[:, :S // 2], v[:, :S // 2], causal=causal, impl="jnp")
        o2, l2 = flash.flash_block_attention(
            q, k[:, S // 2:], v[:, S // 2:], causal=causal,
            kv_offset=S // 2, impl="jnp")
        out, _ = flash.merge_partials(o1, l1, o2, l2)
        ref = dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-10, atol=1e-12)

    def test_offsets_shift_the_causal_frontier(self):
        q, k, v = qkv((B, S, H, D))
        # q sits entirely after kv: causal mask passes everything.
        out, _ = flash.flash_block_attention(q, k, v, causal=True,
                                             q_offset=S, impl="jnp")
        ref = dense_attention(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-12, atol=1e-14)

    def test_fully_masked_block_is_neutral(self):
        q, k, v = qkv((B, S, H, D))
        out, lse = flash.flash_block_attention(q, k, v, causal=True,
                                               kv_offset=S, impl="jnp")
        assert np.all(np.asarray(out) == 0.0)
        assert np.all(np.asarray(lse) == flash.NEG_BIG)
        # Merging it changes nothing.
        o1, l1 = flash.flash_block_attention(q, k, v, impl="jnp")
        o2, l2 = flash.merge_partials(o1, l1, out, lse)
        np.testing.assert_allclose(np.asarray(o2), np.asarray(o1),
                                   rtol=1e-12, atol=1e-14)

    def test_tiled_backward_matches_dense_oracle(self):
        # sk=1024 crosses _BWD_TILE_ABOVE: the backward recomputes scores
        # in KV tiles; gradients must still match the dense oracle.
        q, k, v = qkv((1, 1024, 2, 8), seed=5)
        assert k.shape[1] > flash._BWD_TILE_ABOVE

        def f_flash(q, k, v):
            out, _ = flash.flash_block_attention(q, k, v, causal=True,
                                                 impl="jnp")
            return jnp.sum(out ** 2)

        def f_dense(q, k, v):
            return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-9, atol=1e-11)

    def test_grads_match_dense_oracle(self):
        q, k, v = qkv((B, S, H, D))

        def f_flash(q, k, v):
            out, _ = flash.flash_block_attention(q, k, v, causal=True,
                                                 impl="jnp")
            return jnp.sum(out ** 2)

        def f_dense(q, k, v):
            return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-10, atol=1e-12)


class TestPallasKernel:
    """f32 shapes meeting the TPU tiling constraints, run interpreted."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_jnp_path(self, causal):
        q, k, v = qkv((PB, PS, PH, PD), dtype=jnp.float32)
        o_p, l_p = flash.flash_block_attention(q, k, v, causal=causal,
                                               impl="pallas")
        o_j, l_j = flash.flash_block_attention(q, k, v, causal=causal,
                                               impl="jnp")
        np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_j),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(l_p), np.asarray(l_j),
                                   rtol=1e-5, atol=1e-6)

    def test_traced_offsets(self):
        q, k, v = qkv((PB, PS, PH, PD), dtype=jnp.float32)

        @jax.jit
        def f(off):
            return flash.flash_block_attention(
                q, k, v, causal=True, q_offset=off, impl="pallas")[0]

        got = f(jnp.asarray(PS))
        ref, _ = flash.flash_block_attention(q, k, v, causal=True,
                                             q_offset=PS, impl="jnp")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_grads_flow(self):
        q, k, v = qkv((PB, PS, PH, PD), dtype=jnp.float32)

        def f(q, k, v):
            out, _ = flash.flash_block_attention(q, k, v, causal=True,
                                                 impl="pallas")
            return jnp.sum(out ** 2)

        g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        ref = jax.grad(
            lambda q, k, v: jnp.sum(
                flash.flash_block_attention(q, k, v, causal=True,
                                            impl="jnp")[0] ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


class TestPallasBackwardKernel:
    """The fused dq/dk/dv kernels (interpret mode) vs the jnp backward.
    test_grads_flow above covers the plain-out cotangent; these cover the
    kernel-dispatch predicate and the lse cotangent (dlse is live under
    ring attention, whose merge consumes lse)."""

    def test_bwd_kernel_dispatch_predicate(self):
        q, k, v = qkv((PB, PS, PH, PD), dtype=jnp.float32)
        assert flash._bwd_eligible(q, k)
        qd, kd, vd = qkv((B, S, H, D))          # f64: x64 oracle suite
        assert not flash._bwd_eligible(qd, kd)

    def test_lse_cotangent_matches_jnp(self):
        q, k, v = qkv((1, 256, 2, 128), dtype=jnp.float32, seed=3)

        def loss(impl):
            def f(q, k, v):
                out, lse = flash.flash_block_attention(
                    q, k, v, causal=True, impl=impl)
                # lse participates with a nontrivial weight, as in the
                # ring merge.
                return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))
            return f

        ga = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
        gb = jax.grad(loss("jnp"), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_fully_masked_rows_zero_grads(self):
        # kv entirely in the future of q: every row masked, lse=NEG_BIG;
        # the kernel's where-masking must keep p (= exp(garbage)) out of
        # the gradients, yielding exact zeros like the oracle.
        q, k, v = qkv((1, 128, 1, 64), dtype=jnp.float32)

        def f(q, k, v):
            out, _ = flash.flash_block_attention(
                q, k, v, causal=True, kv_offset=256, impl="pallas")
            return jnp.sum(out ** 2)

        g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        for a in g:
            np.testing.assert_array_equal(np.asarray(a), 0.0)


def _tiles(kernel, qt, kt, q, k):
    """Explicit tiles for one kernel, with the plan's own VMEM count."""
    return flash.kernel_tiles(kernel, qt, kt, q.shape[1], k.shape[1],
                              q.shape[3], q.dtype)


def _kernels_vs_oracle(q, k, v, *, fwd, dq=None, dkv=None, causal=True,
                       window=0, q_off=0, kv_off=0, traced=False, seed=0,
                       rtol=1e-4, atol=1e-4):
    """All three kernels (interpreted here, compiled under ``make
    tpu-test``) at explicit ``(q_tile, kv_tile)`` pairs against the jnp
    oracle: out, lse, and dq / dk / dv for a random cotangent on BOTH
    outputs (dlse is live under ring attention)."""
    dq, dkv = dq or fwd, dkv or fwd
    interpret = not flash._on_tpu()
    rng = np.random.default_rng(seed + 1000)
    do = jnp.asarray(rng.standard_normal(q.shape), q.dtype)
    dlse = jnp.asarray(rng.standard_normal(q.shape[:3]), jnp.float32)

    def kernels(qo, ko):
        out, lse = flash._pallas_block(
            q, k, v, qo, ko, causal, interpret, window,
            tiles=_tiles("fwd", *fwd, q, k))
        dd = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                     axis=-1) - dlse
        return (out, lse) + flash._pallas_bwd(
            q, k, v, do, lse, dd, qo, ko, causal, interpret, window,
            tiles_dq=_tiles("dq", *dq, q, k),
            tiles_dkv=_tiles("dkv", *dkv, q, k))

    offs = (jnp.int32(q_off), jnp.int32(kv_off))
    got = jax.jit(kernels)(*offs) if traced else kernels(*offs)
    (out_j, lse_j), vjp = jax.vjp(
        lambda q, k, v: flash.flash_block_attention(
            q, k, v, causal=causal, q_offset=q_off, kv_offset=kv_off,
            window=window, impl="jnp"), q, k, v)
    want = (out_j, lse_j) + vjp((do, dlse.astype(lse_j.dtype)))
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=rtol, atol=atol, err_msg=name)
    return got


class TestExplicitPlans:
    """Tiles other than the floor must stay oracle-correct, forward AND
    backward — what :func:`flash.tile_plan` may hand a call.  The
    launches take explicit tiles here (tests and the chip probe only;
    the library always asks the plan)."""

    @pytest.mark.parametrize("qt,kt", [(256, 128), (256, 256),
                                       (512, 512), (128, 256)])
    def test_tiles_match_jnp_fwd_bwd(self, qt, kt):
        q, k, v = qkv((1, 512, 2, 64), dtype=jnp.float32, seed=5)
        _kernels_vs_oracle(q, k, v, fwd=(qt, kt), rtol=1e-4, atol=1e-4)

    def test_windowed_gqa_at_wide_tiles(self):
        q, _, _ = qkv((1, 512, 4, 64), dtype=jnp.float32, seed=7)
        _, k, v = qkv((1, 512, 2, 64), dtype=jnp.float32, seed=8)
        _kernels_vs_oracle(q, k, v, fwd=(256, 256), window=100)

    def test_each_kernel_may_have_its_own_tiles(self):
        q, k, v = qkv((1, 512, 2, 64), dtype=jnp.float32, seed=9)
        _kernels_vs_oracle(q, k, v, fwd=(256, 512), dq=(128, 256),
                           dkv=(256, 128), window=200)


class TestInteriorEdgeSplit:
    """Each kernel runs the tiles that lie wholly under the diagonal and
    wholly inside every query's window without a mask, and only the
    tiles the diagonal or the window's edge crosses with one.  Shapes of
    several tiles a side, so that all three kinds of tile occur."""

    @pytest.mark.parametrize("q_off,kv_off", [
        (512, 512),          # a diagonal block of MLA's triangle
        (1024, 512),         # a block wholly under it: no tile masked
        (300, 77), (77, 300), (640, 1)])
    def test_traced_unequal_offsets(self, q_off, kv_off):
        q, k, v = qkv((1, 512, 1, 64), dtype=jnp.float32,
                      seed=q_off + kv_off)
        _kernels_vs_oracle(q, k, v, fwd=(128, 128), q_off=q_off,
                           kv_off=kv_off, traced=True)

    @pytest.mark.parametrize("window,tiles", [
        (300, (128, 128)), (300, (256, 128)), (300, (128, 256)),
        (1024, (256, 256)),          # window == sequence: no window edge
        (1, (128, 128)), (129, (128, 128))])
    def test_windows_that_fit_no_tile(self, window, tiles):
        q, k, v = qkv((1, 1024, 1, 64), dtype=jnp.float32, seed=window)
        _kernels_vs_oracle(q, k, v, fwd=tiles, window=window)

    def test_window_across_offset_blocks(self):
        # A ring block two blocks back, window reaching into it partly.
        q, k, v = qkv((1, 512, 1, 64), dtype=jnp.float32, seed=11)
        _kernels_vs_oracle(q, k, v, fwd=(128, 128), window=900,
                           q_off=1024, kv_off=256, traced=True)

    def test_gqa(self):
        q, _, _ = qkv((1, 512, 4, 64), dtype=jnp.float32, seed=12)
        _, k, v = qkv((1, 512, 2, 64), dtype=jnp.float32, seed=13)
        _kernels_vs_oracle(q, k, v, fwd=(128, 256), dkv=(256, 128))

    def test_head_192_staged_256(self):
        q, k, v = qkv((1, 512, 2, 192), dtype=jnp.float32, seed=14)
        _kernels_vs_oracle(q, k, v, fwd=(256, 128), q_off=512, kv_off=512,
                           traced=True, rtol=2e-4, atol=2e-4)

    def test_float32_under_highest(self):
        # f32 operands keep the f32-exact contract on the TPU
        # (dot_precision); here the flag is inert, the tiles are not.
        q, k, v = qkv((1, 512, 2, 128), dtype=jnp.float32, seed=15)
        assert flash.dot_precision(q.dtype) == jax.lax.Precision.HIGHEST
        _kernels_vs_oracle(q, k, v, fwd=(256, 256), window=384)

    def test_bfloat16_operands(self):
        q, k, v = qkv((1, 512, 2, 128), dtype=jnp.bfloat16, seed=16)
        _kernels_vs_oracle(q, k, v, fwd=(256, 128), rtol=5e-2, atol=5e-2)

    @pytest.mark.parametrize("tiles", [(128, 128), (256, 256)])
    def test_block_wholly_above_the_diagonal(self, tiles):
        q, k, v = qkv((1, 512, 2, 64), dtype=jnp.float32, seed=17)
        out, lse, dq, dk, dv = _kernels_vs_oracle(
            q, k, v, fwd=tiles, q_off=0, kv_off=512, traced=True)
        assert np.all(np.asarray(out) == 0.0)
        assert np.all(np.asarray(lse) == flash.NEG_BIG)
        for g in (dq, dk, dv):
            np.testing.assert_array_equal(np.asarray(g), 0.0)

    @pytest.mark.parametrize("q_off", [384, 100, 0])
    def test_queries_shorter_than_keys(self, q_off):
        # A chunk of a chunked prefill against the cache so far.
        q, _, _ = qkv((1, 128, 2, 64), dtype=jnp.float32, seed=18)
        _, k, v = qkv((1, 512, 2, 64), dtype=jnp.float32, seed=19)
        _kernels_vs_oracle(q, k, v, fwd=(128, 128), q_off=q_off,
                           traced=True)

    def test_not_causal_every_tile_is_interior(self):
        q, k, v = qkv((1, 256, 2, 64), dtype=jnp.float32, seed=20)
        _kernels_vs_oracle(q, k, v, fwd=(128, 128), causal=False)
        assert flash.masked_tile_share(
            256, 256, flash.KernelTiles(128, 128, 0), False) == (4, 0)


class TestLoopBounds:
    """The kernels' loop bounds on Python ints against the mask itself:
    a tile outside ``[a, d)`` attends nothing (skipping it is exact), a
    tile in ``[b, c)`` attends every pair (dropping its mask is exact),
    and both cuts are tight."""

    CASES = [(qt, kt, w, qo, ko)
             for qt, kt in ((128, 128), (256, 128), (128, 512), (512, 256))
             for w in (0, 1, 300, 512, 4096)
             for qo, ko in ((0, 0), (77, 300), (300, 77), (2048, 0),
                            (0, 2048), (1000, 999))]

    @staticmethod
    def _mask(sq, sk, w, qo, ko):
        qp = qo + np.arange(sq)[:, None]
        kp = ko + np.arange(sk)[None, :]
        m = qp >= kp
        return m & (qp - kp < w) if w else m

    @pytest.mark.parametrize("qt,kt,w,qo,ko", CASES)
    def test_kv_bounds_are_exact_and_tight(self, qt, kt, w, qo, ko):
        sq = sk = 1024
        m = self._mask(sq, sk, w, qo, ko)
        for qi in range(sq // qt):
            a, b, c, d = flash._kv_loop_bounds(qo + qi * qt, ko, qt, kt,
                                               sk // kt, True, w)
            assert 0 <= a <= b <= c <= d <= sk // kt
            for j in range(sk // kt):
                t = m[qi * qt:(qi + 1) * qt, j * kt:(j + 1) * kt]
                assert t.any() == (a <= j < d), (qi, j)
                assert t.all() == (b <= j < c), (qi, j)

    @pytest.mark.parametrize("qt,kt,w,qo,ko", CASES)
    def test_q_bounds_are_exact_and_tight(self, qt, kt, w, qo, ko):
        sq = sk = 1024
        m = self._mask(sq, sk, w, qo, ko)
        for ki in range(sk // kt):
            a, b, c, d = flash._q_loop_bounds(ko + ki * kt, qo, qt, kt,
                                              sq // qt, True, w)
            assert 0 <= a <= b <= c <= d <= sq // qt
            for i in range(sq // qt):
                t = m[i * qt:(i + 1) * qt, ki * kt:(ki + 1) * kt]
                assert t.any() == (a <= i < d), (i, ki)
                assert t.all() == (b <= i < c), (i, ki)

    def test_both_loops_visit_the_same_pairs(self):
        t = flash.KernelTiles(256, 128, 0)
        for w, qo, ko in ((0, 0, 0), (300, 77, 0), (4096, 2048, 1024)):
            assert flash.masked_tile_share(1024, 1024, t, True, w, qo, ko) \
                == flash.masked_tile_share(1024, 1024, t, True, w, qo, ko,
                                           over="q")


class TestTilePlan:
    """``flash.tile_plan`` is static per shape, so what a call gets is
    readable without a chip: the tiles, the VMEM bytes, and the share of
    visited tiles that take the mask.  Pinned for every flash call the
    benchmark's five cells send (and `train_long`'s), as PERF.md
    tabulates them: a change of plan shows here first."""

    # name: (queries, keys, head size, window, q_offset, kv_offset),
    #       fwd / dq / dkv tiles, (visited, masked) of each kernel's loop
    CALLS = {
        "mistral-train-4096": (
            (4096, 4096, 128, 4096, 0, 0),
            (256, 512), (512, 512), (512, 512),
            ((72, 16), (36, 8), (36, 8))),
        "mistral-train_long-16384-window-4096": (
            (16384, 16384, 128, 4096, 0, 0),
            (256, 512), (512, 512), (512, 512),
            ((504, 112), (252, 56), (252, 56))),
        "internlm2-prefill-256": (
            (256, 256, 128, 0, 0, 0),
            (256, 256), (256, 256), (256, 256),
            ((1, 1), (1, 1), (1, 1))),
        "internlm2-prefill-1024": (
            (1024, 1024, 128, 0, 0, 0),
            (256, 512), (512, 512), (512, 512),
            ((6, 4), (3, 2), (3, 2))),
        "internlm2-prefill-2048": (
            (2048, 2048, 128, 0, 0, 0),
            (256, 512), (512, 512), (512, 512),
            ((20, 8), (10, 4), (10, 4))),
        "mla-block-2048x192-on-the-diagonal": (
            (2048, 2048, 192, 0, 2048, 2048),
            (256, 512), (512, 512), (512, 512),
            ((20, 8), (10, 4), (10, 4))),
        "mla-block-2048x192-under-the-diagonal": (
            (2048, 2048, 192, 0, 4096, 2048),
            (256, 512), (512, 512), (512, 512),
            ((32, 0), (16, 0), (16, 0))),
    }

    @pytest.mark.parametrize("call", list(CALLS))
    def test_plan_and_masked_share_of_the_cells_calls(self, call):
        (sq, sk, d, w, qo, ko), fwd, dq, dkv, shares = self.CALLS[call]
        plan = flash.tile_plan(sq, sk, d, jnp.bfloat16, True, w)
        assert (plan.fwd[:2], plan.dq[:2], plan.dkv[:2]) == (fwd, dq, dkv)
        got = tuple(
            flash.masked_tile_share(sq, sk, t, True, w, qo, ko, over=over)
            for t, over in ((plan.fwd, "kv"), (plan.dq, "kv"),
                            (plan.dkv, "q")))
        assert got == shares
        for kernel, t in zip(("fwd", "dq", "dkv"), plan):
            assert t == flash.kernel_tiles(kernel, t.q_tile, t.kv_tile, sq,
                                           sk, d, jnp.bfloat16)
            assert t.vmem_bytes <= flash._VMEM_CAP

    @staticmethod
    def _launches(fn, *args):
        """(grid, block shapes, vmem limit) of every pallas_call."""
        found = []
        for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                gm = e.params["grid_mapping"]
                found.append((
                    e.params["name"], gm.grid,
                    [tuple(getattr(b, "block_size", b)
                           for b in bm.block_shape)
                     for bm in gm.block_mappings],
                    e.params["compiler_params"]["mosaic_tpu"]
                    .vmem_limit_bytes))
        return found

    @pytest.mark.parametrize("call", list(CALLS))
    def test_predicates_agree_with_the_launch(self, call):
        """Where ``_eligible`` / ``_bwd_eligible`` say yes the launches
        take the plan's tiles (grid and blocks), and ask Mosaic for more
        scoped VMEM exactly where the plan's count passes its default."""
        (sq, sk, d, w, _, _), *_ = self.CALLS[call]
        like = lambda s: jax.ShapeDtypeStruct((1, s, 2, d), jnp.bfloat16)
        q, k = like(sq), like(sk)
        off = jax.ShapeDtypeStruct((), jnp.int32)
        stat = jax.ShapeDtypeStruct((1, sq, 2), jnp.float32)
        assert flash._eligible(q, k) and flash._bwd_eligible(q, k)
        plan = flash.tile_plan(sq, sk, d, jnp.bfloat16, True, w)
        dp = flash._lane_pad(d)
        limit = lambda t: (t.vmem_bytes if t.vmem_bytes
                           > flash._DEFAULT_SCOPED_VMEM else None)
        (name, grid, blocks, vmem), = self._launches(
            lambda q, k, v, a, b: flash._pallas_block(
                q, k, v, a, b, True, True, w), q, k, k, off, off)
        assert name == flash.KERNEL_NAMES[0]
        assert grid == (2, sq // plan.fwd.q_tile)
        assert blocks[2] == (1, plan.fwd.q_tile, dp)
        assert blocks[3] == (1, sk, dp) and vmem == limit(plan.fwd)
        dq_l, dkv_l = self._launches(
            lambda q, k, v, do, lse, dd, a, b: flash._pallas_bwd(
                q, k, v, do, lse, dd, a, b, True, True, w),
            q, k, k, q, stat, stat, off, off)
        assert (dq_l[0], dkv_l[0]) == flash.KERNEL_NAMES[1:]
        assert dq_l[1] == (2, sq // plan.dq.q_tile)
        assert dq_l[2][2] == (1, plan.dq.q_tile, dp)
        assert dq_l[3] == limit(plan.dq)
        assert dkv_l[1] == (2, sk // plan.dkv.kv_tile)
        assert dkv_l[2][3] == (1, plan.dkv.kv_tile, dp)
        assert dkv_l[2][6] == (1, sq // plan.dkv.q_tile, 8,
                               plan.dkv.q_tile)
        assert dkv_l[3] == limit(plan.dkv)

    def test_the_long_call_asks_for_its_own_vmem(self):
        plan = flash.tile_plan(16384, 16384, 128, jnp.bfloat16, True, 4096)
        assert all(t.vmem_bytes > flash._DEFAULT_SCOPED_VMEM for t in plan)

    @pytest.mark.parametrize("sq,sk,d,dtype,why", [
        (256, 256, 32, jnp.float32, "head size under 64"),
        (200, 256, 128, jnp.float32, "queries no tile divides"),
        (256, 200, 128, jnp.float32, "keys no tile divides"),
        (128, 16384, 128, jnp.float32, "one head's K and V over the budget"),
    ])
    def test_no_plan_no_kernel(self, sq, sk, d, dtype, why):
        assert flash.tile_plan(sq, sk, d, dtype) == (None, None, None), why
        q = jax.ShapeDtypeStruct((1, sq, 1, d), dtype)
        k = jax.ShapeDtypeStruct((1, sk, 1, d), dtype)
        assert not flash._eligible(q, k) and not flash._bwd_eligible(q, k)

    def test_backward_declines_where_q_and_do_pass_the_budget(self):
        # 32,768 float32 queries of 128: q + dO are 32 MB a head.  The
        # forward stages a q tile and still runs.
        plan = flash.tile_plan(32768, 1024, 128, jnp.float32)
        assert plan.fwd is not None and plan.dq is None and plan.dkv is None

    def test_short_sequences_are_one_tile(self):
        plan = flash.tile_plan(100, 64, 64, jnp.float32)
        assert plan.fwd[:2] == plan.dq[:2] == plan.dkv[:2] == (100, 64)

    def test_float32_takes_narrower_tiles(self):
        # Several MXU passes a product under HIGHEST: 256 x 256 was the
        # fastest pair in all three kernels on the chip.
        plan = flash.tile_plan(2048, 2048, 128, jnp.float32)
        assert plan.fwd[:2] == plan.dq[:2] == plan.dkv[:2] == (256, 256)


class TestLanePadding:
    """head_dim 64/96 take the kernel via zero-padding to the 128 lane
    width (round-1 gap: the common d=64 silently fell back to jnp)."""

    @pytest.mark.parametrize("d", [64, 96])
    @pytest.mark.parametrize("causal", [False, True])
    def test_padded_head_dim_matches_jnp(self, d, causal):
        q, k, v = qkv((1, 256, 2, d), dtype=jnp.float32)
        assert flash._eligible(q, k)
        a, la = flash.flash_block_attention(q, k, v, causal=causal,
                                            impl="pallas")
        b, lb = flash.flash_block_attention(q, k, v, causal=causal,
                                            impl="jnp")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-4, atol=1e-5)

    def test_padded_head_dim_grads_match(self):
        q, k, v = qkv((1, 128, 2, 64), dtype=jnp.float32)

        def loss(impl):
            return lambda q, k, v: jnp.sum(flash.flash_block_attention(
                q, k, v, causal=True, impl=impl)[0] ** 2)

        ga = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
        gb = jax.grad(loss("jnp"), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)


class TestCausalTileSkip:
    """The diagonal-cut loop bounds (_kv_loop_bounds and _q_loop_bounds)
    must be exact at UNALIGNED offsets: a bound off by one tile either
    recomputes masked work (benign) or skips live keys (wrong output).
    Sweep odd offsets through the forced kernel path vs the jnp oracle —
    forward, lse, and all three gradients."""

    @pytest.mark.parametrize("q_off,kv_off", [
        (0, 0), (1, 0), (0, 1), (77, 0), (0, 77), (128, 200), (200, 128),
        (1000, 999), (999, 1000), (50, 300),
    ])
    def test_unaligned_offsets_match_jnp(self, q_off, kv_off):
        q, k, v = qkv((1, 256, 1, 64), dtype=jnp.float32,
                      seed=q_off * 7 + kv_off)

        def loss(impl):
            def f(q, k, v):
                out, lse = flash.flash_block_attention(
                    q, k, v, causal=True, q_offset=q_off,
                    kv_offset=kv_off, impl=impl)
                safe = jnp.where(lse > flash.NEG_BIG / 2, lse, 0.0)
                return jnp.sum(out ** 2) + jnp.sum(safe)
            return f

        op, lp = flash.flash_block_attention(
            q, k, v, causal=True, q_offset=q_off, kv_offset=kv_off,
            impl="pallas")
        oj, lj = flash.flash_block_attention(
            q, k, v, causal=True, q_offset=q_off, kv_offset=kv_off,
            impl="jnp")
        np.testing.assert_allclose(np.asarray(op), np.asarray(oj),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lj),
                                   rtol=1e-4, atol=1e-5)
        gp = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
        gj = jax.grad(loss("jnp"), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gj):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)


class TestIntegerPositions:
    def test_positions_exact_beyond_f32_range(self):
        # Query block at position 2^24 against one key at 2^24 + 1.  The
        # earlier f32 position encoding rounded both to 2^24, unmasking
        # the future key for row 0; i32 positions keep the frontier exact
        # (the long-context correctness cliff, ADVICE round 1).
        big = 2 ** 24
        q, k, v = qkv((1, 8, 1, D))
        o, lse = flash.flash_block_attention(
            q, k[:, :1], v[:, :1], causal=True, q_offset=big,
            kv_offset=big + 1, impl="jnp")
        assert float(lse[0, 0, 0]) == flash.NEG_BIG     # masked
        np.testing.assert_array_equal(np.asarray(o[0, 0]), 0.0)
        assert np.all(np.asarray(lse[0, 1:]) > flash.NEG_BIG)  # visible

    def test_pallas_positions_exact_beyond_f32_range(self):
        # Same frontier exactness through the kernel's i32 SMEM offsets +
        # iota path (interpret mode): an f32 regression there would
        # unmask future keys only at long-context offsets.
        big = 2 ** 24
        q, k, v = qkv((1, 128, 1, 64), dtype=jnp.float32)
        a, la = flash.flash_block_attention(
            q, k, v, causal=True, q_offset=big, kv_offset=big + 1,
            impl="pallas")
        b, lb = flash.flash_block_attention(
            q, k, v, causal=True, q_offset=big, kv_offset=big + 1,
            impl="jnp")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
        # Row 0 sees no keys (first key is one position in its future).
        assert float(la[0, 0, 0]) <= -1e29
        assert float(lb[0, 0, 0]) <= -1e29


@pytest.mark.skipif(jax.devices()[0].platform != "tpu",
                    reason="compiled (non-interpret) kernel needs a TPU")
class TestCompiledKernelOnTPU:
    """Hardware gate: the non-interpret Pallas kernel vs the jnp oracle.

    Skipped on the CPU-mesh CI harness (conftest pins the cpu platform
    unless the ``MPI4TORCH_TPU_REAL_DEVICES=1`` hatch is set); run on the
    chip via ``make tpu-test`` — ``chip_smoke.py`` exercises the same
    compiled kernels through impl='auto' inside the flagship train
    step."""

    @pytest.mark.parametrize("d", [64, 128])
    def test_compiled_matches_jnp(self, d):
        q, k, v = qkv((2, 512, 4, d), dtype=jnp.float32)
        a, la = flash.flash_block_attention(q, k, v, causal=True,
                                            impl="pallas")
        b, lb = flash.flash_block_attention(q, k, v, causal=True,
                                            impl="jnp")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-4, atol=1e-5)

    def test_compiled_bench_shape_bf16(self):
        # The flagship attention shape: 4096 tokens, head size 128.
        q, k, v = qkv((4, 4096, 8, 128), dtype=jnp.bfloat16, seed=7)
        a, _ = flash.flash_block_attention(q, k, v, causal=True,
                                           impl="pallas")
        b, _ = flash.flash_block_attention(q, k, v, causal=True,
                                           impl="jnp")
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=5e-2, atol=5e-2)

    def test_compiled_grads_match_jnp(self):
        q, k, v = qkv((2, 512, 4, 128), dtype=jnp.float32)

        def loss(impl):
            return lambda q, k, v: jnp.sum(flash.flash_block_attention(
                q, k, v, causal=True, impl=impl)[0] ** 2)

        ga = jax.jit(jax.grad(loss("pallas"), argnums=(0, 1, 2)))(q, k, v)
        gb = jax.jit(jax.grad(loss("jnp"), argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)

    def test_compiled_chunked_long_kv(self):
        # Over-budget KV on the real chip: auto must scan the compiled
        # kernel over chunks and match the (chunked-jnp) oracle.
        q, _, _ = qkv((1, 128, 1, 128), dtype=jnp.float32, seed=12)
        rng = np.random.default_rng(13)
        k = jnp.asarray(rng.standard_normal((1, 32768, 1, 128)) * 0.3,
                        jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 32768, 1, 128)) * 0.3,
                        jnp.float32)
        assert flash._kv_chunk_for(q, k) == 8192
        got = flash.flash_attention(q, k, v, causal=True, impl="auto")
        want = flash.flash_attention(q, k, v, causal=True, impl="jnp",
                                     kv_chunk=8192)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)

    def test_compiled_gqa_matches_jnp(self):
        # GQA on hardware: grouped KV index maps in all three kernels
        # (fwd, dq, dkv-partial) must lower and match the repeat oracle.
        rng = np.random.default_rng(21)
        q = jnp.asarray(rng.standard_normal((2, 512, 8, 128)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, 512, 2, 128)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, 512, 2, 128)), jnp.float32)

        def loss(impl):
            return lambda q, k, v: jnp.sum(flash.flash_block_attention(
                q, k, v, causal=True, impl=impl)[0] ** 2)

        a, la = flash.flash_block_attention(q, k, v, causal=True,
                                            impl="pallas")
        b, lb = flash.flash_block_attention(q, k, v, causal=True,
                                            impl="jnp")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
        ga = jax.jit(jax.grad(loss("pallas"), argnums=(0, 1, 2)))(q, k, v)
        gb = jax.jit(jax.grad(loss("jnp"), argnums=(0, 1, 2)))(q, k, v)
        for x, y in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-3, atol=1e-4)

    def test_compiled_sliding_window_matches_jnp(self):
        # Windowed masking + two-frontier tile-skip on hardware, fwd and
        # bwd, window deliberately NOT a tile multiple.
        q, k, v = qkv((2, 1024, 4, 128), dtype=jnp.float32, seed=22)

        def loss(impl):
            return lambda q, k, v: jnp.sum(flash.flash_block_attention(
                q, k, v, causal=True, window=200, impl=impl)[0] ** 2)

        a, _ = flash.flash_block_attention(q, k, v, causal=True,
                                           window=200, impl="pallas")
        b, _ = flash.flash_block_attention(q, k, v, causal=True,
                                           window=200, impl="jnp")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
        ga = jax.jit(jax.grad(loss("pallas"), argnums=(0, 1, 2)))(q, k, v)
        gb = jax.jit(jax.grad(loss("jnp"), argnums=(0, 1, 2)))(q, k, v)
        for x, y in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-3, atol=1e-4)

    def test_auto_selects_pallas_and_runs(self):
        # impl='auto' on hardware must engage the compiled kernel (the
        # program holds the Mosaic call) and agree with the oracle — the
        # flagship-model path.
        q, k, v = qkv((2, 512, 4, 128), dtype=jnp.float32)
        assert flash._eligible(q, k)
        auto = jax.jit(lambda q, k, v: flash.flash_attention(
            q, k, v, causal=True, impl="auto"))
        assert flash.KERNEL_NAMES[0] in auto.lower(q, k, v).as_text()
        a = auto(q, k, v)
        b = flash.flash_attention(q, k, v, causal=True, impl="jnp")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


class TestChunkedKV:
    """The long-KV scan path of flash_attention: budget-sized chunks
    through the block kernel, merged by the online-softmax rule — the
    path Ulysses long context takes when one KV block would blow VMEM."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_chunked_matches_unchunked_jnp(self, causal):
        q, k, v = qkv((1, 64, 2, 8), seed=2)   # f64: exact-oracle regime
        a = flash.flash_attention(q, k, v, causal=causal, impl="jnp",
                                  kv_chunk=16)
        b = flash.flash_attention(q, k, v, causal=causal, impl="jnp")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-10, atol=1e-12)

    def test_chunked_grads_match_unchunked(self):
        q, k, v = qkv((1, 64, 2, 8), seed=4)

        def loss(chunk):
            return lambda q, k, v: jnp.sum(flash.flash_attention(
                q, k, v, causal=True, impl="jnp", kv_chunk=chunk) ** 2)

        ga = jax.grad(loss(16), argnums=(0, 1, 2))(q, k, v)
        gb = jax.grad(loss(0), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-9, atol=1e-11)

    def test_chunked_pallas_blocks_match_oracle(self):
        # Forced kernel path (interpret off-TPU), 2 chunks of 128.
        q, k, v = qkv((1, 128, 1, 64), dtype=jnp.float32, seed=6)
        k2 = jnp.concatenate([k, k * 0.5], axis=1)
        v2 = jnp.concatenate([v, v * 2.0], axis=1)
        a = flash.flash_attention(q, k2, v2, causal=True, impl="pallas",
                                  kv_chunk=128)
        b = flash.flash_attention(q, k2, v2, causal=True, impl="jnp")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)

    def test_auto_chunks_over_budget_kv(self):
        # 32K f32 keys at d=128 stage 32 MB — over the 8 MB budget; auto
        # must pick the largest dividing chunk (8192) instead of falling
        # back to the quadratic jnp path.
        q = jnp.zeros((1, 128, 1, 128), jnp.float32)
        k = jnp.zeros((1, 32768, 1, 128), jnp.float32)
        assert not flash._eligible(q, k)
        assert flash._kv_chunk_for(q, k) == 8192

    def test_no_chunk_when_shape_cannot_be_eligible(self):
        q = jnp.zeros((1, 128, 1, 8), jnp.float32)     # d too small
        k = jnp.zeros((1, 32768, 1, 8), jnp.float32)
        assert flash._kv_chunk_for(q, k) == 0
        kr = jnp.zeros((1, 32700, 1, 128), jnp.float32)  # not tile-divisible
        assert flash._kv_chunk_for(
            jnp.zeros((1, 128, 1, 128), jnp.float32), kr) == 0

    def test_bad_kv_chunk_raises(self):
        q, k, v = qkv((1, 128, 1, 64), dtype=jnp.float32)
        with pytest.raises(ValueError, match="kv_chunk"):
            flash.flash_attention(q, k, v, kv_chunk=100)

    def test_long_context_end_to_end(self):
        # A 16K-key attention through the auto-chunked scan (jnp blocks
        # on CPU), against the dense oracle on a thin query block — the
        # memory regime the path exists for, kept CPU-affordable.
        q, _, _ = qkv((1, 128, 1, 128), dtype=jnp.float32, seed=8)
        rng = np.random.default_rng(9)
        k = jnp.asarray(rng.standard_normal((1, 16384, 1, 128)) * 0.3,
                        jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 16384, 1, 128)) * 0.3,
                        jnp.float32)
        assert flash._kv_chunk_for(q, k) == 8192
        got = flash.flash_attention(q, k, v, causal=False, impl="auto")
        want = flash.flash_attention(q, k, v, causal=False, impl="jnp")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)


class TestGQA:
    """Grouped-query attention: k/v carry fewer heads than q; q head h
    attends through KV head h // g.  The jnp path realizes the grouping
    by KV repeat (oracle); the Pallas kernels resolve it in their KV
    BlockSpec index maps without duplicating KV."""

    @staticmethod
    def _gqa_qkv(b, s, hq, hkv, d, dtype, seed=0):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((b, s, hq, d)), dtype)
        k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
        v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
        return q, k, v

    @pytest.mark.parametrize("causal", [False, True])
    def test_jnp_matches_dense_repeat_oracle(self, causal):
        q, k, v = self._gqa_qkv(2, 16, 4, 2, 8, jnp.float64)
        out, _ = flash.flash_block_attention(q, k, v, causal=causal,
                                             impl="jnp")
        kr = jnp.repeat(k, 2, axis=2)
        vr = jnp.repeat(v, 2, axis=2)
        want = dense_attention(q, kr, vr, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-10, atol=1e-12)

    @pytest.mark.parametrize("causal", [False, True])
    def test_pallas_interpret_matches_jnp(self, causal):
        q, k, v = self._gqa_qkv(2, 256, 4, 2, 128, jnp.float32)
        a, la = flash.flash_block_attention(q, k, v, causal=causal,
                                            impl="pallas")
        b, lb = flash.flash_block_attention(q, k, v, causal=causal,
                                            impl="jnp")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-5, atol=1e-6)

    def test_pallas_bwd_interpret_grads_match(self):
        # impl='pallas' routes the backward through the fused dq and
        # per-q-head-partial dkv kernels (interpret mode off-TPU); the
        # group-summed dk/dv must match the jnp oracle's.
        q, k, v = self._gqa_qkv(1, 256, 4, 2, 128, jnp.float32, seed=3)

        def loss(impl):
            return lambda q, k, v: jnp.sum(flash.flash_block_attention(
                q, k, v, causal=True, impl=impl)[0] ** 2)

        ga = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
        gb = jax.grad(loss("jnp"), argnums=(0, 1, 2))(q, k, v)
        assert ga[1].shape == k.shape and ga[2].shape == v.shape
        for a, b in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)

    def test_grads_flow_through_grouping(self):
        # Each KV head's gradient is the SUM of its whole q group's
        # cotangents: the GQA dv must equal the explicit-repeat model's
        # per-head dv summed over the group.
        q, k, v = self._gqa_qkv(1, 16, 4, 1, 8, jnp.float64, seed=5)

        dv_gqa = jax.grad(lambda v: jnp.sum(flash.flash_block_attention(
            q, k, v, impl="jnp")[0]))(v)

        vr = jnp.repeat(v, 4, axis=2)
        dv_rep = jax.grad(lambda vr: jnp.sum(flash.flash_block_attention(
            q, jnp.repeat(k, 4, axis=2), vr, impl="jnp")[0]))(vr)
        want = dv_rep.reshape(1, 16, 1, 4, 8).sum(axis=3)
        np.testing.assert_allclose(np.asarray(dv_gqa), np.asarray(want),
                                   rtol=1e-10, atol=1e-12)

    def test_chunked_gqa_matches_unchunked(self):
        q, k, v = self._gqa_qkv(1, 64, 4, 2, 8, jnp.float64, seed=6)
        a = flash.flash_attention(q, k, v, causal=True, impl="jnp",
                                  kv_chunk=16)
        b = flash.flash_attention(q, k, v, causal=True, impl="jnp")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-10, atol=1e-12)

    def test_bad_head_ratio_raises(self):
        q, k, v = self._gqa_qkv(1, 16, 4, 3, 8, jnp.float64)
        with pytest.raises(ValueError, match="multiple of KV heads"):
            flash.flash_block_attention(q, k, v)


def _dense_windowed(q, k, v, window, q_off=0, kv_off=0):
    """Independent sliding-window oracle: explicit masked softmax."""
    sq, sk = q.shape[1], k.shape[1]
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k) * scale
    qp = q_off + np.arange(sq)[:, None]
    kp = kv_off + np.arange(sk)[None, :]
    mask = (qp >= kp) & (qp - kp < window)
    s = jnp.where(mask[None, :, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask[None, :, None, :], p, 0.0)
    return jnp.einsum("bqhk,bkhd->bqhd", p, v)


class TestSlidingWindow:
    """window > 0: each query attends its last `window` positions (itself
    included).  Masking is global-position-based; the kernels tile-skip
    BOTH frontiers (causal diagonal and window edge)."""

    @pytest.mark.parametrize("window", [1, 3, 7, 100])
    def test_jnp_matches_dense_oracle(self, window):
        q, k, v = qkv((2, 16, 2, 8), seed=11)
        out, _ = flash.flash_block_attention(q, k, v, causal=True,
                                             window=window, impl="jnp")
        want = _dense_windowed(q, k, v, window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-10, atol=1e-12)

    def test_offsets_shift_the_window(self):
        # A window spanning a block boundary: the second block's queries
        # must still see the first block's tail keys.
        q, k, v = qkv((1, 8, 1, 4), seed=12)
        q_hi = q[:, 4:]
        out, _ = flash.flash_block_attention(
            q_hi, k, v, causal=True, q_offset=4, window=6, impl="jnp")
        want = _dense_windowed(q, k, v, 6)[:, 4:]
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-10, atol=1e-12)

    @pytest.mark.parametrize("window", [64, 100, 1000])
    def test_pallas_interpret_matches_jnp(self, window):
        # Window a tile multiple, unaligned, and larger than the whole
        # sequence (=> plain causal).
        q, k, v = qkv((1, 256, 2, 128), dtype=jnp.float32, seed=13)
        a, la = flash.flash_block_attention(q, k, v, causal=True,
                                            window=window, impl="pallas")
        b, lb = flash.flash_block_attention(q, k, v, causal=True,
                                            window=window, impl="jnp")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-5, atol=1e-6)

    def test_pallas_interpret_unaligned_offsets(self):
        q, k, v = qkv((1, 256, 1, 128), dtype=jnp.float32, seed=14)
        a, _ = flash.flash_block_attention(
            q, k, v, causal=True, q_offset=300, kv_offset=170,
            window=200, impl="pallas")
        b, _ = flash.flash_block_attention(
            q, k, v, causal=True, q_offset=300, kv_offset=170,
            window=200, impl="jnp")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)

    def test_pallas_bwd_interpret_grads_match(self):
        q, k, v = qkv((1, 256, 2, 128), dtype=jnp.float32, seed=15)

        def loss(impl):
            return lambda q, k, v: jnp.sum(flash.flash_block_attention(
                q, k, v, causal=True, window=100, impl=impl)[0] ** 2)

        ga = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
        gb = jax.grad(loss("jnp"), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)

    def test_window_with_gqa(self):
        rng = np.random.default_rng(16)
        q = jnp.asarray(rng.standard_normal((1, 256, 4, 128)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 256, 2, 128)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 256, 2, 128)), jnp.float32)
        a, _ = flash.flash_block_attention(q, k, v, causal=True,
                                           window=64, impl="pallas")
        b, _ = flash.flash_block_attention(q, k, v, causal=True,
                                           window=64, impl="jnp")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)

    def test_chunked_windowed_matches_unchunked(self):
        q, k, v = qkv((1, 64, 2, 8), seed=17)
        a = flash.flash_attention(q, k, v, causal=True, window=20,
                                  impl="jnp", kv_chunk=16)
        b = flash.flash_attention(q, k, v, causal=True, window=20,
                                  impl="jnp")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-10, atol=1e-12)

    def test_validation(self):
        q, k, v = qkv((1, 16, 1, 8))
        with pytest.raises(ValueError, match="window must be >= 0"):
            flash.flash_block_attention(q, k, v, causal=True, window=-1)
        with pytest.raises(ValueError, match="requires causal"):
            flash.flash_block_attention(q, k, v, window=8)


class TestEligibility:
    def test_auto_falls_back_on_small_head_dim(self):
        q, k, v = qkv((B, S, H, D))
        # D=8 is below the padded-lane cutoff: auto must take the jnp path
        # (and agree with it bit-for-bit).
        assert not flash._eligible(q, k)
        a, la = flash.flash_block_attention(q, k, v, causal=True)
        b, lb = flash.flash_block_attention(q, k, v, causal=True,
                                            impl="jnp")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_bad_impl_raises(self):
        q, k, v = qkv((B, S, H, D))
        with pytest.raises(ValueError, match="unknown impl"):
            flash.flash_block_attention(q, k, v, impl="cuda")

    def test_forced_pallas_rejects_ineligible_shapes(self):
        # Silently dropping the 300 % 128 tail keys would be wrong output;
        # the forced path must refuse instead.
        q, k, v = qkv((1, 256, 2, 128), dtype=jnp.float32)
        k300 = jnp.concatenate([k, k[:, :44]], axis=1)
        v300 = jnp.concatenate([v, v[:, :44]], axis=1)
        with pytest.raises(ValueError, match="kernel-eligible"):
            flash.flash_block_attention(q, k300, v300, impl="pallas")

    def test_vmem_budget_bounds_kv_block(self):
        # A 32K-key f32 d=128 block stages 32 MB of KV — over budget.
        q = jnp.zeros((1, 128, 1, 128), jnp.float32)
        k = jnp.zeros((1, 32768, 1, 128), jnp.float32)
        assert not flash._eligible(q, k)
        k_ok = jnp.zeros((1, 4096, 1, 128), jnp.float32)
        assert flash._eligible(q, k_ok)


class TestRingAttentionPallas:
    def test_ring_with_pallas_blocks_matches_dense(self):
        # 4-rank ring over eligible f32 shapes, kernel interpreted: the
        # full CP path through the Pallas block primitive.
        NR = 4
        if len(jax.devices()) < NR:
            # Real-device mode exposes the single physical chip; the mesh
            # transport needs NR devices (CPU harness forces 8 virtual).
            pytest.skip(f"needs {NR} devices, have {len(jax.devices())}")
        S_TOT = 512
        q, k, v = qkv((1, S_TOT, 2, 128), dtype=jnp.float32)
        ref = dense_attention(q, k, v, causal=True)
        SL = S_TOT // NR

        def body():
            r = jnp.asarray(comm.rank)
            sl = [jax.lax.dynamic_slice_in_dim(t, r * SL, SL, 1)
                  for t in (q, k, v)]
            return ring_attention(comm, *sl, causal=True, impl="pallas")

        out = np.asarray(mpi.run_spmd(body, nranks=NR)())
        got = np.concatenate(list(out), axis=1)
        np.testing.assert_allclose(got, np.asarray(ref), rtol=2e-4,
                                   atol=2e-5)


class TestDotPrecision:
    """The on-chip precision contract (round-5 postmortem): TPU contracts
    f32 dot_generals in single bf16 passes at default precision, so every
    attention matmul keys its contract precision on the operand dtype —
    f32-or-wider pins HIGHEST, narrower stays on the fast single pass
    (Mosaic rejects fp32 contract precision on bf16 operands).  Asserted
    at the jaxpr level so the policy is CPU-checkable."""

    def test_dot_precision_by_dtype(self):
        assert flash.dot_precision(jnp.float32) == jax.lax.Precision.HIGHEST
        assert flash.dot_precision(jnp.float64) == jax.lax.Precision.HIGHEST
        assert flash.dot_precision(jnp.bfloat16) is None
        assert flash.dot_precision(jnp.float16) is None

    @pytest.mark.parametrize("fn", [
        lambda q: dense_attention(q, q, q, causal=True),
        lambda q: flash.flash_block_attention(q, q, q, causal=True,
                                              impl="jnp")[0],
        lambda q: jax.grad(lambda t: jnp.sum(flash.flash_block_attention(
            t, t, t, causal=True, impl="jnp")[0] ** 2))(q),
    ], ids=["dense", "flash_jnp_fwd", "flash_jnp_bwd"])
    def test_f32_pins_highest_bf16_does_not(self, fn):
        q32 = jnp.ones((1, 8, 1, 8), jnp.float32)
        assert "HIGHEST" in str(jax.make_jaxpr(fn)(q32))
        q16 = q32.astype(jnp.bfloat16)
        assert "HIGHEST" not in str(jax.make_jaxpr(fn)(q16))

    def test_dense_attention_precision_override(self):
        # Callers preferring the single-pass contract for f32 (speed over
        # exactness) can opt out.
        q = jnp.ones((1, 8, 1, 8), jnp.float32)
        jx = str(jax.make_jaxpr(lambda t: dense_attention(
            t, t, t, precision=jax.lax.Precision.DEFAULT))(q))
        assert "HIGHEST" not in jx
