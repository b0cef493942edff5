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


class TestTunableTiles:
    """Non-default _Q_TILE/_KV_TILE configurations (the tunable tile
    sizes) must stay
    oracle-correct, forward AND backward — KV tiles wider than the
    128-lane stat slab exercise _stat_tile's lane-tiling branch."""

    @pytest.mark.parametrize("qt,kt", [(256, 128), (256, 256),
                                       (512, 512), (128, 256)])
    def test_tiles_match_jnp_fwd_bwd(self, qt, kt, monkeypatch):
        monkeypatch.setattr(flash, "_Q_TILE", qt)
        monkeypatch.setattr(flash, "_KV_TILE", kt)
        q, k, v = qkv((1, 512, 2, 64), dtype=jnp.float32, seed=5)

        def loss(impl):
            return lambda q, k, v: jnp.sum(flash.flash_attention(
                q, k, v, causal=True, impl=impl) ** 2)

        out_p = flash.flash_attention(q, k, v, causal=True, impl="pallas")
        out_j = flash.flash_attention(q, k, v, causal=True, impl="jnp")
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_j),
                                   rtol=1e-5, atol=1e-6)
        gp = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
        gj = jax.grad(loss("jnp"), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gj):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_windowed_gqa_at_wide_tiles(self, monkeypatch):
        monkeypatch.setattr(flash, "_Q_TILE", 256)
        monkeypatch.setattr(flash, "_KV_TILE", 256)
        q, _, _ = qkv((1, 512, 4, 64), dtype=jnp.float32, seed=7)
        _, k, v = qkv((1, 512, 2, 64), dtype=jnp.float32, seed=8)

        def loss(impl):
            return lambda q, k, v: jnp.sum(flash.flash_attention(
                q, k, v, causal=True, window=100, impl=impl) ** 2)

        gp = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
        gj = jax.grad(loss("jnp"), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gj):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)


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
    """The diagonal-cut loop bounds (_causal_n_live and the dkv i_start)
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
