"""Flagship-model tests: the 2D (dp x sp) distributed transformer must
reproduce the single-process full-batch full-sequence run — loss AND updated
parameters — for both sequence-parallel attention strategies, on the SPMD
mesh (user-managed 2D shard_map) and the eager runtime."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import mpi4torch_tpu as mpi
from mpi4torch_tpu.models import transformer as T

CFG = T.TransformerConfig(vocab=31, d_model=16, n_heads=8, n_layers=2,
                          d_ff=32, max_seq=16)
B, S = 8, 16


def setup():
    params = T.init_transformer(jax.random.PRNGKey(0), CFG, dtype=jnp.float64)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, CFG.vocab)
    return params, tokens


def reference_step(params, tokens):
    return T.train_step(CFG, params, tokens)  # size-1 world, dense attn


def make_mesh_step(cfg, dp, sp, attn, ep=1):
    """jitted shard_map train step over a dp x sp (x ep) mesh — the one
    place the dynamic-slice + shard_map boilerplate lives."""
    shape = (dp, sp, ep) if ep > 1 else (dp, sp)
    names = ("dp", "sp", "ep")[:len(shape)]
    mesh = Mesh(np.asarray(jax.devices()[:dp * sp * ep]).reshape(shape),
                names)
    comm_dp = mpi.comm_from_mesh(mesh, "dp")
    comm_sp = mpi.comm_from_mesh(mesh, "sp")
    comm_ep = mpi.comm_from_mesh(mesh, "ep") if ep > 1 else None
    bl, sl = B // (dp * ep), S // sp

    def shard_step(params, tokens):
        r_b = jnp.asarray(comm_dp.rank)
        if comm_ep is not None:
            r_b = r_b * ep + jnp.asarray(comm_ep.rank)
        r_sp = jnp.asarray(comm_sp.rank)
        local = jax.lax.dynamic_slice(tokens, (r_b * bl, r_sp * sl),
                                      (bl, sl))
        return T.train_step(cfg, params, local, comm_sp=comm_sp,
                            comm_dp=comm_dp, comm_ep=comm_ep, attn=attn)

    return jax.jit(shard_map(shard_step, mesh=mesh, in_specs=P(),
                             out_specs=P(), check_vma=False))


@pytest.mark.parametrize("attn", ["ring", "ulysses"])
@pytest.mark.parametrize("dp,sp", [(2, 4), (4, 2), (1, 8), (8, 1)])
@pytest.mark.slow  # multi-minute oracle compile; TPU/manual lane (tier-1 budget)
def test_2d_mesh_matches_single_process(attn, dp, sp):
    # CFG.n_heads = 8 divides every sp in the matrix, so the Ulysses
    # head<->sequence reshuffle runs at ALL mesh shapes (no skips).
    assert CFG.n_heads % sp == 0
    params, tokens = setup()
    ref_loss, ref_params = reference_step(params, tokens)

    loss, new_params = make_mesh_step(CFG, dp, sp, attn)(params, tokens)

    np.testing.assert_allclose(float(loss), float(ref_loss),
                               rtol=1e-12, atol=1e-14)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-9, atol=1e-11),
        new_params, ref_params)


def make_zigzag_mesh_step(cfg, dp, sp):
    """Like make_mesh_step but tokens are sharded in the ZIGZAG layout
    (chunk r + mirror chunk), the layout attn='zigzag' consumes."""
    from mpi4torch_tpu.parallel import zigzag_slice

    mesh = Mesh(np.asarray(jax.devices()[:dp * sp]).reshape(dp, sp),
                ("dp", "sp"))
    comm_dp = mpi.comm_from_mesh(mesh, "dp")
    comm_sp = mpi.comm_from_mesh(mesh, "sp")
    bl = B // dp

    def shard_step(params, tokens):
        rows = jax.lax.dynamic_slice_in_dim(
            tokens, jnp.asarray(comm_dp.rank) * bl, bl, 0)
        local = zigzag_slice(comm_sp, rows, axis=1)
        return T.train_step(cfg, params, local, comm_sp=comm_sp,
                            comm_dp=comm_dp, attn="zigzag")

    return jax.jit(shard_map(shard_step, mesh=mesh, in_specs=P(),
                             out_specs=P(), check_vma=False))


@pytest.mark.slow  # multi-minute oracle compile; TPU/manual lane (tier-1 budget)
class TestZigzagFlagship:
    """attn='zigzag' through the full distributed step: the load-balanced
    layout must reproduce the single-process run exactly — the boundary
    labels cross chunk seams via two one-token ring shifts, and the
    positional encoding follows the two global intervals."""

    @pytest.mark.parametrize("dp,sp", [(2, 4), (1, 8)])
    def test_2d_mesh_matches_single_process(self, dp, sp):
        params, tokens = setup()
        ref_loss, ref_params = reference_step(params, tokens)
        loss, new_params = make_zigzag_mesh_step(CFG, dp, sp)(params,
                                                              tokens)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-12, atol=1e-14)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-9, atol=1e-11),
            new_params, ref_params)

    def test_rope_matches_single_process(self):
        # Rope path: positions are computed (not table-indexed); the two
        # zigzag intervals must rotate with their true global angles.
        cfg = dataclasses.replace(CFG, rope=True)
        params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                    dtype=jnp.float64)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                    cfg.vocab)
        ref_loss, ref_params = T.train_step(cfg, params, tokens)
        loss, new_params = make_zigzag_mesh_step(cfg, 2, 4)(params, tokens)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-12, atol=1e-14)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-9, atol=1e-11),
            new_params, ref_params)

    def test_gqa_matches_single_process(self):
        # Grouped-query KV through the zigzag ring: the kernel resolves
        # the head grouping per block call, the layout only reorders
        # sequence ownership.
        cfg = dataclasses.replace(CFG, n_kv_heads=2)
        params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                    dtype=jnp.float64)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                    cfg.vocab)
        ref_loss, ref_params = T.train_step(cfg, params, tokens)
        loss, new_params = make_zigzag_mesh_step(cfg, 2, 4)(params, tokens)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-12, atol=1e-14)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-9, atol=1e-11),
            new_params, ref_params)

    def test_eager_lm_loss_matches_single_process(self):
        # The eager (thread) backend consumes the same zigzag shards:
        # every rank's sp-summed loss equals the unsharded loss.
        from mpi4torch_tpu.parallel import zigzag_positions
        params, tokens = setup()
        ref = float(T.lm_loss(CFG, params, tokens))
        sp = 4
        pos = zigzag_positions(sp, S // sp)

        def body():
            local = tokens[:, pos[mpi.COMM_WORLD.rank]]
            return float(T.lm_loss(CFG, params, local,
                                   comm_sp=mpi.COMM_WORLD, attn="zigzag"))

        for loss in mpi.run_ranks(body, sp):
            np.testing.assert_allclose(loss, ref, rtol=1e-12)

    def test_window_rejected(self):
        cfg = dataclasses.replace(CFG, attn_window=5)
        params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                    dtype=jnp.float64)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                    cfg.vocab)
        with pytest.raises(ValueError, match="does not compose"):
            make_zigzag_mesh_step(cfg, 1, 8)(params, tokens)


def test_eager_sp_matches_single_process():
    params, tokens = setup()
    ref = float(T.lm_loss(CFG, params, tokens))
    sp = 4
    sl = S // sp

    def body():
        comm = mpi.COMM_WORLD
        local = tokens[:, comm.rank * sl:(comm.rank + 1) * sl]
        return float(T.lm_loss(CFG, params, local, comm_sp=comm,
                               attn="ring"))

    outs = mpi.run_ranks(body, sp)
    for loss in outs:
        np.testing.assert_allclose(loss, ref, rtol=1e-12)


@pytest.mark.parametrize("moe", [False, True])
@pytest.mark.slow  # multi-minute oracle compile; TPU/manual lane (tier-1 budget)
def test_remat_preserves_values_and_grads_on_mesh(moe):
    """cfg.remat (jax.checkpoint per block) must be semantics-preserving:
    identical loss and updated params on the distributed step, including
    the re-executed in-block collectives (ring attention; with moe=True
    also the expert-dispatch Alltoall over a 3D dp x sp x ep mesh)."""
    params, tokens = setup()
    if moe:
        cfg = dataclasses.replace(CFG, n_experts=4, capacity=32,
                                  aux_coef=0.0)
        params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                    dtype=jnp.float64)
        dp, sp, ep = 2, 2, 2
    else:
        cfg, (dp, sp, ep) = CFG, (2, 4, 1)

    loss0, params0 = make_mesh_step(cfg, dp, sp, "ring", ep)(params, tokens)
    cfg_r = dataclasses.replace(cfg, remat=True)
    loss1, params1 = make_mesh_step(cfg_r, dp, sp, "ring", ep)(params,
                                                               tokens)

    np.testing.assert_allclose(float(loss1), float(loss0), rtol=1e-12)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-10, atol=1e-12),
        params1, params0)


@pytest.mark.slow  # multi-minute oracle compile; TPU/manual lane (tier-1 budget)
def test_remat_single_device_grads_match():
    params, tokens = setup()
    cfg_r = dataclasses.replace(CFG, remat=True)
    l0, g0 = jax.value_and_grad(
        lambda p: T.lm_loss(CFG, p, tokens))(params)
    l1, g1 = jax.value_and_grad(
        lambda p: T.lm_loss(cfg_r, p, tokens))(params)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-12)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-10, atol=1e-12),
        g1, g0)


@pytest.mark.parametrize("attn,dp,sp", [("ring", 2, 4), ("ulysses", 4, 2)])
@pytest.mark.slow  # multi-minute oracle compile; TPU/manual lane (tier-1 budget)
def test_gqa_2d_mesh_matches_single_process(attn, dp, sp):
    """Grouped-query attention (n_kv_heads < n_heads) through the full
    distributed step: the 2D-mesh GQA transformer must reproduce the
    single-process GQA run exactly.  Ulysses additionally needs the KV
    head count divisible by sp (each rank keeps whole q-head groups)."""
    cfg = dataclasses.replace(CFG, n_kv_heads=2)
    assert cfg.kv_heads % sp == 0 or attn == "ring"
    params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                dtype=jnp.float64)
    hd = cfg.d_model // cfg.n_heads
    assert params["blocks"][0]["wqkv"].shape == (
        cfg.d_model, cfg.d_model + 2 * 2 * hd)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab)
    ref_loss, ref_params = T.train_step(cfg, params, tokens)

    loss, new_params = make_mesh_step(cfg, dp, sp, attn)(params, tokens)

    np.testing.assert_allclose(float(loss), float(ref_loss),
                               rtol=1e-12, atol=1e-14)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-9, atol=1e-11),
        new_params, ref_params)


@pytest.mark.parametrize("attn,dp,sp", [("ring", 1, 8), ("ulysses", 2, 2)])
@pytest.mark.slow  # multi-minute oracle compile; TPU/manual lane (tier-1 budget)
def test_windowed_2d_mesh_matches_single_process(attn, dp, sp):
    """Sliding-window attention (attn_window) through the distributed
    step: windows span sequence-shard boundaries (s_local=2 at sp=8 with
    window=5), so ring correctness depends on global-position masking."""
    cfg = dataclasses.replace(CFG, attn_window=5)
    params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                dtype=jnp.float64)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab)
    ref_loss, ref_params = T.train_step(cfg, params, tokens)
    # Windowing must actually change the model vs full attention.
    full_loss, _ = T.train_step(CFG, params, tokens)
    assert abs(float(ref_loss) - float(full_loss)) > 1e-9

    loss, new_params = make_mesh_step(cfg, dp, sp, attn)(params, tokens)

    np.testing.assert_allclose(float(loss), float(ref_loss),
                               rtol=1e-12, atol=1e-14)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-9, atol=1e-11),
        new_params, ref_params)


@pytest.mark.slow  # multi-minute oracle compile; TPU/manual lane (tier-1 budget)
class TestRoPE:
    """Rotary position embeddings: relative encoding applied to q/k
    before any transport, so distributed strategies need no special
    handling and decode positions extend past any learned table."""

    def test_shift_invariance(self):
        # Rope'd attention depends only on position DIFFERENCES: shifting
        # every position (and the causal offsets) by a constant must not
        # change the output at all.
        cfg = dataclasses.replace(CFG, rope=True)
        rng = np.random.default_rng(31)
        q = jnp.asarray(rng.standard_normal((1, 8, 2, 4)))
        k = jnp.asarray(rng.standard_normal((1, 8, 2, 4)))
        v = jnp.asarray(rng.standard_normal((1, 8, 2, 4)))
        from mpi4torch_tpu.ops.flash import flash_block_attention
        pos0 = jnp.arange(8, dtype=jnp.int32)

        def attend(shift):
            qr = T._rope_rotate(cfg, q, pos0 + shift)
            kr = T._rope_rotate(cfg, k, pos0 + shift)
            out, _ = flash_block_attention(
                qr, kr, v, causal=True, q_offset=shift, kv_offset=shift,
                impl="jnp")
            return out

        np.testing.assert_allclose(np.asarray(attend(0)),
                                   np.asarray(attend(1000)),
                                   rtol=1e-9, atol=1e-11)

    def test_no_learned_table(self):
        cfg = dataclasses.replace(CFG, rope=True)
        params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                    dtype=jnp.float64)
        assert "pos" not in params

    @pytest.mark.parametrize("attn,dp,sp", [("ring", 2, 4),
                                            ("ulysses", 4, 2)])
    def test_rope_2d_mesh_matches_single_process(self, attn, dp, sp):
        cfg = dataclasses.replace(CFG, rope=True, n_kv_heads=2)
        params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                    dtype=jnp.float64)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                    cfg.vocab)
        ref_loss, ref_params = T.train_step(cfg, params, tokens)

        loss, new_params = make_mesh_step(cfg, dp, sp, attn)(params,
                                                             tokens)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-12, atol=1e-14)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-9, atol=1e-11),
            new_params, ref_params)

    def test_teacher_forced_decode_matches_forward(self):
        cfg = dataclasses.replace(CFG, rope=True, attn_window=5)
        params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                    dtype=jnp.float64)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                    cfg.vocab)
        want = T.forward(cfg, params, tokens)
        cache = T.init_kv_cache(cfg, B, jnp.float64)
        got = []
        for i in range(S):
            logits, cache = T.decode_step(cfg, params, cache,
                                          tokens[:, i], i)
            got.append(logits)
        np.testing.assert_allclose(np.asarray(jnp.stack(got, axis=1)),
                                   np.asarray(want),
                                   rtol=1e-9, atol=1e-11)

    def test_odd_head_dim_raises(self):
        with pytest.raises(ValueError, match="even head_dim"):
            T.TransformerConfig(vocab=8, d_model=24, n_heads=8,
                                n_layers=1, d_ff=8, max_seq=8, rope=True)


@pytest.mark.slow  # multi-minute oracle compile; TPU/manual lane (tier-1 budget)
class TestModernArchitecture:
    """RMSNorm + SwiGLU (+ rope/GQA/window): the llama-family block
    variants must satisfy their defining formulas and reproduce the
    single-process run through the distributed step and the decoder."""

    LLAMA = dataclasses.replace(CFG, norm="rmsnorm", ffn="swiglu",
                                rope=True, n_kv_heads=2)

    def test_rmsnorm_formula(self):
        rng = np.random.default_rng(41)
        x = jnp.asarray(rng.standard_normal((3, 16)))
        p = {"scale": jnp.asarray(rng.standard_normal((16,)))}
        got = T._rms_norm(x, p)
        want = x / np.sqrt(np.mean(np.square(np.asarray(x)), -1,
                                   keepdims=True) + 1e-5) * p["scale"]
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-10)
        # no bias parameter, no centering: adding a constant shifts the
        # output (unlike LayerNorm, which would be invariant)
        assert "bias" not in T.init_transformer(
            jax.random.PRNGKey(0), self.LLAMA, jnp.float64)["ln_f"]

    def test_swiglu_formula(self):
        cfg = dataclasses.replace(CFG, ffn="swiglu")
        params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                    dtype=jnp.float64)
        blk = params["blocks"][0]
        assert blk["w1"].shape == (CFG.d_model, 2 * CFG.d_ff)
        rng = np.random.default_rng(42)
        x = jnp.asarray(rng.standard_normal((2, 4, CFG.d_model)))
        got, _ = T._ffn_residual(cfg, blk, x, None)
        y = T._layer_norm(x, blk["ln2"])
        gate, up = jnp.split(y @ blk["w1"], 2, axis=-1)
        want = x + (jax.nn.silu(gate) * up) @ blk["w2"]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-10)

    @pytest.mark.parametrize("attn,dp,sp", [("ring", 2, 4),
                                            ("ulysses", 4, 2)])
    def test_llama_2d_mesh_matches_single_process(self, attn, dp, sp):
        cfg = self.LLAMA
        params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                    dtype=jnp.float64)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                    cfg.vocab)
        ref_loss, ref_params = T.train_step(cfg, params, tokens)
        loss, new_params = make_mesh_step(cfg, dp, sp, attn)(params,
                                                             tokens)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-12, atol=1e-14)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-9, atol=1e-11),
            new_params, ref_params)

    def test_llama_teacher_forced_decode(self):
        cfg = self.LLAMA
        params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                    dtype=jnp.float64)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0,
                                    cfg.vocab)
        want = T.forward(cfg, params, tokens)
        cache = T.init_kv_cache(cfg, 2, jnp.float64)
        got = []
        for i in range(S):
            logits, cache = T.decode_step(cfg, params, cache,
                                          tokens[:, i], i)
            got.append(logits)
        np.testing.assert_allclose(np.asarray(jnp.stack(got, 1)),
                                   np.asarray(want), rtol=1e-9,
                                   atol=1e-11)

    def test_validation(self):
        with pytest.raises(ValueError, match="unknown norm"):
            dataclasses.replace(CFG, norm="batchnorm")
        with pytest.raises(ValueError, match="unknown ffn"):
            dataclasses.replace(CFG, ffn="relu")
        with pytest.raises(ValueError, match="swiglu"):
            dataclasses.replace(CFG, ffn="swiglu", n_experts=2,
                                capacity=8)


class TestChunkedVocabLoss:
    """lm_loss(vocab_chunk=c): the (batch, seq, vocab) logits never
    materialize — per-chunk slabs fold into an online logsumexp.  Must
    equal the dense loss (values AND grads) exactly at f64."""

    # vocab=31 is prime: chunking requires a divisor, so test on a
    # composite-vocab config.
    VCFG = dataclasses.replace(CFG, vocab=32)

    # chunk == vocab (32) deliberately included: lm_loss treats it as
    # the dense fallback (want_hidden False), so the case covers the
    # dispatch boundary, not _chunked_ce; the real single-split boundary
    # coverage is chunk=16.
    @pytest.mark.parametrize("chunk", [4, 8, 16, 32])
    def test_matches_dense(self, chunk):
        params = T.init_transformer(jax.random.PRNGKey(0), self.VCFG,
                                    dtype=jnp.float64)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                    self.VCFG.vocab)
        dense_l, dense_g = jax.value_and_grad(
            lambda p: T.lm_loss(self.VCFG, p, tokens))(params)
        chunk_l, chunk_g = jax.value_and_grad(
            lambda p: T.lm_loss(self.VCFG, p, tokens,
                                vocab_chunk=chunk))(params)
        np.testing.assert_allclose(float(chunk_l), float(dense_l),
                                   rtol=1e-12)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-9, atol=1e-12),
            chunk_g, dense_g)

    def test_matches_dense_on_sp_mesh(self):
        params = T.init_transformer(jax.random.PRNGKey(0), self.VCFG,
                                    dtype=jnp.float64)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                    self.VCFG.vocab)
        ref = float(T.lm_loss(self.VCFG, params, tokens))
        sp, sl = 4, S // 4

        def body():
            c = mpi.COMM_WORLD
            local = tokens[:, c.rank * sl:(c.rank + 1) * sl]
            return float(T.lm_loss(self.VCFG, params, local, comm_sp=c,
                                   attn="ring", vocab_chunk=8))

        for loss in mpi.run_ranks(body, sp):
            np.testing.assert_allclose(loss, ref, rtol=1e-12)

    def test_moe_aux_path(self):
        cfg = dataclasses.replace(self.VCFG, n_experts=4, capacity=B * S)
        params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                    dtype=jnp.float64)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                    cfg.vocab)
        dense = float(T.lm_loss(cfg, params, tokens))
        chunked = float(T.lm_loss(cfg, params, tokens, vocab_chunk=8))
        np.testing.assert_allclose(chunked, dense, rtol=1e-12)

    def test_nondivisor_raises(self):
        params = T.init_transformer(jax.random.PRNGKey(0), self.VCFG,
                                    dtype=jnp.float64)
        tokens = jnp.zeros((1, S), jnp.int32)
        with pytest.raises(ValueError, match="must divide vocab"):
            T.lm_loss(self.VCFG, params, tokens, vocab_chunk=5)


@pytest.mark.slow  # multi-minute oracle compile; TPU/manual lane (tier-1 budget)
class TestZeroTrainStep:
    """zero_train_step: ZeRO-1 over dp composed with sp inside the
    flagship — must reproduce the replicated-DP optax trajectory."""

    @pytest.mark.parametrize("dp,sp", [(4, 1), (2, 2)])
    def test_matches_replicated_adam(self, dp, sp):
        import optax

        opt = optax.adam(1e-2)
        params = T.init_transformer(jax.random.PRNGKey(0), CFG,
                                    dtype=jnp.float64)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                    CFG.vocab)

        # Replicated oracle: mean-over-dp-shards loss, plain adam.
        bl = B // dp

        def mean_loss(p):
            return sum(
                T.lm_loss(CFG, p, tokens[r * bl:(r + 1) * bl])
                for r in range(dp)) / dp

        ref_p, ref_s = params, opt.init(params)
        for _ in range(3):
            _, g = jax.value_and_grad(mean_loss)(ref_p)
            u, ref_s = opt.update(g, ref_s, ref_p)
            ref_p = jax.tree.map(jnp.add, ref_p, u)

        from mpi4torch_tpu.parallel import zero_init

        mesh = Mesh(np.asarray(jax.devices()[:dp * sp]).reshape(dp, sp),
                    ("dp", "sp"))
        cd = mpi.comm_from_mesh(mesh, "dp")
        cs = mpi.comm_from_mesh(mesh, "sp")
        sl = S // sp

        # Per-rank shard states stay INTERNAL to one compiled program
        # (they differ across dp ranks; params return replicated).
        def full(params):
            state = zero_init(cd, opt, params)
            for _ in range(3):
                local = jax.lax.dynamic_slice(
                    tokens, (jnp.asarray(cd.rank) * bl,
                             jnp.asarray(cs.rank) * sl), (bl, sl))
                loss, params, state = T.zero_train_step(
                    CFG, params, local, opt, state, comm_dp=cd,
                    comm_sp=cs, attn="ring")
            return loss, params

        loss, new_params = jax.jit(shard_map(
            full, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False))(params)

        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-9, atol=1e-11),
            new_params, ref_p)

    def test_moe_ep_axis_matches_replicated(self):
        # ep composes as a data axis (train_step's discipline): ZeRO
        # over dp with experts sharded over ep must match replicated
        # Adam on the dense-expert model over all dp x ep data shards.
        import optax
        from mpi4torch_tpu.parallel import zero_init

        cfg = dataclasses.replace(CFG, n_experts=4, capacity=B * S,
                                  aux_coef=0.0)
        opt = optax.adam(1e-2)
        params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                    dtype=jnp.float64)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                    cfg.vocab)
        dp = ep = 2
        bl = B // (dp * ep)

        def mean_loss(p):
            return sum(
                T.lm_loss(cfg, p, tokens[r * bl:(r + 1) * bl])
                for r in range(dp * ep)) / (dp * ep)

        ref_p, ref_s = params, opt.init(params)
        for _ in range(2):
            _, g = jax.value_and_grad(mean_loss)(ref_p)
            u, ref_s = opt.update(g, ref_s, ref_p)
            ref_p = jax.tree.map(jnp.add, ref_p, u)

        mesh = Mesh(np.asarray(jax.devices()[:dp * ep]).reshape(dp, ep),
                    ("dp", "ep"))
        cd = mpi.comm_from_mesh(mesh, "dp")
        ce = mpi.comm_from_mesh(mesh, "ep")

        def full(params):
            state = zero_init(cd, opt, params)
            for _ in range(2):
                r_b = jnp.asarray(cd.rank) * ep + jnp.asarray(ce.rank)
                local = jax.lax.dynamic_slice(
                    tokens, (r_b * bl, jnp.int32(0)), (bl, S))
                loss, params, state = T.zero_train_step(
                    cfg, params, local, opt, state, comm_dp=cd,
                    comm_ep=ce)
            return loss, params

        loss, new_params = jax.jit(shard_map(
            full, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False))(params)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-9, atol=1e-11),
            new_params, ref_p)


@pytest.mark.slow  # multi-minute oracle compile; TPU/manual lane (tier-1 budget)
class TestZero3TrainStep:
    """zero3_train_step: parameters live as 1/dp shards BETWEEN steps;
    the dp reduction rides the Allgather adjoint.  Must reproduce the
    replicated-DP optax trajectory exactly, composed with sp."""

    @pytest.mark.parametrize("dp,sp", [(4, 1), (2, 2)])
    def test_matches_replicated_adam(self, dp, sp):
        import optax

        opt = optax.adam(1e-2)
        params = T.init_transformer(jax.random.PRNGKey(0), CFG,
                                    dtype=jnp.float64)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                    CFG.vocab)
        bl = B // dp

        def mean_loss(p):
            return sum(
                T.lm_loss(CFG, p, tokens[r * bl:(r + 1) * bl])
                for r in range(dp)) / dp

        ref_p, ref_s = params, opt.init(params)
        for _ in range(3):
            _, g = jax.value_and_grad(mean_loss)(ref_p)
            u, ref_s = opt.update(g, ref_s, ref_p)
            ref_p = jax.tree.map(jnp.add, ref_p, u)

        from mpi4torch_tpu.parallel import zero3_init, zero3_params

        mesh = Mesh(np.asarray(jax.devices()[:dp * sp]).reshape(dp, sp),
                    ("dp", "sp"))
        cd = mpi.comm_from_mesh(mesh, "dp")
        cs = mpi.comm_from_mesh(mesh, "sp")
        sl = S // sp

        def full(params):
            p_shards, state = zero3_init(cd, opt, params)
            for _ in range(3):
                local = jax.lax.dynamic_slice(
                    tokens, (jnp.asarray(cd.rank) * bl,
                             jnp.asarray(cs.rank) * sl), (bl, sl))
                loss, p_shards, state = T.zero3_train_step(
                    CFG, p_shards, params, local, opt, state,
                    comm_dp=cd, comm_sp=cs, attn="ring")
            return loss, zero3_params(cd, p_shards, params)

        loss, new_params = jax.jit(shard_map(
            full, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False))(params)

        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-9, atol=1e-11),
            new_params, ref_p)


def test_gqa_bad_head_ratio_raises():
    with pytest.raises(ValueError, match="multiple of n_kv_heads"):
        dataclasses.replace(CFG, n_kv_heads=3)


@pytest.mark.slow  # multi-minute oracle compile; TPU/manual lane (tier-1 budget)
class TestDecoding:
    """KV-cache incremental decoding must be exactly the training forward
    read one position at a time (teacher-forcing equivalence) — including
    under GQA (cache holds only the KV heads) and sliding windows."""

    @pytest.mark.parametrize("cfg", [
        CFG,
        dataclasses.replace(CFG, n_kv_heads=2),
        dataclasses.replace(CFG, attn_window=5),
        dataclasses.replace(CFG, n_kv_heads=4, attn_window=3),
        # Capacity must not bind (B*S covers every token): decode routes
        # per step while training routes per call, so binding capacity
        # legitimately drops different tokens (documented carve-out,
        # models/transformer.py _ffn_residual).
        dataclasses.replace(CFG, n_experts=4, capacity=B * S),
    ], ids=["mha", "gqa", "window", "gqa+window", "moe"])
    def test_teacher_forced_decode_matches_forward(self, cfg):
        params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                    dtype=jnp.float64)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                    cfg.vocab)
        want = T.forward(cfg, params, tokens)        # (B, S, vocab)

        cache = T.init_kv_cache(cfg, B, jnp.float64)
        got = []
        for i in range(S):
            logits, cache = T.decode_step(cfg, params, cache,
                                          tokens[:, i], i)
            got.append(logits)
        got = jnp.stack(got, axis=1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-9, atol=1e-11)

    def test_gqa_cache_holds_only_kv_heads(self):
        cfg = dataclasses.replace(CFG, n_kv_heads=2)
        cache = T.init_kv_cache(cfg, 3, jnp.float32)
        assert cache[0]["k"].shape == (3, S, 2, CFG.d_model // CFG.n_heads)

    def test_generate_greedy_matches_stepwise_argmax(self):
        cfg = CFG
        params = T.init_transformer(jax.random.PRNGKey(2), cfg,
                                    dtype=jnp.float64)
        prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 4), 0,
                                    cfg.vocab)
        out = T.generate(cfg, params, prompt, n_new=6, dtype=jnp.float64)
        assert out.shape == (2, 10)
        assert bool(jnp.all(out[:, :4] == prompt))
        # Oracle: greedy continuation via repeated FULL forwards.
        seq = prompt
        for _ in range(6):
            logits = T.forward(cfg, params, seq)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(prompt.dtype)
            seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))

    def test_generate_overflow_raises(self):
        params = T.init_transformer(jax.random.PRNGKey(0), CFG,
                                    dtype=jnp.float64)
        prompt = jnp.zeros((1, S), jnp.int32)
        with pytest.raises(ValueError, match="exceeds max_seq"):
            T.generate(CFG, params, prompt, n_new=1)

    def test_sampled_generation(self):
        cfg = CFG
        params = T.init_transformer(jax.random.PRNGKey(2), cfg,
                                    dtype=jnp.float64)
        prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 4), 0,
                                    cfg.vocab)
        greedy = T.generate(cfg, params, prompt, n_new=6)
        # Vanishing temperature concentrates the categorical on the
        # argmax: must reproduce greedy exactly.
        cold = T.generate(cfg, params, prompt, n_new=6, temperature=1e-6,
                          key=jax.random.PRNGKey(0))
        np.testing.assert_array_equal(np.asarray(cold), np.asarray(greedy))
        # Same key -> same sample; top_k=1 is greedy regardless of temp.
        s1 = T.generate(cfg, params, prompt, n_new=6, temperature=2.0,
                        key=jax.random.PRNGKey(7))
        s2 = T.generate(cfg, params, prompt, n_new=6, temperature=2.0,
                        key=jax.random.PRNGKey(7))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
        k1 = T.generate(cfg, params, prompt, n_new=6, temperature=5.0,
                        top_k=1, key=jax.random.PRNGKey(9))
        np.testing.assert_array_equal(np.asarray(k1), np.asarray(greedy))
        assert bool(jnp.all(s1 >= 0)) and bool(jnp.all(s1 < cfg.vocab))
        with pytest.raises(ValueError, match="requires a PRNG"):
            T.generate(cfg, params, prompt, n_new=2, temperature=1.0)
        with pytest.raises(ValueError, match="top_k"):
            T.generate(cfg, params, prompt, n_new=2, top_k=cfg.vocab + 1)

    def test_cache_dtype_override_mixed_precision(self):
        # ADVICE r4 (medium): a bf16 serving cache under f32 params must
        # work — decode_step/prefill cast projected k/v to the cache
        # dtype.  Greedy tokens should also agree with the full-precision
        # cache at this tiny config (logit gaps >> bf16 cache rounding;
        # checked, not assumed — a mismatch would fail loudly here).
        cfg = CFG
        params = T.init_transformer(jax.random.PRNGKey(2), cfg,
                                    dtype=jnp.float32)
        prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 4), 0,
                                    cfg.vocab)
        out_bf16 = T.generate(cfg, params, prompt, n_new=6,
                              dtype=jnp.bfloat16)
        assert out_bf16.shape == (2, 10)
        out_f32 = T.generate(cfg, params, prompt, n_new=6)
        np.testing.assert_array_equal(np.asarray(out_bf16),
                                      np.asarray(out_f32))
        # The override must actually reach the cache storage.
        cache = T.init_kv_cache(cfg, 2, jnp.bfloat16)
        _, cache = T.prefill(cfg, params, cache, prompt)
        assert cache[0]["k"].dtype == jnp.bfloat16
        logits, cache = T.decode_step(cfg, params, cache,
                                      prompt[:, -1], 4)
        assert cache[0]["k"].dtype == jnp.bfloat16
        assert logits.dtype == jnp.float32

    def test_decode_step_concrete_overflow_raises(self):
        # Past max_seq the dynamic slice would CLAMP (silently reusing
        # the last positional row and cache slot); concrete positions
        # must fail loudly instead.
        params = T.init_transformer(jax.random.PRNGKey(0), CFG,
                                    dtype=jnp.float64)
        cache = T.init_kv_cache(CFG, 1, jnp.float64)
        tok = jnp.zeros((1,), jnp.int32)
        with pytest.raises(ValueError, match="out of range"):
            T.decode_step(CFG, params, cache, tok, S)


def test_forward_shapes_and_unknown_strategy():
    params, tokens = setup()
    logits = T.forward(CFG, params, tokens)
    assert logits.shape == (B, S, CFG.vocab)
    with pytest.raises(ValueError, match="unknown attention"):
        T._attention(jnp.ones((1, 2, 2, 2)), jnp.ones((1, 2, 2, 2)),
                     jnp.ones((1, 2, 2, 2)),
                     type("C", (), {"size": 2})(), "bogus")
    # dense attention cannot see across sequence shards: must raise, not
    # silently compute block-local attention.
    with pytest.raises(ValueError, match="sequence shards"):
        T._attention(jnp.ones((1, 2, 2, 2)), jnp.ones((1, 2, 2, 2)),
                     jnp.ones((1, 2, 2, 2)),
                     type("C", (), {"size": 2})(), "dense")


# ------------------------------- what a rematerialised uniform block keeps

def _remat_cfg(ffn="swiglu", window=0, **kw):
    base = dict(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=48,
                max_seq=16, n_kv_heads=2, rope=True, norm="rmsnorm",
                ffn=ffn, attn_window=window)
    return T.TransformerConfig(**{**base, **kw})


def _loss_and_grads(cfg, params, tokens):
    return jax.value_and_grad(lambda p: T.lm_loss(cfg, p, tokens))(params)


@pytest.mark.parametrize("ffn,window", [("swiglu", 0), ("gelu", 0),
                                        ("swiglu", 8), ("gelu", 8)])
def test_kept_outputs_are_the_recomputed_ones_bit_for_bit(ffn, window):
    """A kept value and a recomputed one are the same bits: loss and
    gradients of a uniform stack under ``remat`` equal those without it,
    one equation at a time (under ``jit`` the CPU's compiler contracts a
    multiply and an add differently from one program to the next, which
    is no doing of the policy's: there they agree to rounding)."""
    cfg = _remat_cfg(ffn, window)
    params = T.init_transformer(jax.random.PRNGKey(0), cfg, jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    kept = dataclasses.replace(cfg, remat=True)
    assert T._remat_kept_layers(kept, tokens.shape, jnp.float32, 1,
                                T._memory_limit_bytes()) == 2
    l0, g0 = _loss_and_grads(cfg, params, tokens)
    l1, g1 = _loss_and_grads(kept, params, tokens)
    assert float(l0) == float(l1)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), g1, g0)
    l2, g2 = jax.jit(_loss_and_grads, static_argnums=0)(kept, params, tokens)
    np.testing.assert_allclose(float(l2), float(l0), rtol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_array_less(
        np.linalg.norm(a - b), 1e-5 * np.linalg.norm(b) + 1e-30), g2, g0)


def _count(jaxpr, found) -> int:
    """Equations of a jaxpr, and of every jaxpr it holds but a kernel's
    own, that ``found`` takes."""
    n = 0
    for eqn in jaxpr.eqns:
        n += bool(found(eqn))
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.tree.leaves(
                list(eqn.params.values()),
                is_leaf=lambda x: hasattr(x, "eqns") or hasattr(x, "jaxpr")):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                n += _count(sub, found)
    return n


def _over_a_weight(eqn) -> bool:
    # Activations are (b, s, ...) all the way; only a weight has two axes.
    return eqn.primitive.name == "dot_general" and any(
        v.aval.ndim == 2 for v in eqn.invars)


def _flash_forward(eqn) -> bool:
    from mpi4torch_tpu.ops import flash
    return eqn.primitive.name == "pallas_call" \
        and eqn.params["name"] == flash.KERNEL_NAMES[0]


def test_the_backward_of_a_kept_block_runs_each_product_once(monkeypatch):
    """The gradient's jaxpr under the policy holds as many products over
    a weight as without ``remat`` and one flash forward a layer; a
    ``jax.checkpoint`` with no policy, which is what a block past the
    rule's count gets, runs three products and the kernel again (not
    ``h @ w2``: nothing reads it twice).  Traced as on the TPU (the
    kernel's equations are looked for, nothing is lowered)."""
    from mpi4torch_tpu.ops import flash
    monkeypatch.setattr(flash, "_on_tpu", lambda: True)
    cfg = _remat_cfg(d_model=128, n_heads=2, n_kv_heads=1, d_ff=192,
                     max_seq=128)
    tokens = jnp.zeros((2, 128), jnp.int32)

    def counts(cfg, limit):
        monkeypatch.setattr(T, "_memory_limit_bytes", lambda: limit)
        with jax.enable_x64(False):
            params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                        jnp.float32)
            jaxpr = jax.make_jaxpr(jax.grad(
                lambda p: T.lm_loss(cfg, p, tokens)))(params).jaxpr
        return _count(jaxpr, _over_a_weight), _count(jaxpr, _flash_forward)

    layers = cfg.n_layers
    plain = counts(cfg, None)
    # forward and backward of four products a layer, and of the head
    assert plain == (2 * (4 * layers + 1), layers)
    remat = dataclasses.replace(cfg, remat=True)
    assert counts(remat, None) == plain
    assert counts(remat, 1) == (plain[0] + 3 * layers, 2 * layers)
    # room for one layer's outputs beside the parameters: one keeps
    a_layer = 2 * 128 * ((128 + 2 * 64) + 128 + 2 * 192 + 128) * 4 \
        + 2 * 128 * 2 * 4
    p_bytes = sum(p.size * 4 for p in jax.tree.leaves(jax.eval_shape(
        lambda: T.init_transformer(jax.random.PRNGKey(0), cfg, jnp.float32))))
    one = int((3 * p_bytes + 1.5 * a_layer) / T._REMAT_ROOM)
    assert counts(remat, one) == (plain[0] + 3, layers + 1)


def test_how_many_layers_keep_their_outputs():
    """``_remat_kept_layers`` over made-up limits."""
    cfg = _remat_cfg(n_layers=6, remat=True)
    shape, p_bytes = (2, 16), 100_000
    kept = lambda limit, **kw: T._remat_kept_layers(
        cfg, shape, jnp.float32, p_bytes, limit, **kw)
    # qkv 64 + the residual sum 32 + gate and up 96 + the kernel's output
    # 32, and four heads' float32 lse, a token
    a_layer = 32 * ((64 + 32 + 96 + 32) * 4 + 4 * 4)
    assert kept(None) == 6                     # no limit reported: all
    assert kept(p_bytes) == 0                  # the parameters alone pass it
    assert kept(int(3 * p_bytes / T._REMAT_ROOM)) == 0
    at = lambda k: int((3 * p_bytes + (k + 0.5) * a_layer) / T._REMAT_ROOM)
    assert [kept(at(k)) for k in range(8)] == [0, 1, 2, 3, 4, 5, 6, 6]
    counts = [kept(limit) for limit in range(0, at(7), 997)]
    assert counts == sorted(counts) and set(counts) == set(range(7))
    # without the pair (sequence parallelism) a layer's outputs are fewer
    assert kept(at(3), flash_pair=False) == 4
    # the top-1 expert FFN of a uniform block has no named product
    moe = _remat_cfg("gelu", n_layers=6, remat=True, n_experts=2, capacity=4)
    assert T._remat_kept_layers(moe, shape, jnp.float32, p_bytes, at(3)) > 3
    # a spec's uniform layers count, its other layers do not
    spec = _remat_cfg(n_layers=3, remat=True, layers=(
        T.LayerSpec(), T.LayerSpec(only="ffn"), T.LayerSpec()))
    assert T._remat_kept_layers(spec, shape, jnp.float32, p_bytes, None) == 2


def test_the_mistral_cells_keep_all_four_layers():
    """`train_1chip` and `train_dp4`: four layers at Mistral-7B's widths,
    2 x 4,096 tokens a chip in bfloat16, on a chip that reports 16.9 GB:
    705.7 MB a layer beside 3 x 2.27 GB of parameters.  A chip of 12 GB
    has the room for three of them; the published 32 layers at the same
    shape keep none, three times their parameters pass the chip's memory
    by themselves."""
    def kept(n_layers, limit):
        cfg = T.TransformerConfig(
            vocab=32000, d_model=4096, n_heads=32, n_layers=n_layers,
            d_ff=14336, max_seq=4096, n_kv_heads=8, attn_window=4096,
            rope=True, norm="rmsnorm", ffn="swiglu", remat=True)
        shapes = jax.eval_shape(lambda: T.init_transformer(
            jax.random.PRNGKey(0), cfg, jnp.bfloat16))
        p_bytes = sum(p.size * 2 for p in jax.tree.leaves(shapes))
        assert abs(p_bytes - (2.269e9 + (n_layers - 4) * 436.2e6)) < 1e6
        return T._remat_kept_layers(cfg, (2, 4096), jnp.bfloat16, p_bytes,
                                    limit)

    assert kept(4, 16_909_336_064) == 4
    assert kept(4, 12_000_000_000) == 3
    assert kept(32, 16_909_336_064) == 0
