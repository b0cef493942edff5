"""LongCat-Flash on the serving path (ISSUE 34), at small sizes on the
CPU, seeded float32 weights: a shortcut branch carried across two
spec'd layers in every serving program and in the training forward,
softmax routing that is not renormalised, zero-compute experts and
their two counters, the two scaled latents in the cache row, against
the plain reference (``benchmarks/references/longcat_flash.py``), which
writes a published layer out as one double block.

Tolerances.  ``TOL = 2e-5`` on logits of order 1-4: both sides are
float32 (the suite's x64 leaves explicit float32 alone) and differ by
the order of their sums (measured 7e-7 over four spec'd layers).  Each
broken layer below misses it by a hundred times or more over 48
positions, which is the reason it is the tolerance."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4torch_tpu as mpi
from benchmarks import run as harness
from benchmarks.families import longcat_flash as fam
from benchmarks.references import dense_decoder as plain
from benchmarks.references import longcat_flash as ref
from mpi4torch_tpu import serve
from mpi4torch_tpu.models import transformer as T
from mpi4torch_tpu.parallel import moe
from mpi4torch_tpu.serve import kv
from mpi4torch_tpu.utils import profiling

F32 = jnp.float32
TOL = 2e-5
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "longcat-flash-chat.json")) as f:
    PUBLISHED = json.load(f)
CFG = harness.merged(PUBLISHED, PUBLISHED["rehearsal"])
TCFG = fam.transformer_config(CFG)
P_LEN, N_NEW, BS = 16, 8, 8
MM = plain.matmul_f32


def _weights(seed=7, cfg=CFG):
    key = fam.seed_key(seed)
    top = fam.make_top(key, cfg, F32)
    blocks = [fam.make_layer(key, cfg, i, F32)
              for i in range(cfg["num_hidden_layers"])]
    return top, blocks


def _tokens(n=P_LEN + N_NEW, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, CFG["vocab_size"], size=(1, n)).astype(np.int32)


def _reference(top, blocks, toks, cfg=CFG):
    """The reference's logits at every position of ``toks`` (1, n),
    through the entry the benchmark calls."""
    rows = jnp.arange(toks.shape[1])[None]
    return np.asarray(ref.logits_at(cfg, top, iter(blocks),
                                    jnp.asarray(toks), rows))[0]


def _gap(a, b):
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def _paged_state(tcfg, params, toks):
    """A pool holding ``toks``' first P_LEN rows in scattered pages
    (through the chunk view from an empty past and the one compiled
    install), the table that names them, and the prefill's logits."""
    pool = kv.init_kv_pool_tp(tcfg, 8, BS, 1, F32)
    empty = jax.tree.map(lambda a: a[:, :0],
                         kv.init_kv_cache_tp(tcfg, 1, 1, F32))
    logits, rows = kv.prefill_chunk_tp(tcfg, params, empty,
                                       jnp.asarray(toks[:, :P_LEN]))
    n_pages = kv.install_page_count(P_LEN, BS)
    index = np.concatenate([[0, P_LEN], [3, 5],
                            8 + np.arange(n_pages - 2)]).astype(np.int32)
    pool = kv.install_rows_paged(pool, rows, jnp.asarray(index))
    table = np.array([[3, 5, 1, 0, -1, -1, -1, -1]], np.int32)
    return pool, table, logits


# ------------------------------------------------- the walk and its views

def test_the_configuration_is_the_published_one():
    """Every width as published; what is cut is named."""
    want = dict(hidden_size=6144, num_attention_heads=64, q_lora_rank=1536,
                kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                v_head_dim=128, ffn_hidden_size=12288,
                expert_ffn_hidden_size=2048, router_outputs=768,
                zero_expert_num=256, moe_topk=12, routed_scaling_factor=6,
                rope_theta=10000000)
    assert {k: PUBLISHED[k] for k in want} == want
    assert PUBLISHED["reduced"] == ["num_layers", "n_routed_experts",
                                    "vocab_size", "max_position_embeddings"]
    assert set(PUBLISHED["published"]) == set(PUBLISHED["reduced"])
    tcfg = fam.transformer_config(PUBLISHED)
    assert len(tcfg.layers) == 8 and tcfg.layers[0].mixer.n_heads == 64
    assert [sp.branch is not None for sp in tcfg.layers] == [True, False] * 4
    assert [sp.join for sp in tcfg.layers] == [False, True] * 4
    branch = tcfg.layers[0].branch
    assert (branch.width, branch.n_held, branch.score, branch.renorm) \
        == (768, 16, "softmax", False)
    assert tcfg.layers[0].mixer.q_scale == 2.0
    assert tcfg.layers[0].mixer.kv_scale == 12 ** 0.5


@pytest.mark.parametrize("cache", ["paged", "dense"])
def test_prefill_then_decode_equals_the_references_full_forward(cache):
    """The four cache views: the one-piece prefill and the dense step,
    the chunk prefill (with the install) and the paged step."""
    top, blocks = _weights()
    params = dict(top, blocks=blocks)
    toks = _tokens()
    want = _reference(top, blocks, toks)
    if cache == "dense":
        state = kv.init_kv_cache_tp(TCFG, 1, 1, F32)
        logits, state = kv.prefill_tp(TCFG, params, state,
                                      jnp.asarray(toks[:, :P_LEN]))
    else:
        state, table, logits = _paged_state(TCFG, params, toks)
    assert len(state) == CFG["num_hidden_layers"] == 2 * CFG["num_layers"]
    assert _gap(logits[0], want[P_LEN - 1]) < TOL
    for t in range(P_LEN, P_LEN + N_NEW):
        tok, pos = jnp.asarray(toks[:, t]), jnp.asarray([t])
        if cache == "dense":
            logits, state = kv.decode_step_tp(TCFG, params, state, tok, pos)
        else:
            logits, state = kv.decode_step_paged(
                TCFG, params, state, table, tok, pos,
                active=jnp.asarray([True]))
        assert _gap(logits[0], want[t]) < TOL, t


def test_the_cached_row_holds_the_scaled_latent():
    """The absorbed and the expanded read see the same rows: the scale
    is applied once, before the row is formed."""
    top, blocks = _weights()
    spec, p = TCFG.layers[0].mixer, blocks[0]["mixer"]
    bare = dataclasses.replace(spec, q_scale=1.0, kv_scale=1.0)
    y = jnp.asarray(np.random.default_rng(3).standard_normal(
        (1, 11, CFG["hidden_size"])), F32)
    q, c, k_r, _ = T.mla_project(TCFG, spec, p, y, jnp.arange(11))
    q0, c0, k_r0, _ = T.mla_project(TCFG, bare, p, y, jnp.arange(11))
    assert _gap(c, spec.kv_scale * c0) < 1e-6 and _gap(k_r, k_r0) == 0
    lat = kv._Latent(spec, p)
    rows = lat.rows(c, k_r)
    assert _gap(rows[0, :, 0, :spec.kv_rank], c[0]) == 0
    u = kv.latent_rows_attention(
        lat.absorbed(q[:, -1]), rows[:, :, 0], jnp.asarray([10]),
        v_width=spec.kv_rank, scale=lat.scale)
    assert _gap(lat.values(u)[0], lat.expanded(q, rows)[0, -1]) < 2e-6
    with pytest.raises(ValueError, match="needs q_rank"):
        T.MLA(4, 32, 16, 8, 16, q_scale=2.0)


def _engine(params, **serve_cfg):
    base = dict(slots=2, block_size=BS, max_new=N_NEW)
    return serve.Engine(TCFG, params, serve.ServeConfig(**{**base,
                                                           **serve_cfg}))


def _follows_the_reference(top, blocks, out) -> bool:
    """Every served token is the reference's own choice at its
    position, given the tokens before it."""
    want = _reference(top, blocks, out[None, :-1].astype(np.int32))
    return bool(np.array_equal(want[P_LEN - 1:].argmax(-1), out[P_LEN:]))


@pytest.mark.parametrize("how", ["one_piece", "chunked", "prefix_hit"])
def test_engine_on_the_latent_pool_serves_the_references_tokens(how):
    top, blocks = _weights()
    prompt = _tokens()[0, :P_LEN]
    eng = _engine(dict(top, blocks=blocks),
                  prefill_chunk=5 if how == "chunked" else None)
    rid = eng.submit(prompt)
    out = eng.run()[rid]
    if how == "prefix_hit":
        again = eng.submit(prompt)
        assert np.array_equal(eng.run()[again], out)
        assert eng.stats.counters["prefix_hits"] == 1
    assert len(out) == P_LEN + N_NEW
    assert _follows_the_reference(top, blocks, out)
    assert eng.stats.snapshot()["blocks_in_use"] == 0


def test_spmd_engine_counts_and_names_its_scopes():
    """``Engine(spmd=True)``: the reference's tokens, the three scopes
    in both compiled programs, and the two counters on every step
    record by name: the prefill's and the decode step's pairs on the
    admitting step, the live slot's alone afterwards."""
    top, blocks = _weights()
    prompt = _tokens()[0, :P_LEN]
    serve.reset_stats()
    eng = serve.Engine(TCFG, dict(top, blocks=iter(blocks)),
                       serve.ServeConfig(slots=3, block_size=BS,
                                         max_new=N_NEW),
                       spmd=True, nranks=1)
    rid = eng.submit(prompt)
    out = eng.run()[rid]
    assert _follows_the_reference(top, blocks, out)
    texts = eng.program_texts()
    assert set(texts) == {"decode", f"prefill.{P_LEN}"}
    for text in texts.values():
        for scope in ("mla", "moe", "ffn"):
            assert profiling.LAYER_SCOPES[scope] in text, scope
    log = profiling.serve_step_log()
    layers, k = CFG["num_layers"], CFG["moe_topk"]
    assert log[0]["moe_live_pairs"] == (P_LEN + 1) * layers * k
    assert [p for p, _ in log[0]["moe_rows"]] == ["prefill", "decode"]
    for rec in log[1:]:
        assert rec["moe_live_pairs"] == layers * k       # one live slot
        assert 0 <= rec["moe_zero_pairs"] <= rec["moe_live_pairs"]
        assert rec["moe_rows"][0][1].shape == (layers,
                                               CFG["n_routed_experts"])
    zero = sum(r["moe_zero_pairs"] for r in log)
    assert zero == eng.stats.counters["moe_zero_pairs"] > 0
    assert serve.stats()["moe_live_pairs"] \
        == sum(r["moe_live_pairs"] for r in log)


@pytest.mark.parametrize("spmd", [False, True], ids=["eager", "spmd"])
def test_decode_counters_come_down_with_the_tokens(spmd, monkeypatch):
    """A decode-only step makes exactly one device-to-host transfer, the
    ``(slots + counted,)`` array that holds the chosen tokens and the
    step's counters behind them, and its record carries ``moe_rows``,
    ``moe_zero_pairs`` and ``moe_live_pairs`` as the decode program
    hands them out when asked directly (the parent's second fetch)."""
    from mpi4torch_tpu.serve import engine as E

    top, blocks = _weights()
    serve.reset_stats()
    eng = serve.Engine(TCFG, dict(top, blocks=iter(blocks)),
                       serve.ServeConfig(slots=3, block_size=BS,
                                         max_new=N_NEW),
                       spmd=spmd, nranks=1 if spmd else None)
    eng.submit(_tokens()[0, :P_LEN])
    eng.submit(_tokens(seed=1)[0, :P_LEN - 3])
    eng.step()
    eng.step()
    # What the program counts for the step to come, read off a copy of
    # the engine's state (the step takes its pool over).
    rank0 = (lambda t: jax.tree.map(lambda a: a[0], t)) if spmd \
        else (lambda t: t)
    host = eng._host_state()
    want = {}
    kv.decode_step_paged(
        TCFG, rank0(eng._shards), jax.tree.map(jnp.copy, rank0(eng._cache)),
        host["table"], host["tokens"], host["pos"], active=host["live"],
        stats=want)
    want = jax.device_get(want)
    assert set(want) == {"moe_rows", "moe_zero_pairs", "moe_live_pairs",
                         "moe_overflow_calls"}

    class Watched:
        """numpy, but for the arrays the engine copies off the device."""
        down = []

        def __getattr__(self, name):
            return getattr(np, name)

        def asarray(self, x, *a, **kw):
            if isinstance(x, jax.Array):
                self.down.append(x.shape)
            return np.asarray(x, *a, **kw)

        array = asarray

    def no_device_get(tree):
        raise AssertionError("a second fetch in a decode-only step")

    with monkeypatch.context() as m:
        m.setattr(E, "np", Watched())
        m.setattr(jax, "device_get", no_device_get)
        eng.step()
    counted = want["moe_rows"].size + 3
    assert Watched.down == [((1,) if spmd else ())
                            + (eng.serve_cfg.slots + counted,)]
    rec = profiling.serve_step_log()[-1]
    assert rec["admitted"] == 0 and rec["active"] == 2
    assert rec["decode_uploads"] == 0
    assert E.SPAN_FETCH_COUNTERS in [s[0] for s in rec["spans"]]
    ((program, rows),) = rec["moe_rows"]
    assert program == "decode" and rows.shape == want["moe_rows"].shape
    np.testing.assert_array_equal(rows, want["moe_rows"])
    assert rows.sum() > 0
    assert rec["moe_zero_pairs"] == int(want["moe_zero_pairs"]) > 0
    assert rec["moe_overflow_calls"] == int(want["moe_overflow_calls"]) == 0
    assert rec["moe_live_pairs"] == int(want["moe_live_pairs"]) \
        == 2 * CFG["num_layers"] * CFG["moe_topk"]


# ------------------------------------------------------------- training

def _loss(logits, toks):
    logp = jax.nn.log_softmax(logits[:, :-1].astype(F32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, jnp.asarray(toks)[:, 1:, None], axis=-1))


def test_training_forward_and_gradient_equal_the_references():
    """The same spec through the training forward: logits, the loss and
    its gradient in every leaf, against ``jax.grad`` of the reference
    (the selection bias takes no gradient on either side)."""
    top, blocks = _weights()
    toks = _tokens(40)
    params = dict(top, blocks=blocks)
    want = _reference(top, blocks, toks)
    assert _gap(T.forward(TCFG, params, jnp.asarray(toks))[0], want) < TOL
    g_ref = jax.grad(lambda p: _loss(ref.forward(
        CFG, {k: v for k, v in p.items() if k != "blocks"}, p["blocks"],
        jnp.asarray(toks), MM), toks))(params)
    loss, g = jax.value_and_grad(
        lambda p: T.lm_loss(TCFG, p, jnp.asarray(toks)))(params)
    assert abs(float(loss) - float(_loss(want[None], toks))) < 1e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(g)
    flat_ref = jax.tree.leaves(g_ref)
    assert len(flat) == len(flat_ref)
    for (path, a), b in zip(flat, flat_ref):
        name = jax.tree_util.keystr(path)
        norm = float(jnp.linalg.norm(b))
        if name.endswith("['bias']"):
            assert float(jnp.linalg.norm(a)) == norm == 0.0
            continue
        assert norm > 0, name
        assert float(jnp.linalg.norm(a - b)) < 1e-4 * norm, name
    # With remat the branch crosses two rematerialised regions.
    loss_r = T.lm_loss(dataclasses.replace(TCFG, remat=True), params,
                       jnp.asarray(toks))
    assert abs(float(loss_r) - float(loss)) < 1e-6


# ---------------------------------------------------- the cut is a share

def test_the_32_shares_and_the_zero_part_add_up_to_the_uncut_layer():
    """32 ranks each hold one thirty-second of the experts; every rank
    also adds the zero-compute experts' part for its own tokens.  The
    held parts, and that part counted once, are the whole layer."""
    n, zeros = 32, 8
    cfg = harness.merged(CFG, {
        "n_routed_experts": n, "zero_expert_num": zeros, "moe_topk": 6,
        "router_outputs": n + zeros, "published": {"n_routed_experts": n}})
    blk = fam.make_layer(fam.seed_key(5), cfg, 0, F32)["branch"]
    m = jnp.asarray(np.random.default_rng(2).standard_normal(
        (1, 40, cfg["hidden_size"])), F32)
    whole = ref.moe(cfg, blk, m, MM, first=0, held=n)
    zero_part = whole - ref.moe(cfg, blk, m, MM, first=0, held=n, zero=False)
    assert float(jnp.abs(zero_part).max()) > 0.01
    total, zero_pairs = zero_part[0], []
    for rank in range(n):
        spec = moe.Experts(
            n_experts=n, top_k=cfg["moe_topk"],
            d_expert=cfg["expert_ffn_hidden_size"], first_expert=rank,
            n_held=1, scale=float(cfg["routed_scaling_factor"]),
            score="softmax", renorm=False, n_zero=zeros)
        part = dict(blk, w1=blk["w1"][rank:rank + 1],
                    w2=blk["w2"][rank:rank + 1])
        y, rows, zero, _ = moe.held_experts_ffn(m[0], part, spec)
        total = total + (y - zero_part[0])       # the rank's held part
        zero_pairs.append(int(zero))
    assert _gap(total, whole[0]) < TOL
    chosen, _ = moe.route_topk(m[0], blk["router"], blk["bias"], 6, 6.0,
                               score="softmax", renorm=False)
    assert set(zero_pairs) == {int(jnp.sum(chosen >= n))}


def test_a_token_that_is_nobodys_takes_no_time_and_is_not_counted():
    spec = TCFG.layers[0].branch
    p = _weights()[1][0]["branch"]
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (24, CFG["hidden_size"])), F32)
    live = jnp.asarray([True, False, True, True, False, True] * 4)
    y_all, rows_all, zero_all, _ = moe.held_experts_ffn(x, p, spec)
    y, rows, zero, _ = moe.held_experts_ffn(x, p, spec, live=live)
    _, rows_live, zero_live, _ = moe.held_experts_ffn(x[live], p, spec)
    assert np.array_equal(rows, rows_live) and rows.sum() < rows_all.sum()
    assert int(zero) == int(zero_live) < int(zero_all)
    assert _gap(y[live], y_all[live]) < 1e-6
    # The walk's second counter leaves the free slots out too.
    top, blocks = _weights()
    state = kv.init_kv_cache_tp(TCFG, 3, 1, F32)
    stats = {}
    kv.decode_step_tp(TCFG, dict(top, blocks=blocks), state,
                      jnp.asarray([1, 2, 3]), jnp.asarray([0, 0, 0]),
                      active=jnp.asarray([True, False, True]), stats=stats)
    assert int(stats["moe_live_pairs"]) \
        == 2 * CFG["num_layers"] * CFG["moe_topk"]
    assert int(stats["moe_zero_pairs"]) <= int(stats["moe_live_pairs"])


def test_default_fields_are_todays_layer():
    """Kimi's and openPangu's ``Experts`` and ``MLA`` say nothing of the
    new fields and get the layer they had (their programs' pinned texts
    are in ``tests/test_openpangu_moe.py``): sigmoid, renormalised, no
    zero-compute expert, no scale, no count."""
    e = moe.Experts(n_experts=8, top_k=2, d_expert=32, first_expert=0,
                    n_held=2)
    assert (e.score, e.renorm, e.n_zero, e.width) == ("sigmoid", True, 0, 8)
    m = T.MLA(4, 32, 16, 8, 16)
    assert (m.q_scale, m.kv_scale) == (1.0, 1.0)
    p = moe.init_experts(jax.random.PRNGKey(0), e, 64)
    x = jnp.ones((3, 64), F32)
    _, _, zero, _ = moe.held_experts_ffn(x, p, e)
    assert zero == 0 and not isinstance(zero, jax.Array)
    chosen, w = moe.route_topk(x, p["router"], p["bias"], 2, 1.0)
    assert _gap(jnp.sum(w, axis=-1), 1.0) < 1e-6
    with pytest.raises(ValueError, match="unknown score"):
        dataclasses.replace(e, score="tanh")
    wide = dataclasses.replace(e, n_zero=4, top_k=10)
    assert moe.init_experts(jax.random.PRNGKey(0), wide,
                            64)["router"].shape == (64, 12)


# -------------------------------------- what the tolerance can tell apart

def _program_logits(params, toks, tcfg=TCFG):
    return T.forward(tcfg, params, jnp.asarray(toks), None)[0]


def _with_branch(**changes):
    layers = tuple(
        dataclasses.replace(sp, branch=dataclasses.replace(
            sp.branch, **changes)) if sp.branch is not None else sp
        for sp in TCFG.layers)
    return dataclasses.replace(TCFG, layers=layers)


def _zero_experts_dropped(top, blocks):
    """The zero-compute experts are chosen and add nothing: their
    columns leave the router no narrower, but their part is lost."""
    n = TCFG.layers[0].branch.n_experts

    class Dropped(moe.Experts):
        pass

    real = moe.held_experts_ffn

    def without(x, params, spec, comm_ep=None, live=None):
        y, *counts = real(x, params, spec, comm_ep, live=live)
        chosen, w = moe.route_topk(
            x, params["router"], params["bias"], spec.top_k, spec.scale,
            score=spec.score, renorm=spec.renorm)
        lost = jnp.sum(jnp.where(chosen >= n, w, 0), axis=1, keepdims=True)
        return (y - lost * x, *counts)

    return TCFG, dict(top, blocks=blocks), without


def _renormalised(top, blocks):
    return _with_branch(renorm=True), dict(top, blocks=blocks), None


def _joined_one_layer_early(top, blocks):
    layers = tuple(dataclasses.replace(sp, join=sp.branch is not None)
                   for sp in TCFG.layers)
    return dataclasses.replace(TCFG, layers=layers), \
        dict(top, blocks=blocks), None


def _a_latent_scale_left_out(top, blocks):
    layers = tuple(dataclasses.replace(sp, mixer=dataclasses.replace(
        sp.mixer, kv_scale=1.0)) for sp in TCFG.layers)
    return dataclasses.replace(TCFG, layers=layers), \
        dict(top, blocks=blocks), None


@pytest.mark.parametrize("broken", [
    _zero_experts_dropped, _renormalised, _joined_one_layer_early,
    _a_latent_scale_left_out])
def test_the_tolerance_tells_a_broken_layer_from_a_sound_one(
        broken, monkeypatch):
    top, blocks = _weights()
    toks = _tokens(48)
    want = _reference(top, blocks, toks)
    assert _gap(_program_logits(dict(top, blocks=blocks), toks), want) < TOL
    tcfg, params, experts_ffn = broken(top, blocks)
    if experts_ffn is not None:
        monkeypatch.setattr(T, "held_experts_ffn", experts_ffn)
    assert _gap(_program_logits(params, toks, tcfg), want) > 100 * TOL


# ------------------------------------------------------- what is refused

def test_tensor_parallel_serving_refuses_the_shortcut_by_name():
    with pytest.raises(mpi.CommError, match="shortcut branch"):
        kv.validate_tp(TCFG, 2)
    kv.validate_tp(TCFG, 1)


def test_a_branch_is_joined_once():
    mla, ex = TCFG.layers[0].mixer, TCFG.layers[0].branch
    make = lambda *layers: T.TransformerConfig(
        vocab=8, d_model=CFG["hidden_size"], n_heads=4, n_layers=len(layers),
        d_ff=8, max_seq=8, rope=True, norm="rmsnorm", ffn="swiglu",
        layers=layers)
    carry, join = T.LayerSpec(mla, branch=ex), T.LayerSpec(mla, join=True)
    make(carry, join, T.LayerSpec(mla, branch=ex, join=True))
    make(carry, T.LayerSpec(mla), join)
    with pytest.raises(ValueError, match="never joined"):
        make(carry, join, carry)
    with pytest.raises(ValueError, match="none is open"):
        make(carry, join, join)
    with pytest.raises(ValueError, match="is not joined yet"):
        make(carry, carry, join)
    with pytest.raises(ValueError, match="needs a KDA or MLA mixer"):
        make(T.LayerSpec(branch=ex, join=True))
