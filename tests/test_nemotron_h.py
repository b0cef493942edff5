"""Nemotron-3-Super on the serving path (ISSUE 41), at small sizes on the
CPU, seeded float32 weights: layers of one part, a Mamba-2 mixer whose
per-slot state lives beside the cache, attention with no position
signal, relu² experts in a latent, and the engine around them, against
the plain reference (``benchmarks/references/nemotron_h.py``), which
runs the recurrence token by token and the experts one at a time.

Sizes: the configuration's ``rehearsal`` (the cell's eleven letters
``EMEMEMEMEM*`` at small widths; chunks of 8), prompts of 21-40
positions, so that every prefill crosses several chunks and ends inside
one.

Tolerances.  ``TOL = 1e-4`` on logits of order 1: both sides are float32
and differ by the order of their sums (measured under 5e-6); a program
that drops the carried state moves a logit by 1e-2 and more (the last
test of this file holds that), which is the reason it is the
tolerance."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4torch_tpu as mpi
from benchmarks import run as harness
from benchmarks.families import nemotron_h as fam
from benchmarks.references import dense_decoder as plain
from benchmarks.references import nemotron_h as ref
from mpi4torch_tpu import serve
from mpi4torch_tpu.models import transformer as T
from mpi4torch_tpu.parallel import moe
from mpi4torch_tpu.runtime import CommError
from mpi4torch_tpu.serve import kv
from mpi4torch_tpu.utils import profiling

F32 = jnp.float32
TOL = 1e-4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "nemotron-3-super-120b-a12b.json")) as f:
    PUBLISHED = json.load(f)
CFG = harness.merged(PUBLISHED, PUBLISHED["rehearsal"])
TCFG = fam.transformer_config(CFG)
PATTERN = CFG["hybrid_override_pattern"]
P_LEN, N_NEW, BS = 21, 8, 8


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
    """The reference's logits at every position of ``toks`` (1, n)."""
    rows = jnp.arange(toks.shape[1])[None]
    return np.asarray(ref.logits_at(cfg, top, iter(blocks),
                                    jnp.asarray(toks), rows))[0]


def _gap(a, b):
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def _engine(params, spmd=False, slots=2, block_size=BS, **more):
    more.setdefault("prefix_cache", False)
    return serve.Engine(
        TCFG, params, serve.ServeConfig(
            slots=slots, max_new=N_NEW, block_size=block_size,
            num_blocks=16 if block_size else None, **more),
        spmd=spmd, nranks=1)


def _follows_the_reference(top, blocks, out, p_len):
    """Whether every served token of ``out`` (prompt + served) is the
    reference's choice given everything before it."""
    want = _reference(top, blocks, out[None, :-1].astype(np.int32))
    return bool(np.array_equal(want[p_len - 1:].argmax(-1), out[p_len:]))


# ------------------------------------------------- the walk and its views

@pytest.mark.parametrize("cache", ["paged", "dense"])
def test_prefill_then_decode_equals_the_references_full_forward(cache):
    """A prefill of T tokens, then n decode steps through the kept state
    and the convolution's kept inputs, against ONE pass over T + n."""
    top, blocks = _weights()
    params = dict(top, blocks=blocks)
    toks = _tokens()
    want = _reference(top, blocks, toks)
    one = kv.init_kv_cache_tp(TCFG, 1, 1, F32)
    logits, rows = kv.prefill_tp(TCFG, params, one,
                                 jnp.asarray(toks[:, :P_LEN]))
    assert _gap(logits[0], want[P_LEN - 1]) < TOL
    if cache == "dense":
        state = rows
    else:
        # Three slots; the prompt goes to slot 1 and scattered pages.
        pool = kv.init_kv_pool_tp(TCFG, 8, BS, 1, F32, slots=3)
        n_pages = kv.install_page_count(TCFG.max_seq, BS)
        index = np.concatenate([[0, P_LEN], [3, 5, 1],
                                8 + np.arange(n_pages - 3)]).astype(np.int32)
        state = kv.install_rows_paged(pool, rows, jnp.asarray(index),
                                      jnp.int32(1))
        table = np.full((3, 8), -1, np.int32)
        table[1, :4] = [3, 5, 1, 0]
    for t in range(P_LEN, P_LEN + N_NEW):
        if cache == "dense":
            logits, state = kv.decode_step_tp(
                TCFG, params, state, jnp.asarray(toks[:, t]),
                jnp.asarray([t]))
            got = logits[0]
        else:
            logits, state = kv.decode_step_paged(
                TCFG, params, state, table,
                jnp.asarray([0, toks[0, t], 0]), jnp.asarray([0, t, 0]),
                active=jnp.asarray([False, True, False]))
            got = logits[1]
        assert _gap(got, want[t]) < TOL, t
        assert int(np.argmax(got)) == int(np.argmax(want[t]))


def test_the_entries_are_of_three_kinds():
    """An expert layer has no entry, a Mamba-2 layer a per-slot state in
    float32 and the convolution's last three inputs, the attention layer
    its paged K/V; the state is float32 under a bfloat16 cache too."""
    pool = kv.init_kv_pool_tp(TCFG, 16, BS, 1, jnp.bfloat16, slots=5)
    m = TCFG.layers[1].mixer
    for letter, entry in zip(PATTERN, pool):
        if letter == "E":
            assert entry == {}
        elif letter == "M":
            assert set(entry) == set(kv.STATE_LEAVES)
            assert entry["h"].shape == (5, m.n_heads, m.head_dim, m.d_state)
            assert entry["h"].dtype == F32
            assert entry["conv"].shape == (5, m.conv - 1, m.conv_dim)
            assert entry["conv"].dtype == jnp.bfloat16
        else:
            assert set(entry) == {"k", "v"}
            assert entry["k"].shape == (16, BS, CFG["num_key_value_heads"],
                                        CFG["head_dim"])
    with pytest.raises(CommError, match="slots >= 1"):
        kv.init_kv_pool_tp(TCFG, 16, BS, 1, F32)


def test_a_prefill_hands_back_the_last_state_and_the_last_three_inputs():
    """What the install writes over a slot: the state after the prompt's
    last token (the chunked scan's ``h_T``, which the recurrence over
    the same tokens ends in) and the convolution's last three inputs; a
    prompt shorter than three keeps zeros before it."""
    top, blocks = _weights()
    params = dict(top, blocks=blocks)
    one = kv.init_kv_cache_tp(TCFG, 1, 1, F32)
    toks = _tokens()
    _, long = kv.prefill_tp(TCFG, params, one, jnp.asarray(toks[:, :P_LEN]))
    _, short = kv.prefill_tp(TCFG, params, one, jnp.asarray(toks[:, :2]))
    assert np.all(np.asarray(short[1]["conv"][0, 0]) == 0)
    assert np.any(np.asarray(short[1]["conv"][0, 1]) != 0)
    # Layer 1 reads the stream behind layer 0: redo its scan by hand.
    x = top["embed"][toks[:, :P_LEN]]
    x = x + moe.held_experts_ffn(
        T._rms_norm(x, blocks[0]["ln2"])[0], blocks[0]["experts"],
        TCFG.layers[0].ffn)[0][None]
    spec, p = TCFG.layers[1].mixer, blocks[1]["mixer"]
    _, xBC, dt = T.mamba2_project(spec, p, T._rms_norm(x, blocks[1]["ln1"]))
    assert _gap(long[1]["conv"], xBC[:, -3:]) < 1e-6
    # The same scan in two passes, the second from the first's entry.
    _, first = T.mamba2_scan(spec, p, xBC[:, :13], dt[:, :13])
    _, both = T.mamba2_scan(spec, p, xBC[:, 13:], dt[:, 13:], first)
    assert _gap(long[1]["h"], both["h"]) < 1e-5
    assert _gap(long[1]["conv"], both["conv"]) == 0.0


# ------------------------------------------------------- layers of one part

def test_a_one_part_layer_has_one_norm_and_no_leaf_of_the_other_part():
    params = T.init_transformer(jax.random.PRNGKey(0), TCFG, F32)
    assert "pos" not in params                      # nope: no table
    for letter, blk in zip(PATTERN, params["blocks"]):
        want = {"E": {"ln2", "experts"}, "M": {"ln1", "mixer"},
                "*": {"ln1", "wqkv", "wo"}}[letter]
        assert set(blk) == want, letter
    experts = params["blocks"][0]["experts"]
    lat, f = CFG["moe_latent_size"], CFG["moe_intermediate_size"]
    assert experts["w1"].shape == (CFG["n_routed_experts"], lat, f)
    assert experts["w2"].shape == (CFG["n_routed_experts"], f, lat)
    assert experts["down"].shape == (CFG["hidden_size"], lat)
    assert experts["shared_w1"].shape == (
        CFG["hidden_size"], CFG["moe_shared_expert_intermediate_size"])
    assert set(params["blocks"][1]["mixer"]) == {
        "in_proj", "conv", "conv_bias", "dt_bias", "a_log", "d", "norm",
        "out_proj"}


def test_two_one_part_layers_are_the_uniform_block():
    """``x + Attention(N(x))`` then ``x + FFN(N(x))`` as two layers of
    one part each is the configuration's own block on the same leaves:
    one norm and one residual sum a part, nothing else."""
    base = dict(vocab=64, d_model=32, n_heads=4, n_kv_heads=2, d_ff=48,
                max_seq=32, rope=True, norm="rmsnorm", ffn="swiglu")
    whole = T.TransformerConfig(n_layers=1, **base)
    split = T.TransformerConfig(n_layers=2, layers=(
        T.LayerSpec(only="mixer"), T.LayerSpec(only="ffn")), **base)
    params = T.init_transformer(jax.random.PRNGKey(1), whole, F32)
    blk = params["blocks"][0]
    halves = dict(params, blocks=[
        {k: blk[k] for k in ("ln1", "wqkv", "wo")},
        {k: blk[k] for k in ("ln2", "w1", "w2")}])
    toks = jnp.asarray(_tokens(12) % 64)
    assert _gap(T.forward(whole, params, toks),
                T.forward(split, halves, toks)) < 1e-6


@pytest.mark.parametrize("spec,what", [
    (dict(only="mixer", ffn="experts"), "names no other part"),
    (dict(only="ffn", mixer="mamba"), "names no other part"),
    (dict(only="mixer", post_norm=True), "names no other part"),
    (dict(only="both"), "LayerSpec.only is")])
def test_a_one_part_layer_names_nothing_of_the_other_part(spec, what):
    named = {"experts": TCFG.layers[0].ffn, "mamba": TCFG.layers[1].mixer}
    spec = {k: named.get(v, v) if isinstance(v, str) and k != "only" else v
            for k, v in spec.items()}
    with pytest.raises(ValueError, match=what):
        T.TransformerConfig(vocab=64, d_model=64, n_heads=4, n_layers=1,
                            d_ff=48, max_seq=32, nope=True,
                            layers=(T.LayerSpec(**spec),))


def test_nope_is_neither_a_rotation_nor_a_table():
    with pytest.raises(ValueError, match="no position signal"):
        T.TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=1,
                            d_ff=48, max_seq=32, nope=True, rope=True)
    cfg = T.TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=1,
                              d_ff=48, max_seq=32, nope=True)
    assert not cfg.pos_table
    params = T.init_transformer(jax.random.PRNGKey(0), cfg, F32)
    toks = jnp.asarray(_tokens(10) % 64)
    # A token's logits do not depend on where the sequence starts.
    a = T.forward(cfg, params, toks)
    b = T.forward(cfg, params, toks[:, 3:])
    assert _gap(a[:, -1], b[:, -1]) > 1e-4          # but on what it sees
    assert _gap(T.forward(cfg, params, toks[:, :1])[:, 0],
                T.forward(cfg, params, toks[:, :4])[:, 0]) < 1e-6


# ------------------------------------------------------ the latent experts

def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four ranks each hold a quarter of the experts, the projections
    and the shared expert; their shares, the shared expert counted once,
    are the whole layer: ``W_up`` is linear and has no bias."""
    cfg = harness.merged(CFG, {"n_routed_experts": 16, "published":
                               {"n_routed_experts": 16}})
    blk = fam.make_layer(fam.seed_key(5), cfg, 0, F32)["experts"]
    m = jnp.asarray(np.random.default_rng(2).standard_normal(
        (40, cfg["hidden_size"])), F32)
    whole = ref.experts(cfg, blk, m, plain.matmul_f32, first=0, held=16)
    shared = ref.relu2(m, blk["shared_w1"], blk["shared_w2"],
                       plain.matmul_f32)
    total, taken = 0, 0
    for rank in range(4):
        spec = moe.Experts(
            n_experts=16, top_k=cfg["num_experts_per_tok"],
            d_expert=cfg["moe_intermediate_size"], first_expert=4 * rank,
            n_held=4, n_shared=1, scale=cfg["routed_scaling_factor"],
            latent=cfg["moe_latent_size"], act="relu2",
            d_shared=cfg["moe_shared_expert_intermediate_size"])
        part = dict(blk, w1=blk["w1"][4 * rank:4 * rank + 4],
                    w2=blk["w2"][4 * rank:4 * rank + 4])
        y, rows, *_ = moe.held_experts_ffn(m, part, spec)
        total, taken = total + y, taken + int(rows.sum())
    assert taken == 40 * cfg["num_experts_per_tok"]
    assert _gap(total - 3 * shared, whole) < TOL


def test_the_defaults_leave_an_expert_layer_what_it_was():
    """No latent, swiglu, a shared expert of ``n_shared * d_expert``:
    the leaves and the result of the accepted families' layers."""
    spec = moe.Experts(n_experts=8, top_k=2, d_expert=24, first_expert=0,
                       n_held=4, n_shared=1)
    assert (spec.latent, spec.act, spec.shared_width) == (0, "swiglu", 24)
    p = moe.init_experts(jax.random.PRNGKey(0), spec, 32, F32)
    assert set(p) == {"router", "bias", "w1", "w2", "shared_w1",
                      "shared_w2"}
    assert p["w1"].shape == (4, 32, 48) and p["shared_w1"].shape == (32, 48)
    with pytest.raises(ValueError, match="zero-compute"):
        moe.Experts(n_experts=8, top_k=2, d_expert=24, first_expert=0,
                    n_held=4, latent=16, n_zero=2)
    with pytest.raises(ValueError, match="activation"):
        moe.Experts(n_experts=8, top_k=2, d_expert=24, first_expert=0,
                    n_held=4, act="gelu")


# ---------------------------------------------------------------- the engine

@pytest.mark.parametrize("how", ["paged", "dense_slots", "spmd",
                                 "spmd_dense"])
def test_engine_serves_the_references_tokens(how):
    """Two requests of unequal length in flight in two slots, a third
    that takes the slot the first one frees: every served token is the
    reference's choice given everything before it."""
    top, blocks = _weights()
    eng = _engine(dict(top, blocks=blocks), spmd=how.startswith("spmd"),
                  block_size=0 if "dense" in how else BS)
    prompts = [_tokens(n, seed=n)[0] for n in (P_LEN, 33, 5)]
    budgets = (4, N_NEW, 6)
    rids = [eng.submit(p, max_new=b) for p, b in zip(prompts, budgets)]
    out = eng.run()
    # The third request waited for a slot and took the first one's.
    assert [slot for _, slot in eng.slot_log] == [0, 1, 0]
    for rid, prompt, budget in zip(rids, prompts, budgets):
        assert len(out[rid]) == len(prompt) + budget
        assert _follows_the_reference(top, blocks, out[rid], len(prompt))
    if "dense" not in how:
        assert eng.stats.snapshot()["blocks_in_use"] == 0
    if how == "spmd":
        texts = eng.program_texts()
        assert profiling.LAYER_SCOPES["ssm_update"] in texts["decode"]
        assert profiling.LAYER_SCOPES["ssm_scan"] not in texts["decode"]
        for name, text in texts.items():
            assert profiling.LAYER_SCOPES["ssm"] + "/" in text
            assert profiling.LAYER_SCOPES["moe"] in text
            if name != "decode":
                assert profiling.LAYER_SCOPES["ssm_scan"] in text


@pytest.mark.parametrize("block_size", [BS, 0])
def test_a_slot_freed_and_taken_again_carries_nothing_over(block_size):
    """One slot, two requests one after the other: the second's tokens
    are those of an engine that never served the first."""
    top, blocks = _weights()
    first, second = _tokens(30, seed=1)[0], _tokens(P_LEN, seed=2)[0]
    used = _engine(dict(top, blocks=blocks), slots=1, block_size=block_size)
    used.submit(first)
    rid = used.submit(second)
    fresh = _engine(dict(top, blocks=blocks), slots=1,
                    block_size=block_size)
    alone = fresh.submit(second)
    assert np.array_equal(used.run()[rid], fresh.run()[alone])
    assert [slot for _, slot in used.slot_log] == [0, 0]


@pytest.mark.parametrize("spmd", [False, True])
def test_the_two_counters_ride_the_tokens_fetch(spmd):
    """``ssm_states_live`` is the live slots times the Mamba-2 layers,
    ``ssm_states_touched`` every slot's (the update runs over the slot
    table); both come down with the tokens: a decode step makes no
    device round trip of its own for them or for a token."""
    top, blocks = _weights()
    serve.reset_stats()
    eng = _engine(dict(top, blocks=blocks), spmd=spmd, slots=3)
    for n in (5, P_LEN):
        eng.submit(_tokens(n, seed=n)[0], max_new=4)
    eng.run()
    log = [r for r in profiling.serve_step_log() if r["active"]]
    n_m = PATTERN.count("M")
    assert len(log) == 3
    for r in log:
        assert r["ssm_states_live"] == 2 * n_m
        assert r["ssm_states_touched"] == 3 * n_m
        assert r["decode_select_syncs"] == 0
    c = eng.stats.counters
    assert c["ssm_states_live"] == 3 * 2 * n_m
    assert c["decode_select_syncs"] == 0


def test_the_state_is_counted_as_resident_with_its_slot():
    top, blocks = _weights()
    eng = _engine(dict(top, blocks=blocks))
    assert eng.kv_bytes_resident() == 0
    eng.submit(_tokens(P_LEN)[0], max_new=4)
    eng.step()            # admitted, and one decode step on
    m = TCFG.layers[1].mixer
    kept = PATTERN.count("M") * 4 * (
        m.n_heads * m.head_dim * m.d_state + (m.conv - 1) * m.conv_dim)
    row = 2 * CFG["num_key_value_heads"] * CFG["head_dim"] * 4
    pages = -(-(P_LEN + 1) // BS)
    assert eng.kv_bytes_resident() == kept + pages * BS * row


# ------------------------------------------------------ refused, by name

def test_a_state_layer_is_served_on_one_rank_only():
    with pytest.raises(CommError, match="Mamba2 mixer is served on one "
                                        "rank"):
        kv.validate_tp(TCFG, 2)


def test_prefix_sharing_and_chunked_prefill_are_refused_with_a_state_layer():
    top, blocks = _weights()
    with pytest.raises(CommError, match="prefix sharing"):
        _engine(dict(top, blocks=iter(blocks)), prefix_cache=True)
    with pytest.raises(CommError, match="chunked prefill"):
        _engine(dict(top, blocks=iter(blocks)), prefill_chunk=8)
    # The dense engine shares no prefix: its default is not refused.
    _engine(dict(top, blocks=blocks), block_size=0, prefix_cache=True)
    past = jax.tree.map(lambda a: a[:, :0],
                        kv.init_kv_cache_tp(TCFG, 1, 1, F32))
    with pytest.raises(CommError, match="chunk view"):
        kv.prefill_chunk_tp(TCFG, dict(top, blocks=blocks), past,
                            jnp.asarray(_tokens(8)))


def test_the_training_forward_refuses_a_mamba2_mixer_by_name():
    params = T.init_transformer(jax.random.PRNGKey(0), TCFG, F32)
    toks = jnp.asarray(_tokens(16))
    with pytest.raises(CommError, match="Mamba2 mixer.*backward"):
        T.forward(TCFG, params, toks)
    with pytest.raises(CommError, match="Mamba2 mixer"):
        T.train_step(TCFG, params, toks, lr=0.1)


def test_a_kda_mixer_is_still_refused_by_name():
    cfg = T.TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=1, d_ff=48, max_seq=32,
        rope=True, layers=(T.LayerSpec(mixer=T.KDA(n_heads=2,
                                                   head_dim=16)),))
    with pytest.raises(CommError, match="KDA mixer.*not written yet"):
        kv.validate_tp(cfg, 1)


# ------------------------------------------------------------------ the cut

def test_the_cut_keeps_the_published_widths_and_counts_its_parameters():
    """The configuration file at its real size, as shapes alone: the
    issue's count, every width the catalog's."""
    cfg = PUBLISHED
    tcfg = fam.transformer_config(cfg)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: dict(
        fam.make_top(key, cfg, jnp.bfloat16),
        blocks=[fam.make_layer(key, cfg, i, jnp.bfloat16)
                for i in range(cfg["num_hidden_layers"])]))
    count = lambda tree: sum(int(np.prod(a.shape))
                             for a in jax.tree.leaves(tree))
    assert count(params) == 4_648_163_712
    by = dict(zip("EM*", (count(params["blocks"][PATTERN.index(c)])
                          for c in "EM*")))
    assert by == {"E": 759_173_632, "M": 109_640_064, "*": 35_655_680}
    assert cfg["hybrid_override_pattern"] == "EMEMEMEMEM*" == \
        cfg["published"]["hybrid_override_pattern"][26:37]
    m, e = tcfg.layers[1].mixer, tcfg.layers[0].ffn
    assert (m.n_heads, m.head_dim, m.d_state, m.n_groups, m.conv,
            m.chunk) == (128, 64, 128, 8, 4, 128)
    assert (m.d_inner, m.conv_dim) == (8192, 10240)
    assert (e.n_experts, e.top_k, e.d_expert, e.latent, e.shared_width,
            e.n_held, e.scale, e.act) == (512, 22, 2688, 1024, 5376, 128,
                                          5.0, "relu2")
    assert (tcfg.d_model, tcfg.n_heads, tcfg.kv_heads, tcfg.vocab,
            tcfg.nope, tcfg.rope) == (4096, 32, 2, 32768, True, False)
    shapes = jax.eval_shape(lambda: T.init_transformer(key, tcfg,
                                                       jnp.bfloat16))
    same = jax.tree.map(lambda a, b: a.shape == b.shape, shapes, params)
    assert all(jax.tree.leaves(same))


# ------------------------------------------------------------ the tolerance

def test_the_tolerance_tells_a_program_that_drops_the_carried_state(
        monkeypatch):
    """Every decode step starting its Mamba-2 layers from ``H = 0`` and
    an empty convolution tail: the logits leave the reference by a
    hundred tolerances and more."""
    top, blocks = _weights()
    params = dict(top, blocks=blocks)
    toks = _tokens()
    want = _reference(top, blocks, toks)
    scan = T.mamba2_scan
    monkeypatch.setattr(kv, "mamba2_scan", lambda spec, p, xBC, dt,
                        entry=None: scan(spec, p, xBC, dt, None if entry
                                         is None else jax.tree.map(
                                             jnp.zeros_like, entry)))
    state = kv.init_kv_cache_tp(TCFG, 1, 1, F32)
    logits, state = kv.prefill_tp(TCFG, params, state,
                                  jnp.asarray(toks[:, :P_LEN]))
    assert _gap(logits[0], want[P_LEN - 1]) < TOL     # no state to drop yet
    worst = 0.0
    for t in range(P_LEN, P_LEN + N_NEW):
        logits, state = kv.decode_step_tp(TCFG, params, state,
                                          jnp.asarray(toks[:, t]),
                                          jnp.asarray([t]))
        worst = max(worst, _gap(logits[0], want[t]))
    assert worst > 100 * TOL
