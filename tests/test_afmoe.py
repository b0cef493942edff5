"""Trinity-Mini on the serving path (ISSUE 45), at small sizes on the
CPU, seeded float32 weights: gated QK-normed attention as a mixer on the
layer spec, sliding (rotated, a window) and full (no rotation) layers in
one stack, their pages in two classes of which the window class holds a
window's pages a slot and frees the rest, all experts of a layer held,
and the engine around them, against the plain reference
(``benchmarks/references/afmoe.py``), which writes the scores out under
a window mask and runs the experts one at a time.

Sizes: the configuration's ``rehearsal`` (the cell's five layers at
small widths; a window of 16 positions against pages of 8), sequences of
84-100 positions, so that every context is several windows long, every
decode releases pages behind the window and another slot is given them.

Tolerances.  ``TOL = 1e-4`` on logits of order 1: both sides are float32
and differ by the order of their sums (measured under 5e-6); a program
that reads past its window, or rotates a full layer, moves a logit by
1e-2 and more (the last tests of this file hold that), which is the
reason it is the tolerance."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as harness
from benchmarks.families import afmoe as fam
from benchmarks.references import afmoe as ref
from benchmarks.references import dense_decoder as plain
from mpi4torch_tpu import serve
from mpi4torch_tpu.models import transformer as T
from mpi4torch_tpu.parallel import moe
from mpi4torch_tpu.runtime import CommError
from mpi4torch_tpu.serve import kv, paging
from mpi4torch_tpu.utils import profiling

F32 = jnp.float32
TOL = 1e-4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "trinity-mini.json")) as f:
    PUBLISHED = json.load(f)
CFG = harness.merged(PUBLISHED, PUBLISHED["rehearsal"])
TCFG = fam.transformer_config(CFG)
WINDOW, BS = CFG["sliding_window"], 8
N_BLK = CFG["max_position_embeddings"] // BS
P_LEN, N_NEW = 61, 12
PAD = 96
# One layer of each kind, by its index in the cell's five.
KINDS = {"dense+sliding": 0, "experts+sliding": 1, "experts+full": 4}


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
    """The reference's logits at every position of ``toks`` (1, n).  The
    sequence is handed over padded to ``PAD`` positions: no position
    reads a later one, so the logits are those of the sequence alone,
    and every length this file asks for is one compiled shape."""
    n = toks.shape[1]
    padded = np.zeros((1, PAD), np.int32)
    padded[:, :n] = toks
    return np.asarray(ref.logits_at(cfg, top, iter(blocks),
                                    jnp.asarray(padded),
                                    jnp.arange(n)[None]))[0]


def _gap(a, b):
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def _engine(params, spmd=False, slots=2, tcfg=TCFG, **more):
    more.setdefault("prefix_cache", False)
    more.setdefault("block_size", BS)
    more.setdefault("max_new", N_NEW)
    return serve.Engine(tcfg, params, serve.ServeConfig(slots=slots, **more),
                        spmd=spmd, nranks=1)


def _follows_the_reference(top, blocks, out, p_len):
    """Whether every served token of ``out`` (prompt + served) is the
    reference's choice given everything before it."""
    want = _reference(top, blocks, out[None, :-1].astype(np.int32))
    return bool(np.array_equal(want[p_len - 1:].argmax(-1), out[p_len:]))


# ------------------------------------------------------ the mixer, trained

@pytest.mark.parametrize("kind", list(KINDS) + ["stack"])
def test_the_forward_is_the_references(kind):
    """``T.forward`` over 84 positions (five windows) against the
    reference: each kind of layer alone, and the cell's five."""
    if kind == "stack":
        cfg = CFG
        top, blocks = _weights()
    else:
        i = KINDS[kind]
        cfg = dict(CFG, num_hidden_layers=1,
                   num_dense_layers=int(ref.layer_is_dense(CFG, i)),
                   layer_types=[CFG["layer_types"][i]])
        top, blocks = _weights()
        blocks = [blocks[i]]
    tcfg = fam.transformer_config(cfg)
    toks = _tokens(84)
    got = jax.jit(functools.partial(T.forward, tcfg))(
        dict(top, blocks=blocks), jnp.asarray(toks))[0]
    assert _gap(got, _reference(top, blocks, toks, cfg)) < TOL


def test_the_mixers_leaves_and_what_none_still_means():
    """``init_transformer`` makes a ``GQA`` mixer's leaves at the
    mixer's own widths, beside an expert FFN and four norms; a layer
    whose mixer is ``None`` keeps the configuration's attention and
    still may not stand beside experts or post-norms."""
    p = T.init_transformer(jax.random.PRNGKey(0), TCFG, F32)
    h, h_kv, hd, d = 4, 2, 16, CFG["hidden_size"]
    for spec, blk in zip(TCFG.layers, p["blocks"]):
        assert set(blk["mixer"]) == {"wqkv", "wo", "q_norm", "k_norm"}
        assert blk["mixer"]["wqkv"].shape == (d, (2 * h + 2 * h_kv) * hd)
        assert blk["mixer"]["wo"].shape == (h * hd, d)
        assert blk["mixer"]["q_norm"]["scale"].shape == (hd,)
        assert {"ln1", "ln1_post", "ln2", "ln2_post"} <= set(blk)
        assert ("experts" in blk) == (spec.ffn is not None)
    assert "pos" not in p
    plain_gqa = T.GQA(n_heads=4, n_kv_heads=2, head_dim=16)
    leaves = T._init_mixer(jax.random.PRNGKey(0), plain_gqa, d, F32)
    assert set(leaves) == {"wqkv", "wo"}
    assert leaves["wqkv"].shape == (d, (h + 2 * h_kv) * hd)
    with pytest.raises(ValueError, match="stated on the layer"):
        dataclasses.replace(TCFG, layers=(T.LayerSpec(
            ffn=TCFG.layers[1].ffn),) * 5)
    with pytest.raises(ValueError, match="multiple of n_kv_heads"):
        T.GQA(n_heads=4, n_kv_heads=3, head_dim=16)
    with pytest.raises(ValueError, match="window"):
        T.GQA(n_heads=4, n_kv_heads=2, head_dim=16, window=-1)


def test_the_embedding_is_scaled_once():
    """``embed_scale`` multiplies the looked-up rows in every pass that
    embeds; 1.0 leaves the rows as they are (the same array)."""
    top, _ = _weights()
    toks = jnp.asarray(_tokens(5))
    rows = top["embed"][toks]
    assert np.array_equal(T.embed_tokens(
        dataclasses.replace(TCFG, embed_scale=1.0), top, toks), rows)
    assert _gap(T.embed_tokens(TCFG, top, toks),
                rows * np.sqrt(CFG["hidden_size"])) < 1e-6
    flat = T.TransformerConfig(vocab=16, d_model=8, n_heads=2, n_layers=1,
                               d_ff=16, max_seq=8)
    assert flat.embed_scale == 1.0


# ------------------------------------------------- the walk and its views

# Compiled once for the cases below.
_PAGED_STEP = jax.jit(functools.partial(kv.decode_step_paged, TCFG))
_DENSE_STEP = jax.jit(functools.partial(kv.decode_step_tp, TCFG))


def _two_class_pool(slots=3, full_pages=40, window_pages=12):
    return kv.init_kv_pool_tp(TCFG, full_pages, BS, 1, F32, slots=slots,
                              window_blocks=window_pages)


def _installed(rows, p_len, full_ids, window_ids, first_w, pool=None):
    """A prompt's rows in a two-class pool: every page in the full
    class, the pages from ``first_w`` on in the window class."""
    pool = _two_class_pool() if pool is None else pool
    n_pages = kv.install_page_count(TCFG.max_seq, BS)
    total = -(-p_len // BS)

    def index(ids, first, extent):
        out = extent + np.arange(n_pages)
        out[first:total] = ids
        return np.concatenate([[0, p_len], out]).astype(np.int32)

    return kv.install_rows_paged(
        pool, rows, {"full": jnp.asarray(index(full_ids, 0, 40)),
                     "window": jnp.asarray(index(window_ids, first_w, 12))},
        classes=kv.page_classes(TCFG))


@functools.lru_cache(maxsize=None)
def _prefilled():
    """The prompt's prefill and the reference's one pass over prompt and
    answer, once for the three caches below (arrays nobody writes)."""
    top, blocks = _weights()
    params = dict(top, blocks=blocks)
    toks = _tokens()
    logits, rows = kv.prefill_tp(TCFG, params,
                                 kv.init_kv_cache_tp(TCFG, 1, 1, F32),
                                 jnp.asarray(toks[:, :P_LEN]))
    return params, toks, _reference(top, blocks, toks), logits, rows


@pytest.mark.parametrize("cache", ["paged", "paged_nan", "dense"])
def test_prefill_then_decode_equals_the_references_full_forward(cache):
    """A prefill of 61 tokens (almost four windows), then 12 decode
    steps, against ONE pass over 73.  Paged: through the two-class pool,
    the window class holding the prompt's last pages alone and giving a
    page back as soon as the window has left it; ``paged_nan`` fills
    every page of the window class the slot does not hold with NaN
    before every step, the released ones too, and no logit moves."""
    params, toks, want, logits, rows = _prefilled()
    assert _gap(logits[0], want[P_LEN - 1]) < TOL
    w = paging.WindowBlocks(12, BS, WINDOW)
    if cache == "dense":
        state = rows
    else:
        # Three slots; the prompt goes to slot 1 and scattered pages.
        total, first_w = -(-P_LEN // BS), w.first_page(P_LEN)
        full_ids = [3, 5, 1, 17, 9, 30, 2, 11][:total]
        held = w.alloc(total - first_w)
        state = _installed(rows, P_LEN, full_ids, held, first_w)
        table = {"full": np.full((3, N_BLK), -1, np.int32),
                 "window": np.full((3, N_BLK), -1, np.int32)}
        table["full"][1, :total] = full_ids
        table["window"][1, first_w:total] = held
        spare_full = iter([20, 21, 22, 23])
    for t in range(P_LEN, P_LEN + N_NEW):
        if cache == "dense":
            logits, state = _DENSE_STEP(
                params, state, jnp.asarray(toks[:, t]), jnp.asarray([t]))
            got = logits[0]
        else:
            if table["full"][1, t // BS] < 0:
                table["full"][1, t // BS] = next(spare_full)
                table["window"][1, t // BS] = w.alloc(1)[0]
            if cache == "paged_nan":
                # A page taken again holds another slot's stale rows
                # (finite: rows behind a frontier inside a live page are
                # masked, not skipped); one nobody holds, NaN.
                lost = np.setdiff1d(np.arange(12), table["window"][1])
                state = [{k: (jnp.where(jnp.isnan(a), 7.0, a)
                              .at[lost].set(jnp.nan)
                              if cls == "window" else a)
                          for k, a in entry.items()} for cls, entry in
                         zip(kv.page_classes(TCFG), state)]
            logits, state = _PAGED_STEP(
                params, state, table,
                jnp.asarray([0, toks[0, t], 0]), jnp.asarray([0, t, 0]),
                active=jnp.asarray([False, True, False]))
            # On the host before the tables change under the step (the
            # CPU backend reads a numpy argument where it lies).
            got = np.asarray(logits[1])
            # Behind the step: the page the window has left.
            before = w.first_page(t + 1) - 1
            if before >= 0 and table["window"][1, before] >= 0:
                w.release([int(table["window"][1, before])])
                table["window"][1, before] = -1
            assert (table["window"][1] >= 0).sum() <= w.pages_a_slot
        assert _gap(got, want[t]) < TOL, t
        assert int(np.argmax(got)) == int(np.argmax(want[t]))
    if cache != "dense":
        assert w.blocks_in_use <= w.pages_a_slot < (P_LEN + N_NEW) // BS


def test_the_entries_lie_in_two_classes():
    """Every layer's entry is ``{"k", "v"}`` of the mixer's own rows;
    the sliding layers' under the window class's extent, the full
    layer's under the full class's; a dense cache knows no class."""
    assert kv.page_classes(TCFG) == ("window",) * 4 + ("full",)
    assert kv.window_of(TCFG) == WINDOW
    pool = _two_class_pool()
    for cls, entry in zip(kv.page_classes(TCFG), pool):
        assert set(entry) == {"k", "v"}
        assert entry["k"].shape == (12 if cls == "window" else 40, BS, 2, 16)
    dense = kv.init_kv_cache_tp(TCFG, 3, 1, F32)
    assert {e["k"].shape for e in dense} == {(3, TCFG.max_seq, 2, 16)}
    with pytest.raises(CommError, match="window_blocks"):
        kv.init_kv_pool_tp(TCFG, 40, BS, 1, F32, slots=3)
    flat = T.TransformerConfig(vocab=16, d_model=8, n_heads=2, n_layers=2,
                               d_ff=16, max_seq=8, attn_window=4)
    assert kv.page_classes(flat) == ("full", "full")
    assert kv.window_of(flat) == 0


# ------------------------------------------------------------ the experts

def test_every_expert_held_is_the_uncut_layer_and_two_halves_add_up():
    """``n_held == n_experts``: every (token, choice) pair is held and
    the layer is the reference's uncut one; two ranks that hold a half
    each add up to it, the shared expert counted once."""
    blk = fam.make_layer(fam.seed_key(5), CFG, 1, F32)["experts"]
    m = jnp.asarray(np.random.default_rng(2).standard_normal(
        (40, CFG["hidden_size"])), F32)
    n, k = CFG["num_experts"], CFG["num_experts_per_tok"]
    whole = ref.experts(CFG, blk, m, plain.matmul_f32)
    shared = ref.swiglu(m, blk["shared_w1"], blk["shared_w2"],
                        plain.matmul_f32)
    spec = TCFG.layers[1].ffn
    assert (spec.n_held, spec.first_expert) == (n, 0)
    y, rows, _, overflow = moe.held_experts_ffn(m, blk, spec)
    assert _gap(y, whole) < TOL
    assert int(rows.sum()) == 40 * k and int(overflow) == 0
    total = taken = 0
    for rank in range(2):
        half = dataclasses.replace(spec, first_expert=rank * n // 2,
                                   n_held=n // 2)
        part = dict(blk, w1=blk["w1"][rank * n // 2:(rank + 1) * n // 2],
                    w2=blk["w2"][rank * n // 2:(rank + 1) * n // 2])
        y, rows, *_ = moe.held_experts_ffn(m, part, half)
        total, taken = total + y, taken + int(rows.sum())
        want = ref.experts(CFG, part, m, plain.matmul_f32,
                           first=rank * n // 2, held=n // 2)
        assert _gap(y, want) < TOL
    assert taken == 40 * k
    assert _gap(total - shared, whole) < TOL


# -------------------------------------------------------------- the engine

@pytest.fixture(scope="module")
def spare_page_engine():
    """The compiled engine on two slots whose window class has one page
    more than they hold at most (compiled once for the tests that serve
    through it, one after another), with the weights it serves."""
    top, blocks = _weights()
    return top, blocks, _engine(dict(top, blocks=iter(blocks)), spmd=True,
                                window_blocks=7)


def test_engine_serves_the_references_tokens(spare_page_engine):
    """Four requests through two slots of the compiled engine (every
    slot is freed and taken again, with pages of the window class another
    slot released), prompts of 40-75 positions and 12 served tokens each:
    every served token is the reference's argmax given the tokens before
    it.  (The views themselves run eagerly in the tests above.)"""
    top, blocks, eng = spare_page_engine
    rng = np.random.default_rng(3)
    lens = [61, 40, 75, 40]
    rids = [eng.submit(rng.integers(0, CFG["vocab_size"], size=(n,))
                       .astype(np.int32)) for n in lens]
    out = eng.run()
    for rid, n in zip(rids, lens):
        assert _follows_the_reference(top, blocks, out[rid], n), rid
    assert eng.stats.counters["window_pages_freed"] > 0
    assert eng.stats.counters["preempted"] == 0
    assert eng._mgr.blocks_in_use == 0 and eng._mgr.window.blocks_in_use == 0


def test_a_window_layer_holds_a_windows_pages_and_the_full_class_all():
    """While two slots decode 12 tokens behind prompts of 61 and 75: the
    window class never holds more than ``window / bs + 1`` pages a slot,
    a release leaves the full class's table and population as they were,
    the step records count what the tables hold, and the census prices
    each class at its own layers' bytes."""
    profiling.reset_serve_stats()
    top, blocks = _weights()
    eng = _engine(dict(top, blocks=iter(blocks)), spmd=True)
    w = eng._mgr.window
    assert w.pages_a_slot == WINDOW // BS + 1 == 3
    assert w.num_blocks == 2 * 3               # the default: slots x 3
    rng = np.random.default_rng(4)
    for n in (61, 75):
        eng.submit(rng.integers(0, CFG["vocab_size"], size=(n,))
                   .astype(np.int32))
    freed = steps = 0
    while eng.pending():
        full_before = eng._mgr.blocks_in_use
        eng.step()
        steps += 1
        held = (eng._table_w >= 0).sum(axis=1)
        assert held.max() <= w.pages_a_slot
        assert w.blocks_in_use == held.sum()
        full = (eng._table >= 0).sum(axis=1)
        live = [j for j, r in enumerate(eng._slot_req) if r is not None]
        for j in live:
            # The full class keeps every page up to the frontier.
            assert full[j] == -(-int(eng._pos[j]) // BS)
        now = eng.stats.counters["window_pages_freed"]
        if now > freed and len(live) == 2:
            assert eng._mgr.blocks_in_use >= full_before
        freed = now
        if len(live) == 2:
            row = 2 * BS * 2 * 16 * 4           # K and V, 2 heads of 16
            assert eng.kv_bytes_resident() == (
                eng._mgr.blocks_in_use * 1 + w.blocks_in_use * 4) * row
    assert freed > 0 and "table_window" in eng._state
    records = [r for r in profiling.serve_step_log() if r["active"]]
    assert records and all(
        0 < r["window_pages_held"] <= 3 * r["window_slots_live"]
        and r["window_slots_live"] == r["active"] for r in records)
    assert sum(r["window_pages_freed"] for r in records) == freed
    # Both tables' pages: the full class's up to the frontier and the
    # window class's under the window.
    assert all(r["decode_pages_live"] > r["window_pages_held"]
               for r in records)


def test_a_page_released_by_one_slot_is_given_to_another_that_decodes(
        spare_page_engine):
    """Two slots decode side by side over a window class of one page
    more than they hold at most: a released page goes to the end of the
    free list, and the ids one slot held turn up in the other's row
    while the first still decodes."""
    _, _, eng = spare_page_engine
    rng = np.random.default_rng(5)
    for n in (61, 64):
        eng.submit(rng.integers(0, CFG["vocab_size"], size=(n,))
                   .astype(np.int32))
    seen = [set(), set()]
    passed_on = False
    while eng.pending():
        eng.step()
        for j in range(2):
            if eng._slot_req[j] is None:
                continue
            held = {int(b) for b in eng._table_w[j] if b >= 0}
            if held & seen[1 - j] and eng._slot_req[1 - j] is not None:
                passed_on = True
            seen[j] |= held
    assert passed_on
    assert eng.stats.counters["preempted"] == 0


def test_a_configuration_without_a_window_keeps_one_class():
    """The same stack with every layer full: one class, one table in the
    slot state, no window population and no window counter moving."""
    full = dataclasses.replace(TCFG, layers=tuple(
        dataclasses.replace(s, mixer=dataclasses.replace(
            s.mixer, window=0)) for s in TCFG.layers))
    top, blocks = _weights()
    eng = _engine(dict(top, blocks=iter(blocks)), spmd=True, tcfg=full)
    assert eng._mgr.window is None and eng._table_w is None
    eng.submit(_tokens(20)[0], max_new=3)
    eng.run()
    assert "table_window" not in eng._state
    assert eng.stats.counters["window_pages_held"] == 0


# ------------------------------------------------------------ the refusals

def test_prefix_sharing_and_chunked_prefill_are_refused_with_a_window_class():
    top, blocks = _weights()
    with pytest.raises(CommError, match="prefix sharing.*window class"):
        _engine(dict(top, blocks=iter(blocks)), prefix_cache=True)
    with pytest.raises(CommError, match="chunked prefill.*window class"):
        _engine(dict(top, blocks=iter(blocks)), prefill_chunk=8)
    # One page of max_seq a slot shares no prefix: its default stands.
    eng = _engine(dict(top, blocks=iter(blocks)), block_size=0,
                  prefix_cache=True)
    assert eng._mgr.window.num_blocks == 2


def test_a_window_class_is_served_on_one_rank_and_has_one_window():
    with pytest.raises(CommError, match="served on one rank"):
        kv.validate_tp(TCFG, 2)
    layers = list(TCFG.layers)
    layers[0] = dataclasses.replace(layers[0], mixer=dataclasses.replace(
        layers[0].mixer, window=8))
    with pytest.raises(CommError, match=r"windows \[8, 16\]"):
        kv.validate_tp(dataclasses.replace(TCFG, layers=tuple(layers)), 1)


def test_a_request_the_window_class_can_never_hold_is_refused_at_submit():
    top, blocks = _weights()
    eng = _engine(dict(top, blocks=iter(blocks)), window_blocks=2)
    with pytest.raises(ValueError, match="window class"):
        eng.submit(_tokens(40)[0])
    assert eng.submit(_tokens(8)[0], max_new=4) is not None


# ------------------------------------------------------------ the controls

@pytest.mark.parametrize("fault", ["no_window", "rope_on_full"])
def test_the_tolerance_tells_a_program_that_reads_otherwise(fault):
    """The two controls of the cell, at the rehearsal sizes: sliding
    layers that read every position, and a full layer that rotates, each
    move a logit by far more than ``TOL``."""
    top, blocks = _weights()
    toks = _tokens(84)
    want = _reference(top, blocks, toks)
    change = {"no_window": lambda m: dataclasses.replace(m, window=0)
              if m.window else m,
              "rope_on_full": lambda m: m if m.window
              else dataclasses.replace(m, rope=True)}[fault]
    broken = dataclasses.replace(TCFG, layers=tuple(
        dataclasses.replace(s, mixer=change(s.mixer)) for s in TCFG.layers))
    got = T.forward(broken, dict(top, blocks=blocks), jnp.asarray(toks))[0]
    assert _gap(got, want) > 100 * TOL
