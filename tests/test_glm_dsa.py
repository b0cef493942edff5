"""GLM-5.2 on the serving path (ISSUE 39), at small sizes on the CPU,
seeded float32 weights: latent attention that reads only the rows a
learned indexer selects, the selection carried from a scoring layer to
the shared layers above it, the index-key cache entry beside the latent
one, the two new reads and the engine around them, against the plain
reference (``benchmarks/references/glm_dsa.py``), which writes its
scores out, selects with ``jax.lax.top_k`` and scatters a mask.

Sizes: the configuration's ``rehearsal`` (three layers: dense + scoring,
shared, scoring; ``index_topk`` 8), prompts of 24-40 positions, so that
every query past the eighth selects.

Tolerances.  ``TOL = 1e-4`` on logits of order 1: both sides are float32
and differ by the order of their sums (measured under 3e-6); a
selection decided otherwise would move a logit by 1e-2 and more (the
last test of this file holds that), which is the reason it is the
tolerance."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4torch_tpu as mpi
from benchmarks import run as harness
from benchmarks.families import glm_dsa as fam
from benchmarks.references import dense_decoder as plain
from benchmarks.references import glm_dsa as ref
from mpi4torch_tpu import serve
from mpi4torch_tpu.models import transformer as T
from mpi4torch_tpu.ops import flash
from mpi4torch_tpu.ops import paged_attention as pa
from mpi4torch_tpu.parallel import moe
from mpi4torch_tpu.serve import kv
from mpi4torch_tpu.utils import profiling

F32 = jnp.float32
TOL = 1e-4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmarks", "configs", "glm-5.2.json")) as f:
    PUBLISHED = json.load(f)
CFG = harness.merged(PUBLISHED, PUBLISHED["rehearsal"])
TCFG = fam.transformer_config(CFG)
TOP_K = CFG["index_topk"]
P_LEN, N_NEW, BS = 24, 8, 8


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
    index = np.concatenate([[0, P_LEN], [3, 5, 1],
                            8 + np.arange(n_pages - 3)]).astype(np.int32)
    pool = kv.install_rows_paged(pool, rows, jnp.asarray(index))
    table = np.array([[3, 5, 1, 0, -1, -1, -1, -1]], np.int32)
    return pool, table, logits


# ------------------------------------------------- the walk and its views

@pytest.mark.parametrize("cache", ["paged", "dense"])
def test_prefill_then_decode_equals_the_references_full_forward(cache):
    """Selection live at every position past the eighth, in the prefill
    (a mask) and in the decode steps (positions, gathered)."""
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
        assert int(np.argmax(logits[0])) == int(np.argmax(want[t]))


def test_a_scoring_layer_keeps_an_index_key_beside_its_latent_row():
    """Two entries on a scoring layer, one on a shared one, in the same
    pages: one row of ``index_head_dim`` a token."""
    pool = kv.init_kv_pool_tp(TCFG, 4, BS, 1, F32)
    dense = kv.init_kv_cache_tp(TCFG, 2, 1, F32)
    kinds = CFG["indexer_types"]
    assert kinds == ["full", "shared", "full"]
    for kind, p, d in zip(kinds, pool, dense):
        assert set(p) == set(d) == ({"c", "ik"} if kind == "full"
                                    else {"c"})
        assert p["c"].shape == (4, BS, 1, kv.latent_width(TCFG.layers[0].mixer))
        if kind == "full":
            assert p["ik"].shape == (4, BS, 1, CFG["index_head_dim"])
            assert d["ik"].shape == (2, TCFG.max_seq, 1,
                                     CFG["index_head_dim"])
    real = fam.transformer_config(PUBLISHED)
    entries = kv._cache_entries(real, (1, 1), 1, lambda shape: shape)
    assert [sorted(e) for e in entries] == [
        ["c", "ik"], ["c"], ["c"], ["c"], ["c", "ik"]]
    assert entries[0]["ik"] == (1, 1, 1, 128)
    assert entries[0]["c"] == (1, 1, 1, 640)


def test_a_shared_layers_selection_is_the_scoring_layers():
    """The walk hands layer 1 the selection layer 0 made, and layer 2
    makes its own; the reference's selections are the same sets."""
    top, blocks = _weights()
    params = dict(top, blocks=blocks)
    toks = _tokens(P_LEN)
    seen = []
    real = kv._Latent.expanded

    def spy(self, q, rows, q_offset=None, selected=None):
        seen.append(np.asarray(selected))
        return real(self, q, rows, q_offset, selected)

    kv._Latent.expanded = spy
    try:
        kv.prefill_tp(TCFG, params, kv.init_kv_cache_tp(TCFG, 1, 1, F32),
                      jnp.asarray(toks))
    finally:
        kv._Latent.expanded = real
    assert len(seen) == 3
    assert np.array_equal(seen[0], seen[1])
    assert not np.array_equal(seen[1], seen[2])
    causal = np.tril(np.ones((P_LEN, P_LEN), bool))
    for mask in seen:
        assert not (mask & ~causal).any()
        assert np.array_equal(mask.sum(-1),
                              np.minimum(np.arange(P_LEN) + 1, TOP_K))
    theirs = []
    x = top["embed"].astype(F32)[jnp.asarray(toks)]
    ref.forward(CFG, iter(blocks), x, selections=theirs)
    for mask, chosen in zip(seen, theirs):
        named = np.zeros((P_LEN, P_LEN + 1), bool)
        np.put_along_axis(named, np.where(np.asarray(chosen[0]) >= 0,
                                          np.asarray(chosen[0]), P_LEN),
                          True, axis=-1)
        assert np.array_equal(mask, named[:, :P_LEN])


def test_selection_is_neither_constant_nor_the_most_recent_rows():
    """With seeded weights the indexer picks by content: of the
    positions the late queries select, a good share lies before the most
    recent ``top_k``, and two queries do not select the same set."""
    top, blocks = _weights()
    toks = _tokens(56, seed=3)
    theirs = []
    ref.forward(CFG, iter(blocks), top["embed"].astype(F32)[
        jnp.asarray(toks)], selections=theirs)
    chosen = np.asarray(theirs[0][0])                     # (56, k)
    late = np.arange(4 * TOP_K, 56)
    outside = (chosen[late] < (late - TOP_K + 1)[:, None]).mean()
    assert 0.5 < outside < 1.0
    assert len({tuple(sorted(c)) for c in chosen[late]}) > len(late) // 2


# ------------------------------------------------------------- selection

def _mask_of(rows, n):
    m = np.zeros((rows.shape[0], n + 1), bool)
    np.put_along_axis(m, np.where(rows >= 0, rows, n), True, axis=-1)
    return m[:, :n]


@pytest.mark.parametrize("case", ["random", "ties", "few", "none", "neg_inf"])
def test_the_two_forms_of_a_selection_name_the_same_positions(case):
    """``select_mask`` (a prefill's form: a threshold found bit by bit)
    and ``select_rows`` (a decode step's: ``jax.lax.top_k``) agree,
    with equal scores going to the earlier position."""
    rng = np.random.default_rng(5)
    n, k = 40, 8
    scores = rng.standard_normal((6, n)).astype(np.float32)
    valid = np.ones((6, n), bool)
    if case == "ties":
        scores = rng.integers(0, 3, size=(6, n)).astype(np.float32)
        scores[0] = 0.0
    elif case == "few":
        valid = np.arange(n)[None, :] < np.array([1, 3, 8, 9, 20, 40])[:, None]
    elif case == "none":
        valid[:] = False
    elif case == "neg_inf":
        scores[:, ::2] = -np.inf
        scores[1] = -np.inf
    mask = np.asarray(T.select_mask(jnp.asarray(scores), jnp.asarray(valid),
                                    k))
    rows = np.asarray(T.select_rows(jnp.asarray(scores), jnp.asarray(valid),
                                    k))
    assert rows.shape == (6, k) and rows.dtype == np.int32
    assert np.array_equal(mask, _mask_of(rows, n))
    assert np.array_equal(mask.sum(-1), np.minimum(valid.sum(-1), k))
    assert not (mask & ~valid).any()
    # The positions that mean one come first.
    assert (np.diff((rows >= 0).astype(int), axis=-1) <= 0).all()
    if case == "ties":
        assert np.array_equal(np.flatnonzero(mask[0]), np.arange(k))
        for r in range(6):
            worst = scores[r][mask[r]].min()
            tied = np.flatnonzero((scores[r] == worst))
            kept = np.flatnonzero(mask[r] & (scores[r] == worst))
            assert np.array_equal(kept, tied[:len(kept)])


def test_select_rows_pads_a_short_extent():
    rows = np.asarray(T.select_rows(jnp.asarray([[3.0, 1.0, 2.0]]),
                                    jnp.asarray([[True, True, True]]), 5))
    assert rows.tolist() == [[0, 2, 1, -1, -1]]


def test_index_select_mask_scores_a_block_of_queries_at_a_time():
    """More queries than one block, a remainder, and an offset: the
    mask is what scoring all of them at once gives."""
    rng = np.random.default_rng(9)
    sq, off, hi, di, k = 2 * T._INDEX_BLOCK + 5, 11, 2, 8, 6
    q_i = jnp.asarray(rng.standard_normal((sq, hi, di)), F32)
    k_i = jnp.asarray(rng.standard_normal((off + sq, di)), F32)
    w = jnp.asarray(rng.standard_normal((sq, hi)), F32)
    got = np.asarray(T.index_select_mask(q_i, k_i, w, k, q_offset=off))
    scores = pa.index_scores(q_i, k_i, w)
    valid = jnp.arange(off + sq)[None, :] <= (off + jnp.arange(sq))[:, None]
    want = np.asarray(T.select_mask(scores, valid, k))
    assert got.shape == (sq, off + sq) and np.array_equal(got, want)


def test_masked_attention_is_attention_over_the_named_keys():
    rng = np.random.default_rng(2)
    sq, off, h, d = 37, 5, 3, 8
    sk = off + sq
    q = jnp.asarray(rng.standard_normal((1, sq, h, d)), F32)
    k = jnp.asarray(rng.standard_normal((1, sk, h, d)), F32)
    v = jnp.asarray(rng.standard_normal((1, sk, h, d)), F32)
    mask = rng.random((sq, sk)) < 0.4
    mask &= np.arange(sk)[None, :] <= (off + np.arange(sq))[:, None]
    mask[3] = False                                  # names nothing: zeros
    s = np.einsum("qhd,khd->hqk", q[0], k[0]) / np.sqrt(d)
    s = np.where(mask[None], s, -np.inf)
    with np.errstate(invalid="ignore"):
        p = np.exp(s - s.max(-1, keepdims=True))
        p = np.nan_to_num(p / p.sum(-1, keepdims=True))
    want = np.einsum("hqk,khd->qhd", p, v[0])
    one = flash.masked_attention(q, k, v, jnp.asarray(mask), q_offset=off)
    blocks = flash.masked_attention(q, k, v, jnp.asarray(mask), q_offset=off,
                                    block_q=8, block_k=16)
    assert _gap(one[0], want) < 1e-5 and _gap(blocks[0], want) < 1e-5
    assert float(jnp.abs(blocks[0, 3]).max()) == 0.0


# --------------------------------------------------------- the two reads

def _pool_of(rows, table, nb, bs):
    """``rows`` (slots, n, w) laid into a pool's pages by ``table``."""
    slots, n, w = rows.shape
    pool = np.full((nb, bs, 1, w), 7.0, np.float32)       # stale pages
    for s in range(slots):
        for b in range(n // bs):
            if table[s, b] >= 0:
                pool[table[s, b], :, 0] = rows[s, b * bs:(b + 1) * bs]
    return pool


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_paged_index_scores_equal_the_oracle_up_to_the_frontier(impl):
    rng = np.random.default_rng(1)
    slots, hi, di, bs, n_blk = 3, 4, 128, 128, 4
    q_i = rng.standard_normal((slots, hi, di)).astype(np.float32)
    w = rng.standard_normal((slots, hi)).astype(np.float32)
    keys = rng.standard_normal((slots, n_blk * bs, di)).astype(np.float32)
    table = np.array([[5, 2, 9, 0], [1, 7, -1, -1], [3, -1, -1, -1]],
                     np.int32)
    pos = np.array([3 * bs + 17, bs + 1, 40], np.int32)
    live = np.array([True, True, False])
    pool = _pool_of(keys, table, 10, bs)
    assert pa._index_eligible(jnp.asarray(q_i), jnp.asarray(pool))
    got = np.asarray(pa.paged_index_scores(
        jnp.asarray(q_i), jnp.asarray(w), jnp.asarray(pool), table, pos,
        active=jnp.asarray(live), impl=impl))
    want = np.asarray(pa.index_rows_scores(
        jnp.asarray(q_i), jnp.asarray(keys), jnp.asarray(w)))
    direct = np.einsum("sjn,sj->sn", np.maximum(
        np.einsum("sjc,snc->sjn", q_i, keys), 0), w)
    assert _gap(want, direct) < 1e-4
    assert got.shape == (slots, n_blk * bs)
    for s in range(slots):
        if live[s]:
            assert _gap(got[s, :pos[s] + 1], want[s, :pos[s] + 1]) < 1e-4
    if impl == "pallas":
        # Beyond a frontier's page, and for a free slot, nothing is read.
        assert not got[1, 2 * bs:].any() and not got[2].any()


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_the_sparse_latent_read_attends_the_named_rows_and_no_others(impl):
    """Against attention written out over the named rows; and under a
    selection that names every row, against the dense latent read."""
    rng = np.random.default_rng(4)
    slots, h, width, vw, bs, n_blk, k = 3, 4, 256, 128, 16, 16, 128
    q = rng.standard_normal((slots, h, width)).astype(np.float32)
    lat = rng.standard_normal((slots, n_blk * bs, width)).astype(np.float32)
    table = rng.permutation(48).reshape(slots, n_blk).astype(np.int32)
    pool = _pool_of(lat, table, 48, bs)
    pos = np.array([250, 99, 140], np.int32)
    scores = rng.standard_normal((slots, n_blk * bs)).astype(np.float32)
    valid = np.arange(n_blk * bs)[None, :] <= pos[:, None]
    valid[2] = False                                   # a free slot
    rows = np.asarray(T.select_rows(jnp.asarray(scores), jnp.asarray(valid),
                                    k))
    assert (rows[0] >= 0).all() and (rows[1] >= 0).sum() == 100
    got = np.asarray(pa.paged_sparse_latent_attention(
        jnp.asarray(q), jnp.asarray(pool), table, rows, v_width=vw,
        scale=0.11, impl=impl))
    for s in range(slots):
        named = rows[s][rows[s] >= 0]
        if not len(named):
            assert not got[s].any()
            continue
        sc = 0.11 * q[s] @ lat[s, named].T
        p = np.exp(sc - sc.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ lat[s, named, :vw]
        assert _gap(got[s], want) < 1e-4
    everything = np.where(valid, np.arange(n_blk * bs)[None, :], -1)
    order = np.argsort(everything < 0, axis=-1, kind="stable")
    everything = np.take_along_axis(everything, order, -1).astype(np.int32)
    sparse = pa.paged_sparse_latent_attention(
        jnp.asarray(q), jnp.asarray(pool), table, everything, v_width=vw,
        scale=0.11, impl=impl)
    dense = pa.paged_latent_attention(
        jnp.asarray(q), jnp.asarray(pool), table, pos, v_width=vw,
        scale=0.11, active=jnp.asarray(valid.any(-1)), impl="jnp")
    assert _gap(sparse, dense) < 1e-4


def test_the_gather_reads_by_page_and_offset_through_the_table():
    rng = np.random.default_rng(6)
    bs, n_blk, width = 8, 4, 16
    lat = rng.standard_normal((2, n_blk * bs, width)).astype(np.float32)
    table = np.array([[6, 1, 4, 0], [2, 5, -1, -1]], np.int32)
    pool = _pool_of(lat, table, 8, bs)
    rows = np.array([[31, 0, 9, -1], [15, 8, 7, 3]], np.int32)
    got = np.asarray(pa.sparse_rows_gather(jnp.asarray(pool), table, rows))
    for s in range(2):
        for j, t in enumerate(rows[s]):
            if t >= 0:
                assert np.array_equal(got[s, j], lat[s, t])


# ------------------------------------------------------------ the engine

def _engine(params, spmd=False, **serve_cfg):
    base = dict(slots=2, block_size=BS, max_new=N_NEW)
    return serve.Engine(TCFG, params,
                        serve.ServeConfig(**{**base, **serve_cfg}),
                        spmd=spmd, nranks=1 if spmd else None)


def _follows_the_reference(top, blocks, out, p_len=P_LEN) -> bool:
    """Every served token is the reference's own choice at its
    position, given the tokens before it."""
    want = _reference(top, blocks, out[None, :-1].astype(np.int32))
    return bool(np.array_equal(want[p_len - 1:].argmax(-1), out[p_len:]))


@pytest.mark.parametrize("how", ["one_piece", "chunked", "prefix_hit",
                                 "dense_slots", "spmd"])
def test_engine_serves_the_references_tokens(how):
    """A chunked prefill and a prefix hit score cached index keys and
    read cached latent rows where the one-piece prefill reads its own;
    a prefix hit's copied page carries both entries (copy on write):
    the same tokens, which are the reference's."""
    top, blocks = _weights()
    prompt = _tokens()[0, :P_LEN - 3]      # the last page written in part
    p_len = len(prompt)
    eng = _engine(dict(top, blocks=blocks), spmd=how == "spmd",
                  prefill_chunk=5 if how == "chunked" else None,
                  block_size=0 if how == "dense_slots" else BS)
    rid = eng.submit(prompt)
    out = eng.run()[rid]
    if how == "prefix_hit":
        again = eng.submit(prompt)
        assert np.array_equal(eng.run()[again], out)
        assert eng.stats.counters["prefix_hits"] == 1
        assert eng.stats.counters["cow_copies"] == 1
    assert len(out) == p_len + N_NEW
    assert _follows_the_reference(top, blocks, out, p_len)
    if how != "dense_slots":
        assert eng.stats.snapshot()["blocks_in_use"] == 0
    if how == "spmd":
        texts = eng.program_texts()
        for text in texts.values():
            for scope in ("mla", "dsa", "moe"):
                assert profiling.LAYER_SCOPES[scope] in text
        assert pa.SPARSE_GATHER_SCOPE in texts["decode"]


def test_one_install_writes_both_entries_of_a_page():
    """The compiled install takes the whole tree: latent rows and index
    keys of a position land at the same page and offset."""
    top, blocks = _weights()
    params = dict(top, blocks=blocks)
    toks = _tokens()
    pool, table, _ = _paged_state(TCFG, params, toks)
    dense = kv.init_kv_cache_tp(TCFG, 1, 1, F32)
    _, dense = kv.prefill_tp(TCFG, params, dense,
                             jnp.asarray(toks[:, :P_LEN]))
    for layer, entry in enumerate(pool):
        for name, leaf in entry.items():
            for t in range(P_LEN):
                page, off = table[0, t // BS], t % BS
                assert np.array_equal(np.asarray(leaf[page, off]),
                                      np.asarray(dense[layer][name][0, t]))


@pytest.mark.parametrize("spmd", [False, True])
def test_dsa_rows_read_is_the_sum_of_min_pos_plus_1_and_top_k(spmd):
    """The three counters ride the tokens' fetch; what attention reads
    of the latent pool in a decode step is ``min(pos + 1, top_k)`` rows
    a live slot and layer, whatever the context."""
    top, blocks = _weights()
    serve.reset_stats()
    eng = _engine(dict(top, blocks=blocks), spmd=spmd, slots=3)
    lens = (3, P_LEN)
    for n in lens:
        eng.submit(_tokens(n, seed=n)[0], max_new=4)
    eng.run()
    log = [r for r in profiling.serve_step_log() if r["active"]]
    layers = len(TCFG.layers)
    full = sum(k == "full" for k in CFG["indexer_types"])
    # Both admitted in the first step, which hands out their first
    # tokens; three decode steps for the other three.
    assert len(log) == 3
    for i, r in enumerate(log):
        pos = [n + i for n in lens]
        assert r["dsa_rows_live"] == layers * sum(p + 1 for p in pos)
        assert r["dsa_rows_read"] == layers * sum(
            min(p + 1, TOP_K) for p in pos)
        assert r["dsa_rows_scored"] == full * sum(p + 1 for p in pos)
    c = eng.stats.counters
    assert c["dsa_rows_read"] == sum(r["dsa_rows_read"] for r in log)
    assert c["dsa_rows_read"] < c["dsa_rows_live"]


def test_a_long_prompts_expert_layer_runs_in_pieces_and_adds_up(monkeypatch):
    spec = TCFG.layers[1].ffn
    p = _weights()[1][1]["experts"]
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (23, CFG["hidden_size"])), F32)
    whole = moe.held_experts_ffn(x, p, spec)
    monkeypatch.setattr(kv, "_EXPERT_ROWS", 8)
    y, rows, zero, over = kv._held_experts_in_pieces(x, p, spec, None)
    assert _gap(y, whole[0]) < 1e-6
    assert rows.shape == (3, spec.n_held)
    assert np.array_equal(rows.sum(0), whole[1])
    assert int(zero) == int(whole[2])
    assert int(over) == int(whole[3]) == 0


@pytest.mark.parametrize("spmd", [False, True])
def test_each_piece_is_a_call_of_its_own_on_the_step_record(monkeypatch,
                                                            spmd):
    """A prompt of three pieces: three ``prefill`` entries of ``moe_rows``
    on its step's record, each ``(expert layers, held)`` as a short
    prompt's one is, adding up to the unpieced prefill's; the tokens are
    the reference's."""
    top, blocks = _weights()
    prompt = _tokens()[0, :P_LEN - 3]

    def served():
        serve.reset_stats()
        eng = _engine(dict(top, blocks=blocks), spmd=spmd)
        rid = eng.submit(prompt, max_new=3)
        out = eng.run()[rid]
        return out, profiling.serve_step_log()[0]["moe_rows"]

    out, whole = served()
    assert [p for p, _ in whole] == ["prefill", "decode"]
    monkeypatch.setattr(kv, "_EXPERT_ROWS", 8)
    again, pieces = served()
    assert np.array_equal(out, again)
    assert [p for p, _ in pieces] == ["prefill"] * 3 + ["decode"]
    assert all(rows.shape == whole[0][1].shape for _, rows in pieces)
    assert np.array_equal(sum(rows for _, rows in pieces[:3]), whole[0][1])
    assert _follows_the_reference(top, blocks, out, len(prompt))


# ---------------------------------------------------- the cut is a share

def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen ranks each hold one sixteenth of the experts and the
    shared expert; their shares, the shared expert counted once, are the
    whole layer."""
    cfg = harness.merged(CFG, {"n_routed_experts": 16, "published":
                               {"n_routed_experts": 16}})
    blk = fam.make_layer(fam.seed_key(5), cfg, 1, F32)["experts"]
    m = jnp.asarray(np.random.default_rng(2).standard_normal(
        (1, 40, cfg["hidden_size"])), F32)
    whole = ref.experts(cfg, blk, m, plain.matmul_f32, first=0, held=16)
    shared = ref.swiglu(m, blk["shared_w1"], blk["shared_w2"],
                        plain.matmul_f32)
    total = 0
    for rank in range(16):
        spec = moe.Experts(n_experts=16, top_k=cfg["num_experts_per_tok"],
                           d_expert=cfg["moe_intermediate_size"],
                           first_expert=rank, n_held=1, n_shared=1,
                           scale=cfg["routed_scaling_factor"])
        part = dict(blk, w1=blk["w1"][rank:rank + 1],
                    w2=blk["w2"][rank:rank + 1])
        y, rows, *_ = moe.held_experts_ffn(m[0], part, spec)
        total = total + y
    assert _gap(total - 15 * shared[0], whole[0]) < TOL


def test_the_cut_keeps_the_published_widths_and_counts_its_parameters():
    """At the published widths, from shapes alone: the count of the
    configuration file."""
    key = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(lambda: dict(
        fam.make_top(key, PUBLISHED, jnp.bfloat16),
        blocks=[fam.make_layer(key, PUBLISHED, i, jnp.bfloat16)
                for i in range(PUBLISHED["num_hidden_layers"])]))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == 3_881_517_056
    mixer = shapes["blocks"][0]["mixer"]
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(
        mixer["index"])) == 9_371_904
    assert "index" not in shapes["blocks"][1]["mixer"]
    spec = fam.transformer_config(PUBLISHED).layers
    ix = spec[0].mixer.index
    assert (ix.n_heads, ix.head_dim, ix.rope, ix.top_k) == (32, 128, 64, 2048)
    assert [sp.mixer.index == "shared" for sp in spec] == [
        False, True, True, True, False]
    assert spec[0].mixer.v_dim == spec[0].mixer.qk_nope + spec[0].mixer.qk_rope


# ------------------------------------------------------- what is refused

def _mla(index):
    return T.MLA(n_heads=2, kv_rank=8, qk_nope=4, qk_rope=4, v_dim=8,
                 q_rank=8, rope=True, index=index)


def _stack(*index):
    return T.TransformerConfig(
        vocab=16, d_model=8, n_heads=2, n_layers=len(index), d_ff=16,
        max_seq=16, rope=True, norm="rmsnorm", ffn="swiglu",
        layers=tuple(T.LayerSpec(mixer=_mla(i)) for i in index))


def test_a_shared_selection_needs_a_scoring_layer_below():
    ix = T.Indexer(n_heads=2, head_dim=4, rope=2, top_k=4)
    _stack(ix, "shared", "shared", ix)
    with pytest.raises(ValueError, match="shares a selection"):
        _stack("shared", ix)
    with pytest.raises(ValueError, match="shares a selection"):
        _stack(None, "shared")
    with pytest.raises(ValueError, match="MLA.index is None, an Indexer"):
        _mla("full")
    with pytest.raises(ValueError, match="needs q_rank > 0"):
        T.MLA(n_heads=2, kv_rank=8, qk_nope=4, qk_rope=4, v_dim=8, index=ix)
    with pytest.raises(ValueError, match="Indexer.rope"):
        T.Indexer(n_heads=2, head_dim=4, rope=3, top_k=4)


@pytest.mark.parametrize("kind", ["scoring", "shared"])
def test_the_training_forward_refuses_an_indexed_mixer_by_name(kind):
    ix = T.Indexer(n_heads=2, head_dim=4, rope=2, top_k=4)
    cfg = _stack(ix, "shared") if kind == "shared" else _stack(ix)
    params = T.init_transformer(jax.random.PRNGKey(0), cfg)
    assert ("index" in params["blocks"][-1]["mixer"]) == (kind == "scoring")
    with pytest.raises(mpi.CommError, match="indexed mixer"):
        T.forward(cfg, params, jnp.zeros((1, 8), jnp.int32))
    bare = dataclasses.replace(cfg, layers=tuple(
        dataclasses.replace(sp, mixer=dataclasses.replace(
            sp.mixer, index=None)) for sp in cfg.layers))
    assert T.forward(bare, params, jnp.zeros((1, 8), jnp.int32)).shape \
        == (1, 8, 16)


def test_sparse_latent_attention_is_served_on_one_rank_only():
    with pytest.raises(mpi.CommError, match="MLA.index"):
        kv.validate_tp(TCFG, 2)
    kv.validate_tp(TCFG, 1)


# ------------------------------------- what the tolerance can tell apart

def test_the_tolerance_tells_the_most_recent_rows_from_the_scored_ones(
        monkeypatch):
    """A program that attends the most recent ``top_k`` positions in the
    place of the scored ones misses the reference by orders."""
    top, blocks = _weights()
    params = dict(top, blocks=blocks)
    toks = _tokens()
    want = _reference(top, blocks, toks)

    def recent_mask(q_i, k_i, w, top_k, q_offset=0):
        t = q_offset + jnp.arange(q_i.shape[0])[:, None]
        s = jnp.arange(k_i.shape[0])[None, :]
        return (s <= t) & (s > t - top_k)

    def recent_rows(scores, valid, top_k):
        return T.select_rows(jnp.broadcast_to(
            jnp.arange(scores.shape[-1], dtype=F32), scores.shape),
            valid, top_k)

    def logits_of():
        state = kv.init_kv_cache_tp(TCFG, 1, 1, F32)
        pre, state = kv.prefill_tp(TCFG, params, state,
                                   jnp.asarray(toks[:, :P_LEN]))
        dec, _ = kv.decode_step_tp(TCFG, params, state,
                                   jnp.asarray(toks[:, P_LEN]),
                                   jnp.asarray([P_LEN]))
        return pre[0], dec[0]

    pre, dec = logits_of()
    assert _gap(pre, want[P_LEN - 1]) < TOL and _gap(dec, want[P_LEN]) < TOL
    monkeypatch.setattr(kv, "index_select_mask", recent_mask)
    monkeypatch.setattr(kv, "select_rows", recent_rows)
    pre, dec = logits_of()
    assert _gap(pre, want[P_LEN - 1]) > 100 * TOL
    assert _gap(dec, want[P_LEN]) > 100 * TOL
