"""openPangu-Ultra-MoE on the serving path (ISSUE 32), at small sizes on
the CPU, seeded float32 weights: the serving walk's latent cache entry,
its two attention paths, the held expert share inside the serving
programs, and the engine around them, against the plain reference
(``benchmarks/references/openpangu_moe.py``), which writes its scores
out, never absorbs a product and loops over the held experts.

Tolerances.  ``TOL = 2e-5`` on logits of order 1: both sides are float32
(the suite's x64 leaves explicit float32 alone), they differ by the
order of their sums, and five sandwich layers keep that under 5e-6 here
(measured 1e-6).  Every broken variant below misses it over 48
positions: a router whose weights were rounded to bfloat16 by 9 times
(1.8e-4, without one routing choice flipping), an unrotated key by four
orders (0.49), a left-out post-norm by five (3.2), which is the reason
it is the tolerance."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4torch_tpu as mpi
from benchmarks import run as harness
from benchmarks.families import openpangu_moe as fam
from benchmarks.references import dense_decoder as plain
from benchmarks.references import openpangu_moe as ref
from mpi4torch_tpu import serve
from mpi4torch_tpu.models import transformer as T
from mpi4torch_tpu.parallel import moe
from mpi4torch_tpu.serve import kv
from mpi4torch_tpu.utils import profiling

F32 = jnp.float32
TOL = 2e-5
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "openpangu-ultra-moe-718b.json")) as f:
    PUBLISHED = json.load(f)
CFG = harness.merged(PUBLISHED, PUBLISHED["rehearsal"])
TCFG = fam.transformer_config(CFG)
P_LEN, N_NEW, BS = 16, 8, 8


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
    index = np.concatenate([[0, P_LEN], [3, 5],
                            8 + np.arange(n_pages - 2)]).astype(np.int32)
    pool = kv.install_rows_paged(pool, rows, jnp.asarray(index))
    table = np.array([[3, 5, 1, 0, -1, -1, -1, -1]], np.int32)
    return pool, table, logits


# ------------------------------------------------- the walk and its views

@pytest.mark.parametrize("cache", ["paged", "dense"])
def test_prefill_then_decode_equals_the_references_full_forward(cache):
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


def test_the_latent_entry_is_one_row_a_token_for_all_heads():
    pool = kv.init_kv_pool_tp(TCFG, 4, BS, 1, F32)
    dense = kv.init_kv_cache_tp(TCFG, 2, 1, F32)
    spec = TCFG.layers[0].mixer
    width = kv.latent_width(spec)
    assert width == 128 and width >= spec.kv_rank + spec.qk_rope
    assert kv.latent_width(T.MLA(128, 512, 128, 64, 128)) == 640
    for entry in pool:
        assert set(entry) == {"c"} and entry["c"].shape == (4, BS, 1, width)
    for entry in dense:
        assert entry["c"].shape == (2, TCFG.max_seq, 1, width)


def test_absorbed_decode_equals_expanded_attention_on_one_cache():
    """Decode never forms a key or a value; a prefill expands every row.
    On the same cached rows the two give one attention output."""
    top, blocks = _weights()
    blk, spec = blocks[1], TCFG.layers[1].mixer
    lat = kv._Latent(spec, blk["mixer"])
    rng = np.random.default_rng(3)
    n, h = 21, spec.n_heads
    y = jnp.asarray(rng.standard_normal((1, n, CFG["hidden_size"])), F32)
    q, c, k_r, _ = T.mla_project(TCFG, spec, blk["mixer"], y, jnp.arange(n))
    rows = lat.rows(c, k_r)                              # (1, n, 1, width)
    expanded = lat.expanded(q, rows)[0, -1]              # (h, dv) at n - 1
    u = kv.latent_rows_attention(
        lat.absorbed(q[:, -1]), rows[:, :, 0], jnp.asarray([n - 1]),
        v_width=spec.kv_rank, scale=lat.scale)
    absorbed = lat.values(u)[0]
    assert absorbed.shape == (h, spec.v_dim)
    assert _gap(absorbed, expanded) < 2e-6


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
    """A chunked prefill and a prefix hit read cached latent rows,
    expanded, where the one-piece prefill reads its own: the same
    tokens, which are the reference's."""
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


def test_spmd_engine_takes_its_layers_one_at_a_time():
    """``params["blocks"]`` may make its layers as they are asked for;
    the engine walks it once and serves the same tokens."""
    top, blocks = _weights()
    prompt = _tokens()[0, :P_LEN]
    taken = []

    def lazily():
        for i, blk in enumerate(blocks):
            taken.append(i)
            yield blk

    eng = serve.Engine(TCFG, dict(top, blocks=lazily()),
                       serve.ServeConfig(slots=2, block_size=BS,
                                         max_new=N_NEW),
                       spmd=True, nranks=1)
    assert taken == list(range(len(blocks)))
    rid = eng.submit(prompt)
    out = eng.run()[rid]
    assert _follows_the_reference(top, blocks, out)
    texts = eng.program_texts()
    assert set(texts) == {"decode", f"prefill.{P_LEN}"}
    for text in texts.values():
        assert profiling.LAYER_SCOPES["mla"] in text
        assert profiling.LAYER_SCOPES["moe"] in text
    with pytest.raises(ValueError, match="layers for n_layers"):
        serve.Engine(TCFG, dict(top, blocks=iter(blocks[:-1])),
                     serve.ServeConfig(slots=2))


def test_step_records_carry_the_rows_the_held_experts_took():
    """``moe_rows``: one entry a program call with an expert layer, the
    prefill's over its prompt, the decode step's over its live slots
    only (a free slot's row is nobody's)."""
    top, blocks = _weights()
    serve.reset_stats()
    eng = _engine(dict(top, blocks=blocks), slots=3)
    eng.submit(_tokens()[0, :P_LEN], max_new=3)
    eng.run()
    log = profiling.serve_step_log()
    first, second = log[0]["moe_rows"], log[1]["moe_rows"]
    assert [p for p, _ in first] == ["prefill", "decode"]
    assert [p for p, _ in second] == ["decode"]
    n_layers = sum(sp.ffn is not None for sp in TCFG.layers)
    held, k = CFG["n_routed_experts"], CFG["num_experts_per_tok"]
    for program, rows in first + second:
        assert rows.shape == (n_layers, held) and rows.dtype.kind == "i"
        tokens = P_LEN if program == "prefill" else 1    # one live slot
        assert (rows.sum(axis=-1) <= tokens * k).all()
    assert first[0][1].sum() > 0


def test_a_token_that_is_nobodys_takes_no_experts_time():
    spec = TCFG.layers[1].ffn
    p = _weights()[1][1]["experts"]
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (6, CFG["hidden_size"])), F32)
    live = jnp.asarray([True, False, True, True, False, True])
    y_all, rows_all, *_ = moe.held_experts_ffn(x, p, spec)
    y, rows, *_ = moe.held_experts_ffn(x, p, spec, live=live)
    _, rows_live, *_ = moe.held_experts_ffn(x[live], p, spec)
    assert np.array_equal(rows, rows_live) and rows.sum() < rows_all.sum()
    assert _gap(y[live], y_all[live]) < 1e-6


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


# -------------------------------------- what the tolerance can tell apart

def _program_logits(params, toks, tcfg=TCFG):
    """Every position's logits, through the training forward: the one
    set of projections serves both paths."""
    return T.forward(tcfg, params, jnp.asarray(toks), None)[0]


def _bf16_router(top, blocks):
    def down(blk):
        if "experts" not in blk:
            return blk
        router = blk["experts"]["router"].astype(jnp.bfloat16).astype(F32)
        return dict(blk, experts=dict(blk["experts"], router=router))
    return TCFG, dict(top, blocks=[down(b) for b in blocks])


def _no_post_norm(top, blocks):
    layers = tuple(dataclasses.replace(sp, post_norm=False)
                   for sp in TCFG.layers)
    return dataclasses.replace(TCFG, layers=layers), dict(top, blocks=blocks)


def _unrotated_key(top, blocks):
    mixer = dataclasses.replace(TCFG.layers[0].mixer, rope=False)
    layers = tuple(dataclasses.replace(sp, mixer=mixer)
                   for sp in TCFG.layers)
    return dataclasses.replace(TCFG, layers=layers), dict(top, blocks=blocks)


@pytest.mark.parametrize("broken", [_bf16_router, _no_post_norm,
                                    _unrotated_key])
def test_the_tolerance_tells_a_broken_layer_from_a_sound_one(broken):
    top, blocks = _weights()
    toks = _tokens(48)
    want = _reference(top, blocks, toks)
    assert _gap(_program_logits(dict(top, blocks=blocks), toks), want) < TOL
    tcfg, params = broken(top, blocks)
    assert _gap(_program_logits(params, toks, tcfg), want) > 5 * TOL


# ------------------------------------------------------- what is refused

def test_a_layer_spec_is_served_on_one_rank_only():
    with pytest.raises(mpi.CommError, match="served on one rank"):
        kv.validate_tp(TCFG, 2)
    kv.validate_tp(TCFG, 1)
    with pytest.raises(ValueError, match="needs a KDA or MLA mixer"):
        T.TransformerConfig(vocab=8, d_model=8, n_heads=2, n_layers=1,
                            d_ff=8, max_seq=8,
                            layers=(T.LayerSpec(post_norm=True),))


# ----------------------- the other configurations' programs did not move

# sha256 of the lowered text of the parent commit's programs (PR 31,
# 90d8c4d; the two decode steps: PR 38, whose step takes the slot state
# stacked and hands it back advanced, the walk between unchanged), taken
# in this suite's environment (x64 on, jax 0.9.0): Kimi's training step,
# InternLM2's paged decode step and openPangu's latent paged decode step
# at their rehearsal sizes.  A PR that means to change one of these programs
# replaces its line; one that does not has changed it by accident.  PR 42
# replaced Kimi's and openPangu's: both hand out one more counter,
# ``moe_overflow_calls``; their expert layers, under the row constant at
# these sizes, are the parent's (``tests/test_moe_prefix.py``, bit for bit).
# PR 46 replaced Kimi's: the names it put on the flash kernel's ``(out,
# lse)`` and on ``y @ w1`` keep nothing in a spec's regions, and the text
# is the parent's but for the number at the end of 76 private functions'
# names, one higher each (``@silu_160`` -> ``@silu_161``); with those
# numbers taken out the two texts are equal.
# PR 48 added the last five, from ITS parent (PR 47, 1e7a865): Mistral's
# data-parallel training step over two devices (``all_average_tree``,
# ``_norm``) and the paged decode steps of the four other expert serving
# configurations (``held_experts_ffn``'s one-rank path, the spec walk,
# ``GQA``).  With the first three these are the programs of all nine cells
# the benchmark had: PR 48 split ``held_experts_ffn`` (``experts_ffn``,
# ``_held_share``, ``_at_home``), gave the norms their ``eps`` as an
# argument and hands ``_forward``'s counters out by name, and every one of
# the eight lowers to its parent's text, letter for letter (no old cell
# was run on the chip for that PR: four-chip machines were scarce).
# PR 51 replaced Mistral's data-parallel step, the program it meant to
# change: the parameters enter the loss through ``replicated_tree``, so the
# forward ``all_reduce`` of the parameters' bucket and the mean's ``divide``
# behind it left the text (4 ``stablehlo.all_reduce`` -> 3: the adjoint's
# and the loss's stay), and the adjoint builds its bucket with one
# ``concatenate`` of the cotangents where the transposed slices were 15
# ``pad``s and their sums.  The other seven are the parent's, letter for
# letter: their steps have no communicator larger than one.
PARENT_TEXTS = {
    "kimi": "c8f6723c62b880c17377b0e0b8a36e11f36a27c25731b3c74602c7af50198f9e",
    "internlm2": "ddcbb9f523103a01172891ee29d82bf8839323abf424f1e05587b9142ec5ad65",
    "openpangu": "cd48ae9b73a5afa03dceab1ab8e7f1069b6759f10c550555e566776f4e353fc7",
    "mistral_dp": "b8c45b1194fc1d905be33eedd5f7db620f98d49f8dfda222c1ddc3254fd9f808",
    "trinity": "31999685375c08a8e7afe257fd62224894c879429401a8ca6c89622960c5743d",
    "longcat": "93a18b45bdaff5a8c9d0bddab3b1e5c4246db276631b09416f65975bd1ec4034",
    "glm": "e3db8fb97a6e095b0c6495dc0e593c12c44975b8a687287abeec1fa33451768e",
    "nemotron": "cc2ff00615d8e20a3aa91ebe6a0100453d23f9f40eddf4dc12ede076e32a0a66",
}
# (configuration, family) of the serving configurations whose family file
# makes the parameters.
_SERVED = {"trinity": ("trinity-mini", "afmoe"),
           "longcat": ("longcat-flash-chat", "longcat_flash"),
           "glm": ("glm-5.2", "glm_dsa"),
           "nemotron": ("nemotron-3-super-120b-a12b", "nemotron_h")}


def _lowered_text(which: str) -> str:
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks import program, weights
    from benchmarks.families import kimi_linear

    name = {"kimi": "kimi-linear-48b-a3b", "internlm2": "internlm2-1.8b",
            "openpangu": PUBLISHED["name"], "mistral_dp": "mistral-7b-v0.1",
            **{k: v[0] for k, v in _SERVED.items()}}
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name[which] + ".json")) as f:
        cfg = json.load(f)
    cfg = harness.merged(cfg, cfg["rehearsal"])
    if which == "mistral_dp":
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("mpi",))
        repl = NamedSharding(mesh, P())
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=repl),
            jax.eval_shape(lambda: weights.make_params(cfg, 1, F32)))
        step = program.build_train_step(
            program.transformer_config(cfg, remat=True), mesh, 2, 0.3, True)
        return step.lower(params, jax.ShapeDtypeStruct(
            (4, 64), jnp.int32, sharding=repl)).as_text()
    if which in _SERVED:
        import importlib

        family = importlib.import_module(
            "benchmarks.families." + _SERVED[which][1])
        # A window class of pages and a Mamba-2 layer refuse prefix
        # sharing.
        eng = serve.Engine(
            family.transformer_config(cfg), family.make_params(cfg, 1, F32),
            serve.ServeConfig(slots=4, block_size=8,
                              prefix_cache=which in ("longcat", "glm")),
            spmd=True, nranks=1)
        eng.submit(np.arange(1, 10), max_new=3)
        eng.step()
        return eng.lower_step().as_text()
    if which == "kimi":
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("mpi",))
        repl = NamedSharding(mesh, P())
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=repl),
            jax.eval_shape(lambda: kimi_linear.make_params(cfg, 1, F32)))
        step = kimi_linear.build_train_step(
            kimi_linear.transformer_config(cfg, remat=True), mesh, 2, 0.03,
            False)
        return step.lower(params, jax.ShapeDtypeStruct(
            (2, 64), jnp.int32, sharding=repl)).as_text()
    if which == "openpangu":
        tcfg, params = TCFG, fam.make_params(CFG, 1, F32)
    else:
        tcfg = program.transformer_config(cfg)
        params = weights.make_params(cfg, 1, F32)
    eng = serve.Engine(tcfg, params, serve.ServeConfig(slots=4, block_size=8),
                       spmd=True, nranks=1)
    eng.submit(np.arange(1, 10), max_new=3)
    eng.step()
    return eng.lower_step().as_text()


@pytest.mark.parametrize("which", sorted(PARENT_TEXTS))
def test_lowered_step_text_is_the_parents(which):
    got = hashlib.sha256(_lowered_text(which).encode()).hexdigest()
    assert got == PARENT_TEXTS[which]
