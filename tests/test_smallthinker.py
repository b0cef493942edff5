"""SmallThinker-21BA3B on the training path: a router that reads the
layer's input before attention, ReGLU experts, window and full attention
in one stack, and the top-k layer's rows exchanged over an expert-parallel
communicator — the program against the plain float32 reference of
``benchmarks/references/smallthinker.py`` on seeded weights at the
configuration's rehearsal sizes (hidden 64, 4 query / 2 KV heads of 16,
8 experts top-2, window 32, one period of four layers).

Tolerances.  Program and reference both compute in float32 here, so they
differ by summation order alone: 2e-4 of a leaf's norm covers logits, a
gradient and one step (``tests/test_kimi_linear.py``'s, for its reason;
the widest seen here is 2e-6).  A program that routes on the rows behind
the attention, a swiglu in the reglu's place, or the norm's 1e-5 in the
place of 1e-6 on a stream of small rows, misses it tenfold and more,
which the last tests pin down.
"""

import dataclasses
import functools
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import mpi4torch_tpu as mpi  # noqa: E402
from benchmarks.families import smallthinker as family  # noqa: E402
from benchmarks.references import smallthinker as ref  # noqa: E402
from benchmarks.run import merged  # noqa: E402
from mpi4torch_tpu.models import transformer as T  # noqa: E402
from mpi4torch_tpu.parallel import moe  # noqa: E402
from mpi4torch_tpu.serve import kv as serve_kv  # noqa: E402

F32 = jnp.float32
TOL = 2e-4
LR = 0.3
RANKS = 4


def _cfg(chips: int):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "smallthinker-21ba3b.json")) as f:
        cfg = json.load(f)
    return merged(merged(cfg, cfg["rehearsal"]),
                  {"deployment_share": {"chips_per_layer": chips}})


CFG1, CFG4 = _cfg(1), _cfg(RANKS)
TCFG1 = family.transformer_config(CFG1, remat=True)
TCFG4 = family.transformer_config(CFG4, remat=True)


def _mesh(ranks: int):
    return Mesh(np.asarray(jax.devices()[:ranks]), ("mpi",))


@functools.lru_cache(maxsize=None)
def _params(seed=7):
    return family.make_params(CFG1, seed, F32)


def _tokens(seed=1, shape=(2, 80)):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                              CFG1["vocab_size"], dtype=jnp.int32)


def _close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.linalg.norm(b), 1e-30)
    assert np.linalg.norm(a - b) <= tol * scale, \
        (np.linalg.norm(a - b), scale)


def _tree_close(a, b, tol=TOL):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        _close(x, y, tol)


def _worst(prog, plain) -> float:
    """Widest per-leaf gap of two vectors of norms; a leaf whose
    reference norm is zero (the selection bias) is read against the
    median leaf."""
    prog, plain = np.asarray(prog), np.asarray(plain)
    return float(np.max(np.abs(prog - plain)
                        / np.maximum(plain, np.median(plain))))


# ------------------------------------ the reference's own attention, in blocks

@pytest.mark.parametrize("window", [0, 24, 80])
def test_the_references_attention_in_blocks_is_the_mask_written_out(
        window, monkeypatch):
    """The reference writes its scores out a block of queries at a time
    (against every key on a full layer, against the window's span on a
    sliding one).  At the rehearsal's lengths a sequence is ONE block;
    the cell's 16,384 tokens are 64: a first form read the full layers'
    keys from the wrong rows in every block but the last and passed every
    test of one block (the chip found it: PERF.md section 6, PR 48).
    Here 80 positions in blocks of 32 (the last one half empty: its idle
    queries must not turn the gradient into NaN), values and gradients
    against the whole masked matrix."""
    monkeypatch.setattr(ref, "QUERY_BLOCK", 32)
    s, h, h_kv, hd = 80, 4, 2, 16
    q, k, v = (jax.random.normal(key, (s, n, hd), F32) for key, n in zip(
        jax.random.split(jax.random.PRNGKey(window), 3), (h, h_kv, h_kv)))

    def whole(q, k, v):
        k, v = (jnp.repeat(a, h // h_kv, axis=1) for a in (k, v))
        i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
        keep = (j <= i) & ((i - j < window) if window else True)
        scores = jnp.einsum("ihd,jhd->hij", q, k) / jnp.sqrt(F32(hd))
        return jnp.einsum("hij,jhd->ihd", jax.nn.softmax(
            jnp.where(keep[None], scores, -jnp.inf), axis=-1), v)

    blocks = lambda q, k, v: ref.attention(q, k, v, window,
                                           ref.MATMULS["f32"])
    _close(blocks(q, k, v), whole(q, k, v), 1e-5)
    grads = lambda f: jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))),
                               argnums=(0, 1, 2))(q, k, v)
    _tree_close(grads(blocks), grads(whole), 1e-5)


# --------------------------------------- (a) one rank, every expert held

def test_logits_match_the_reference():
    tokens = _tokens()
    _close(jax.jit(lambda p, t: T.forward(TCFG1, p, t))(_params(), tokens),
           ref.logits(CFG1, _params(), tokens))


def test_a_step_matches_the_reference_on_one_rank():
    """Loss, every leaf's gradient norm as the benchmark's family takes
    it, and the stepped parameters leaf by leaf."""
    tokens = _tokens()
    loss_r, new_r, norms = ref.step(CFG1, _params(), tokens, LR)
    loss_p, new_p, stats = family.build_train_step(
        TCFG1, _mesh(1), 2, LR, dp=False)(
            jax.tree.map(jnp.copy, _params()), tokens)
    assert abs(float(loss_p[0]) - loss_r) <= TOL * loss_r
    _tree_close(new_p, new_r)
    grads = family.build_grad_norms(TCFG1, _mesh(1), 2, dp=False)(
        _params(), tokens)
    assert _worst(grads, jax.tree.leaves(norms)) <= TOL
    # every (token, choice) row went through an expert held here
    assert np.asarray(stats["moe_rows"]).sum(axis=1).tolist() \
        == [2 * 80 * 2] * 4
    assert "ep_rows_sent" not in stats


# ----------------------- (b) four ranks, 2 of 8 experts a rank: the share

def _on_four(params):
    """The tree as the cell lays it out: a layer's expert leaves over the
    mesh on their expert axis."""
    mesh = _mesh(RANKS)
    return jax.device_put(jax.tree.map(jnp.copy, params), jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        family.param_specs(mesh, params)))


def test_the_four_shares_are_the_uncut_layer():
    """The test that ties the shares to the model: four ranks, each with
    its own sequence and 2 of the 8 experts, against the reference's
    step of the WHOLE batch with every expert: loss (the same on every
    rank), every leaf's gradient (an expert's on its owner), the stepped
    parameters."""
    tokens = _tokens(shape=(RANKS, 80))
    loss_r, new_r, norms = ref.step(CFG1, _params(), tokens, LR)
    loss_p, new_p, stats = family.build_train_step(
        TCFG4, _mesh(RANKS), 1, LR, dp=True)(_on_four(_params()), tokens)
    loss_p = np.asarray(loss_p)
    assert loss_p.shape == (RANKS,) and loss_p.max() == loss_p.min()
    assert abs(float(loss_p[0]) - loss_r) <= TOL * loss_r
    _tree_close(new_p, new_r)
    w1 = new_p["blocks"][0]["experts"]["w1"]
    assert w1.sharding.spec == P("mpi") and w1.shape[0] == 8
    grads = family.build_grad_norms(TCFG4, _mesh(RANKS), 1, dp=True)(
        _on_four(_params()), tokens)
    assert _worst(grads, jax.tree.leaves(norms)) <= TOL
    # the counters: a rank sent all its 160 rows, all of them arrived
    sent = np.asarray(stats["ep_rows_sent"])
    assert sent.shape == (4, RANKS) and (sent.sum(axis=1) == 160).all()
    assert np.asarray(stats["ep_rows_received"]).sum(axis=1).tolist() \
        == [RANKS * 160] * 4
    assert int(np.asarray(stats["ep_overflow_rounds"]).sum()) == 0


def test_make_params_lays_the_experts_over_the_mesh():
    repl = NamedSharding(_mesh(RANKS), P())
    p = family.make_params(CFG4, 7, F32, repl)
    _tree_close(p, _params(), 0.0)
    blk = p["blocks"][1]
    assert blk["experts"]["w2"].sharding.spec == P("mpi")
    assert blk["experts"]["router"].sharding.spec == P()
    assert blk["mixer"]["wqkv"].sharding.spec == P()


def test_the_gradient_program_is_compiled_beside_the_step():
    """What a run's time on the chip rests on: compiling the family's
    step compiles the gradient program too (on a thread, beside it), and
    the jitted function the harness is handed behind the window then
    runs without compiling anything."""
    from benchmarks import common

    mesh = _mesh(RANKS)
    repl = NamedSharding(mesh, P())
    params = family.make_params(CFG4, 3, F32, repl)
    tokens = jax.device_put(np.asarray(_tokens(shape=(RANKS, 80))), repl)
    counter = common.CompileCounter()
    step = family.build_train_step(TCFG4, mesh, 1, 0.031, dp=True)
    step.lower(params, jax.ShapeDtypeStruct(
        tokens.shape, tokens.dtype, sharding=repl)).compile()
    assert counter.count == 2
    norms = family.build_grad_norms(TCFG4, mesh, 1, True)(params, tokens)
    assert counter.count == 2
    assert norms.shape == (len(jax.tree.leaves(params)),)


def test_a_mesh_of_another_size_is_refused():
    with pytest.raises(ValueError, match="over 4 chips; the mesh has 2"):
        family.build_train_step(TCFG4, _mesh(2), 1, LR, dp=True)


# ------------- (c) the exchange under any routing: even, with gaps, collapsed

def _layer(ranks: int, spec, params, x, lower=False):
    """``(y, gradient, counts)`` of ``sum(sin(layer(x)))`` with the layer
    over ``ranks`` ranks: ``x`` (ranks, T, d), a rank's own rows; the
    gradient towards the parameters (that of the replicated leaves summed
    over the ranks) and, under ``"x"``, towards the rows.  ``lower``: the
    program lowered for a TPU, as text, in the place of its results."""
    mesh = _mesh(ranks)
    comm = mpi.comm_from_mesh(mesh, "mpi")
    specs = {k: P("mpi") if k in ("w1", "w2") else P() for k in params}

    def body(p, x):
        def loss(p, x):
            y, c = moe.exchanged_experts_ffn(x[0], p, spec, comm)
            return jnp.sum(jnp.sin(y)), (y, c)

        (_, (y, c)), (g, g_x) = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(p, x)
        g = {k: v if specs[k] == P("mpi") else jax.lax.psum(v, "mpi")
             for k, v in g.items()}
        return y[None], dict(g, x=g_x), \
            jax.tree.map(lambda a: jnp.asarray(a)[None], c)

    run = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(specs, P("mpi")),
        out_specs=(P("mpi"), dict(specs, x=P("mpi")), P("mpi")),
        check_vma=False))
    if lower:
        return run.trace(params, x).lower(
            lowering_platforms=("tpu",)).as_text()
    return run(params, x)


def _exchange_case(routing: str):
    """``(whole, share, params, x)`` at 4 ranks x 24 tokens of 16, 8
    experts top-2, two a rank.  ``"even"``: the seeded router.
    ``"collapsed"``: a selection bias sends every token to rank 0's two
    experts.  ``"gaps"``: the router reads a token's first 8 entries as
    its scores, rank 0's tokens never choose experts 0 and 1 (owner 0
    gets no row from sender 0: a buffer that is all tail), rank 1's never
    1 and 2 (an owner's LAST run of a sender's rows empty before the
    tail, another's FIRST)."""
    d, tokens = 16, 24
    whole = moe.Experts(8, 2, 8, 0, 8, score="softmax", act="reglu")
    share = dataclasses.replace(whole, n_held=2)
    p = moe.init_experts(jax.random.PRNGKey(0), whole, d, jnp.float64)
    x = jax.random.normal(jax.random.PRNGKey(1), (RANKS, tokens, d),
                          jnp.float64)
    if routing == "collapsed":
        p["bias"] = p["bias"].at[:2].set(10.0)
    if routing == "gaps":
        p["router"] = jnp.eye(d, 8, dtype=jnp.float64)
        x = x.at[0, :, 0:2].add(-10.0).at[1, :, 1:3].add(-10.0)
    return whole, share, p, x


@pytest.mark.parametrize("routing", ["even", "gaps", "collapsed"])
def test_no_row_is_dropped_whatever_the_routing(routing, monkeypatch):
    """A round's buffer of 1.25 even shares in tiles of 4 rows.  The
    owner's grouped products take the buffer as it arrived, a sender's
    runs by expert and then its tail: under an even routing, under one
    that leaves runs of no rows between them and a sender's whole buffer
    tail, and under a selection bias that sends every token to rank 0's
    two experts, so that the other ranks' buffers overflow threefold, the
    rounds behind the first run (every sender's tail empty at rank 0,
    whole at the others) and the counter says how many: the result and
    every gradient (towards the rows, both matrices and the router) are
    the one-rank layer's of all the rows."""
    monkeypatch.setattr(moe, "_ROW_TILE", 4)
    whole, share, p, x = _exchange_case(routing)
    tokens = x.shape[1]

    def plain(p, x):
        ys = [moe.held_experts_ffn(row, p, whole)[0] for row in x]
        return sum(jnp.sum(jnp.sin(y)) for y in ys), jnp.stack(ys)

    (_, y_plain), (g_plain, g_x) = jax.value_and_grad(
        plain, argnums=(0, 1), has_aux=True)(p, x)
    y, g, counts = _layer(RANKS, share, p, x)
    _close(y, y_plain, 1e-12)
    _close(g["x"], g_x, 1e-12)
    for leaf in ("router", "w1", "w2"):
        _close(g[leaf], g_plain[leaf], 1e-12)
    cap = moe._exchange_rows(tokens * 2, RANKS)
    assert cap == 16
    rounds, sent = np.asarray(counts["rounds"]), np.asarray(counts["sent"])
    assert (sent.sum(axis=1) == tokens * 2).all()
    if routing == "collapsed":
        assert (sent[:, 0] == tokens * 2).all() and rounds.tolist() == [2] * 4
        assert np.asarray(counts["rows"])[0].sum() == RANKS * tokens * 2
    else:
        assert rounds.tolist() == [int(sent.max() > cap)] * 4
    if routing == "gaps":
        chosen = np.asarray(jax.vmap(
            lambda r: moe.route_experts(r, p, whole)[0])(x))
        assert sent[0, 0] == 0 and not np.isin(chosen[1], (1, 2)).any()
        assert np.isin(chosen[2:], (0, 1, 2)).any()
    assert np.asarray(counts["padding"]).tolist() \
        == [(rounds[0] + 1) * RANKS * cap - tokens * 2] * RANKS


def test_the_owner_permutes_no_buffer_and_keeps_the_grouped_products(
        monkeypatch):
    """The layer with its gradient, lowered for a TPU: 14 grouped
    products, as many as when the owner regrouped the rows by expert
    first (round 0: two forward, four backward; the one body of the later
    rounds: two forward, and two recomputed and four in its backward
    turn), which the benchmark's count of a step's ``ragged-dot`` events
    rests on; and no gather that reads the ``(R * C, d)`` buffer into
    another of ``R * C`` rows, of which there were ten.  What reads the
    buffer still is the sender's own: its returned rows summed at their
    tokens, and the adjoint of laying them out."""
    monkeypatch.setattr(moe, "_ROW_TILE", 4)
    _, share, p, x = _exchange_case("even")
    text = _layer(RANKS, share, p, x, lower=True)
    assert text.count('"chlo.ragged_dot"(') == 14
    buffer = RANKS * moe._exchange_rows(x.shape[1] * 2, RANKS)
    rows = f"tensor<{buffer}x{x.shape[2]}xf64>"
    gathers = re.findall(
        r'"stablehlo.gather"\(.*: \((tensor<[^>]*>), tensor<[^>]*>\) -> '
        r"(tensor<[^>]*>)", text)
    assert (rows, rows) not in gathers
    assert sum(src == rows for src, _ in gathers) == 5


def test_held_experts_ffn_exchanges_over_a_communicator():
    """The one entry: with a communicator of four ranks
    ``held_experts_ffn`` is the whole layer, its counts the exchange's;
    with one of one rank it is the one-rank path to the bit."""
    d = 16
    whole = moe.Experts(8, 2, 8, 0, 8, score="softmax", act="reglu")
    share = dataclasses.replace(whole, n_held=2)
    p = moe.init_experts(jax.random.PRNGKey(0), whole, d, jnp.float64)
    x = jax.random.normal(jax.random.PRNGKey(1), (RANKS, 24, d), jnp.float64)
    mesh = _mesh(RANKS)
    comm = mpi.comm_from_mesh(mesh, "mpi")
    specs = {k: P("mpi") if k in ("w1", "w2") else P() for k in p}

    def body(p, x):
        y, rows, zero, overflow = moe.held_experts_ffn(x[0], p, share, comm)
        return y[None], rows[None]

    y, rows = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(specs, P("mpi")),
        out_specs=(P("mpi"), P("mpi")), check_vma=False))(p, x)
    _close(y, jnp.stack([moe.held_experts_ffn(r, p, whole)[0] for r in x]),
           1e-12)
    assert int(np.asarray(rows).sum()) == RANKS * 24 * 2

    class One:
        size = 1

    alone, with_one = (jax.jit(lambda p, x, c=c: moe.held_experts_ffn(
        x, p, whole, c)[0]).lower(p, x[0]).as_text() for c in (None, One()))
    assert alone == with_one


# --------------------------------- (d) the router reads the layer's input

def _spec_with(**changes):
    layers = tuple(dataclasses.replace(s, **changes) for s in TCFG1.layers)
    return dataclasses.replace(TCFG1, layers=layers)


def _gap(cfg, params=None) -> float:
    """How far a program's logits lie from the reference's, in the
    reference's norm."""
    tokens = _tokens()
    got = np.asarray(jax.jit(lambda p, t: T.forward(cfg, p, t))(
        params or _params(), tokens), np.float64)
    want = np.asarray(ref.logits(CFG1, _params(), tokens), np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def test_a_router_behind_the_attention_fails_the_comparison():
    assert _gap(TCFG1) <= TOL
    assert _gap(_spec_with(route_on="")) > 10 * TOL


def test_route_on_is_checked_and_the_serving_walk_refuses_it():
    held = TCFG1.layers[0].ffn
    with pytest.raises(ValueError, match="route_on"):
        dataclasses.replace(TCFG1, layers=tuple(
            dataclasses.replace(s, route_on="output") for s in TCFG1.layers))
    with pytest.raises(ValueError, match="before its mixer"):
        T.TransformerConfig(
            vocab=8, d_model=8, n_heads=2, n_layers=1, d_ff=8, max_seq=8,
            norm="rmsnorm", layers=(T.LayerSpec(
                mixer=None, ffn=held, only="ffn", route_on="input"),))
    with pytest.raises(mpi.CommError, match="route_on"):
        serve_kv.validate_tp(TCFG1, 1)


# ----------------------------------------- (e) reglu and norm_eps, alone

def test_reglu_is_relu_of_the_gate_times_up():
    d, f = 6, 4
    spec = moe.Experts(2, 2, f, 0, 2, score="softmax", act="reglu")
    p = moe.init_experts(jax.random.PRNGKey(0), spec, d, jnp.float64)
    assert p["w1"].shape == (2, d, 2 * f)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, d), jnp.float64)
    y, rows, _, _ = moe.held_experts_ffn(x, p, spec)
    chosen, w = moe.route_experts(x, p, spec)
    want = jnp.zeros_like(x)
    for e in range(2):
        gate, up = jnp.split(x @ p["w1"][e], 2, axis=-1)
        weight = jnp.sum(jnp.where(chosen == e, w, 0), axis=1, keepdims=True)
        want = want + weight * ((jnp.maximum(gate, 0) * up) @ p["w2"][e])
    _close(y, want, 1e-12)
    assert rows.tolist() == [5, 5]
    with pytest.raises(ValueError, match="unknown expert activation"):
        dataclasses.replace(spec, act="geglu")
    assert _gap(_spec_with(ffn=dataclasses.replace(
        TCFG1.layers[0].ffn, act="swiglu"))) > 10 * TOL


def test_norm_eps_is_the_configurations():
    x = 1e-3 * jax.random.normal(jax.random.PRNGKey(0), (3, 8), jnp.float64)
    p = {"scale": jnp.ones((8,), jnp.float64)}
    for eps in (1e-6, 1e-5):
        cfg = dataclasses.replace(TCFG1, norm_eps=eps)
        _close(T._norm(cfg, x, p),
               x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps), 1e-12)
    assert TCFG1.norm_eps == 1e-6
    assert T.TransformerConfig(vocab=8, d_model=8, n_heads=2, n_layers=1,
                               d_ff=8, max_seq=8).norm_eps == 1e-5
    # rows of 1e-3: the published 1e-6 against the old constant
    small = jax.tree.map(jnp.copy, _params())
    small["embed"] = 1e-3 * small["embed"]

    def logits(cfg):
        return np.asarray(jax.jit(lambda p, t: T.forward(cfg, p, t))(
            small, _tokens()), np.float64)

    want = np.asarray(ref.logits(CFG1, small, _tokens()), np.float64)
    gap = lambda got: np.linalg.norm(got - want) / np.linalg.norm(want)
    assert gap(logits(TCFG1)) <= TOL
    assert gap(logits(dataclasses.replace(TCFG1, norm_eps=1e-5))) > 10 * TOL


def test_only_a_rank_s_own_experts_are_left_out_of_the_average():
    paths = {jax.tree_util.keystr(path): T.held_expert_leaf(path)
             for path, _ in jax.tree_util.tree_flatten_with_path(
                 _params())[0]}
    own = sorted(k for k, v in paths.items() if v)
    assert own == sorted(f"['blocks'][{i}]['experts']['{w}']"
                         for i in range(4) for w in ("w1", "w2"))

