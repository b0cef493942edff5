"""``held_experts_ffn`` on a prefix of the sorted pairs: the layer works
on about twice the held experts' even share of the (token, choice)
pairs and takes the rows behind that prefix only where a held expert's
row lies there (``jax.lax.cond``), so every routing stays exact.

Held against two things: the all-rows path (the same function where the
prefix is the whole buffer), and a copy of the function as it stood
before the prefix, kept here, which the bypassed shapes (a buffer under
the row constant; half or more of the experts held) must equal bit for
bit.  The sizes here are under any sensible row constant, so the cases
that want a prefix shorter than the buffer lower the module's two
constants, the way the families' tests steer ``route_topk``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4torch_tpu.parallel import moe
from mpi4torch_tpu.serve import kv

F32 = jnp.float32
D, TOKENS = 16, 64


# ---------------------------------------- the function before the prefix

@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    return x[perm]


_permute_rows.defvjp(
    lambda x, perm, inverse: (x[perm], (perm, inverse)),
    lambda res, g: (g[res[1]], None, None))


@jax.custom_vjp
def _pair_rows(x, order, inverse, keep):
    k = order.shape[0] // x.shape[0]
    return jnp.where(keep, x[order // k], 0)


def _pair_rows_bwd(res, g):
    order, inverse, keep, T = res
    g = jnp.where(keep, g, 0)[inverse]
    return (jnp.sum(g.reshape(T, -1, g.shape[-1]), axis=1, dtype=g.dtype),
            None, None, None)


_pair_rows.defvjp(
    lambda x, order, inverse, keep: (
        _pair_rows(x, order, inverse, keep),
        (order, inverse, keep, x.shape[0])),
    _pair_rows_bwd)


def _all_rows_ffn(x, params, spec, live=None):
    """``parallel/moe.py:held_experts_ffn`` of commit bd1360b: every
    buffer has all ``top_k * T`` rows."""
    T, d = x.shape
    k, held = spec.top_k, spec.n_held
    chosen, weight = moe.route_topk(x, params["router"], params["bias"], k,
                                    spec.scale, score=spec.score,
                                    renorm=spec.renorm)
    local = chosen.reshape(-1) - spec.first_expert
    here = (local >= 0) & (local < held)
    if live is not None:
        here &= jnp.repeat(live, k)
    group = jnp.where(here, local, held)
    order = jnp.argsort(group, stable=True)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    rows = jnp.sum(jax.nn.one_hot(group, held, dtype=jnp.int32), axis=0)
    is_held = (group[order] < held)[:, None]

    expert = moe._EXPERT[spec.act]
    xs = _pair_rows(x @ params["down"] if spec.latent else x, order,
                    inverse, is_held)
    grouped = lambda a, w: jax.lax.ragged_dot(a, w, rows)
    ys = jnp.where(is_held, expert(xs, params["w1"], params["w2"], grouped),
                   0)
    ys = _permute_rows(ys, inverse, order).reshape(T, k, xs.shape[-1])
    y = jnp.sum(ys.astype(weight.dtype) * weight[..., None], axis=1)
    zero_pairs = 0
    if spec.n_zero:
        is_zero = chosen >= spec.n_experts
        y = y + jnp.sum(jnp.where(is_zero, weight, 0), axis=1,
                        keepdims=True) * x.astype(weight.dtype)
        if live is not None:
            is_zero &= live[:, None]
        zero_pairs = jnp.sum(is_zero, dtype=jnp.int32)
    y = y.astype(x.dtype)
    if spec.latent:
        y = y @ params["up"]
    if spec.n_shared:
        y = y + expert(x, params["shared_w1"], params["shared_w2"],
                       jnp.matmul)
    return y, rows, zero_pairs


# ------------------------------------------------------------ the cases

# 4 of 16 experts held and 4 chosen: an even share of a quarter, so the
# prefix is half the buffer, and a router that is steered can send every
# pair to a held expert.
CASES = {
    "plain": dict(),
    "shared": dict(n_shared=1),
    "zero_compute": dict(n_zero=3),
    "latent_relu2": dict(latent=8, act="relu2", n_shared=1, d_shared=24),
    "softmax_raw": dict(score="softmax", renorm=False),
}
LIVE = {"all": None, "some": np.arange(TOKENS) % 5 != 0}


def _spec(case, **over):
    return moe.Experts(**{**dict(n_experts=16, top_k=4, d_expert=8,
                                 first_expert=4, n_held=4), **CASES[case],
                          **over})


def _inputs(spec, steered: bool):
    p = moe.init_experts(jax.random.PRNGKey(0), spec, D, F32)
    if steered:
        held = slice(spec.first_expert, spec.first_expert + spec.n_held)
        p["bias"] = p["bias"].at[held].set(10.0)
    return p, jax.random.normal(jax.random.PRNGKey(1), (TOKENS, D), F32)


def _run(ffn, spec, p, x, live):
    """The layer's outputs, its counts and the gradients of a loss in
    every parameter and in ``x``, under ``jax.checkpoint`` and
    ``jax.jit`` as a training step takes them."""
    live = None if live is None else jnp.asarray(live)

    def loss(p, x):
        y, *counts = ffn(x, p, spec, live=live)
        mix = jnp.cos(jnp.arange(y.size, dtype=y.dtype)).reshape(y.shape)
        return jnp.sum(y * mix), (y, counts)

    (_, (y, counts)), grads = jax.jit(jax.value_and_grad(
        jax.checkpoint(loss), argnums=(0, 1), has_aux=True))(p, x)
    return y, counts, grads


def _close(a, b, tol=1e-5):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        assert np.linalg.norm(x - y) <= tol * max(np.linalg.norm(y), 1e-30)


@pytest.fixture
def short_prefix(monkeypatch):
    """A prefix shorter than the buffer at this file's sizes."""
    monkeypatch.setattr(moe, "_MIN_PAIRS", 0)
    monkeypatch.setattr(moe, "_ROW_TILE", 8)


@pytest.mark.parametrize("live", sorted(LIVE))
@pytest.mark.parametrize("steered", [False, True],
                         ids=["even", "every_pair_held"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_prefix_and_the_rest_are_the_whole_buffers_result(
        case, steered, live, short_prefix):
    """At an even routing the prefix holds every held row and nothing
    overflows; with every pair steered to a held expert the held rows
    are twice the prefix and the rest goes through the condition:
    outputs, counts and every gradient are the all-rows path's."""
    spec = _spec(case)
    pairs = spec.top_k * TOKENS
    assert moe._prefix_rows(pairs, spec) == pairs // 2
    p, x = _inputs(spec, steered)
    y, (rows, zero, overflow), grads = _run(
        moe.held_experts_ffn, spec, p, x, LIVE[live])
    y_all, (rows_all, zero_all), grads_all = _run(
        _all_rows_ffn, spec, p, x, LIVE[live])
    np.testing.assert_array_equal(rows, rows_all)
    assert int(zero) == int(zero_all)
    held_rows = int(rows.sum())
    if steered:
        tokens = TOKENS if LIVE[live] is None else int(LIVE[live].sum())
        # every choice a routed expert can take is a held one
        assert held_rows == tokens * min(spec.top_k, spec.n_held)
        assert held_rows > pairs // 2 and int(overflow) == 1
    else:
        assert 0 < held_rows <= pairs // 2 and int(overflow) == 0
    _close(y, y_all)
    _close(grads, grads_all)
    assert float(jnp.linalg.norm(grads[0]["router"])) > 0
    assert float(jnp.linalg.norm(grads[0]["w1"])) > 0


@pytest.mark.parametrize("bypass", ["under_the_row_constant",
                                    "half_the_experts_held"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_a_bypassed_shape_runs_the_function_as_it_stood(case, bypass,
                                                        monkeypatch):
    """Bit for bit, outputs and gradients: a buffer under the row
    constant, and a held share whose doubled even share is the buffer."""
    if bypass == "half_the_experts_held":
        monkeypatch.setattr(moe, "_MIN_PAIRS", 0)
        monkeypatch.setattr(moe, "_ROW_TILE", 8)
        spec = _spec(case, n_held=8)
    else:
        spec = _spec(case)
    assert moe._prefix_rows(spec.top_k * TOKENS, spec) == spec.top_k * TOKENS
    p, x = _inputs(spec, steered=False)
    y, (rows, zero, overflow), grads = _run(
        moe.held_experts_ffn, spec, p, x, LIVE["some"])
    y_was, (rows_was, zero_was), grads_was = _run(
        _all_rows_ffn, spec, p, x, LIVE["some"])
    assert int(overflow) == 0
    np.testing.assert_array_equal(rows, rows_was)
    assert int(zero) == int(zero_was)
    for got, want in zip(jax.tree.leaves((y, grads)),
                         jax.tree.leaves((y_was, grads_was)), strict=True):
        np.testing.assert_array_equal(got, want)


def test_rows_at_the_prefixs_edge(short_prefix):
    """Held rows of exactly the prefix do not overflow; one more does,
    and a held expert's group may straddle the edge."""
    spec = _spec("plain")
    p, x = _inputs(spec, steered=True)
    for tokens, want in ((TOKENS // 2, 0), (TOKENS // 2 + 1, 1)):
        live = np.arange(TOKENS) < tokens     # held rows: 4 a live token
        y, (rows, _, overflow), grads = _run(
            moe.held_experts_ffn, spec, p, x, live)
        assert int(rows.sum()) == 4 * tokens and int(overflow) == want
        y_all, _, grads_all = _run(_all_rows_ffn, spec, p, x, live)
        _close(y, y_all)
        _close(grads, grads_all)


@pytest.mark.parametrize("steered", [False, True],
                         ids=["even", "every_pair_held"])
@pytest.mark.parametrize("held", [4, 3], ids=["two_turns", "a_last_turn_cut"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_a_serving_program_takes_the_rows_a_prefix_at_a_time(
        case, held, steered, short_prefix):
    """Where nothing is differentiated the layer is one body in a loop,
    a prefix's rows a turn while a held row is left: its result is the
    all-rows path's, also where the prefix does not divide the pairs (3
    of 16 held: 96 of 256, the last turn's rows padded) and where every
    turn runs."""
    spec = _spec(case, n_held=held)
    pairs = spec.top_k * TOKENS
    prefix = moe._prefix_rows(pairs, spec)
    assert prefix == {4: 128, 3: 96}[held]
    p, x = _inputs(spec, steered)
    live = jnp.asarray(LIVE["some"])
    y, rows, zero, overflow = jax.jit(
        lambda p, x: moe.held_experts_ffn(x, p, spec, live=live))(p, x)
    y_all, rows_all, zero_all = jax.jit(
        lambda p, x: _all_rows_ffn(x, p, spec, live=live))(p, x)
    np.testing.assert_array_equal(rows, rows_all)
    assert int(zero) == int(zero_all)
    assert int(overflow) == (int(rows.sum()) > prefix) == steered
    _close(y, y_all)


# pairs, the spec's (experts, top_k, held), the prefix: the five expert
# cells' shapes (benchmarks/configs), a prefill and the decode step each
@pytest.mark.parametrize("pairs,experts,top_k,held,prefix", [
    (8 * 16384, 256, 8, 32, 32768),      # train_kda_8k, a step
    (8 * 4096, 256, 8, 16, 4096),        # serve_latent_4k, serve_dsa_16k
    (8 * 1024, 256, 8, 16, 1024),
    (8 * 32, 256, 8, 16, 8 * 32),        # a decode step: the whole buffer
    (12 * 512, 512, 12, 16, 512),        # serve_scmoe_1k: tiles of 512
    (12 * 2048, 512, 12, 16, 1536),
    (12 * 32, 512, 12, 16, 12 * 32),
    (22 * 256, 512, 22, 128, 3072),      # serve_ssm_chat
    (22 * 1024, 512, 22, 128, 11264),
    (22 * 128, 512, 22, 128, 22 * 128),
    (8 * 4096, 16, 8, 8, 8 * 4096),      # half held: the whole buffer
])
def test_the_prefix_follows_from_shapes_and_the_spec(pairs, experts, top_k,
                                                     held, prefix):
    spec = moe.Experts(experts, top_k, 8, first_expert=0, n_held=held)
    assert moe._prefix_rows(pairs, spec) == prefix
    assert prefix == pairs or prefix % moe._ROW_TILE == 0


def test_the_pieces_of_a_long_prompt_count_their_overflows(short_prefix,
                                                           monkeypatch):
    """A prefill whose expert layer runs in pieces: each piece is a call
    and overflows, or does not, on its own rows."""
    spec = _spec("shared")
    p, x = _inputs(spec, steered=True)
    monkeypatch.setattr(kv, "_EXPERT_ROWS", 16)
    live = jnp.asarray(np.arange(TOKENS) < 40)    # pieces 0, 1 and half of 2
    y, rows, _, overflow = kv._held_experts_in_pieces(x, p, spec, live)
    assert rows.shape == (4, spec.n_held)
    assert rows.sum(axis=1).tolist() == [64, 64, 32, 0]
    assert int(overflow) == 2                     # a piece's prefix: 32 rows
    _close(y, _all_rows_ffn(x, p, spec, live)[0])


@pytest.mark.parametrize("limit", [2 ** 40, 40_000, 9_000])
def test_a_table_is_gathered_in_column_pieces(limit, monkeypatch):
    """One piece, two and four (of whole lane tiles, the last one
    narrower): the same sums."""
    monkeypatch.setattr(moe, "_GATHER_PIECE_BYTES", limit)
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.standard_normal((24, 416)), F32)   # 39,936 B
    pieces = moe._column_pieces(table)
    assert [p.shape[1] for p in pieces] == {
        2 ** 40: [416], 40_000: [416], 9_000: [128, 128, 128, 32]}[limit]
    np.testing.assert_array_equal(jnp.concatenate(pieces, axis=1), table)
    at = jnp.asarray(rng.integers(-8, 32, (10, 4)), jnp.int32)
    weight = jnp.asarray(rng.standard_normal((10, 4)), F32)
    inside = np.asarray((at >= 0) & (at < 24))
    rows = np.where(inside[..., None], np.asarray(table)[np.clip(at, 0, 23)],
                    0)
    _close(moe._sum_by_choice(table, at), rows.sum(axis=1), 1e-6)
    _close(moe._sum_by_choice(table, at, weight),
           (rows * np.asarray(weight)[..., None]).sum(axis=1), 1e-6)
