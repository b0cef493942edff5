"""A stack whose layers differ: Kimi Delta Attention (the gated delta
rule, chunked) and latent attention without rotation as mixers, a dense
swiglu or a held share of sigmoid-routed top-k experts as FFN, stated as
a per-layer spec on ``TransformerConfig`` — the program against the
plain float32 reference of ``benchmarks/references/kimi_linear.py`` on
seeded weights at the configuration's rehearsal sizes (hidden 64, 4
heads of 16, 8 experts top-2 with 2 held, 5 layers in the published
pattern).

Tolerances.  Program and reference both compute in float32 here (the
reference by construction, the program because its parameters are), so
they differ by summation order alone: 2e-4 of a leaf's norm covers a
forward, a gradient and one step (the widest seen is 3e-5, the
embedding's gradient, a scatter-add of 160 rows).  Over three steps the
differences compound through the routing (a near-tied token's choice
may flip once the parameters differ in their last bits), so the
three-step change is held to 2e-3 (seen: 3.5e-4).  A mixer whose KDA
state is kept in bfloat16, or a layer whose router scores are, misses
2e-4 by an order of magnitude and more, which the last tests pin down.
"""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import mpi4torch_tpu as mpi  # noqa: E402
from benchmarks.families import kimi_linear as family  # noqa: E402
from benchmarks.references import kimi_linear as ref  # noqa: E402
from benchmarks.run import merged  # noqa: E402
from mpi4torch_tpu.models import transformer as T  # noqa: E402
from mpi4torch_tpu.ops import kda  # noqa: E402
from mpi4torch_tpu.parallel import moe  # noqa: E402
from mpi4torch_tpu.serve import kv as serve_kv  # noqa: E402

F32 = jnp.float32
TOL = 2e-4
LR = 0.3


def _cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        cfg = json.load(f)
    return merged(cfg, cfg["rehearsal"])


CFG = _cfg()
PLAN = ref.plan(CFG)
TCFG = family.transformer_config(CFG, remat=True)
MM = ref.MATMULS["f32"]


@functools.lru_cache(maxsize=None)
def _params(seed=7):
    return family.make_params(CFG, seed, F32)


def _tokens(seed=1, shape=(2, 80)):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                              CFG["vocab_size"], dtype=jnp.int32)


def _x(seed=3, shape=(2, 80)):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             shape + (CFG["hidden_size"],), F32)


def _close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.linalg.norm(b), 1e-30)
    assert np.linalg.norm(a - b) <= tol * scale, \
        (np.linalg.norm(a - b), scale)


def _tree_close(a, b, tol=TOL):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        _close(x, y, tol)


# ------------------------------------------------------------------- KDA

def _kda_inputs(s, dtype=jnp.float64, rate=1.0, seed=0):
    b, h, dk, dv = 2, 3, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    return (unit(jax.random.normal(ks[0], (b, s, h, dk), dtype)),
            unit(jax.random.normal(ks[1], (b, s, h, dk), dtype)),
            jax.random.normal(ks[2], (b, s, h, dv), dtype),
            -rate * jax.nn.softplus(jax.random.normal(ks[3], (b, s, h, dk),
                                                      dtype)),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h), dtype)))


def _value_and_grads(fn, n_args):
    """(value, gradients of sum(sin(value)) in every argument), jitted."""
    return jax.jit(lambda *a: (fn(*a), jax.grad(
        lambda *b: jnp.sum(jnp.sin(fn(*b))), argnums=range(n_args))(*a)))


@pytest.mark.parametrize("s", [64, 100, 128])
def test_chunked_kda_equals_the_recurrence(s):
    """At sequence lengths that are and are not multiples of the chunk,
    values and every gradient, in float64."""
    args = _kda_inputs(s)
    out_c, grads_c = _value_and_grads(kda.kda_chunked, 5)(*args)
    out_r, grads_r = _value_and_grads(kda.kda_recurrent, 5)(*args)
    _close(out_c, out_r, 1e-12)
    for g_c, g_r in zip(grads_c, grads_r):
        _close(g_c, g_r, 1e-11)


def _kernel_inputs(s, dtype=F32, rate=1.0, seed=0, h=3):
    """Heads the kernel takes (128 channels), in ``dtype``; the decay
    and the write strength in float32, as the mixer hands them."""
    b, d = 2, 128
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    normal = lambda key, *shape: jax.random.normal(key, shape, F32)
    return (unit(normal(ks[0], b, s, h, d)).astype(dtype),
            unit(normal(ks[1], b, s, h, d)).astype(dtype),
            normal(ks[2], b, s, h, d).astype(dtype),
            -rate * jax.nn.softplus(normal(ks[3], b, s, h, d)),
            jax.nn.sigmoid(normal(ks[4], b, s, h)))


def _gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_chunked_kda_survives_a_fast_decay(impl):
    """exp(-G) overflows float32 after 30 such tokens; measured from the
    middle of each block of 16 rows, nothing does, on the plain path (4
    heads of 16) and through the kernel (heads of 128, where the plain
    path itself stands 2e-5 off the float32 recurrence: the kernel is
    held to the plain path, and to no more of a gap than it has)."""
    if impl == "jnp":
        args = _kda_inputs(128, F32, rate=6.0)
        out = jax.jit(kda.kda_chunked)(*args)
        assert bool(jnp.all(jnp.isfinite(out)))
        _close(out, jax.jit(kda.kda_recurrent)(*args), 1e-5)
        return
    args = _kernel_inputs(128, rate=6.0)
    out, plain = (jax.jit(functools.partial(kda.kda_chunked, impl=i))(*args)
                  for i in ("pallas", "jnp"))
    assert bool(jnp.all(jnp.isfinite(out)))
    _close(out, plain, 1e-5)
    exact = jax.jit(kda.kda_recurrent)(*args)
    assert _gap(out, exact) <= 1.1 * _gap(plain, exact) + 1e-6


# Widest gap seen to the float32 recurrence: 9.5e-7 from the kernel and
# 6.9e-7 from the plain path in float32, 3.8e-3 and 3.4e-3 in bfloat16
# (the operands' rounding on the way into each product).
KERNEL_TOL = {F32: 5e-6, jnp.bfloat16: 6e-3}


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("s,block", [(64, None), (100, None), (128, None),
                                     (320, None), (320, 128)])
def test_the_forward_kernel_equals_both_oracles(s, block, dtype):
    """The kernel, interpreted, against the recurrence and the plain
    path: whole chunks and a tail, one token block and three (the last
    of them half past the sequence's end)."""
    args = _kernel_inputs(s, dtype)
    if block is None:
        run = functools.partial(kda.kda_chunked, impl="pallas")
    else:
        run = lambda *a: kda._pallas_forward(
            *a, 128 ** -0.5, kda.CHUNK, interpret=True, block=block)
    out = jax.jit(run)(*args)
    out_j = jax.jit(functools.partial(kda.kda_chunked, impl="jnp"))(*args)
    exact = jax.jit(kda.kda_recurrent)(*(a.astype(F32) for a in args))
    assert out.dtype == out_j.dtype and out.shape == out_j.shape
    _close(out, exact, KERNEL_TOL[dtype])
    _close(out, out_j, KERNEL_TOL[dtype])


@pytest.mark.parametrize("chunk", [32, 128])
def test_the_kernel_takes_other_chunks(chunk):
    """Four chunks of 32 side by side along the lanes, or one of 128:
    the same rule."""
    args = _kernel_inputs(200)
    out = jax.jit(functools.partial(kda.kda_chunked, chunk=chunk,
                                    impl="pallas"))(*args)
    _close(out, jax.jit(kda.kda_recurrent)(*args), KERNEL_TOL[F32])


def test_the_kernels_inverse_holds_where_keys_are_alike():
    """One key all chunk long, written at full strength with no decay:
    ``I + A`` is ones below the diagonal, its inverse two diagonals.
    Block forward substitution keeps float32's digits there (4e-7, as
    ``solve_triangular`` on the plain path); the sum of powers the
    inverse could also be built from read 2e-4."""
    q, k, v, g, beta = _kernel_inputs(128, h=2)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    args = (k, k, v, jnp.zeros_like(g), jnp.ones_like(beta))
    out = jax.jit(functools.partial(kda.kda_chunked, impl="pallas"))(*args)
    _close(out, jax.jit(kda.kda_recurrent)(*args), KERNEL_TOL[F32])


# The kernels' gradients against the plain path's (another program for
# the same function: the chunks run again and transposed by autodiff).
# Widest gap seen between the two: 3.6e-6 in float32 (the decay's, at
# chunks of 128), 6.4e-3 in bfloat16, where each rounds other operands
# on the way into its products and stands 3e-3 to 8e-3 off the float32
# recurrence's gradient.  So bfloat16 is also held to that second
# oracle: no gradient of the kernels' may lie further from it than 1.5
# times the plain path's does.
GRAD_TOL = {"float32": 1e-5, "bfloat16": 2e-2}


def _kernels_gradients_hold(args, chunk=kda.CHUNK, block=None, tol=None,
                            skip=()):
    """The five gradients of the kernels (interpreted), for one
    cotangent, against the plain path's and the float32 recurrence's
    under ``jax.vjp``.  With a ``block`` the backward is called as the
    ``custom_vjp`` calls it, but on token blocks of that many."""
    tol = GRAD_TOL[args[0].dtype.name] if tol is None else tol
    cot = jnp.cos(args[2].astype(F32))
    pull = lambda run: jax.jit(lambda *a: jax.vjp(run, *a)[1](cot))
    if block is None:
        got = pull(functools.partial(kda.kda_chunked, chunk=chunk,
                                     impl="pallas"))(*args)
    else:
        got = jax.jit(lambda *a: kda._pallas_backward(
            *a, cot, 128 ** -0.5, chunk, interpret=True, block=block))(*args)
    plain = pull(functools.partial(kda.kda_chunked, chunk=chunk,
                                   impl="jnp"))(*args)
    exact = pull(kda.kda_recurrent)(*(a.astype(F32) for a in args))
    for name, dx, dx_j, dx_r, x in zip("qkvgb", got, plain, exact, args,
                                       strict=True):
        assert dx.dtype == x.dtype and dx.shape == x.shape, name
        assert bool(jnp.all(jnp.isfinite(dx))), name
        if name in skip:
            continue
        _close(dx, dx_j, tol)
        assert _gap(dx, dx_r) <= 1.5 * _gap(dx_j, dx_r) + GRAD_TOL["float32"], name


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("s,heads", [(64, 16), (100, 16), (100, 3)])
def test_the_kernels_gradients_are_the_plain_paths(s, heads, dtype):
    """Through the ``custom_vjp`` every gradient for the same cotangent
    is the plain path's within `GRAD_TOL`: the backward is two kernels
    of its own (the chunks' adjoint in fast memory, the state's in
    scratch), the same function by other arithmetic.  The model's heads
    come in groups (16 here, two of the plain path's) or not (3: the
    column of ``beta`` a head picks is not the first)."""
    _kernels_gradients_hold(_kernel_inputs(s, dtype, h=heads))


@pytest.mark.parametrize("s,block,chunk", [
    (192, None, 64), (200, 128, 64), (320, 128, 64), (512, 256, 64),
    (200, None, 32), (200, None, 128)],
    ids=["half_a_turn", "a_tail_in_the_last_block",
         "a_block_half_past_the_end", "two_whole_blocks",
         "chunks_of_32", "chunks_of_128"])
def test_the_reverse_walk_crosses_blocks_turns_and_tails(s, block, chunk):
    """What a walk from the last token to the first can get wrong: a
    sequence that ends inside a turn (192: the last turn's second chunk
    is no token), inside a chunk of the last block (200 in blocks of
    128) or half a block early (320 in blocks of 128: what lies past
    the end gets no gradient and gives none); the state's adjoint
    carried from block to block and from turn to turn (512 in blocks of
    256, nothing masked); four chunks a turn, or one."""
    _kernels_gradients_hold(_kernel_inputs(s, h=2), chunk=chunk, block=block)


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_kernels_gradients_survive_a_fast_decay(dtype):
    """The decay of `test_chunked_kda_survives_a_fast_decay`: exponents
    at the cap on both sides of a block's middle row, Gram entries past
    the diagonal that overflow and are selected out.  Every gradient is
    finite and the plain path's within ten times `GRAD_TOL` (seen:
    1.9e-5 and 2.4e-2, the decay's, whose terms near the cap are
    ``e^80`` times their sum; the plain path's own gap to the
    recurrence there is 1.8e-5 and 3.3e-2)."""
    _kernels_gradients_hold(_kernel_inputs(128, dtype, rate=6.0),
                            tol=10 * GRAD_TOL[jnp.dtype(dtype).name])


def test_the_kernels_gradients_hold_where_keys_are_alike():
    """The inputs of `test_the_kernels_inverse_holds_where_keys_are_alike`
    (``I + A`` ones below the diagonal): the inverse's adjoint, ``T^T
    [dw | du]`` and ``dA``, keeps float32's digits (seen against the
    plain path: 2.3e-5 in ``k``, 3.8e-5 in ``beta``, and closer to the
    recurrence than it in both).  The decay's gradient there is a
    difference of terms that cancel to a hundredth of their rounding
    (the plain path's lies 145 times its norm off the recurrence's, the
    kernels' 62 times): held to be finite."""
    q, k, v, g, beta = _kernel_inputs(128, h=2)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    _kernels_gradients_hold((k, k, v, jnp.zeros_like(g),
                             jnp.ones_like(beta)), tol=1e-4, skip="g")


@pytest.mark.parametrize("case,dtype,d,takes", [
    ("the_cells_shape", jnp.bfloat16, 128, True),
    ("float32", F32, 128, True),
    ("float64", jnp.float64, 128, False),
    ("the_rehearsals_heads", jnp.bfloat16, 16, False)])
def test_which_shapes_the_kernel_takes(monkeypatch, case, dtype, d, takes):
    """By what the call can see in its operands: type and head size on a
    TPU, nothing anywhere else."""
    like = jax.ShapeDtypeStruct((2, 8192, 32, d), dtype)
    assert not kda.uses_kernel(like, like, like)            # the CPU
    monkeypatch.setattr(kda, "_on_tpu", lambda: True)
    assert kda.uses_kernel(like, like, like) == takes
    if not takes:
        with pytest.raises(ValueError, match="kernel takes"):
            kda.kda_chunked(*_kda_inputs(64, dtype)[:3], None, None,
                            impl="pallas")
    with pytest.raises(ValueError, match="unknown impl"):
        kda.kda_chunked(like, like, like, like, like, impl="mosaic")


def _kernel_calls(jaxpr) -> dict:
    """How many ``pallas_call`` equations of each name a jaxpr and
    everything it holds have."""
    n = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n[eqn.params["name"]] = n.get(eqn.params["name"], 0) + 1
        for sub in jax.tree.leaves(
                list(eqn.params.values()),
                is_leaf=lambda x: hasattr(x, "eqns") or hasattr(x, "jaxpr")):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                for name, m in _kernel_calls(sub).items():
                    n[name] = n.get(name, 0) + m
    return n


@pytest.mark.parametrize("policy,calls", [(T._SAVED_IN_REMAT, 1), (None, 2)],
                         ids=["kda_out_saved", "nothing_saved"])
def test_a_rematerialised_mixer_runs_the_kernel_once(monkeypatch, policy,
                                                     calls):
    """The ``custom_vjp`` keeps its inputs and nothing of the kernel's
    output, and the mixer's output is saved by name: the gradient of a
    rematerialised mixer holds one call of the forward kernel, and one
    of each backward kernel.  (With nothing saved the region's backward
    needs the rule's output again and runs the forward kernel twice:
    the count sees both.)"""
    wide = dict(CFG, linear_attn_config=dict(
        CFG["linear_attn_config"], head_dim=128, num_heads=2))
    p = family.make_layer(jax.random.PRNGKey(0), wide, 0, F32)["mixer"]
    spec = T.KDA(2, 128)
    monkeypatch.setattr(T, "kda_chunked", functools.partial(
        kda.kda_chunked, impl="pallas"))
    region = jax.checkpoint(lambda p_, x_: T._kda_mixer(spec, p_, x_),
                            policy=policy)
    grad = jax.grad(lambda p_, x_: jnp.sum(region(p_, x_)))
    assert _kernel_calls(jax.make_jaxpr(grad)(p, _x()).jaxpr) == {
        kda.KERNEL_NAME: calls,
        **{name: 1 for name in kda.BACKWARD_KERNEL_NAMES}}
    jax.jit(grad).lower(p, _x())


def test_the_uniform_blocks_names_do_nothing_in_a_specs_regions(
        monkeypatch, capsys):
    """What the rematerialised regions of this stack keep is what they
    kept before a uniform block's products and the flash kernel's
    ``(out, lse)`` had names: ``kda_out`` of each KDA layer, every
    region's output and nothing by another name, though the latent
    attention's blocks run the named kernel and the dense FFN the named
    product.  Read with the names in place and with every name but
    ``kda_out`` taken out of both modules."""
    from jax.ad_checkpoint import checkpoint_name, print_saved_residuals

    from mpi4torch_tpu.ops import flash

    def saved():
        print_saved_residuals(lambda p: T.lm_loss(TCFG, p, _tokens()),
                              _params())
        return capsys.readouterr().out

    # a line: the residual's type, then where it comes from
    kept = lambda text: [line.split(" ", 1)[0] for line in text.splitlines()]
    named = saved()
    only_kda = lambda x, name: checkpoint_name(x, name) \
        if name == "kda_out" else x
    monkeypatch.setattr(T, "checkpoint_name", only_kda)
    monkeypatch.setattr(flash, "checkpoint_name", only_kda)
    assert kept(named) == kept(saved())
    assert named.count("_kda_mixer") == sum(
        isinstance(s.mixer, T.KDA) for s in TCFG.layers) > 0
    for name in T._KEPT_PRODUCTS + flash.RESIDUAL_NAMES:
        assert f"named '{name}'" not in named


# ---------------------------------------------------------------- mixers

@pytest.mark.parametrize("layer,mixer,reference", [
    (0, T._kda_mixer, ref.kda),
    (3, lambda spec, p, y: T._mla_mixer(TCFG, spec, p, y, None), ref.mla)])
def test_mixer_matches_the_reference(layer, mixer, reference):
    spec = TCFG.layers[layer].mixer
    p, x = _params()["blocks"][layer]["mixer"], _x()
    out_p, grads_p = _value_and_grads(
        lambda p_, x_: mixer(spec, p_, x_), 2)(p, x)
    out_r, grads_r = _value_and_grads(
        lambda p_, x_: reference(PLAN, p_, x_, MM), 2)(p, x)
    _close(out_p, out_r)
    _tree_close(grads_p, grads_r)


def test_blockwise_causal_attention_is_one_call_in_blocks():
    """The triangle of block calls MLA uses where one flash call does not
    fit the kernels: values and gradients of one call, in float64."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (2, 96, 3, 24), jnp.float64)
               for kk in ks)
    out_b, grads_b = _value_and_grads(
        lambda *a: T._blockwise_causal_attention(*a, 32), 3)(q, k, v)
    out_1, grads_1 = _value_and_grads(
        lambda *a: T.flash_attention(*a, causal=True), 3)(q, k, v)
    _close(out_b, out_1, 1e-12)
    _tree_close(grads_b, grads_1, 1e-11)


def _flat(y):
    return y.reshape(-1, y.shape[-1])


def test_experts_match_the_reference():
    spec = TCFG.layers[1].ffn
    p, x = _params()["blocks"][1]["experts"], _x()
    out_p, grads_p = _value_and_grads(
        lambda p_, x_: moe.held_experts_ffn(_flat(x_), p_, spec)[0], 2)(p, x)
    out_r, grads_r = _value_and_grads(
        lambda p_, x_: _flat(ref.experts(PLAN, p_, x_, MM)), 2)(p, x)
    _close(out_p, out_r)
    _tree_close(grads_p, grads_r)


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of all four shares (2 of 8 experts each), with
    the shared expert counted once, are the reference's whole layer."""
    x = _flat(_x())
    width, held = PLAN.router_width, PLAN.held
    shares = [family._expert_leaves(jax.random.PRNGKey(c), CFG, F32)
              for c in range(width // held)]
    whole = dict(shares[0])
    for name in ("w1", "w2"):
        whole[name] = jnp.concatenate([s[name] for s in shares], axis=0)
    base = TCFG.layers[1].ffn
    total = ref.swiglu(x, whole["shared_w1"], whole["shared_w2"], MM)
    for c, share in enumerate(shares):
        spec = moe.Experts(base.n_experts, base.top_k, base.d_expert,
                           first_expert=c * held, n_held=held,
                           n_shared=0, scale=base.scale)
        # every share routes with the same router and selection bias
        p = dict(share, router=whole["router"], bias=whole["bias"])
        total = total + moe.held_experts_ffn(x, p, spec)[0]
    _close(total, ref.experts(PLAN, whole, x, MM, first=0, held=width))


@pytest.mark.parametrize("buffer", ["whole", "prefix"])
def test_no_row_is_dropped_under_skew(buffer, monkeypatch):
    """A selection bias that sends every token to held expert 0, which
    then takes half of all rows (top-2): the row counts hold every row
    routed to a held expert and the result is still the reference's.
    With a prefix shorter than the buffer (the module's two constants
    lowered: 2 of 8 experts held, so half the pairs) and both held
    experts steered to, every pair is a held row, twice the prefix: the
    rest goes through the condition, is counted, and outputs and
    gradients are the reference's all the same."""
    spec = TCFG.layers[1].ffn
    p = dict(_params()["blocks"][1]["experts"])
    x = _flat(_x())
    pairs = spec.top_k * x.shape[0]
    p["bias"] = p["bias"].at[0].set(10.0)
    if buffer == "prefix":
        monkeypatch.setattr(moe, "_MIN_PAIRS", 0)
        monkeypatch.setattr(moe, "_ROW_TILE", 8)
        p["bias"] = p["bias"].at[1].set(10.0)
        assert moe._prefix_rows(pairs, spec) == pairs // 2
    else:
        assert moe._prefix_rows(pairs, spec) == pairs
    y, rows, _, overflow = moe.held_experts_ffn(x, p, spec)
    rows = np.asarray(rows)
    chosen, _ = moe.route_topk(x, p["router"], p["bias"], spec.top_k,
                               spec.scale)
    assert rows[0] == x.shape[0]
    assert rows.sum() == int(jnp.sum(chosen < spec.n_held))
    assert int(overflow) == (buffer == "prefix")
    if buffer == "prefix":
        assert rows.sum() == pairs
    _close(y, ref.experts(PLAN, p, x, MM))
    out_p, grads_p = _value_and_grads(
        lambda p_, x_: moe.held_experts_ffn(x_, p_, spec)[0], 2)(p, x)
    out_r, grads_r = _value_and_grads(
        lambda p_, x_: ref.experts(PLAN, p_, x_, MM), 2)(p, x)
    _close(out_p, out_r)
    _tree_close(grads_p, grads_r)
    for name in ("w1", "w2", "router"):
        assert float(jnp.linalg.norm(grads_p[0][name])) > 0


def test_a_step_counts_the_layers_that_overflowed(monkeypatch):
    """``train_step(return_stats=True)`` hands out ``moe_overflow_calls``
    beside ``moe_rows``: every expert layer of a stack whose routers are
    all steered to their held experts, none of the stack as seeded."""
    monkeypatch.setattr(moe, "_MIN_PAIRS", 0)
    monkeypatch.setattr(moe, "_ROW_TILE", 8)
    step = jax.jit(lambda p, t: T.train_step(TCFG, p, t, lr=LR,
                                             return_stats=True))
    params = _params()
    _, _, stats = step(params, _tokens())
    layers = stats["moe_rows"].shape[0]
    pairs = TCFG.layers[1].ffn.top_k * _tokens().size
    assert layers > 0 and int(stats["moe_overflow_calls"]) == 0
    assert int(stats["moe_rows"].sum(axis=1).max()) <= pairs // 2
    steered = dict(params, blocks=[
        dict(blk, experts=dict(blk["experts"], bias=blk["experts"][
            "bias"].at[:2].set(10.0))) if "experts" in blk else blk
        for blk in params["blocks"]])
    loss, _, stats = step(steered, _tokens())
    assert np.isfinite(float(loss))
    assert int(stats["moe_overflow_calls"]) == layers
    assert stats["moe_rows"].sum(axis=1).tolist() == [pairs] * layers


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_a_bfloat16_kda_state_fails_the_comparison(monkeypatch, impl):
    """The plain path inside the mixer at the rehearsal's sizes against
    the reference; the kernel (which takes its state's type from the
    same function) alone at heads of 128 against the recurrence."""
    if impl == "pallas":
        args = _kernel_inputs(128)
        exact = jax.jit(kda.kda_recurrent)(*args)
        monkeypatch.setattr(kda, "_state_dtype", lambda q: jnp.bfloat16)
        out = jax.jit(functools.partial(kda.kda_chunked, impl=impl))(*args)
        assert _gap(out, exact) > 100 * KERNEL_TOL[F32]
        return
    spec, p, x = TCFG.layers[0].mixer, _params()["blocks"][0]["mixer"], _x()
    plain = jax.jit(lambda p_, x_: ref.kda(PLAN, p_, x_, MM))(p, x)
    monkeypatch.setattr(kda, "_state_dtype", lambda q: jnp.bfloat16)
    out = jax.jit(lambda p_, x_: T._kda_mixer(spec, p_, x_))(p, x)
    assert np.linalg.norm(out - plain) > 10 * TOL * np.linalg.norm(plain)


def test_a_bfloat16_router_fails_the_comparison(monkeypatch):
    """The layer's output misses the tolerance, and the router's own
    gradient misses it by far."""
    spec, p, x = TCFG.layers[1].ffn, _params()["blocks"][1]["experts"], _x()
    out_r, (grads_r, _) = _value_and_grads(
        lambda p_, x_: _flat(ref.experts(PLAN, p_, x_, MM)), 2)(p, x)
    half = lambda a: a.astype(jnp.bfloat16).astype(a.dtype)
    route = moe.route_topk
    monkeypatch.setattr(
        moe, "route_topk", lambda x_, router, bias, k, scale, **how: route(
            half(x_), half(router), bias, k, scale, **how))
    out_p, (grads_p, _) = _value_and_grads(
        lambda p_, x_: moe.held_experts_ffn(_flat(x_), p_, spec)[0], 2)(p, x)
    rel = lambda a, b: np.linalg.norm(a - b) / np.linalg.norm(b)
    assert rel(out_p, out_r) > TOL
    assert rel(grads_p["router"], grads_r["router"]) > 10 * TOL


# -------------------------------------------------------------- the step

def test_three_steps_match_the_reference():
    """Loss, the gradient's norm leaf by leaf (as the benchmark's family
    takes it), the three-step parameter change, and leaf by leaf the
    parameters themselves."""
    step = jax.jit(lambda p, t: T.train_step(TCFG, p, t, lr=LR))
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("mpi",))
    grad_norms = family.build_grad_norms(TCFG, mesh, 2, dp=False)
    prog, plain, grads = [_params()], [_params()], []
    for seed in (1, 2, 3):
        tokens = _tokens(seed)
        loss_p, new_p = step(prog[-1], tokens)
        loss_r, new_r, norms = ref.step(CFG, plain[-1], tokens, LR)
        assert abs(float(loss_p) - float(loss_r)) <= TOL * float(loss_r)
        grads.append((np.asarray(grad_norms(prog[-1], tokens)),
                      np.asarray(jax.tree.leaves(norms))))
        prog.append(new_p)
        plain.append(new_r)

    def worst(p, r):
        """Widest per-leaf gap; a leaf whose reference norm is all but
        zero (the selection bias) is read against the median leaf."""
        return float(np.max(np.abs(p - r) / np.maximum(r, np.median(r))))

    def change(trail, k):
        return np.asarray([float(jnp.linalg.norm(x - y)) for x, y in zip(
            jax.tree.leaves(trail[0]), jax.tree.leaves(trail[k]),
            strict=True)])

    assert worst(*grads[0]) <= TOL
    # the first step's change is lr times that gradient
    _close(change(prog, 1), LR * grads[0][0], 1e-5)
    assert worst(change(prog, 3), change(plain, 3)) <= 10 * TOL
    _tree_close(prog[1], plain[1])
    _tree_close(prog[3], plain[3], 10 * TOL)


@pytest.mark.parametrize("ranks", [1, 2], ids=["one_chip", "dp2"])
def test_the_benchmarks_gradient_norms_are_the_steps(ranks):
    """``build_grad_norms``, the program the benchmark compares with the
    reference, takes the gradient ``train_step`` takes: with float32
    parameters ``(p0 - p1) / lr`` of the family's step shows it, alone
    and under the data-parallel average."""
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:ranks]), ("mpi",))
    dp, tokens = ranks > 1, _tokens(shape=(2 * ranks, 80))
    norms = np.asarray(family.build_grad_norms(TCFG, mesh, 2, dp)(
        _params(), tokens))
    _, new, _ = family.build_train_step(TCFG, mesh, 2, LR, dp)(
        jax.tree.map(jnp.copy, _params()), tokens)
    moved = np.asarray([float(jnp.linalg.norm(a - b)) / LR for a, b in zip(
        jax.tree.leaves(_params()), jax.tree.leaves(new), strict=True)])
    assert np.max(np.abs(norms - moved)
                  / np.maximum(moved, np.median(moved))) <= TOL


def test_stats_come_out_of_the_step_under_run_spmd():
    """``train_step`` of a two-layer spec (KDA + experts, MLA + dense,
    ``init_transformer``'s own leaves) under ``run_spmd`` on two
    data-parallel ranks: one loss, and each rank's routing counters, one
    row per expert layer."""
    experts = moe.Experts(n_experts=8, top_k=2, d_expert=16, first_expert=4,
                          n_held=2, n_shared=1, scale=2.0)
    cfg = T.TransformerConfig(
        vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_seq=32,
        rope=True, norm="rmsnorm", ffn="swiglu", layers=(
            T.LayerSpec(T.KDA(n_heads=2, head_dim=8), experts),
            T.LayerSpec(T.MLA(n_heads=2, kv_rank=8, qk_nope=8, qk_rope=4,
                              v_dim=8))))
    params = T.init_transformer(jax.random.PRNGKey(0), cfg, F32)
    tokens = _tokens(shape=(4, 16)) % cfg.vocab

    def body(p, t):
        comm = mpi.COMM_WORLD
        local = jax.lax.dynamic_slice_in_dim(t, jnp.asarray(comm.rank) * 2,
                                             2, 0)
        return T.train_step(cfg, p, local, comm_dp=comm, lr=LR,
                            return_stats=True)

    loss, new, stats = mpi.run_spmd(body, nranks=2)(params, tokens)
    assert float(loss[0]) == float(loss[1]) and np.isfinite(float(loss[0]))
    assert set(stats) == {"moe_rows", "moe_overflow_calls"}
    assert stats["moe_rows"].shape == (2, 1, 2)
    assert stats["moe_overflow_calls"].tolist() == [0, 0]
    # top-2 of 8 over 2 x 16 tokens a rank: at most every pair is held
    assert 0 < int(stats["moe_rows"].sum()) <= 2 * 2 * 2 * 16
    moved = jax.tree.map(lambda a, b: bool(jnp.any(a != b[0])), params, new)
    assert moved["blocks"][0]["mixer"]["a_log"] \
        and moved["blocks"][0]["experts"]["w1"] \
        and not moved["blocks"][0]["experts"]["bias"]


# ------------------------------------------------------- what is refused

def test_a_spec_of_default_layers_lowers_as_no_spec_does():
    base = dict(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                max_seq=16, n_kv_heads=2, rope=True, norm="rmsnorm",
                ffn="swiglu", remat=True)
    texts = []
    for layers in ((), (T.LayerSpec(),) * 2):
        cfg = T.TransformerConfig(layers=layers, **base)
        params = T.init_transformer(jax.random.PRNGKey(0), cfg, F32)
        tokens = jnp.zeros((2, 16), jnp.int32)
        texts.append(jax.jit(lambda p, t: T.train_step(cfg, p, t)).lower(
            params, tokens).as_text())
    assert texts[0] == texts[1]


def test_a_spec_is_checked_when_the_configuration_is_made():
    base = dict(vocab=64, d_model=32, n_heads=4, d_ff=64, max_seq=16)
    with pytest.raises(ValueError, match="2 entries for n_layers=3"):
        T.TransformerConfig(n_layers=3, layers=(T.LayerSpec(),) * 2, **base)
    with pytest.raises(ValueError, match="needs a KDA or MLA mixer"):
        T.TransformerConfig(n_layers=1, layers=(
            T.LayerSpec(None, TCFG.layers[1].ffn),), **base)
    for first, held in ((7, 2), (0, 0)):
        with pytest.raises(ValueError, match="held experts"):
            moe.Experts(n_experts=8, top_k=2, d_expert=4,
                        first_expert=first, n_held=held)


def test_an_expert_parallel_communicator_needs_every_expert_held():
    """Over ``R`` ranks the layer exchanges its rows (tests/
    test_smallthinker.py), and every expert then has an owner: this
    configuration's share, 2 of 8 experts, does not lay the layer over 2
    ranks, and a serving step's free slots are not exchanged."""
    class Two:
        size = 2

    p = _params()["blocks"][1]["experts"]
    x = _x().reshape(-1, CFG["hidden_size"])
    with pytest.raises(ValueError, match="n_held=2 of 8"):
        moe.held_experts_ffn(x, p, TCFG.layers[1].ffn, comm_ep=Two())
    with pytest.raises(mpi.CommError, match="free slots"):
        moe.held_experts_ffn(x, p, TCFG.layers[1].ffn, comm_ep=Two(),
                             live=jnp.ones((x.shape[0],), bool))


@pytest.mark.parametrize("call", [
    lambda: T.init_kv_cache(TCFG, 1),
    lambda: T.decode_step(TCFG, {}, [], jnp.zeros((1,), jnp.int32), 0),
    lambda: T.prefill(TCFG, {}, [], jnp.zeros((1, 4), jnp.int32)),
    lambda: T.generate(TCFG, {}, jnp.zeros((1, 4), jnp.int32), 2),
    lambda: serve_kv.validate_tp(TCFG, 1),
], ids=["init_kv_cache", "decode_step", "prefill", "generate", "validate_tp"])
def test_serving_refuses_a_layer_spec(call):
    with pytest.raises(mpi.CommError, match="per-layer spec"):
        call()
