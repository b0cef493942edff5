"""``parallel.dp.replicated_tree``: the way a replicated tree enters a
differentiated loss without the forward all-reduce, and ``train_step``
on it.

1. **Forward** — the leaves as they came in, bit for bit; no collective
   in the lowered text.
2. **Adjoint** — ``all_average_tree``'s adjoint on the same cotangents,
   bit for bit: eager and SPMD, fused and per leaf, at a rank count that
   is no power of two, under ``deterministic_mode``, a
   ``compression_scope`` and the overlap scheduler.
3. **A rule a bucket** — as many differentiation rules as the fused path
   has buckets, one under the overlap window.
4. **``train_step``** — over four ranks on dp, sp and ep: the loss, the
   gradient and the new parameters of the reference's recipe written out
   with ``all_average_tree``, and the ranks in lock-step over three
   steps.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import mpi4torch_tpu as mpi
from mpi4torch_tpu import config
from mpi4torch_tpu.models import transformer as T
from mpi4torch_tpu.parallel.dp import all_average_tree, replicated_tree

comm = mpi.COMM_WORLD
BUCKET = 4096            # bytes: "a", "d", "e" travel alone, "b" and "c" share


def _tree(seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    leaf = lambda *shape, dt=dtype: jnp.asarray(rng.normal(size=shape), dt)
    return {"a": leaf(40, 30), "b": leaf(7), "c": leaf(3, 3),
            "d": leaf(5, dt=jnp.bfloat16), "e": leaf(2000)}


TREE = _tree(0)
COTANGENTS = [_tree(10 + r) for r in range(4)]


def _bits(tree):
    return [np.asarray(x).view(np.uint8) for x in jax.tree.leaves(tree)]


def assert_same_bits(a, b):
    for x, y in zip(_bits(a), _bits(b), strict=True):
        np.testing.assert_array_equal(x, y)


def _value_and_adjoint(enter, **kw):
    """A rank's body: ``enter``'s value on TREE and its adjoint on this
    rank's cotangents."""
    def body():
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *COTANGENTS)
        mine = jax.tree.map(
            lambda x: x[comm.rank] if isinstance(comm.rank, int)
            else jnp.take(x, jnp.asarray(comm.rank), axis=0), stacked)
        value, vjp = jax.vjp(lambda t: enter(comm, t, **kw), TREE)
        return value, vjp(mine)[0]
    return body


def _run(backend, body, nranks):
    """Every rank's ``(value, adjoint)``."""
    if backend == "eager":
        return mpi.run_ranks(body, nranks)
    value, adjoint = mpi.run_spmd(body, nranks=nranks)()
    return [(jax.tree.map(lambda x: x[r], value),
             jax.tree.map(lambda x: x[r], adjoint)) for r in range(nranks)]


SCOPES = {
    "deterministic": config.deterministic_mode,
    "q8": lambda: config.compression_scope("q8"),
    "rhd": lambda: config.algorithm_scope("rhd"),
}


class TestForward:
    @pytest.mark.parametrize("backend", ["eager", "spmd"])
    @pytest.mark.parametrize("bucket_bytes", [None, 0, BUCKET])
    def test_the_leaves_as_they_came_in(self, backend, bucket_bytes):
        outs = _run(backend, _value_and_adjoint(
            replicated_tree, bucket_bytes=bucket_bytes), 4)
        for value, _ in outs:
            assert_same_bits(value, TREE)

    @pytest.mark.parametrize("bucket_bytes", [None, 0])
    def test_lowers_to_no_collective(self, bucket_bytes):
        enter = lambda fn: mpi.run_spmd(
            lambda: fn(comm, TREE, bucket_bytes=bucket_bytes),
            nranks=4).lower_as_called().as_text()
        assert "stablehlo.all_reduce" in enter(all_average_tree)
        text = enter(replicated_tree)
        for op in ("all_reduce", "all_gather", "reduce_scatter",
                   "collective_permute", "all_to_all"):
            assert "stablehlo." + op not in text

    def test_it_does_not_make_unequal_replicas_equal(self):
        # The contract is the caller's; ``all_average_tree`` is the
        # primitive that averages.
        def body(enter):
            return lambda: enter(
                comm, {"w": jnp.full((3,), float(comm.rank))})["w"]
        kept = mpi.run_ranks(body(replicated_tree), 4)
        averaged = mpi.run_ranks(body(all_average_tree), 4)
        for r in range(4):
            np.testing.assert_array_equal(kept[r], np.full(3, float(r)))
            np.testing.assert_array_equal(averaged[r], np.full(3, 1.5))


class TestAdjoint:
    @pytest.mark.parametrize("backend", ["eager", "spmd"])
    @pytest.mark.parametrize("bucket_bytes", [None, 0, BUCKET])
    @pytest.mark.parametrize("nranks", [4, 3])
    def test_is_the_averages_adjoint_bit_for_bit(self, backend, bucket_bytes,
                                                 nranks):
        ours = _run(backend, _value_and_adjoint(
            replicated_tree, bucket_bytes=bucket_bytes), nranks)
        theirs = _run(backend, _value_and_adjoint(
            all_average_tree, bucket_bytes=bucket_bytes), nranks)
        for (_, got), (_, want) in zip(ours, theirs, strict=True):
            assert_same_bits(got, want)
        # one all-reduce's output: the same bits on every rank
        for _, got in ours[1:]:
            assert_same_bits(got, ours[0][1])

    @pytest.mark.parametrize("backend", ["eager", "spmd"])
    @pytest.mark.parametrize("scope", ["deterministic", "q8", "rhd"])
    @pytest.mark.parametrize("bucket_bytes", [None, 0])
    def test_under_the_scopes_of_the_call(self, backend, scope, bucket_bytes):
        def scoped(enter):
            inner = _value_and_adjoint(enter, bucket_bytes=bucket_bytes)

            def body():
                # rank threads do not see the caller's scope: open it here
                with SCOPES[scope]():
                    return inner()
            return body
        with SCOPES[scope]():    # run_spmd reads the scope where it is called
            ours = _run(backend, scoped(replicated_tree), 4)
            theirs = _run(backend, scoped(all_average_tree), 4)
        for (_, got), (_, want) in zip(ours, theirs, strict=True):
            assert_same_bits(got, want)
        if scope == "q8":
            # the codec engaged: the adjoint is not the exact mean
            exact = _run(backend, _value_and_adjoint(
                all_average_tree, bucket_bytes=bucket_bytes), 4)
            assert any(not np.array_equal(x, y) for x, y in zip(
                _bits(ours[0][1]), _bits(exact[0][1])))

    def test_the_scopes_are_those_of_the_call_not_of_the_backward(self):
        # jax.vjp inside the scope, the pullback outside it: the plan is
        # the forward's, as ``all_average_tree``'s is.
        def body(enter):
            def run():
                with config.compression_scope("q8"):
                    _, vjp = jax.vjp(lambda t: enter(comm, t), TREE)
                return vjp(COTANGENTS[comm.rank])[0]
            return run
        ours = mpi.run_ranks(body(replicated_tree), 4)
        theirs = mpi.run_ranks(body(all_average_tree), 4)
        for got, want in zip(ours, theirs, strict=True):
            assert_same_bits(got, want)

    @pytest.mark.parametrize("backend", ["eager", "spmd"])
    @pytest.mark.parametrize("overlap", [True, 3])
    def test_under_the_overlap_window(self, backend, overlap):
        kw = dict(bucket_bytes=BUCKET, overlap=overlap)
        ours = _run(backend, _value_and_adjoint(replicated_tree, **kw), 4)
        theirs = _run(backend, _value_and_adjoint(all_average_tree, **kw), 4)
        blocking = _run(backend, _value_and_adjoint(
            replicated_tree, bucket_bytes=BUCKET), 4)
        for (value, got), (_, want), (_, same) in zip(ours, theirs, blocking,
                                                     strict=True):
            assert_same_bits(value, TREE)
            assert_same_bits(got, same)
            if backend == "spmd":
                assert_same_bits(got, want)
            else:
                # The eager pipeline's adjoint is the pipeline reversed,
                # its sums in the order autodiff accumulates them; ours
                # is the pipeline's own ascending-rank fold.
                jax.tree.map(lambda a, b: np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    rtol=2e-2 if a.dtype == jnp.bfloat16 else 1e-5,
                    atol=1e-6), got, want)


def _rules(jaxpr) -> int:
    """The differentiation rules (``custom_vjp`` calls) of a jaxpr and
    of every jaxpr it holds."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name.startswith("custom_vjp_call")
        for sub in jax.tree.leaves(
                list(eqn.params.values()),
                is_leaf=lambda x: hasattr(x, "eqns") or hasattr(x, "jaxpr")):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                n += _rules(sub)
    return n


class TestOneRuleABucket:
    def test_as_many_rules_as_buckets(self):
        from mpi4torch_tpu.fuse.bucketing import bucket_layout

        buckets = bucket_layout(TREE, BUCKET).num_buckets
        assert 1 < buckets < len(jax.tree.leaves(TREE))
        count = lambda **kw: _rules(jax.make_jaxpr(
            lambda t: mpi.run_spmd(
                lambda: replicated_tree(comm, t, **kw), nranks=4,
                jit=False)())(TREE).jaxpr)
        assert count(bucket_bytes=BUCKET) == buckets
        assert count(bucket_bytes=0) == len(jax.tree.leaves(TREE))
        assert count(bucket_bytes=BUCKET, overlap=True) == 1
        with config.overlap_scope(2):
            assert count(bucket_bytes=BUCKET) == 1

    @pytest.mark.parametrize("bucket_bytes,all_reduces", [
        (BUCKET, None), (0, 5), (1 << 20, 2)])
    def test_an_all_reduce_a_bucket_in_the_backward(self, bucket_bytes,
                                                    all_reduces):
        from mpi4torch_tpu.fuse.bucketing import bucket_layout

        if all_reduces is None:
            all_reduces = bucket_layout(TREE, bucket_bytes).num_buckets

        def grad():
            return jax.grad(lambda t: sum(
                jnp.sum(x.astype(jnp.float32) ** 2) for x in jax.tree.leaves(
                    replicated_tree(comm, t, bucket_bytes=bucket_bytes))))(
                        TREE)
        text = mpi.run_spmd(grad, nranks=4).lower_as_called().as_text()
        assert text.count("stablehlo.all_reduce") == all_reduces
        assert "mpi4torch.replicated_tree" in mpi.run_spmd(
            grad, nranks=4).lower_as_called().as_text(debug_info=True)


# --------------------------------------------------------------- train_step

B, S, LR = 8, 16, 0.05
CFG = T.TransformerConfig(vocab=31, d_model=16, n_heads=4, n_layers=2,
                          d_ff=32, max_seq=16)
MOE = dataclasses.replace(CFG, n_experts=4, capacity=B * S)


def _recipe_step(cfg, params, tokens, comm_sp=None, comm_dp=None,
                 comm_ep=None, attn="ring", lr=LR):
    """``train_step`` as it was before ``replicated_tree``: the
    reference's recipe on every axis, written out with
    ``all_average_tree``."""
    from mpi4torch_tpu.constants import MPI_SUM

    axes = [c for c in (comm_dp, comm_sp, comm_ep) if c is not None]

    def global_loss(p):
        for c in axes:
            p = all_average_tree(c, p)
        loss = T.lm_loss(cfg, p, tokens, comm_sp, attn, comm_ep=comm_ep)
        for c in (comm_dp, comm_ep):
            if c is not None:
                loss = c.Allreduce(loss, MPI_SUM, compression=False) / c.size
        return loss

    loss, grads = jax.value_and_grad(global_loss)(params)
    return loss, jax.tree.map(lambda p, g: p - lr * g, params, grads), grads


def _mesh_steps(cfg, axis: str, steps: int, recipe: bool, dtype):
    """``steps`` steps over four devices on one axis; every rank's
    losses and final parameters, stacked."""
    mesh = Mesh(np.asarray(jax.devices()[:4]), (axis,))
    c = mpi.comm_from_mesh(mesh, axis)
    comms = {"comm_" + axis: c}
    params = T.init_transformer(jax.random.PRNGKey(0), cfg, dtype=dtype)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (steps, B, S), 0,
                                cfg.vocab)

    def shard(params, tokens):
        r = jnp.asarray(c.rank)
        losses = []
        for t in range(steps):
            local = (jax.lax.dynamic_slice_in_dim(tokens[t], r * (S // 4),
                                                  S // 4, 1)
                     if axis == "sp" else
                     jax.lax.dynamic_slice_in_dim(tokens[t], r * (B // 4),
                                                  B // 4, 0))
            if recipe:
                loss, params, _ = _recipe_step(cfg, params, local, **comms)
            else:
                loss, params = T.train_step(cfg, params, local, lr=LR,
                                            **comms)
            losses.append(loss)
        return jnp.stack(losses)[None], jax.tree.map(lambda a: a[None],
                                                     params)

    return jax.jit(shard_map(shard, mesh=mesh, in_specs=P(),
                             out_specs=P(axis), check_vma=False))(
                                 params, tokens)


@pytest.mark.parametrize("axis,cfg", [("dp", CFG), ("sp", CFG), ("ep", MOE)])
@pytest.mark.parametrize("dtype", [jnp.float64, jnp.bfloat16],
                         ids=["float64", "bfloat16"])
def test_train_step_is_the_recipes_on_equal_replicas(axis, cfg, dtype):
    """Four equal copies sum to ``4 x`` and halve twice exactly, so the
    recipe's forward average is the identity to the bit and the two
    steps compute the same numbers; three steps, so the second and third
    start from parameters the step itself left."""
    losses, params = _mesh_steps(cfg, axis, 3, False, dtype)
    want_losses, want_params = _mesh_steps(cfg, axis, 3, True, dtype)
    if dtype == jnp.float64:
        np.testing.assert_allclose(losses, want_losses, rtol=1e-12,
                                   atol=1e-14)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            a, b, rtol=1e-9, atol=1e-11), params, want_params)
    else:
        assert_same_bits(losses, want_losses)
        assert_same_bits(params, want_params)
    # lock-step: every rank holds rank 0's bits after three steps
    for leaf in jax.tree.leaves((losses, params)):
        for r in range(1, 4):
            np.testing.assert_array_equal(np.asarray(leaf[r]),
                                          np.asarray(leaf[0]))


@pytest.mark.parametrize("axis,cfg", [("dp", CFG), ("sp", CFG), ("ep", MOE)])
def test_train_steps_gradient_is_the_recipes(axis, cfg):
    """``(p - new) / lr`` of one step against the recipe's own
    gradient, and against the one-rank step over the whole batch."""
    params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                dtype=jnp.float64)
    _, new = _mesh_steps(cfg, axis, 1, False, jnp.float64)
    _, want = _mesh_steps(cfg, axis, 1, True, jnp.float64)
    grad = lambda after: jax.tree.map(
        lambda p, n: (p - np.asarray(n[0])) / LR, params, after)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-7, atol=1e-10), grad(new), grad(want))
    if cfg is CFG:
        tokens = jax.random.randint(jax.random.PRNGKey(1), (1, B, S), 0,
                                    cfg.vocab)[0]
        _, whole = T.train_step(cfg, params, tokens, lr=LR)
        jax.tree.map(lambda n, w: np.testing.assert_allclose(
            np.asarray(n[0]), w, rtol=1e-9, atol=1e-11), new, whole)


def test_eager_ranks_stay_in_lock_step_over_three_steps():
    params = T.init_transformer(jax.random.PRNGKey(0), CFG,
                                dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, B, S), 0,
                                CFG.vocab)

    def body(step):
        def run():
            p, losses = params, []
            for t in range(3):
                local = tokens[t, comm.rank * 2:comm.rank * 2 + 2]
                loss, p = step(CFG, p, local, comm_dp=comm, lr=LR)[:2]
                losses.append(loss)
            return jnp.stack(losses), p
        return run

    ours = mpi.run_ranks(body(T.train_step), 4)
    theirs = mpi.run_ranks(body(_recipe_step), 4)
    for got, want in zip(ours, theirs, strict=True):
        assert_same_bits(got, ours[0])
        assert_same_bits(got, want)


def test_unequal_replicas_are_all_average_trees():
    """``train_step`` no longer averages what it is handed: a caller
    whose replicas differ averages them first, as the reference's recipe
    does every step."""
    params = T.init_transformer(jax.random.PRNGKey(0), CFG,
                                dtype=jnp.float64)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, CFG.vocab)

    def body(average):
        def run():
            p = jax.tree.map(lambda a: a * (1.0 + 0.01 * comm.rank), params)
            if average:
                p = all_average_tree(comm, p)
            local = tokens[comm.rank * 2:comm.rank * 2 + 2]
            return T.train_step(CFG, p, local, comm_dp=comm, lr=LR)[1]
        return run

    apart = mpi.run_ranks(body(False), 4)
    together = mpi.run_ranks(body(True), 4)
    assert any(not np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(apart[0]), jax.tree.leaves(apart[1])))
    for other in together[1:]:
        assert_same_bits(other, together[0])
