"""ZeRO-1 sharded optimizer (parallel/zero.py): per-rank optimizer state
is 1/size of the replicated state, gradients arrive by reduce-scatter,
updated shards return by allgather — and for element-wise optimizers the
trajectory must EXACTLY match plain replicated DP."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import mpi4torch_tpu as mpi
from mpi4torch_tpu import COMM_WORLD as comm
from mpi4torch_tpu.parallel import all_average_tree, zero_init, zero_step

N, D, STEPS = 32, 5, 12
NR = 4


def _data():
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((N, D)))
    y = x @ jnp.asarray(rng.standard_normal((D,)))
    # A pytree with an awkward leaf shape (3, D) so padding (3*5=15,
    # not divisible by 4) is exercised.
    params0 = {"w": jnp.zeros((D,)), "m": jnp.zeros((3, D))}
    return x, y, params0


def _local_loss(p, xl, yl):
    pred = xl @ p["w"] + jnp.sum(p["m"]) * 0.01
    return jnp.sum((yl - pred) ** 2)


def _replicated_oracle(opt, x, y, params):
    """Single-process trajectory of the plain-DP lock-step: the DP loss
    is the rank-MEAN of local losses (Allreduce/size), so the oracle
    gradient is the full-batch loss divided by the rank count — the same
    mean the reduce-scatter/size inside zero_step produces."""
    state = opt.init(params)
    for _ in range(STEPS):
        g = jax.grad(lambda p: _local_loss(p, x, y) / NR)(params)
        updates, state = opt.update(g, state, params)
        params = jax.tree.map(jnp.add, params, updates)
    return params


@pytest.mark.parametrize("make_opt", [
    lambda: optax.adam(1e-1),
    lambda: optax.sgd(1e-2, momentum=0.9),
], ids=["adam", "sgd-momentum"])
def test_zero_matches_replicated_oracle_eager(make_opt):
    x, y, params0 = _data()
    ref = _replicated_oracle(make_opt(), x, y, params0)
    shard = N // NR

    def body():
        xl = x[comm.rank * shard:(comm.rank + 1) * shard]
        yl = y[comm.rank * shard:(comm.rank + 1) * shard]
        opt = make_opt()
        params = params0
        state = zero_init(comm, opt, params)
        for _ in range(STEPS):
            # UN-reduced local grads: the reduce-scatter inside
            # zero_step performs the global reduction.
            g = jax.grad(lambda p: _local_loss(p, xl, yl))(params)
            params, state = zero_step(comm, opt, params, g, state)
        return params

    outs = mpi.run_ranks(body, NR)
    for got in outs:
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-9, atol=1e-12),
            got, ref)


def test_zero_matches_replicated_oracle_spmd():
    # The whole training loop is ONE compiled SPMD program: per-rank
    # shard states live inside the region (sliced at the symbolic rank),
    # only the final replicated params come out (rank-stacked by
    # run_spmd; every row must equal the oracle).
    x, y, params0 = _data()
    opt = optax.adam(1e-1)
    ref = _replicated_oracle(opt, x, y, params0)
    shard = N // NR

    def body():
        r = jnp.asarray(comm.rank)
        xl = jax.lax.dynamic_slice_in_dim(x, r * shard, shard, 0)
        yl = jax.lax.dynamic_slice_in_dim(y, r * shard, shard, 0)
        params = params0
        state = zero_init(comm, opt, params)
        for _ in range(STEPS):
            g = jax.grad(lambda p: _local_loss(p, xl, yl))(params)
            params, state = zero_step(comm, opt, params, g, state)
        return params

    stacked = mpi.run_spmd(body, nranks=NR)()
    for rank in range(NR):
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a)[rank], np.asarray(b), rtol=1e-9,
                atol=1e-12),
            stacked, ref)


def test_state_is_sharded():
    def body():
        opt = optax.adam(1e-1)
        p = {"w": jnp.zeros((NR * 6,))}
        state = zero_init(comm, opt, p)
        # Adam's mu/nu leaves are shard-sized: 1/size of the params.
        mu = state[0].mu["w"]
        assert mu.shape == (6,)
        return True

    assert all(mpi.run_ranks(body, NR))


def test_global_norm_clipping_matches_replicated():
    """Global-norm clipping through the grad_transform hook: the sharded
    norm helper must reproduce optax.chain(clip_by_global_norm, adam)
    on the replicated oracle exactly — shard-LOCAL clipping would not
    (each rank would scale by a different factor)."""
    x, y, params0 = _data()
    max_norm = 0.5  # far below the actual grad norm: clipping engages
    chain = optax.chain(optax.clip_by_global_norm(max_norm),
                        optax.adam(1e-1))
    ref = _replicated_oracle(chain, x, y, params0)
    shard = N // NR

    from mpi4torch_tpu.parallel import shard_global_norm

    def body():
        xl = x[comm.rank * shard:(comm.rank + 1) * shard]
        yl = y[comm.rank * shard:(comm.rank + 1) * shard]
        opt = optax.adam(1e-1)
        params = params0
        state = zero_init(comm, opt, params)

        def clip(gs):
            # The documented zero-safe form (NaN-free at norm == 0).
            norm = shard_global_norm(comm, gs)
            scale = max_norm / jnp.maximum(norm, max_norm)
            return jax.tree.map(lambda g: g * scale, gs)

        for _ in range(STEPS):
            g = jax.grad(lambda p: _local_loss(p, xl, yl))(params)
            params, state = zero_step(comm, opt, params, g, state,
                                      grad_transform=clip)
        return params

    outs = mpi.run_ranks(body, NR)
    for got in outs:
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-9, atol=1e-12),
            got, ref)

    # Same thing on the SPMD mesh backend (symbolic rank, psum
    # lowering, 0-d scalar Allreduce inside the norm).
    def spmd_body():
        r = jnp.asarray(comm.rank)
        xl = jax.lax.dynamic_slice_in_dim(x, r * shard, shard, 0)
        yl = jax.lax.dynamic_slice_in_dim(y, r * shard, shard, 0)
        opt = optax.adam(1e-1)
        params, state = params0, zero_init(comm, opt, params0)

        def clip(gs):
            norm = shard_global_norm(comm, gs)
            scale = max_norm / jnp.maximum(norm, max_norm)
            return jax.tree.map(lambda g: g * scale, gs)

        for _ in range(STEPS):
            g = jax.grad(lambda p: _local_loss(p, xl, yl))(params)
            params, state = zero_step(comm, opt, params, g, state,
                                      grad_transform=clip)
        return params

    stacked = mpi.run_spmd(spmd_body, nranks=NR)()
    for rank in range(NR):
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a)[rank], np.asarray(b), rtol=1e-9,
                atol=1e-12),
            stacked, ref)


def test_shard_global_norm_equals_full_norm():
    rng = np.random.default_rng(3)
    tree = {"a": jnp.asarray(rng.standard_normal((13,))),
            "b": jnp.asarray(rng.standard_normal((3, 5)))}
    want = float(jnp.sqrt(sum(jnp.sum(jnp.square(v))
                              for v in tree.values())))

    from mpi4torch_tpu.parallel import shard_global_norm
    from mpi4torch_tpu.parallel.zero import _my_shard, _pad_flat

    def body():
        shards = jax.tree.map(
            lambda p: _my_shard(comm, _pad_flat(p, comm.size)), tree)
        return float(shard_global_norm(comm, shards))

    for got in mpi.run_ranks(body, NR):
        np.testing.assert_allclose(got, want, rtol=1e-12)


def _checkpoint_resume_harness(tmp_path, init_fn, step_fn, final_fn):
    """Shared crash/resume oracle for the ZeRO stages: run STEPS
    uninterrupted, run STEPS/2 + save per rank + restore + STEPS/2, and
    require identical final replicated parameters on every rank.

    ``init_fn() -> carry``; ``step_fn(carry, xl, yl) -> carry``;
    ``final_fn(carry) -> replicated params tree`` — all called inside a
    rank-thread.  Per-rank carries are DIFFERENT trees of the same
    shape: each rank persists its own directory.  IO runs serialized on
    the main thread — orbax checkpointers are not safe to call from the
    rank-threads concurrently (under the multi-process runtime each
    process has its own interpreter, so this is a thread-harness
    artifact, not a deployment constraint).  The just-saved carries
    serve as their own restore templates (restore only consumes
    shape/dtype structure)."""
    x, y, _ = _data()
    shard = N // NR
    half = STEPS // 2

    from mpi4torch_tpu.utils import save_checkpoint, restore_checkpoint

    def local_xy():
        xl = x[comm.rank * shard:(comm.rank + 1) * shard]
        yl = y[comm.rank * shard:(comm.rank + 1) * shard]
        return xl, yl

    def run_steps(carry, n):
        xl, yl = local_xy()
        for _ in range(n):
            carry = step_fn(carry, xl, yl)
        return carry

    ref = mpi.run_ranks(lambda: final_fn(run_steps(init_fn(), STEPS)), NR)

    halves = mpi.run_ranks(lambda: run_steps(init_fn(), half), NR)
    for r, carry in enumerate(halves):
        save_checkpoint(str(tmp_path / f"rank{r}"), carry)
    restored = [
        restore_checkpoint(str(tmp_path / f"rank{r}"), halves[r])
        for r in range(NR)
    ]

    outs = mpi.run_ranks(
        lambda: final_fn(run_steps(restored[comm.rank], STEPS - half)),
        NR)
    for got, want in zip(outs, ref):
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-12),
            got, want)


@pytest.mark.slow  # heavyweight compile/run; TPU-manual lane (tier-1 budget)
def test_zero_state_checkpoint_resume(tmp_path):
    """Crash/resume with SHARDED optimizer state: each rank saves its
    own shard, restores it, and the resumed trajectory is identical to
    the uninterrupted run on every rank."""
    _, _, params0 = _data()
    opt = optax.adam(1e-1)

    def init_fn():
        return {"params": params0, "opt": zero_init(comm, opt, params0)}

    def step_fn(carry, xl, yl):
        g = jax.grad(lambda p: _local_loss(p, xl, yl))(carry["params"])
        params, state = zero_step(comm, opt, carry["params"], g,
                                  carry["opt"])
        return {"params": params, "opt": state}

    _checkpoint_resume_harness(tmp_path, init_fn, step_fn,
                               lambda c: c["params"])


class TestZero3:
    """ZeRO-3 (parallel/zero.py zero3_*): parameters persist as 1/size
    flat shards between steps, gathered on use; the gradient arrives
    sharded through the Allgather ADJOINT (the reduce-scatter), and the
    trajectory must exactly match plain replicated DP."""

    @pytest.mark.parametrize("make_opt", [
        lambda: optax.adam(1e-1),
        lambda: optax.sgd(1e-2, momentum=0.9),
    ], ids=["adam", "sgd-momentum"])
    def test_matches_replicated_oracle_eager(self, make_opt):
        from mpi4torch_tpu.parallel import zero3_init, zero3_params, \
            zero3_step
        x, y, params0 = _data()
        ref = _replicated_oracle(make_opt(), x, y, params0)
        shard = N // NR

        def body():
            xl = x[comm.rank * shard:(comm.rank + 1) * shard]
            yl = y[comm.rank * shard:(comm.rank + 1) * shard]
            opt = make_opt()
            p_shards, state = zero3_init(comm, opt, params0)
            for _ in range(STEPS):
                _, p_shards, state = zero3_step(
                    comm, opt, p_shards, params0,
                    lambda p: _local_loss(p, xl, yl), state)
            return zero3_params(comm, p_shards, params0)

        outs = mpi.run_ranks(body, NR)
        for got in outs:
            jax.tree.map(
                lambda a, b: np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-9, atol=1e-12),
                got, ref)

    def test_matches_replicated_oracle_spmd(self):
        from mpi4torch_tpu.parallel import zero3_init, zero3_params, \
            zero3_step
        x, y, params0 = _data()
        opt = optax.adam(1e-1)
        ref = _replicated_oracle(opt, x, y, params0)
        shard = N // NR

        def body():
            r = jnp.asarray(comm.rank)
            xl = jax.lax.dynamic_slice_in_dim(x, r * shard, shard, 0)
            yl = jax.lax.dynamic_slice_in_dim(y, r * shard, shard, 0)
            p_shards, state = zero3_init(comm, opt, params0)
            for _ in range(STEPS):
                _, p_shards, state = zero3_step(
                    comm, opt, p_shards, params0,
                    lambda p: _local_loss(p, xl, yl), state)
            return zero3_params(comm, p_shards, params0)

        stacked = mpi.run_spmd(body, nranks=NR)()
        for rank in range(NR):
            jax.tree.map(
                lambda a, b: np.testing.assert_allclose(
                    np.asarray(a)[rank], np.asarray(b), rtol=1e-9,
                    atol=1e-12),
                stacked, ref)

    def test_everything_is_sharded(self):
        from mpi4torch_tpu.parallel import zero3_init

        def body():
            opt = optax.adam(1e-1)
            p = {"w": jnp.zeros((NR * 6,)), "m": jnp.zeros((3, 5))}
            p_shards, state = zero3_init(comm, opt, p)
            # Parameters AND Adam moments are shard-sized (padded:
            # 15 -> ceil(15/4) = 4 per rank).
            assert p_shards["w"].shape == (6,)
            assert p_shards["m"].shape == (4,)
            assert state[0].mu["w"].shape == (6,)
            assert state[0].nu["m"].shape == (4,)
            return True

        assert all(mpi.run_ranks(body, NR))

    def test_wire_pattern_hlo(self):
        # ZeRO-3's canonical overhead: one step lowers to allgathers
        # (params, forward) + reduce-scatters (gradient adjoint) — and
        # crucially NO all_reduce (a full gradient allreduce would mean
        # the sharding saved nothing on the wire).
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from mpi4torch_tpu.parallel import zero3_init, zero3_step

        mesh = Mesh(np.asarray(jax.devices()[:NR]), ("z",))
        c = mpi.comm_from_mesh(mesh, "z")
        x, y, params0 = _data()
        opt = optax.sgd(1e-2)

        def body():
            p_shards, state = zero3_init(c, opt, params0)
            _, p_shards, state = zero3_step(
                c, opt, p_shards, params0,
                lambda p: _local_loss(p, x, y), state)
            return jax.tree.leaves(p_shards)[0]

        txt = jax.jit(shard_map(body, mesh=mesh, in_specs=(),
                                out_specs=P(), check_vma=False)).lower()
        txt = txt.as_text()
        assert txt.count("stablehlo.all_gather") >= 1
        assert txt.count("stablehlo.reduce_scatter") >= 1
        assert txt.count("stablehlo.all_reduce") == 0, txt

    @pytest.mark.slow  # heavyweight compile/run; TPU-manual lane (tier-1 budget)
    def test_zero3_state_checkpoint_resume(self, tmp_path):
        """Crash/resume with SHARDED PARAMETERS: each rank persists its
        1/size parameter shard + optimizer shard (the whole point of
        stage 3 — no rank ever needs to materialize the full tree to
        checkpoint), and the resumed trajectory is identical to the
        uninterrupted run."""
        from mpi4torch_tpu.parallel import (zero3_init, zero3_params,
                                            zero3_step)

        _, _, params0 = _data()
        opt = optax.adam(1e-1)

        def init_fn():
            ps, st = zero3_init(comm, opt, params0)
            return {"p_shards": ps, "opt": st}

        def step_fn(carry, xl, yl):
            _, ps, st = zero3_step(
                comm, opt, carry["p_shards"], params0,
                lambda p: _local_loss(p, xl, yl), carry["opt"])
            return {"p_shards": ps, "opt": st}

        _checkpoint_resume_harness(
            tmp_path, init_fn, step_fn,
            lambda c: zero3_params(comm, c["p_shards"], params0))
