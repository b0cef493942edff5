"""mpi4torch_tpu.reshard (ISSUE 9): sharding -> sharding redistribution.

Pins the tentpole contracts: the planner picks the documented strategy
per transition shape and never auto-picks the gather baseline; every
planned transition is BITWISE equal to the gather-then-slice oracle (and
the numpy assemble-and-slice reference) on both backends, including
``deterministic_mode``; the VJP is the reverse plan (cotangents
redistribute spec' -> spec, replication adjoints sum); the censused peak
live bytes of planned lowerings sit strictly below the gather
baseline's; plans compose with the tune cache's transition dimension,
the resilience fault grammar, and the compress wide-hop codec; and the
step-kind registry stays in sync with both executors, the adjoint
closure, and this file's coverage (the PR 4/6/7 guard pattern).

The heavyweight cross-world transition matrix rides the slow lane and
`make reshard-smoke`; tier-1 keeps the representative cells.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4torch_tpu as mpi
from mpi4torch_tpu import reshard as rs
from mpi4torch_tpu.reshard.executor import _EAGER_EXEC, _SPMD_EXEC
from mpi4torch_tpu.runtime import CommError

NR = 8
G = (16, 8)
FULL = np.random.default_rng(0).standard_normal(G)


def np_shard(lay, r, arr=None):
    return np.asarray(rs.slice_shard(FULL if arr is None else arr, lay, r))


L8 = rs.layout((8,), 0, None)
L24 = rs.layout((2, 4), 0, 1)
L42 = rs.layout((4, 2), 0, 1)

# (name, from, to, expected auto strategy)
CASES = [
    ("migrate", L8, L24, "alltoall"),
    ("migrate-T", L8, L42, "alltoall"),
    ("axis-move", L8, rs.layout((8,), None, 0), "alltoall"),
    ("coarsen", L8, rs.layout((2, 4), (0,), None), "allgather"),
    ("refine", rs.layout((2, 4), (0,), None), L8, "local"),
    ("relabel", L8, rs.layout((2, 4), (0, 1), None), "local"),
    ("block-permute", rs.layout((2, 4), (0, 1), None),
     rs.layout((2, 4), (1, 0), None), "permute"),
    ("replicate", L8, rs.layout((8,), None, None), "allgather"),
    ("slice", rs.layout((8,), None, None), L8, "local"),
    ("zero-to-tp", L8, rs.layout((2, 4), None, 1), "alltoall"),
]


class TestLayout:
    def test_block_maps_row_major(self):
        assert [L8.block(r) for r in range(3)] == [(0, 0), (1, 0), (2, 0)]
        # (2,4): rank 5 = coords (1, 1) -> row-half 1, col-quarter 1
        assert L24.block(5) == (1, 1)
        assert rs.layout((2, 4), (1, 0), None).block(5) == (3, 0)

    def test_shard_and_global_shapes_roundtrip(self):
        assert L24.shard_shape(G) == (8, 2)
        assert L24.global_shape((8, 2)) == G
        with pytest.raises(CommError, match="not divisible"):
            L8.shard_shape((15, 8))

    def test_validation(self):
        with pytest.raises(CommError, match="at most one"):
            rs.Layout((2, 4), ((0,), (0,)))
        with pytest.raises(CommError, match="mesh has"):
            rs.Layout((2,), ((3,),))
        with pytest.raises(CommError, match="Layout"):
            rs.executor.as_layout("nope")

    def test_replica_axes(self):
        assert rs.layout((2, 4), None, 1).replica_axes == (0,)
        assert L24.replica_axes == ()


class TestPlanner:
    @pytest.mark.parametrize("name,fl,tl,want",
                             [(c[0], c[1], c[2], c[3]) for c in CASES])
    def test_auto_strategy(self, name, fl, tl, want):
        plan = rs.plan_reshard(fl, tl, G, np.float64)
        assert plan.strategy == want, name
        assert plan.strategy != "gather"

    def test_identity_transition_is_empty_plan(self):
        plan = rs.plan_reshard(L8, L8, G, np.float64)
        assert plan.steps == () and plan.wire_bytes == 0

    def test_gather_is_explicit_only_and_costs_full_array(self):
        plan = rs.plan_reshard(L8, L24, G, np.float64, strategy="gather")
        assert plan.strategy == "gather"
        assert plan.peak_bytes >= NR * math.prod(L8.shard_shape(G)) * 8
        auto = rs.plan_reshard(L8, L24, G, np.float64)
        assert auto.peak_bytes < plan.peak_bytes
        assert auto.wire_bytes < plan.wire_bytes

    def test_explicit_inapplicable_strategy_raises(self):
        with pytest.raises(CommError, match="cannot serve"):
            rs.plan_reshard(L8, L24, G, np.float64, strategy="permute")

    def test_world_size_change_raises(self):
        with pytest.raises(CommError, match="world size"):
            rs.plan_reshard(L8, rs.layout((4,), 0, None), G, np.float64)

    def test_plans_cached_per_transition(self):
        a = rs.plan_reshard(L8, L24, G, np.float32)
        b = rs.plan_reshard(L8, L24, G, np.float32)
        assert a is b
        c = rs.plan_reshard(L8, L24, G, np.float64)
        assert c is not a

    def test_adjoint_is_reverse_program_in_grammar(self):
        plan = rs.plan_reshard(L8, L24, G, np.float64)
        adj = plan.adjoint()
        assert adj.in_shape == plan.out_shape
        assert adj.out_shape == plan.in_shape
        assert all(s.kind in rs.STEP_KINDS for s in adj.steps)
        # adjoint of adjoint restores the forward step kinds
        assert [s.kind for s in adj.adjoint().steps] == \
            [s.kind for s in plan.steps]

    def test_adjoint_kind_pairing(self):
        gplan = rs.plan_reshard(L8, L24, G, np.float64, strategy="gather")
        kinds = [s.kind for s in gplan.adjoint().steps]
        assert kinds == ["pad", "reduce_scatter"]

    def test_strategy_knob_and_validation(self):
        mpi.config.set_default_reshard_strategy("rounds")
        try:
            plan = rs.plan_reshard(L8, L24, G, np.float64)
            assert plan.strategy == "rounds"
            fp = mpi.config.thresholds_fingerprint()
            assert "rounds" in fp
        finally:
            mpi.config.set_default_reshard_strategy(None)
        assert rs.plan_reshard(L8, L24, G, np.float64).strategy == \
            "alltoall"
        with pytest.raises(ValueError, match="reshard strategy"):
            mpi.config.set_default_reshard_strategy("warp")

    def test_tune_cache_winner_overrides(self):
        # The autotuner cache key grows a transition dimension: a
        # recorded winner for THIS transition redirects auto selection
        # (to the gather baseline here — the only way gather is ever
        # auto-picked), without touching other transitions or the
        # collective-algorithm keys.
        from mpi4torch_tpu import tune

        plan = rs.plan_reshard(L8, L24, G, np.float64)
        nbytes = math.prod(plan.in_shape) * 8
        key = tune.make_key("reshard", np.float64, nbytes, NR,
                            transition=plan.transition)
        assert "transition=" in key
        assert key != tune.make_key("reshard", np.float64, nbytes, NR)
        tune.record("reshard", np.float64, nbytes, NR, "gather",
                    persist=False, transition=plan.transition)
        try:
            assert rs.plan_reshard(L8, L24, G,
                                   np.float64).strategy == "gather"
            # a different transition still auto-selects normally
            assert rs.plan_reshard(L8, L42, G,
                                   np.float64).strategy == "alltoall"
        finally:
            tune.clear()
        assert rs.plan_reshard(L8, L24, G, np.float64).strategy == \
            "alltoall"

    def test_recording_unknown_strategy_raises(self):
        from mpi4torch_tpu import tune

        with pytest.raises(ValueError, match="unknown reshard strategy"):
            tune.record("reshard", np.float64, 1024, NR, "warp",
                        persist=False, transition="x->y")


class TestRegistrySync:
    def test_step_kinds_match_executors_and_coverage(self):
        # The structural guard: a step kind is only real if BOTH
        # executors serve it, its adjoint stays in the grammar, and the
        # CASES table (fwd + adjoint + gather baseline) exercises it.
        kinds = set(rs.STEP_KINDS)
        assert set(_SPMD_EXEC) == kinds
        assert set(_EAGER_EXEC) == kinds
        exercised = set()
        for _, fl, tl, _w in CASES:
            for strat in (None, "gather"):
                plan = rs.plan_reshard(fl, tl, G, np.float64, strat)
                exercised |= {s.kind for s in plan.steps}
                exercised |= {s.kind for s in plan.adjoint().steps}
        plan = rs.plan_reshard(L8, L24, G, np.float64, "rounds")
        exercised |= {s.kind for s in plan.steps}
        exercised |= {s.kind for s in plan.adjoint().steps}
        assert exercised == kinds, (
            f"coverage drift: {sorted(exercised)} vs {sorted(kinds)}")


def eager_ranks(fn, n=NR):
    return mpi.run_ranks(fn, n)


class TestEagerParity:
    @pytest.mark.parametrize("name,fl,tl",
                             [(c[0], c[1], c[2]) for c in CASES])
    def test_bitwise_vs_oracles(self, name, fl, tl):
        def body():
            c = mpi.COMM_WORLD
            x = jnp.asarray(np_shard(fl, c.rank))
            return (c.Reshard(x, fl, tl),
                    rs.gather_then_slice(c, x, fl, tl))

        out = eager_ranks(body)
        for r in range(NR):
            want = np_shard(tl, r)
            got, oracle = out[r]
            assert np.array_equal(np.asarray(got), want), (name, r)
            assert np.array_equal(np.asarray(oracle), want), (name, r)

    def test_rounds_strategy_bitwise(self):
        def body():
            c = mpi.COMM_WORLD
            x = jnp.asarray(np_shard(L8, c.rank))
            return c.Reshard(x, L8, L24, strategy="rounds")

        out = eager_ranks(body)
        for r in range(NR):
            assert np.array_equal(np.asarray(out[r]), np_shard(L24, r))

    def test_deterministic_mode_bitwise(self):
        def body():
            c = mpi.COMM_WORLD
            with mpi.config.deterministic_mode(True):
                x = jnp.asarray(np_shard(L8, c.rank))
                return c.Reshard(x, L8, L24)

        out = eager_ranks(body)
        for r in range(NR):
            assert np.array_equal(np.asarray(out[r]), np_shard(L24, r))

    def test_pytree_and_rule_driven_specs(self):
        tree = {"w": FULL, "b": FULL[:, 0]}
        rules_from = [(r"w", L8), (r"b", rs.layout((8,), 0))]
        rules_to = [(r"w", L24), (r"b", rs.layout((2, 4), (0, 1)))]
        froms = rs.match_partition_rules(rules_from, tree)
        tos = rs.match_partition_rules(rules_to, tree)

        def body():
            c = mpi.COMM_WORLD
            shards = rs.shard_of(tree, froms, c.rank)
            return c.Reshard(shards, froms, tos)

        out = eager_ranks(body)
        for r in range(NR):
            assert np.array_equal(np.asarray(out[r]["w"]),
                                  np_shard(L24, r))
            assert np.array_equal(
                np.asarray(out[r]["b"]),
                np_shard(rs.layout((2, 4), (0, 1)), r,
                         arr=FULL[:, 0]))


class TestSpmdParity:
    def _spmd(self, fl, tl, strategy=None, det=False):
        shard = fl.shard_shape(G)
        starts = np.asarray([[b * s for b, s in zip(fl.block(r), shard)]
                             for r in range(NR)])

        def body():
            c = mpi.COMM_WORLD
            row = jnp.asarray(starts)[jnp.asarray(c.rank + 0)]
            x = jax.lax.dynamic_slice(
                jnp.asarray(FULL), (row[0], row[1]), shard)
            with mpi.config.deterministic_mode(det):
                return c.Reshard(x, fl, tl, strategy=strategy)

        return np.asarray(mpi.run_spmd(body, nranks=NR)())

    def test_migration_bitwise_all_ranks(self):
        out = self._spmd(L8, L24)
        for r in range(NR):
            assert np.array_equal(out[r], np_shard(L24, r))

    def test_deterministic_mode_migration(self):
        out = self._spmd(L8, L24, det=True)
        for r in range(NR):
            assert np.array_equal(out[r], np_shard(L24, r))

    @pytest.mark.slow
    @pytest.mark.parametrize("name,fl,tl",
                             [(c[0], c[1], c[2]) for c in CASES])
    def test_full_matrix_bitwise(self, name, fl, tl):
        out = self._spmd(fl, tl)
        for r in range(NR):
            assert np.array_equal(out[r], np_shard(tl, r)), (name, r)

    @pytest.mark.slow
    def test_rounds_strategy_spmd(self):
        out = self._spmd(L8, L24, strategy="rounds")
        for r in range(NR):
            assert np.array_equal(out[r], np_shard(L24, r))


class TestCensus:
    def _lowered(self, fl, tl, strategy=None, compression=None,
                 grad=False):
        from jax.sharding import Mesh, PartitionSpec as P

        from jax import shard_map

        mesh = Mesh(np.asarray(jax.devices()[:NR]), ("w",))
        c = mpi.comm_from_mesh(mesh, "w")

        def f(a):
            out = c.Reshard(a, fl, tl, strategy=strategy,
                            compression=compression)
            return jnp.sum(out)

        # value_and_grad keeps the forward live (plain grad would DCE
        # it: sum's cotangent is primal-independent).
        prog = jax.value_and_grad(f) if grad else f
        fn = shard_map(prog, mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
        x = jnp.zeros(fl.shard_shape(G), jnp.float32)
        return jax.jit(fn).lower(x).as_text()

    def _counts(self, txt):
        return {k: txt.count(f"stablehlo.{k}")
                for k in ("all_to_all", "all_gather", "reduce_scatter",
                          "collective_permute", "all_reduce")}

    def test_alltoall_plan_is_one_all_to_all(self):
        got = self._counts(self._lowered(L8, L24))
        assert got["all_to_all"] == 1
        assert got["all_gather"] == 0 and got["all_reduce"] == 0

    def test_allgather_plan_is_one_all_gather(self):
        got = self._counts(self._lowered(
            L8, rs.layout((2, 4), (0,), None)))
        assert got["all_gather"] == 1 and got["all_to_all"] == 0

    def test_permute_plan_is_one_collective_permute(self):
        got = self._counts(self._lowered(
            rs.layout((2, 4), (0, 1), None),
            rs.layout((2, 4), (1, 0), None)))
        assert got["collective_permute"] == 1

    def test_local_plan_has_no_collectives(self):
        got = self._counts(self._lowered(
            rs.layout((2, 4), (0,), None), L8))
        assert all(v == 0 for v in got.values())

    def test_rounds_plan_is_chunk_permutes(self):
        txt = self._lowered(L8, L24, strategy="rounds")
        got = self._counts(txt)
        assert got["collective_permute"] >= 2
        assert got["all_to_all"] == 0

    def test_backward_adds_the_adjoint_exchange(self):
        got = self._counts(self._lowered(L8, L24, grad=True))
        assert got["all_to_all"] == 2        # forward + reverse plan

    def test_gather_adjoint_is_reduce_scatter(self):
        got = self._counts(self._lowered(L8, L24, strategy="gather",
                                         grad=True))
        assert got["all_gather"] == 1
        assert got["reduce_scatter"] == 1

    def test_peak_live_bytes_bounded_vs_gather(self):
        # THE acceptance inequality: the planned (8,)->(2,4) migration
        # must lower with strictly less peak live bytes than the
        # gather-everything baseline, by the same estimator.
        planned = rs.peak_live_bytes(self._lowered(L8, L24))
        gathered = rs.peak_live_bytes(self._lowered(L8, L24,
                                                    strategy="gather"))
        assert 0 < planned < gathered

    def test_named_scopes_in_lowering(self):
        from jax.sharding import Mesh, PartitionSpec as P

        from jax import shard_map

        mesh = Mesh(np.asarray(jax.devices()[:NR]), ("w",))
        c = mpi.comm_from_mesh(mesh, "w")
        fn = shard_map(lambda a: c.Reshard(a, L8, L24), mesh=mesh,
                       in_specs=P(), out_specs=P(), check_vma=False)
        txt = jax.jit(fn).lower(
            jnp.zeros(L8.shard_shape(G), jnp.float32)).as_text(
                debug_info=True)
        assert "mpi4torch.Reshard" in txt
        assert "mpi4torch.Reshard.alltoall" in txt

    def test_compressed_wide_hop_ships_int8(self):
        import re

        txt = self._lowered(L8, L24, strategy="gather", compression="q8")
        assert re.search(r"all_gather.*xi8>", txt)

    def test_codec_without_wide_hop_raises(self):
        with pytest.raises(ValueError, match="wide full-world gather"):
            self._lowered(L8, L24, compression="q8")


class TestGrads:
    def test_vjp_redistributes_cotangents_bitwise(self):
        w = np.random.default_rng(1).standard_normal(
            (NR,) + L24.shard_shape(G))

        def body():
            c = mpi.COMM_WORLD
            x = jnp.asarray(np_shard(L8, c.rank))
            wr = jnp.asarray(w)[c.rank]
            return jax.grad(
                lambda v: jnp.vdot(c.Reshard(v, L8, L24), wr))(x)

        g = eager_ranks(body)
        wfull = np.zeros(G)
        sh = L24.shard_shape(G)
        for r in range(NR):
            blk = L24.block(r)
            wfull[tuple(slice(b * s, (b + 1) * s)
                        for b, s in zip(blk, sh))] = w[r]
        for r in range(NR):
            assert np.array_equal(np.asarray(g[r]),
                                  np_shard(L8, r, arr=wfull))

    def test_replication_adjoint_sums_cotangents(self):
        # sharded -> replicated: the adjoint reduce-scatters (sums) the
        # per-rank cotangents — grads-tested under deterministic_mode so
        # the fold order matches the eager oracle bitwise.
        tl = rs.layout((8,), None, None)
        w = np.random.default_rng(2).standard_normal((NR,) + G)

        def body():
            c = mpi.COMM_WORLD
            with mpi.config.deterministic_mode(True):
                x = jnp.asarray(np_shard(L8, c.rank))
                wr = jnp.asarray(w)[c.rank]
                return jax.grad(
                    lambda v: jnp.vdot(c.Reshard(v, L8, tl), wr))(x)

        g = eager_ranks(body)
        acc = w[0]
        for r in range(1, NR):
            acc = acc + w[r]
        for r in range(NR):
            assert np.array_equal(np.asarray(g[r]),
                                  np_shard(L8, r, arr=acc))

    def test_block_permutation_grads_ride_inverse(self):
        lay = rs.layout((8,), 0, None)
        perm = tuple(np.random.default_rng(3).permutation(16).tolist())

        def body():
            c = mpi.COMM_WORLD
            x = jnp.asarray(np_shard(lay, c.rank))
            wr = jnp.full_like(x, c.rank + 1.0)
            return jax.grad(lambda v: jnp.vdot(
                rs.reshard_blocks(c, v, lay, 0, perm), wr))(x)

        g = eager_ranks(body)
        wfull = np.concatenate(
            [np.full((2, G[1]), r + 1.0) for r in range(NR)])
        inv = np.empty(16, int)
        inv[list(perm)] = np.arange(16)
        for r in range(NR):
            assert np.array_equal(np.asarray(g[r]),
                                  wfull[inv][r * 2:(r + 1) * 2])


class TestScenarios:
    def test_zero3_to_tp_handoff(self):
        from mpi4torch_tpu.parallel import (zero3_shard_params,
                                            zero3_to_tp)

        params = {"w": jnp.asarray(FULL),
                  "v": jnp.asarray(FULL[:10, :6])}   # 10 rows: unaligned
        tp = {"w": rs.layout((2, 4), None, 1),
              "v": rs.layout((2, 4), 0, None)}

        def body():
            c = mpi.COMM_WORLD
            shards = zero3_shard_params(c, params)
            return zero3_to_tp(c, shards, params, tp)

        out = eager_ranks(body)
        for r in range(NR):
            for k in params:
                assert np.array_equal(
                    np.asarray(out[r][k]),
                    np_shard(tp[k], r, arr=np.asarray(params[k]))), (k, r)

    def test_moe_rebalance_and_assignment(self):
        from mpi4torch_tpu.parallel import (balanced_assignment,
                                            rebalance_experts)

        E = 16
        stack = np.random.default_rng(4).standard_normal((E, 4))
        loads = list(range(E))
        perm = balanced_assignment(loads, NR)
        assert sorted(perm) == list(range(E))
        totals = [sum(loads[e] for e in perm[r * 2:(r + 1) * 2])
                  for r in range(NR)]
        assert max(totals) - min(totals) <= max(loads) // 2 + 1

        def body():
            c = mpi.COMM_WORLD
            mine = jnp.asarray(stack[c.rank * 2:(c.rank + 1) * 2])
            return rebalance_experts(c, {"w": mine}, perm)

        out = eager_ranks(body)
        want = stack[list(perm)]
        for r in range(NR):
            assert np.array_equal(np.asarray(out[r]["w"]),
                                  want[r * 2:(r + 1) * 2])

        with pytest.raises(ValueError, match="not divisible"):
            balanced_assignment(list(range(9)), NR)


class TestRules:
    def test_paths_and_matching(self):
        tree = {"layer": {"w": np.zeros((8, 8)), "b": np.zeros((8,))},
                "step": np.zeros(())}
        paths = rs.tree_paths(tree)
        assert paths["layer"]["w"] == "layer/w"
        lays = rs.match_partition_rules(
            [(r"layer/w", L24), (r".*", rs.layout((2, 4), 0))], tree)
        assert lays["layer"]["w"] is L24
        assert lays["layer"]["b"].factors == (2,)
        # scalars never partition: replicated on the first rule's mesh
        assert lays["step"].spec == ()
        assert lays["step"].mesh == (2, 4)

    def test_no_match_and_ndim_mismatch_raise(self):
        with pytest.raises(CommError, match="no partition rule"):
            rs.match_partition_rules([(r"w", L24)],
                                     {"x": np.zeros((4, 4))})
        with pytest.raises(CommError, match="axis layout"):
            rs.match_partition_rules([(r".*", L24)],
                                     {"x": np.zeros((4, 4, 4))})


class TestErrors:
    def test_hier_comm_raises(self):
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.devices()[:NR]).reshape(2, 4),
                    ("a", "b"))
        c = mpi.comm_from_mesh(mesh, ("a", "b"))
        with pytest.raises(CommError, match="flat communicator"):
            rs.execute_plan(c, rs.plan_reshard(L8, L24, G, np.float32),
                            jnp.zeros(L8.shard_shape(G)))

    def test_world_size_mismatch_raises(self):
        def body():
            c = mpi.COMM_WORLD
            x = jnp.zeros(rs.layout((4,), 0, None).shard_shape(G))
            return c.Reshard(x, rs.layout((4,), 0, None),
                             rs.layout((2, 2), 0, 1))

        with pytest.raises(CommError, match="spans 4 ranks"):
            eager_ranks(body, n=3)

    def test_wrong_shard_shape_raises(self):
        # Facade path: the implied global shape must divide under the
        # target layout.
        def body():
            return mpi.COMM_WORLD.Reshard(jnp.zeros((3, 3)), L8, L24)

        with pytest.raises(CommError, match="not divisible"):
            eager_ranks(body)
        # Executor path: a plan only serves shards of its own shape.
        plan = rs.plan_reshard(L8, L24, G, np.float32)
        with pytest.raises(CommError, match="expects"):
            def body2():
                return rs.execute_plan(mpi.COMM_WORLD, plan,
                                       jnp.zeros((3, 3), jnp.float32))

            eager_ranks(body2)

    def test_spec_tree_structure_mismatch(self):
        def body():
            c = mpi.COMM_WORLD
            return c.Reshard({"a": jnp.zeros((2, 8))}, {"b": L8}, L24)

        with pytest.raises(CommError, match="matching the state tree"):
            eager_ranks(body)


class TestFaultComposition:
    def test_rank_death_during_reshard_is_attributed(self):
        # The Mode B executor rides World.exchange — the resilience
        # chokepoint — so the PR 7 fault grammar covers reshard traffic
        # with zero reshard-specific hooks.
        from mpi4torch_tpu.resilience import FaultSpec, fault_scope

        with fault_scope([FaultSpec("rank_death", rank=2,
                                    op="Reshard")]):
            def body():
                c = mpi.COMM_WORLD
                x = jnp.asarray(np_shard(L8, c.rank))
                return c.Reshard(x, L8, L24)

            with pytest.raises(mpi.RankFailedError) as ei:
                mpi.run_ranks(body, NR, timeout=20.0)
        assert 2 in ei.value.ranks

    @pytest.mark.slow
    def test_delay_fault_recovers_with_retries(self):
        from mpi4torch_tpu.resilience import FaultSpec, fault_scope

        mpi.config.set_comm_retries(3)
        try:
            with fault_scope([FaultSpec("delay", rank=1, op="Reshard",
                                        seconds=0.2)]):
                def body():
                    c = mpi.COMM_WORLD
                    x = jnp.asarray(np_shard(L8, c.rank))
                    return c.Reshard(x, L8, L24)

                out = mpi.run_ranks(body, NR, timeout=5.0)
            for r in range(NR):
                assert np.array_equal(np.asarray(out[r]),
                                      np_shard(L24, r))
        finally:
            mpi.config.set_comm_retries(0)


@pytest.mark.slow
class TestCrossWorldMatrixSlow:
    """The heavyweight leg: the transition matrix on non-power-of-two
    and small worlds, both backends (the smoke lane covers the compiled
    sweep on 8)."""

    @pytest.mark.parametrize("n", [3, 6])
    def test_small_world_transitions(self, n):
        gs = (2 * n, n)
        full = np.random.default_rng(n).standard_normal(gs)
        fl = rs.layout((n,), 0, None)
        cases = [rs.layout((n,), None, 0),
                 rs.layout((n,), None, None)]
        if n == 6:
            cases += [rs.layout((2, 3), 0, 1),
                      rs.layout((2, 3), (0,), None)]
        for tl in cases:
            def body(tl=tl):
                c = mpi.COMM_WORLD
                x = jnp.asarray(np_shard(fl, c.rank, arr=full))
                return c.Reshard(x, fl, tl)

            out = mpi.run_ranks(body, n)
            for r in range(n):
                assert np.array_equal(
                    np.asarray(out[r]), np_shard(tl, r, arr=full)), \
                    (tl.describe(), r)
