"""Multi-pod tier-stack collectives (ISSUE 18).

The tier matrix: N-level communicators (``comm_from_mesh`` with three
or more axis names, flat worlds under ``config.tier_stack``), the
csched tier dimension (tier-annotated steps, per-tier synthesis ranked
by the bandwidth-weighted wire census), and the per-tier accounting
chain (``analyze.tier_wire_table`` / ``obs.reconcile(tiers=)`` /
``tune.make_key(tiers=)``).  ``make tiers-smoke`` runs the standalone
verdict lane over the same surface.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import mpi4torch_tpu as mpi
from mpi4torch_tpu import analyze
from mpi4torch_tpu import config
from mpi4torch_tpu import constants as C
from mpi4torch_tpu import csched
from mpi4torch_tpu import obs
from mpi4torch_tpu import overlap
from jax import shard_map
from mpi4torch_tpu.ops import spmd as op_spmd

NR = 8
STACKS = ((2, 2, 2), (4, 2), (2, 4), (8,))
SKEW = (1.0, 1.0, 0.05)


@pytest.fixture(autouse=True)
def _isolated_state(tmp_path, monkeypatch):
    monkeypatch.setenv("MPI4TORCH_TPU_TUNE_CACHE",
                       str(tmp_path / "tune_cache.json"))
    from mpi4torch_tpu.csched import synth as S
    mpi.tune.clear()
    S.clear_installed()
    yield
    mpi.tune.clear()
    S.clear_installed()
    config.set_tier_stack(None)
    config.set_tier_bandwidths(None)


def _lower_text(fn, n=NR, nelem=64, det=False, dtype=jnp.float32):
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("w",))
    ctx = op_spmd.SpmdContext(axis_name="w", size=n)
    x = jnp.arange(nelem, dtype=dtype)
    wrapped = shard_map(lambda v: fn(ctx, v), mesh=mesh, in_specs=P(),
                        out_specs=P(), check_vma=False)
    with config.deterministic_mode(det):
        return jax.jit(wrapped).lower(x).as_text()


def _skew_for(stack):
    return tuple([1.0] * (len(stack) - 1) + [0.05]) \
        if len(stack) > 1 else (1.0,)


# ---------------------------------------------------------------------------
# Config knobs
# ---------------------------------------------------------------------------


class TestConfigKnobs:
    def test_tier_stack_validation(self):
        config.set_tier_stack((2, 2, 2))
        assert config.tier_stack() == (2, 2, 2)
        config.set_tier_stack(None)
        assert config.tier_stack() is None
        for bad in ((1, 4), (), 5, ("x",)):
            with pytest.raises(ValueError):
                config.set_tier_stack(bad)

    def test_tier_bandwidths_validation(self):
        config.set_tier_bandwidths((1.0, 0.05))
        assert config.tier_bandwidths() == (1.0, 0.05)
        config.set_tier_bandwidths(None)
        for bad in ((), (1.0, 0.0), (1.0, -2.0), "fast"):
            with pytest.raises(ValueError):
                config.set_tier_bandwidths(bad)

    def test_knobs_ride_the_thresholds_fingerprint(self):
        base = config.thresholds_fingerprint()
        config.set_tier_stack((2, 4))
        with_stack = config.thresholds_fingerprint()
        config.set_tier_bandwidths((1.0, 0.1))
        with_both = config.thresholds_fingerprint()
        assert len({base, with_stack, with_both}) == 3
        config.set_tier_stack(None)
        config.set_tier_bandwidths(None)
        assert config.thresholds_fingerprint() == base

    def test_process_state_round_trip(self):
        config.set_tier_stack((2, 2, 2))
        config.set_tier_bandwidths((1.0, 1.0, 0.05))
        snap = config.snapshot_process_state()
        assert snap["tier_stack"] == (2, 2, 2)
        assert snap["tier_bandwidths"] == (1.0, 1.0, 0.05)
        config.set_tier_stack(None)
        config.set_tier_bandwidths(None)
        config.apply_process_state(snap)
        assert config.tier_stack() == (2, 2, 2)
        assert config.tier_bandwidths() == (1.0, 1.0, 0.05)

    def test_resolve_tier_stack_contract(self):
        from mpi4torch_tpu.tune import resolve_tier_stack

        assert resolve_tier_stack(8) == (2, 4)   # hier pair default
        config.set_tier_stack((2, 2, 2))
        assert resolve_tier_stack(8) == (2, 2, 2)
        with pytest.raises(mpi.CommError, match="does not factor"):
            resolve_tier_stack(6)


# ---------------------------------------------------------------------------
# Mode A/B parity matrix over nested factorizations
# ---------------------------------------------------------------------------


class TestNestedParityMatrix:
    """Deterministic grouped-fold forms stay bitwise Mode A == Mode B
    per tier on every factorization of the 8-device world, forward and
    backward."""

    def _payload(self, seed=0):
        rng = np.random.default_rng(seed)
        return jnp.asarray(rng.standard_normal((NR, 37)), jnp.float32)

    def _mode_a(self, vals, det=True, grad=False):
        def body():
            idx = jax.lax.axis_index("mpi")
            if grad:
                return jax.grad(lambda v: jnp.vdot(
                    mpi.COMM_WORLD.Allreduce(v, mpi.MPI_SUM,
                                             algorithm="hier"),
                    vals[idx]))(vals[idx])
            return mpi.COMM_WORLD.Allreduce(vals[idx], mpi.MPI_SUM,
                                            algorithm="hier")

        with config.deterministic_mode(det):
            return mpi.run_spmd(body, nranks=NR)()

    def _mode_b(self, vals, grad=False):
        def body(rank):
            if grad:
                return jax.grad(lambda v: jnp.vdot(
                    mpi.COMM_WORLD.Allreduce(v, mpi.MPI_SUM,
                                             algorithm="hier"),
                    vals[rank]))(vals[rank])
            return mpi.COMM_WORLD.Allreduce(vals[rank], mpi.MPI_SUM,
                                            algorithm="hier")
        return mpi.run_ranks(body, nranks=NR)

    @pytest.mark.parametrize("stack", [(2, 2, 2), (4, 2), (2, 4)])
    def test_det_hier_bitwise_fwd(self, stack):
        config.set_tier_stack(stack)
        vals = self._payload(1)
        a = self._mode_a(vals)
        b = self._mode_b(vals)
        assert bool(jnp.all(a == a[0]))
        assert all(bool(jnp.all(r == a[0])) for r in b)

    @pytest.mark.parametrize("stack", [(2, 2, 2), (2, 4)])
    def test_det_hier_bitwise_bwd(self, stack):
        # The backward of an MPI_SUM allreduce is the transposed
        # program — itself an allreduce, folded with the SAME per-tier
        # association in both modes.
        config.set_tier_stack(stack)
        vals = self._payload(2)
        a = self._mode_a(vals, grad=True)
        b = self._mode_b(vals, grad=True)
        assert all(bool(jnp.all(b[r] == a[r])) for r in range(NR))

    @pytest.mark.parametrize("stack", [(2, 2, 2), (4, 2)])
    def test_nondet_hier_correct(self, stack):
        config.set_tier_stack(stack)
        vals = self._payload(3)
        a = self._mode_a(vals, det=False)
        np.testing.assert_allclose(np.asarray(a[0]),
                                   np.asarray(vals.sum(0)), rtol=1e-5)

    def test_single_tier_stack_raises_for_explicit_hier(self):
        # (8,) has no 2-level split: the explicit request raises the
        # SAME way in both modes (the shared resolve_hier_group gate).
        config.set_tier_stack((8,))
        vals = self._payload(4)
        with pytest.raises(mpi.CommError, match="single flat tier"):
            self._mode_b(vals)
        with pytest.raises(mpi.CommError, match="single flat tier"):
            self._mode_a(vals)

    def test_process_transport_bitwise(self):
        # The tier stack rides the process-state snapshot: worker
        # processes fold with the same nested chain as rank-threads.
        config.set_tier_stack((2, 2, 2))
        rng = np.random.default_rng(5)
        base = rng.standard_normal(33).astype(np.float32)

        def body(rank):
            x = jnp.asarray(base) * (rank + 1)
            return np.asarray(mpi.COMM_WORLD.Allreduce(
                x, mpi.MPI_SUM, algorithm="hier"))

        try:
            got = mpi.run_ranks(body, NR, backend="process")
        finally:
            # Don't leak an 8-worker pool into later test modules whose
            # respawn accounting assumes a pool sized to their own runs.
            from mpi4torch_tpu.transport import shutdown
            shutdown()
        oracle = mpi.run_ranks(body, NR, backend="thread")
        for r in range(NR):
            np.testing.assert_array_equal(got[r], oracle[r])

    @pytest.mark.parametrize("comp", ["exact", "q8-slow"])
    def test_synth_composition_bitwise(self, comp):
        # Integer-valued payloads: po2-scale block-q8 round-trips
        # integer grids exactly, so the q8-slow cell compares real
        # schedules, not two rounding paths.
        stack = (2, 2, 2)
        rng = np.random.default_rng(18)
        vals = [jnp.asarray(rng.integers(-40, 40, 257), jnp.float32)
                for _ in range(NR)]
        prog = csched.fold_program(NR, stack, stack)
        if comp == "q8-slow":
            prog = csched.rewrite_fold_codec(prog, (len(stack) - 1,))
        name = csched.install(prog)
        oracle = csched.interpret_allreduce(prog, C.MPI_SUM, vals)
        stacked = jnp.stack(vals)

        def body():
            idx = jax.lax.axis_index("mpi")
            return mpi.COMM_WORLD.Allreduce(stacked[idx], mpi.MPI_SUM,
                                            algorithm=name)

        with config.deterministic_mode(True):
            rows = mpi.run_spmd(body, nranks=NR)()
        assert bool(jnp.all(rows[0] == oracle))
        assert bool(jnp.all(rows == rows[0]))
        eager = mpi.run_ranks(
            lambda rank: mpi.COMM_WORLD.Allreduce(
                vals[rank], mpi.MPI_SUM, algorithm=name), nranks=NR)
        assert all(bool(jnp.all(r == oracle)) for r in eager)


# ---------------------------------------------------------------------------
# Per-tier census
# ---------------------------------------------------------------------------


class TestTierCensus:
    def test_tier_of_group_attribution_rule(self):
        tiers = (2, 2, 2)
        assert csched.tier_of_group((0, 1), tiers) == 0
        assert csched.tier_of_group((0, 2), tiers) == 1
        assert csched.tier_of_group((0, 4), tiers) == 2
        assert csched.tier_of_group((0, 5), tiers) == 2
        assert csched.tier_of_groups(None, tiers) == 2
        assert csched.tier_of_groups(((0, 1), (2, 3)), tiers) == 0

    def test_weighted_cost_arithmetic(self):
        assert csched.weighted_cost((100, 50), (1.0, 0.05)) \
            == 100 + 50 / 0.05
        assert csched.weighted_cost((100, 50)) == 150.0

    @pytest.mark.parametrize("stack", [(2, 2, 2), (4, 2), (2, 4)])
    def test_program_tier_census_sums_to_wire(self, stack):
        prog = csched.fold_program(NR, stack, stack)
        per = csched.program_tier_census(prog, 1024, 4, stack)
        assert len(per) == len(stack)
        assert all(w > 0 for w in per)
        assert sum(per) \
            == csched.program_census(prog, 1024, 4)["wire_bytes_per_rank"]

    def test_lowering_tier_table_matches_program_census(self):
        # The analyze-side table of the ACTUAL lowering equals the
        # program-side prediction, with DISTINCT replica groups feeding
        # distinct tiers.
        stack = (2, 2, 2)
        prog = csched.fold_program(NR, stack, stack)
        name = csched.install(prog)
        txt = _lower_text(
            lambda c, v: op_spmd._allreduce_fwd_value(c, v, C.MPI_SUM,
                                                      name),
            nelem=256, det=True)
        got = analyze.tier_wire_table(txt, stack)
        assert got == csched.program_tier_census(prog, 256, 4, stack)
        assert sum(1 for w in got if w > 0) == 3
        parsed = analyze.parse_program(txt)
        tables = {str(op.replica_groups) for op in parsed.collectives
                  if op.replica_groups}
        assert len(tables) >= 2, "tiers share one replica-group table"

    def test_weighted_wire_cost_config_fallback(self):
        stack = (2, 4)
        txt = _lower_text(
            lambda c, v: op_spmd._allreduce_fwd_value(c, v, C.MPI_SUM,
                                                      "hier"),
            nelem=256, det=True)
        explicit = analyze.weighted_wire_cost(txt, (1.0, 0.05),
                                              tiers=stack)
        assert explicit == csched.weighted_cost(
            analyze.tier_wire_table(txt, stack), (1.0, 0.05))
        config.set_tier_stack(stack)
        assert analyze.weighted_wire_cost(txt, (1.0, 0.05)) == explicit
        config.set_tier_stack(None)
        with pytest.raises(ValueError, match="tier stack"):
            analyze.weighted_wire_cost(txt, (1.0, 0.05))


# ---------------------------------------------------------------------------
# Weighted synthesis verdict
# ---------------------------------------------------------------------------


class TestSynthesisWeighted:
    def test_pinned_skewed_verdict(self):
        # The acceptance numbers on the (2,2,2)/slow-outer cell: the
        # synthesized tier program beats flat bidir on the weighted
        # census, with the outer-tier byte reduction visible in the
        # per-tier breakdown.
        res = csched.synthesize_tiers(NR, 4096, 4, tiers=(2, 2, 2),
                                      tier_bandwidths=SKEW)
        assert res["tier_wire"] == [4096, 4096, 1040]
        assert res["weighted_cost"] == 28992.0
        assert res["bidir_tier_wire"] == [0, 0, 7168]
        assert res["bidir_weighted_cost"] == 143360.0
        assert res["beats_bidir"]
        assert res["tier_wire"][-1] < res["bidir_tier_wire"][-1]
        assert res["composition"] == "q8-slow"
        # and the all-exact runner-up is reported alongside
        assert res["exact_tier_wire"][-1] < res["bidir_tier_wire"][-1]

    @pytest.mark.parametrize("stack", STACKS)
    def test_search_is_deterministic(self, stack):
        a = csched.synthesize_tiers(NR, 4096, 4, tiers=stack,
                                    tier_bandwidths=_skew_for(stack))
        b = csched.synthesize_tiers(NR, 4096, 4, tiers=stack,
                                    tier_bandwidths=_skew_for(stack))
        assert a["winner"] == b["winner"]
        assert a["program"].digest() == b["program"].digest()

    @pytest.mark.parametrize("stack", [(2, 2, 2), (4, 2), (2, 4)])
    def test_uniform_bandwidths_stay_exact(self, stack):
        # No skew -> the q8-slow rewrite never fires: every candidate
        # is exact, so enabling tiers cannot regress accuracy.
        res = csched.synthesize_tiers(NR, 4096, 4, tiers=stack)
        assert all(c["composition"] == "exact"
                   for c in res["candidates"])
        assert res["winner"] == res["exact_winner"]

    def test_two_level_stack_is_hier_text_identical(self):
        # Uniform weights + a 2-level stack: TierStackBackend (flat
        # config form) lowers byte-identically to the pre-tier hier.
        config.set_hier_group_size(2)
        try:
            base = _lower_text(
                lambda c, v: op_spmd._allreduce_fwd_value(
                    c, v, C.MPI_SUM, "hier"), det=True)
        finally:
            config.set_hier_group_size(None)
        config.set_tier_stack((2, 4))
        tiered = _lower_text(
            lambda c, v: op_spmd._allreduce_fwd_value(
                c, v, C.MPI_SUM, "hier"), det=True)
        config.set_tier_stack(None)
        assert base == tiered

    def test_two_level_mesh_backend_is_hier_mesh_backend(self):
        from mpi4torch_tpu.ops.spmd import (HierMeshBackend,
                                            TierStackBackend)

        assert issubclass(HierMeshBackend, TierStackBackend)
        mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                    ("g", "l"))
        x = jnp.arange(64, dtype=jnp.float32)

        def lower(back):
            wrapped = shard_map(
                lambda v: back.allreduce(v, C.MPI_SUM), mesh=mesh,
                in_specs=P(), out_specs=P(), check_vma=False)
            return jax.jit(wrapped).lower(x).as_text()

        assert lower(TierStackBackend(("g", "l"), (2, 4))) \
            == lower(HierMeshBackend(("g", "l"), (2, 4)))


# ---------------------------------------------------------------------------
# Tier-keyed autotuner cache
# ---------------------------------------------------------------------------


class TestCacheTiers:
    def test_make_key_tier_dimension(self):
        flat = mpi.tune.make_key("allreduce", "float32", 1 << 14, NR,
                                 platform="cpu")
        tiered = mpi.tune.make_key("allreduce", "float32", 1 << 14, NR,
                                   platform="cpu", tiers=(2, 2, 2))
        assert "tiers=" not in flat
        assert tiered == flat + "|tiers=2x2x2"
        assert mpi.tune.make_key("allreduce", "float32", 1 << 14, NR,
                                 platform="cpu", tiers="2x2x2") == tiered
        # grammar order: codec= before tiers= before transition=
        full = mpi.tune.make_key("allreduce", "float32", 1 << 14, NR,
                                 platform="cpu", codec="synth",
                                 tiers=(2, 4), transition="warm")
        assert full.endswith("|codec=synth|tiers=2x4|transition=warm")

    def test_cache_version_is_3_and_v2_files_silently_ignored(self):
        from mpi4torch_tpu.tune import autotuner as A

        assert A.CACHE_VERSION == 3
        key = mpi.tune.make_key("allreduce", "float32", 512, NR)
        with open(mpi.tune.cache_path(), "w") as f:
            json.dump({"version": 2,
                       "entries": {key: {"algorithm": "hier"}}}, f)
        mpi.tune.clear()
        # pre-tier digests/keys are discarded by the version gate --
        # silently: no crash, defaults apply.
        assert mpi.tune.lookup("allreduce", "float32", 512, NR) is None
        assert mpi.tune.select_auto(nbytes=512, dtype=jnp.float32,
                                    nranks=NR) == "ring"

    def test_tier_synthesis_records_under_tier_keys(self):
        rep = csched.autotune_tier_synthesis(
            nranks=NR, sizes=(1 << 12,), tiers=(2, 2, 2),
            tier_bandwidths=SKEW)
        ent = rep["entries"][str(1 << 12)]
        assert ent["recorded"]
        # exact winner under codec="synth" (the slot select_auto's
        # deterministic path may consult), lossy under "synth_q8"
        # (never consulted implicitly).
        got_exact = mpi.tune.lookup_algorithm(
            "allreduce", jnp.float32, 1 << 12, NR, codec="synth",
            tiers=(2, 2, 2))
        got_lossy = mpi.tune.lookup_algorithm(
            "allreduce", jnp.float32, 1 << 12, NR, codec="synth_q8",
            tiers=(2, 2, 2))
        assert got_exact == ent["exact_winner"]
        assert got_lossy == ent["winner"]
        # the tier slot never leaks into flat lookups or auto selection
        assert mpi.tune.lookup_algorithm("allreduce", jnp.float32,
                                         1 << 12, NR) is None
        assert not mpi.tune.select_auto(
            collective="allreduce", nbytes=1 << 12, dtype=jnp.float32,
            nranks=NR, deterministic=True).startswith("synth:")

    def test_tune_show_has_tier_column(self):
        from mpi4torch_tpu.tune.__main__ import _COLUMNS, _rows

        assert "tiers" in _COLUMNS
        csched.autotune_tier_synthesis(nranks=NR, sizes=(1 << 12,),
                                       tiers=(2, 2, 2),
                                       tier_bandwidths=SKEW)
        mpi.tune.record("allreduce", "float32", 512, NR, "tree",
                        platform="cpu")
        rows = _rows(json.load(open(mpi.tune.cache_path())))
        by_tier = {r[5] for r in rows}
        assert "2x2x2" in by_tier and "-" in by_tier
        tiered = [r for r in rows if r[5] == "2x2x2"]
        assert all(r[6].startswith("synth:") for r in tiered)


# ---------------------------------------------------------------------------
# obs.reconcile prices per-tier traffic exactly
# ---------------------------------------------------------------------------


class TestReconcileTiers:
    def test_measured_tier_wire_matches_predicted_exactly(self):
        stack = (2, 2, 2)
        res = csched.synthesize_tiers(NR, 4096, 4, tiers=stack,
                                      tier_bandwidths=SKEW)
        name = csched.install(res["program"])
        x = jnp.arange(1024, dtype=jnp.float32)

        with obs.trace() as t:
            mpi.run_ranks(
                lambda rank: mpi.COMM_WORLD.Allreduce(
                    x * (rank + 1), mpi.MPI_SUM, algorithm=name), NR)
        lowered = _lower_text(
            lambda c, v: op_spmd._allreduce_fwd_value(c, v, C.MPI_SUM,
                                                      name),
            nelem=1024, det=True)
        rep = obs.reconcile(t.events, lowered, dropped=t.dropped,
                            tiers=stack)
        assert rep["ok"], rep
        assert rep["matches"]["tier_wire"]
        assert rep["measured"]["tier_wire"] \
            == rep["predicted"]["tier_wire"] == res["tier_wire"]

    def test_reconcile_without_tiers_is_unchanged(self):
        x = jnp.arange(256, dtype=jnp.float32)
        with obs.trace() as t:
            mpi.run_ranks(
                lambda rank: mpi.COMM_WORLD.Allreduce(
                    x * (rank + 1), mpi.MPI_SUM, algorithm="ring"), NR)
        lowered = _lower_text(
            lambda c, v: op_spmd._allreduce_fwd_value(c, v, C.MPI_SUM,
                                                      "ring"),
            nelem=256)
        rep = obs.reconcile(t.events, lowered, dropped=t.dropped)
        assert rep["ok"], rep
        assert "tier_wire" not in rep["measured"]
        assert "tier_wire" not in rep["matches"]


# ---------------------------------------------------------------------------
# Overlap window widening for slow outer tiers
# ---------------------------------------------------------------------------


class TestOverlapTierWindow:
    def _lower_tree(self, ov, nb=4):
        mesh = Mesh(np.asarray(jax.devices()[:NR]), ("w",))
        c = mpi.comm_from_mesh(mesh, "w")
        tree = [jnp.ones(1024, jnp.float32) for _ in range(nb)]
        wrapped = shard_map(
            lambda t: c.Allreduce_tree(t, mpi.MPI_SUM,
                                       bucket_bytes=4096, overlap=ov),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
        return jax.jit(wrapped).lower(tree)

    def test_tier_window_depth_derivation(self):
        assert overlap.tier_window_depth() is None
        config.set_tier_stack((2, 2, 2))
        assert overlap.tier_window_depth() is None   # no bandwidths
        config.set_tier_bandwidths((1.0, 1.0, 0.05))
        assert overlap.tier_window_depth() == 21     # ceil(20) + 1
        config.set_tier_bandwidths((1.0, 1.0, 1.0))
        assert overlap.tier_window_depth() is None   # uniform: no skew
        config.set_tier_bandwidths((1.0, 0.05))      # misaligned stack
        assert overlap.tier_window_depth() is None

    def test_skewed_config_widens_the_window(self):
        blocking = overlap.scheduled_exposure(self._lower_tree(False))
        default = overlap.scheduled_exposure(self._lower_tree(True))
        txt_default = self._lower_tree(True).as_text()
        config.set_tier_stack((2, 2, 2))
        config.set_tier_bandwidths((1.0, 1.0, 0.05))
        widened = overlap.scheduled_exposure(self._lower_tree(True))
        txt_wide = self._lower_tree(True).as_text()
        assert blocking["exposed_fraction"] == 1.0
        assert widened["exposed_fraction"] \
            < blocking["exposed_fraction"]
        assert widened["exposed_fraction"] \
            <= default["exposed_fraction"]
        assert all(b["split_phase"]
                   for b in widened["buckets"].values())
        # the widened window IS a different schedule (deeper start ->
        # wait spans), not a relabeling
        assert txt_wide != txt_default

    def test_explicit_tier_window_parameter(self):
        from mpi4torch_tpu.fuse.collectives import fused_allreduce_tree

        mesh = Mesh(np.asarray(jax.devices()[:NR]), ("w",))
        c = mpi.comm_from_mesh(mesh, "w")
        tree = [jnp.ones(1024, jnp.float32) for _ in range(4)]

        def lower(tw):
            wrapped = shard_map(
                lambda t: fused_allreduce_tree(
                    c, t, mpi.MPI_SUM, bucket_bytes=4096, overlap=True,
                    tier_window=tw),
                mesh=mesh, in_specs=P(), out_specs=P(),
                check_vma=False)
            return jax.jit(wrapped).lower(tree).as_text()

        assert lower(4) != lower(None)
        # widen-only: a window shallower than the overlap depth is a
        # no-op
        assert lower(1) == lower(None)


# ---------------------------------------------------------------------------
# Registry guard + N-axis communicator
# ---------------------------------------------------------------------------


class TestRegistryGuard:
    def test_tier_program_problems_empty(self):
        from mpi4torch_tpu.analyze.registry import tier_program_problems
        assert tier_program_problems() == []

    def test_standing_problems_still_empty(self):
        from mpi4torch_tpu.analyze.registry import standing_problems
        assert standing_problems() == []


class TestCommFromMeshND:
    def _mesh3(self):
        return Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                    ("pod", "host", "chip"))

    def test_three_axis_comm_allreduce_fwd_bwd(self):
        from mpi4torch_tpu.ops.spmd import (HierMeshBackend,
                                            TierStackBackend)

        mesh = self._mesh3()
        c = mpi.comm_from_mesh(mesh, ("pod", "host", "chip"))
        assert isinstance(c._backend(), TierStackBackend)
        assert not isinstance(c._backend(), HierMeshBackend)
        assert c._backend().size == 8
        x = jnp.arange(48, dtype=jnp.float32)

        def run(fn):
            wrapped = shard_map(fn, mesh=mesh, in_specs=P(),
                                out_specs=P(), check_vma=False)
            return jax.jit(wrapped)(x)

        out = run(lambda v: c.Allreduce(v, mpi.MPI_SUM))
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(x) * 8, rtol=1e-6)
        g = run(lambda v: jax.grad(
            lambda t: jnp.vdot(c.Allreduce(t, mpi.MPI_SUM), t))(v))
        # d/dt vdot(AR(t), t) = AR(t) + AR(t) = 2 * 8 * t for equal
        # per-rank operands
        np.testing.assert_allclose(np.asarray(g),
                                   np.asarray(x) * 16, rtol=1e-6)

    def test_three_axis_det_mode_lowers_grouped_chain(self):
        mesh = self._mesh3()
        c = mpi.comm_from_mesh(mesh, ("pod", "host", "chip"))
        x = jnp.arange(64, dtype=jnp.float32)
        wrapped = shard_map(lambda v: c.Allreduce(v, mpi.MPI_SUM),
                            mesh=mesh, in_specs=P(), out_specs=P(),
                            check_vma=False)
        with config.deterministic_mode(True):
            txt = jax.jit(wrapped).lower(x).as_text()
        got = analyze.tier_wire_table(txt, (2, 2, 2))
        assert len(got) == 3 and all(w > 0 for w in got)

    def test_two_axis_tuple_still_builds_hier(self):
        from mpi4torch_tpu.ops.spmd import HierMeshBackend

        mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                    ("g", "l"))
        c = mpi.comm_from_mesh(mesh, ("g", "l"))
        assert isinstance(c._backend(), HierMeshBackend)

    def test_error_paths(self):
        mesh = self._mesh3()
        with pytest.raises(mpi.CommError, match="two or more"):
            mpi.comm_from_mesh(mesh, ("pod",))
        with pytest.raises(mpi.CommError, match="not in mesh"):
            mpi.comm_from_mesh(mesh, ("pod", "rack"))
