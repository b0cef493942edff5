"""The static collective-schedule verifier (mpi4torch_tpu.analyze).

Four layers of evidence:

* **parser** — typed CollectiveOp records (kinds, replica_groups with
  declared shape, source_target_pairs, channels, payload dtype/bytes,
  named-scope labels) read off real lowerings, plus synthetic-text unit
  cases for the grammar corners;
* **lints** — each soundness lint exercised on a minimal synthetic
  program AND via the seeded-defect corpus on real mutated schedules
  (every defect caught BY ITS NAMED LINT, the ledger complete);
* **accounting** — the migrated ``wire_bytes_per_device`` /
  ``peak_live_bytes`` / ``scheduled_exposure`` passes regression-pinned
  BIT-IDENTICAL to the counts recorded at PR 6/8/9 (q8-bidir
  7280 B, the (8,)->(2,4) reshard migration 98304 B planned vs
  917504 B gather, the serve decode step's 14336 B / 3584.0 B-per-token
  wire and its exposure fractions), with the historical entry points
  (overlap.census, reshard.census) verified to delegate;
* **sweep** — the full registry-wide lint sweep lints clean on the
  (1,), (3,), (8,) and (2,4) worlds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import mpi4torch_tpu as mpi
from mpi4torch_tpu import analyze
from jax import shard_map

NR = 8


@pytest.fixture(autouse=True)
def _isolated_tune_cache(tmp_path, monkeypatch):
    """Private tune cache per test: the sweep and the serve decode legs
    consult the selector, so an ambient user cache (or a winner another
    test measured) must not change which wire a lowering rides."""
    monkeypatch.setenv("MPI4TORCH_TPU_TUNE_CACHE",
                       str(tmp_path / "tune_cache.json"))
    from mpi4torch_tpu import tune
    tune.clear()
    yield
    tune.clear()


def _lower(body, *args, nr=NR, debug=True):
    mesh = Mesh(np.asarray(jax.devices()[:nr]), ("w",))
    comm = mpi.comm_from_mesh(mesh, "w")
    fn = shard_map(lambda *a: body(comm, *a), mesh=mesh, in_specs=P(),
                   out_specs=P(), check_vma=False)
    return jax.jit(fn).lower(*args).as_text(debug_info=debug)


# =========================================================================
# Synthetic programs: precise grammar-corner cases without lowering cost
# =========================================================================

def synth(*op_lines, npart=8):
    body = "\n".join(f"    {ln}" for ln in op_lines)
    return (
        "module @m attributes "
        f"{{mhlo.num_partitions = {npart} : i32, "
        "mhlo.num_replicas = 1 : i32} {\n"
        "  func.func public @main(%arg0: tensor<32xf32>) "
        "-> (tensor<32xf32>) {\n"
        f"{body}\n"
        "    return %arg0 : tensor<32xf32>\n"
        "  }\n"
        "}\n")


def permute_line(pairs, res="%1", arg="%arg0", handle=1,
                 ty="tensor<32xf32>"):
    table = str([list(p) for p in pairs])
    return (f'{res} = "stablehlo.collective_permute"({arg}) '
            f"<{{channel_handle = #stablehlo.channel_handle<handle = "
            f"{handle}, type = 1>, source_target_pairs = "
            f"dense<{table}> : tensor<{len(pairs)}x2xi64>}}> : "
            f"({ty}) -> {ty}")


def all_gather_line(groups, res="%1", arg="%arg0",
                    ty_in="tensor<32xf32>", ty_out="tensor<64xf32>"):
    table = str([list(g) for g in groups])
    r, c = len(groups), len(groups[0])
    return (f'{res} = "stablehlo.all_gather"({arg}) '
            f"<{{all_gather_dim = 0 : i64, channel_handle = "
            f"#stablehlo.channel_handle<handle = 1, type = 1>, "
            f"replica_groups = dense<{table}> : tensor<{r}x{c}xi64>, "
            f"use_global_device_ids}}> : ({ty_in}) -> {ty_out}")


class TestParser:
    def test_synthetic_permute_record(self):
        p = analyze.parse_program(
            synth(permute_line([(0, 1), (1, 2), (2, 0)], handle=7)))
        assert p.num_partitions == 8
        (op,) = p.ops("collective_permute")
        assert op.source_target_pairs == ((0, 1), (1, 2), (2, 0))
        assert op.channel == 7
        assert op.dtype == "f32"
        assert op.payload_bytes == 128
        assert op.replica_groups is None

    def test_synthetic_all_gather_record(self):
        p = analyze.parse_program(
            synth(all_gather_line([[0, 1, 2, 3], [4, 5, 6, 7]])))
        (op,) = p.ops("all_gather")
        assert op.replica_groups == ((0, 1, 2, 3), (4, 5, 6, 7))
        assert op.group_shape == (2, 4)
        assert op.group_size == 4
        assert op.operand_types == ("32xf32",)
        assert op.result_types == ("64xf32",)

    def test_splat_replica_groups(self):
        # A splat dense literal expands to the declared shape.
        line = all_gather_line([[0]]).replace(
            "dense<[[0]]> : tensor<1x1xi64>", "dense<0> : tensor<1x1xi64>")
        (op,) = analyze.parse_program(synth(line, npart=1)).collectives
        assert op.replica_groups == ((0,),)

    def test_tensor_bytes(self):
        assert analyze.tensor_bytes("8x128xf32") == 8 * 128 * 4
        assert analyze.tensor_bytes("16xi8") == 16
        assert analyze.tensor_bytes("f64") == 8
        assert analyze.tensor_bytes("?xf32") == 0      # dynamic dim
        assert analyze.tensor_bytes("4x!quant") == 0   # unknown elem

    def test_bucket_of(self):
        assert analyze.bucket_of(
            "jit(f)/mpi4torch.Allreduce_tree.bucket2of5.start/x") == \
            ("Allreduce_tree", 2, 5, "start")
        assert analyze.bucket_of("mpi4torch.Allreduce") is None

    def test_real_ring_lowering(self):
        txt = _lower(lambda c, x: c.Allreduce(x, mpi.MPI_SUM),
                     jnp.ones((64,), jnp.float32))
        p = analyze.parse_program(txt)
        assert p.num_partitions == NR
        (op,) = p.collectives
        assert op.kind == "all_reduce"
        assert op.group_size == NR
        assert sorted(v for g in op.replica_groups for v in g) == \
            list(range(NR))
        # The named scope survives onto the wire op's record — the
        # region op's loc sits on its `}) :` closing line.
        assert op.label == "mpi4torch.Allreduce"

    def test_real_bidir_rotations(self):
        # The typed records replace compress.int8_rotation_census-style
        # table matching: both counter-rotations appear as
        # source_target_pairs on the dual ring.
        txt = _lower(
            lambda c, x: c.Allreduce(x, mpi.MPI_SUM, algorithm="bidir"),
            jnp.ones((512,), jnp.float32))
        p = analyze.parse_program(txt)
        tables = {op.source_target_pairs
                  for op in p.ops("collective_permute")}
        fwd = tuple((i, (i + 1) % NR) for i in range(NR))
        bwd = tuple((i, (i - 1) % NR) for i in range(NR))
        assert fwd in tables and bwd in tables
        # distinct channels per hop
        chans = [op.channel for op in p.ops("collective_permute")]
        assert len(set(chans)) == len(chans)

    def test_census_matches_text_counts(self):
        txt = _lower(
            lambda c, x: c.Allreduce(x, mpi.MPI_SUM, algorithm="hier"),
            jnp.ones((512,), jnp.float32))
        got = analyze.parse_program(txt).census()
        want = {k: txt.count(f"stablehlo.{k}")
                for k in analyze.COLLECTIVE_KINDS}
        assert got == want


# =========================================================================
# Lints: synthetic corners
# =========================================================================

class TestLints:
    def test_clean_permute_lints_clean(self):
        assert analyze.run_lints(
            synth(permute_line([(0, 1), (1, 2)]))) == []

    def test_duplicate_target_fires(self):
        (v,) = analyze.run_lints(
            synth(permute_line([(0, 1), (2, 1)])))
        assert v.lint == "permute-pairs" and "target" in v.detail

    def test_duplicate_source_fires(self):
        (v,) = analyze.run_lints(
            synth(permute_line([(0, 1), (0, 2)])))
        assert v.lint == "permute-pairs" and "source" in v.detail

    def test_out_of_range_rank_fires(self):
        (v,) = analyze.run_lints(
            synth(permute_line([(0, 9)])))
        assert v.lint == "permute-pairs" and "outside" in v.detail

    def test_partial_permutation_is_legal(self):
        # A PARTIAL permutation (not every rank sends) is valid — the
        # tree/binomial schedules permute shrinking subsets.
        assert analyze.run_lints(
            synth(permute_line([(4, 0), (5, 1)]))) == []

    def test_non_partitioning_group_fires(self):
        viols = analyze.run_lints(
            synth(all_gather_line([[0, 1, 2, 3], [4, 5, 6, 6]])))
        assert {v.lint for v in viols} == {"replica-groups"}
        details = " ".join(v.detail for v in viols)
        assert "[6]" in details        # duplicated rank
        assert "[7]" in details        # rank in no group

    def test_group_partition_of_subset_mesh(self):
        # num_partitions comes from the module: 4-device groups over a
        # 4-partition module partition correctly.
        line = all_gather_line([[0, 1], [2, 3]])
        assert analyze.run_lints(synth(line, npart=4)) == []

    def test_vjp_symmetry_self(self):
        fwd = synth(permute_line([(0, 1), (1, 0)]))
        both = synth(permute_line([(0, 1), (1, 0)]),
                     permute_line([(0, 1), (1, 0)], res="%2", arg="%1",
                                  handle=2))
        assert analyze.check_vjp_symmetry(fwd, both, "self") == []
        (v,) = analyze.check_vjp_symmetry(fwd, fwd, "self")
        assert v.lint == "vjp-symmetry"

    def test_vjp_symmetry_transpose_mapping(self):
        # A gather-shaped schedule may declare its adjoint scatters:
        # fwd = all_gather, bwd adds a reduce_scatter.
        fwd = synth(all_gather_line([[0, 1, 2, 3, 4, 5, 6, 7]]))
        rs = all_gather_line([[0, 1, 2, 3, 4, 5, 6, 7]], res="%2",
                             arg="%1").replace(
            "stablehlo.all_gather", "stablehlo.reduce_scatter")
        both = synth(all_gather_line([[0, 1, 2, 3, 4, 5, 6, 7]]), rs)
        decl = {"all_gather": "reduce_scatter"}
        assert analyze.check_vjp_symmetry(fwd, both, decl) == []
        assert analyze.check_vjp_symmetry(fwd, both, "self") != []

    def test_unknown_declaration_raises(self):
        fwd = synth(permute_line([(0, 1)]))
        with pytest.raises(ValueError, match="vjp_census"):
            analyze.check_vjp_symmetry(fwd, fwd, "mirror")

    def test_every_registered_algorithm_declares_symmetry(self):
        from mpi4torch_tpu import tune
        for name in tune.available_algorithms():
            decl = tune.get_algorithm(name).vjp_census
            assert decl == "self" or isinstance(decl, dict), (name, decl)


# =========================================================================
# Seeded-defect corpus: every lint fires, by name
# =========================================================================

@pytest.fixture(scope="module")
def corpus_programs():
    from mpi4torch_tpu.analyze.__main__ import _corpus_programs
    return _corpus_programs()


class TestDefectCorpus:
    def test_every_defect_caught_by_its_named_lint(self, corpus_programs):
        records = analyze.run_defect_corpus(corpus_programs)
        assert sorted(r["defect"] for r in records) == sorted(
            analyze.DEFECTS)
        for rec in records:
            assert rec["clean_ok"], rec
            assert rec["fired"], rec

    def test_ledger_every_lint_covered(self, corpus_programs):
        records = analyze.run_defect_corpus(corpus_programs)
        assert analyze.defect_ledger_problems(records) == []

    def test_ledger_detects_uncovered_lint(self, monkeypatch):
        ghost = analyze.DEFECTS.pop("non-partitioning-group")
        try:
            problems = analyze.defect_ledger_problems()
            assert problems and "replica-groups" in " ".join(problems)
        finally:
            analyze.DEFECTS[ghost.name] = ghost

    def test_ledger_detects_unfired_defect(self, corpus_programs):
        records = analyze.run_defect_corpus(corpus_programs)
        records[0] = dict(records[0], fired=False)
        problems = analyze.defect_ledger_problems(records)
        assert any("did not fire" in p for p in problems)


# =========================================================================
# Accounting: recorded BENCH/smoke numbers, bit-identical
# =========================================================================

class TestWireBytesRegression:
    """The PR 6 multipath wire table, re-read through the analyzer
    parse: the recorded per-device bytes must reproduce EXACTLY."""

    @pytest.fixture(scope="class")
    def multipath(self):
        x = jnp.ones((1 << 12,), jnp.float32)   # the recorded payload
        out = {}
        for label, codec, algo in (("fp32-bidir", False, "bidir"),
                                   ("q8-bidir", "q8", "bidir")):
            out[label] = _lower(
                lambda c, v, codec=codec, algo=algo: c.Allreduce(
                    v, mpi.MPI_SUM, compression=codec, algorithm=algo),
                x, debug=False)
        return out

    def test_q8_bidir_wire_bytes_pinned(self, multipath):
        wire, counts = analyze.wire_bytes_per_device(
            multipath["q8-bidir"])
        assert wire == 7280
        assert counts == {"collective_permute": 28, "all_gather": 4}

    def test_fp32_bidir_wire_bytes_pinned(self, multipath):
        wire, counts = analyze.wire_bytes_per_device(
            multipath["fp32-bidir"])
        assert wire == 28672
        assert counts == {"collective_permute": 28}
        # the recorded 3.938x >= 3.5 wire-advantage verdict
        assert round(28672 / 7280, 3) == 3.938


class TestReshardCensusRegression:
    """The PR 8 (8,)->(2,4) migration census: wire bytes AND peak live
    bytes, planned vs gather, pinned to the recorded values.  They
    were recorded without x64 (the liveness scan prices i32 index
    constants there, i64 under the x64 test harness — wire bytes are
    invariant but peak live shifts by the constant widths), so the
    programs lower under ``jax.enable_x64(False)`` to reproduce the recorded
    numbers bit-identically."""

    @pytest.fixture(scope="class")
    def migration(self):
        from mpi4torch_tpu import reshard as rs
        fl = rs.layout((NR,), 0, None)
        tl = rs.layout((2, 4), 0, 1)
        G = (1024, 256)                          # the recorded shapes
        x = jnp.zeros(fl.shard_shape(G), jnp.float32)
        with jax.enable_x64(False):
            return {
                strategy or "planned": _lower(
                    lambda c, v, s=strategy: c.Reshard(v, fl, tl,
                                                       strategy=s),
                    x, debug=False)
                for strategy in (None, "gather")}

    def test_planned_pinned(self, migration):
        wire, counts = analyze.wire_bytes_per_device(
            migration["planned"])
        assert (wire, counts) == (98304, {"all_to_all": 1})
        assert analyze.peak_live_bytes(migration["planned"]) == 426039

    def test_gather_pinned(self, migration):
        wire, counts = analyze.wire_bytes_per_device(
            migration["gather"])
        assert (wire, counts) == (917504, {"all_gather": 1})
        assert analyze.peak_live_bytes(migration["gather"]) == 1343606

    def test_reshard_entry_point_delegates(self, migration):
        from mpi4torch_tpu import reshard as rs
        assert rs.peak_live_bytes(migration["planned"]) == \
            analyze.peak_live_bytes(migration["planned"])
        assert rs.tensor_bytes("4x2xf32") == analyze.tensor_bytes(
            "4x2xf32")


class TestServeCensusRegression:
    """The PR 9 serve decode-step census: per-step/per-token wire bytes
    and the scheduled-exposure fractions, pinned to the recorded
    values (slots=4 on the 8-rank TP world)."""

    @pytest.fixture(scope="class")
    def decode(self):
        from mpi4torch_tpu.models import transformer as T
        from mpi4torch_tpu.serve import Engine, ServeConfig

        cfg = T.TransformerConfig(vocab=256, d_model=64, n_heads=8,
                                  n_layers=4, d_ff=128, max_seq=64)
        out = {}
        # The recorded fractions were taken without x64 (see the
        # reshard regression class) and under a stand-in latency
        # crossover of 16 KiB (decode chunks land in the latency tier,
        # which picks the wire schedule the fractions census).
        prev = mpi.config.latency_crossover_bytes()
        mpi.config.set_latency_crossover_bytes(1 << 14)
        try:
            with jax.enable_x64(False):
                params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                            dtype=jnp.float32)
                for name, ov in (("overlap", True), ("blocking", False)):
                    eng = Engine(cfg, params,
                                 ServeConfig(slots=4, overlap=ov),
                                 spmd=True, nranks=NR)
                    eng.submit(np.array([1, 2, 3, 4, 5]), max_new=3)
                    eng.step()
                    out[name] = eng.lower_step().as_text(
                        debug_info=True)
        finally:
            mpi.config.set_latency_crossover_bytes(prev)
        return out

    def test_wire_bytes_per_step_pinned(self, decode):
        for name in ("overlap", "blocking"):
            wire, _ = analyze.wire_bytes_per_device(decode[name])
            assert wire == 14336, name
            assert round(wire / 4, 1) == 3584.0   # per-token at slots=4

    def test_exposure_fractions_pinned(self, decode):
        ov = analyze.scheduled_exposure(decode["overlap"])
        bl = analyze.scheduled_exposure(decode["blocking"])
        assert (ov["n_buckets"], ov["exposed_fraction"]) == (16, 0.5625)
        assert (bl["n_buckets"], bl["exposed_fraction"]) == (8, 1.0)

    def test_overlap_entry_point_delegates(self, decode):
        assert mpi.overlap.scheduled_exposure(decode["overlap"]) == \
            analyze.scheduled_exposure(decode["overlap"])


# =========================================================================
# Registry guards + sweep
# =========================================================================

class TestRegistryGuards:
    def test_set_drift_formats_message(self):
        from mpi4torch_tpu.analyze.registry import set_drift
        assert set_drift({"a"}, {"a"}, "x") == []
        (msg,) = set_drift({"a", "b"}, {"a"},
                           "reg {registered} cov {covered}")
        assert msg == "reg ['a', 'b'] cov ['a']"

    def test_standing_problems_clean(self):
        from mpi4torch_tpu.analyze.registry import standing_problems
        assert standing_problems() == []

    def test_tune_guard_catches_ghost_algorithm(self):
        from mpi4torch_tpu import tune
        from mpi4torch_tpu.analyze.registry import tune_problems
        from mpi4torch_tpu.tune.registry import _REGISTRY, AlgorithmSpec

        ghost = AlgorithmSpec(name="ghost_algo")
        _REGISTRY[ghost.name] = ghost
        try:
            algos = tuple(a for a in tune.available_algorithms()
                          if a != "ghost_algo")
            problems = tune_problems(algos, algos,
                                     ("ring", "bidir", "torus"))
            assert problems and "ghost_algo" in " ".join(problems)
        finally:
            del _REGISTRY[ghost.name]


class TestSweep:
    """Satellite: the full registry sweep lints clean on the (1,),
    (3,), (8,) and (2,4) worlds.  The serve decode leg (an engine
    compile) runs once, on the full world."""

    @pytest.mark.parametrize("world", [(1,), (3,), (8,), (2, 4)])
    def test_sweep_world_lints_clean(self, world):
        res = analyze.run_sweep(world, include_serve=False)
        assert res["violations"] == []
        assert res["problems"] == []
        assert res["n_cases"] > 0

    def test_sweep_serve_leg_lints_clean(self):
        from mpi4torch_tpu.analyze.sweep import _sweep_serve
        records = []
        _sweep_serve(records, NR)
        assert [r["violations"] for r in records] == [[], []]
        exposures = {r["case"].split(".")[-1]: r["scheduled_exposure"]
                     for r in records}
        assert exposures["blocking"] == 1.0
        assert exposures["overlap"] < 1.0

    def test_sweep_worlds_enumeration(self):
        assert analyze.sweep_worlds(8) == [(8,), (3,), (1,), (2, 4)]
        assert analyze.sweep_worlds(2) == [(2,), (1,)]

    def test_sweep_rejects_oversized_world(self):
        with pytest.raises(ValueError, match="devices"):
            analyze.run_sweep((64,))
