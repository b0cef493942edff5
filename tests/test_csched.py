"""Collective-schedule IR + compiler (mpi4torch_tpu.csched, ISSUE 14).

The re-expression matrix: every registered allreduce algorithm's IR
program must lower to BIT-IDENTICAL StableHLO text as the hand-written
schedule it replaces (forward AND transposition-derived backward,
deterministic and not), the one interpreter must equal the eager
rendezvous fold bitwise, the q8 codec must ride per-step program
rewrites with the same pins, the tree Bcast_/Reduce_ pair must be each
other's transposition, the grouped-fold dedupe
(constants.reduce_grouped/reduce_torus → the interpreter's one
level_fold path) must be bitwise-invisible, the census generator must
reconcile EXACTLY with analyze.parse of the actual lowering, and
synthesis must be deterministic, cache-round-trippable, and
census-better than the deterministic ring.  `make ir-smoke` runs the
same matrix as a standalone lane.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import mpi4torch_tpu as mpi
from mpi4torch_tpu import constants as C
from mpi4torch_tpu import csched
from jax import shard_map
from mpi4torch_tpu.ops import eager as op_eager
from mpi4torch_tpu.ops import spmd as op_spmd

NR = 8
ALGOS = ("ring", "rhd", "tree", "hier", "bidir", "torus")


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


def _lower_text(fn, n=NR, nelem=64, det=False, dtype=jnp.float32):
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("w",))
    ctx = op_spmd.SpmdContext(axis_name="w", size=n)
    x = jnp.arange(nelem, dtype=dtype)
    wrapped = shard_map(lambda v: fn(ctx, v), mesh=mesh, in_specs=P(),
                        out_specs=P(), check_vma=False)
    with mpi.config.deterministic_mode(det):
        return jax.jit(wrapped).lower(x).as_text()


# The hand-written forms — the bit-identity references the IR lowering
# is pinned against (they double as the registered emitter bodies).
LEGACY_FWD = {
    "ring": lambda c, v, op, det:
        op_spmd._ordered_fold_allreduce(c, v, op) if det
        else jax.lax.psum(v, c.axis_name),
    "rhd": lambda c, v, op, det: op_spmd._rhd_allreduce_value(c, v, op),
    "tree": lambda c, v, op, det:
        op_spmd._tree_allreduce_value(c, v, op),
    "hier": lambda c, v, op, det:
        op_spmd._hier_allreduce_value(c, v, op),
    "bidir": lambda c, v, op, det:
        op_spmd._bidir_allreduce_value(c, v, op),
    "torus": lambda c, v, op, det:
        op_spmd._torus_allreduce_value(c, v, op),
}


class TestReexpressionMatrix:
    """Lowered-text equality, forward and backward, per algorithm."""

    @pytest.mark.parametrize("det", [False, True])
    @pytest.mark.parametrize("algo", ALGOS)
    def test_forward_text_identical(self, algo, det):
        t_legacy = _lower_text(
            lambda c, v: LEGACY_FWD[algo](c, v, C.MPI_SUM, det), det=det)
        t_ir = _lower_text(
            lambda c, v: op_spmd._allreduce_fwd_value(c, v, C.MPI_SUM,
                                                      algo), det=det)
        assert t_legacy == t_ir

    @pytest.mark.parametrize("algo", ALGOS)
    def test_backward_text_is_transposed_program(self, algo):
        # The hand-written backward: bidir swaps channel directions,
        # everything else re-runs the forward.
        def legacy_bwd(c, v):
            if algo == "bidir":
                return op_spmd._bidir_allreduce_value(c, v, C.MPI_SUM,
                                                      reverse=True)
            return LEGACY_FWD[algo](c, v, C.MPI_SUM, False)

        t_legacy = _lower_text(legacy_bwd)
        t_ir = _lower_text(
            lambda c, v: op_spmd._allreduce_bwd_value(c, v, algo))
        assert t_legacy == t_ir

    def test_non_sum_ops_route_identically(self):
        for op, det in ((C.MPI_MAX, False), (C.MPI_PROD, False)):
            t_legacy = _lower_text(
                lambda c, v: jax.lax.pmax(v, c.axis_name)
                if op == C.MPI_MAX
                else op_spmd._ordered_fold_allreduce(c, v, op), det=det)
            t_ir = _lower_text(
                lambda c, v: op_spmd._allreduce_fwd_value(c, v, op,
                                                          "ring"),
                det=det)
            assert t_legacy == t_ir

    def test_rhd_raises_same_message_off_power_of_two(self):
        with pytest.raises(mpi.CommError, match="power-of-two"):
            csched.allreduce_program("rhd", 6, C.MPI_SUM,
                                     deterministic=False, nelems=8,
                                     itemsize=4)

    def test_minloc_raises_through_builder(self):
        with pytest.raises(NotImplementedError, match="MPI_MINLOC"):
            csched.allreduce_program("bidir", 8, C.MPI_MINLOC,
                                     deterministic=False, nelems=8,
                                     itemsize=4)


class TestBcastReducePrograms:
    def test_tree_bcast_text_identical(self):
        t_legacy = _lower_text(
            lambda c, v: op_spmd._tree_bcast_value(c, v, 1))
        t_ir = _lower_text(lambda c, v: csched.lower_value(
            csched.bcast_program("tree", NR, 1, nbytes=64 * 4), c, v))
        assert t_legacy == t_ir

    def test_tree_reduce_is_transposed_bcast(self):
        """The acceptance pin: the tree Reduce_ form IS the transposed
        tree Bcast_ program, at the lowered-text level."""
        t_reduce = _lower_text(
            lambda c, v: op_spmd._tree_reduce_value(c, v, C.MPI_SUM, 1))
        t_transposed = _lower_text(lambda c, v: csched.lower_value(
            csched.transpose(csched.bcast_program(
                "tree", NR, 1, nbytes=64 * 4)), c, v))
        assert t_reduce == t_transposed

    def test_ring_bcast_reduce_transpose_pair(self):
        bcast = csched.bcast_program("ring", NR, 0, nbytes=1 << 20)
        red = csched.transpose(bcast)
        kinds = [s.kind for s in red.steps()]
        assert kinds == ["native_allreduce", "mask_root"]
        assert csched.transpose(red).steps() == bcast.steps()

    def test_facade_bcast_reduce_text_unchanged(self):
        """The facade _bcast_value/_reduce_value (now IR-routed) keep
        the historical lowerings: size dispatch, masked psum, masks."""
        t_small = _lower_text(
            lambda c, v: op_spmd._bcast_value(c, v, 1))
        t_tree = _lower_text(
            lambda c, v: op_spmd._tree_bcast_value(c, v, 1))
        assert t_small == t_tree          # 256 B <= tree threshold
        t_red = _lower_text(
            lambda c, v: op_spmd._reduce_value(c, v, C.MPI_SUM, 1))
        t_manual = _lower_text(lambda c, v: op_spmd._mask_to_root(
            c, jax.lax.psum(v, c.axis_name), 1))
        assert t_red == t_manual


class TestInterpreter:
    @pytest.mark.parametrize("n", [3, 8])
    @pytest.mark.parametrize("algo", ALGOS)
    def test_interpreter_matches_rendezvous_fold(self, algo, n):
        if algo == "rhd" and n & (n - 1):
            pytest.skip("rhd needs a power-of-two world")
        if algo in ("hier", "torus") and n == 3:
            # No 2-level factorization: both the builder and the
            # rendezvous fold degrade/raise identically — nothing to
            # compare (the degrade rule is pinned in test_tune).
            pytest.skip("hier/torus need a factorable world")
        rng = np.random.default_rng(3)
        vals = [jnp.asarray(rng.standard_normal(41), jnp.float32)
                for _ in range(n)]
        prog = csched.allreduce_program(algo, n, C.MPI_SUM,
                                        deterministic=True, nelems=41,
                                        itemsize=4)
        _, fold = op_eager._rendezvous_fold(n, algo)
        got = csched.interpret_allreduce(prog, C.MPI_SUM, vals)
        assert jnp.all(got == fold(C.MPI_SUM, vals))

    def test_interpreter_matches_mode_a_deterministic(self):
        rng = np.random.default_rng(4)
        stack = jnp.asarray(rng.standard_normal((NR, 33)), jnp.float32)

        def body():
            idx = jax.lax.axis_index("mpi")
            return mpi.COMM_WORLD.Allreduce(stack[idx], mpi.MPI_SUM,
                                            algorithm="hier")

        with mpi.config.deterministic_mode(True):
            outs = mpi.run_spmd(body, nranks=NR)()
        prog = csched.allreduce_program("hier", NR, C.MPI_SUM,
                                        deterministic=True, nelems=33,
                                        itemsize=4)
        oracle = csched.interpret_allreduce(prog, C.MPI_SUM,
                                            list(stack))
        assert jnp.all(outs == oracle[None])


class TestGroupedFoldDedupe:
    """The triplicated grouped-fold bodies collapse onto the
    interpreter's one level_fold path — bitwise pinned against verbatim
    copies of the pre-dedupe implementations on (3,), (8,) and the
    (2,4) grid."""

    @staticmethod
    def _legacy_grouped(op, values, group):
        vals = list(values)
        partials = [C.reduce_ordered(op, vals[b:b + group])
                    for b in range(0, len(vals), group)]
        return C.reduce_ordered(op, partials)

    @classmethod
    def _legacy_torus(cls, op, values, inner):
        vals = list(values)
        n = len(vals)
        outer = n // inner
        shape = vals[0].shape
        flats = [v.reshape(-1) for v in vals]
        total = flats[0].size
        m = C.multipath_split(total)
        h0 = cls._legacy_grouped(op, [f[:m] for f in flats], inner)
        if m >= total:
            return h0.reshape(shape)
        perm = [o * inner + i for i in range(inner)
                for o in range(outer)]
        h1 = cls._legacy_grouped(op, [flats[p][m:] for p in perm],
                                 outer)
        xp = np if isinstance(h0, np.ndarray) else jnp
        return xp.concatenate([h0, h1]).reshape(shape)

    @pytest.mark.parametrize("n,group", [(3, 3), (8, 2), (8, 4)])
    def test_reduce_grouped_bitwise(self, n, group):
        rng = np.random.default_rng(n * 10 + group)
        vals = [jnp.asarray(rng.standard_normal(29), jnp.float32)
                for _ in range(n)]
        got = C.reduce_grouped(C.MPI_SUM, vals, group)
        assert jnp.all(got == self._legacy_grouped(C.MPI_SUM, vals,
                                                   group))

    @pytest.mark.parametrize("n,inner", [(3, 3), (8, 2), (8, 4)])
    def test_reduce_torus_bitwise(self, n, inner):
        # (8, 4) is the (2,4) grid of the two-axis communicator tests.
        rng = np.random.default_rng(n * 100 + inner)
        vals = [jnp.asarray(rng.standard_normal(37), jnp.float32)
                for _ in range(n)]
        got = C.reduce_torus(C.MPI_SUM, vals, inner)
        assert jnp.all(got == self._legacy_torus(C.MPI_SUM, vals,
                                                 inner))

    def test_numpy_dtype_preserved(self):
        vals = [np.arange(11, dtype=np.float64) * (r + 1)
                for r in range(8)]
        got = C.reduce_grouped(C.MPI_PROD, vals, 4)
        assert isinstance(got, np.ndarray) and got.dtype == np.float64
        assert np.all(got == self._legacy_grouped(C.MPI_PROD, vals, 4))
        got_t = C.reduce_torus(C.MPI_SUM, vals, 2)
        assert isinstance(got_t, np.ndarray)
        assert np.all(got_t == self._legacy_torus(C.MPI_SUM, vals, 2))


class TestTransposition:
    @pytest.mark.parametrize("algo", ALGOS)
    def test_vjp_census_agreement(self, algo):
        """Transposition-derived symmetry == the registry's declared
        AlgorithmSpec.vjp_census, for all six."""
        assert csched.declared_vjp_census(algo, NR) \
            == mpi.tune.get_algorithm(algo).vjp_census

    def test_bidir_transpose_flips_directions(self):
        prog = csched.allreduce_program("bidir", NR, C.MPI_SUM,
                                        deterministic=False,
                                        nelems=64, itemsize=4)
        back = csched.transpose(prog)
        assert [s.params[0] for s in prog.steps()] == [1, -1]
        assert [s.params[0] for s in back.steps()] == [-1, 1]
        assert csched.transpose(back) == prog

    def test_every_step_kind_has_dispatch_coverage(self):
        kinds = set(csched.STEP_KINDS)
        assert set(csched.lowering_covers()) == kinds
        assert set(csched.interpreter_covers()) == kinds
        assert set(csched.transposition_covers()) == kinds
        assert set(csched.census_covers()) == kinds

    def test_registry_guard_clean(self):
        from mpi4torch_tpu.analyze.registry import csched_problems
        assert csched_problems() == []


class TestCodecRewrite:
    @pytest.mark.parametrize("algo", ["ring", "bidir", "torus"])
    def test_q8_text_identical(self, algo):
        from mpi4torch_tpu.compress import get_codec
        from mpi4torch_tpu.compress import spmd as cspmd

        codec = get_codec("q8")
        t_legacy = _lower_text(
            lambda c, v: cspmd._fused_allreduce_value(c, v, codec, algo,
                                                      False), nelem=512)
        t_ir = _lower_text(
            lambda c, v: cspmd._allreduce_value(c, v, codec, algo),
            nelem=512)
        assert t_legacy == t_ir

    def test_q8_steps_carry_codec_annotation(self):
        prog = csched.q8_allreduce_program("bidir", NR, "q8_ef_hop",
                                           256)
        assert prog.codec == "q8_ef_hop"
        assert all(s.kind == "q8_ring_channel"
                   and s.codec == "q8_ef_hop" for s in prog.steps())
        # reverse = the transposed program (bidir directions flip)
        rev = csched.q8_allreduce_program("bidir", NR, "q8_ef_hop", 256,
                                          reverse=True)
        assert rev == csched.transpose(prog)

    def test_q8_interpreter_matches_hop_oracle(self):
        from mpi4torch_tpu.compress import get_codec

        codec = get_codec("q8_ef_hop")
        base = codec.base()
        rng = np.random.default_rng(9)
        vals = [jnp.asarray(rng.standard_normal(300), jnp.float32)
                for _ in range(NR)]
        prog = csched.q8_allreduce_program("bidir", NR, "q8_ef_hop",
                                           base.block)
        got = csched.interpret_allreduce(prog, C.MPI_SUM, vals)
        ref = C.reduce_q8_hop(
            vals, block=base.block, algorithm="bidir",
            stochastic=base.stochastic, hop_ef=base.hop_ef,
            ef_rounds=codec.ef_rounds)
        assert jnp.all(got == ref)


class TestCensus:
    @pytest.mark.parametrize("algo", ALGOS)
    def test_hlo_census_reconciles_with_parse(self, algo):
        """The program census's predicted per-kind collective counts
        equal analyze.parse_program of the actual lowering EXACTLY —
        no per-algorithm census tables anywhere in the chain."""
        from mpi4torch_tpu.analyze import parse_program

        prog = csched.allreduce_program(algo, NR, C.MPI_SUM,
                                        deterministic=False, nelems=64,
                                        itemsize=4)
        txt = _lower_text(
            lambda c, v: op_spmd._allreduce_fwd_value(c, v, C.MPI_SUM,
                                                      algo))
        got = parse_program(txt).census()
        pred = csched.program_census(prog, 64, 4)["hlo"]
        for kind, count in pred.items():
            assert got.get(kind, 0) == count, (algo, kind, got, pred)

    def test_det_ring_census_reconciles(self):
        from mpi4torch_tpu.analyze import parse_program

        prog = csched.allreduce_program("ring", NR, C.MPI_SUM,
                                        deterministic=True, nelems=64,
                                        itemsize=4)
        txt = _lower_text(
            lambda c, v: op_spmd._allreduce_fwd_value(c, v, C.MPI_SUM,
                                                      "ring"), det=True)
        got = parse_program(txt).census()
        pred = csched.program_census(prog, 64, 4)["hlo"]
        for kind, count in pred.items():
            assert got.get(kind, 0) == count

    def test_wire_accounting_matches_registry_formulas(self):
        s = 1 << 14
        ring = csched.program_census(csched.allreduce_program(
            "ring", NR, C.MPI_SUM, deterministic=False,
            nelems=s // 4, itemsize=4), s // 4, 4)
        assert ring["wire_bytes_per_rank"] == int(2 * s * 7 / 8)
        det = csched.program_census(csched.allreduce_program(
            "ring", NR, C.MPI_SUM, deterministic=True,
            nelems=s // 4, itemsize=4), s // 4, 4)
        assert det["wire_bytes_per_rank"] == 7 * s  # gather fold


class TestSerialization:
    @pytest.mark.parametrize("algo", ALGOS)
    def test_json_round_trip(self, algo):
        prog = csched.allreduce_program(algo, NR, C.MPI_SUM,
                                        deterministic=True, nelems=64,
                                        itemsize=4)
        blob = json.dumps(prog.to_json())
        back = csched.Program.from_json(json.loads(blob))
        assert back == prog
        assert back.digest() == prog.digest()


class TestSynthesis:
    def test_deterministic_and_beats_ring(self):
        a = csched.synthesize(NR, 1 << 14, 4)
        b = csched.synthesize(NR, 1 << 14, 4)
        assert a["winner"] == b["winner"]
        assert a["chain"] == [2, 2, 2]
        assert a["synthesis_beats_ring"]
        assert a["census"]["wire_bytes_per_rank"] \
            < a["ring_census"]["wire_bytes_per_rank"]

    def test_cache_round_trip_and_version_bump(self):
        from mpi4torch_tpu.csched import synth as S
        from mpi4torch_tpu.tune import autotuner as A

        rep = csched.autotune_synthesis(nranks=NR, sizes=(1 << 14,))
        ent = rep["entries"][str(1 << 14)]
        assert ent["recorded"] and ent["winner"].startswith("synth:")
        name = ent["winner"]
        # Cross-"process" round trip: drop in-memory state, re-read the
        # persisted file — the entry revalidates and reinstalls.
        # Synthesis entries live under their own codec="synth" slot so
        # they never collide with wall-clock-measured winners.
        S.clear_installed()
        mpi.tune.clear()
        got = mpi.tune.lookup_algorithm("allreduce", jnp.float32,
                                        1 << 14, NR, codec="synth")
        assert got == name and S.synth_applicable(name, NR)
        assert mpi.tune.lookup_algorithm("allreduce", jnp.float32,
                                         1 << 14, NR) is None
        # A CACHE_VERSION bump discards the entry safely (defaults
        # apply, nothing crashes) — the versioned-cache contract.
        S.clear_installed()
        mpi.tune.clear()
        old = A.CACHE_VERSION
        A.CACHE_VERSION = old + 1
        try:
            assert mpi.tune.lookup_algorithm(
                "allreduce", jnp.float32, 1 << 14, NR,
                codec="synth") is None
        finally:
            A.CACHE_VERSION = old

    def test_select_auto_serves_synth_in_det_mode_only(self):
        csched.autotune_synthesis(nranks=NR, sizes=(1 << 14,))
        det = mpi.tune.select_auto(collective="allreduce",
                                   nbytes=1 << 14, dtype=jnp.float32,
                                   nranks=NR, deterministic=True)
        assert det.startswith("synth:")
        nondet = mpi.tune.select_auto(collective="allreduce",
                                      nbytes=1 << 14,
                                      dtype=jnp.float32, nranks=NR,
                                      deterministic=False)
        assert nondet == "ring"

    def test_mode_a_b_bitwise_for_synth_winner(self):
        res = csched.synthesize(NR, 1 << 12, 4)
        name = csched.install(res["program"])
        rng = np.random.default_rng(11)
        stack = jnp.asarray(rng.standard_normal((NR, 50)), jnp.float32)
        oracle = csched.interpret_allreduce(res["program"], C.MPI_SUM,
                                            list(stack))

        def body():
            idx = jax.lax.axis_index("mpi")
            return mpi.COMM_WORLD.Allreduce(stack[idx], mpi.MPI_SUM,
                                            algorithm=name)

        outs = mpi.run_spmd(body, nranks=NR)()
        assert jnp.all(outs == oracle[None])
        eager = mpi.run_ranks(
            lambda rank: mpi.COMM_WORLD.Allreduce(
                stack[rank], mpi.MPI_SUM, algorithm=name), nranks=NR)
        assert all(jnp.all(r == oracle) for r in eager)

    def test_tune_show_renders_synth_distinctly(self):
        from mpi4torch_tpu.tune.__main__ import _rows

        csched.autotune_synthesis(nranks=NR, sizes=(1 << 14,))
        data = json.load(open(mpi.tune.cache_path()))
        rows = _rows(data)
        synth_rows = [r for r in rows if r[6].startswith("synth:")]
        assert synth_rows
        assert synth_rows[0][7] == "synthesized(3 steps)"

    def test_synth_degrades_when_not_installed(self):
        # Scope default naming an uninstalled synth program degrades to
        # auto; an explicit request raises — the standard rule.
        assert mpi.tune.resolve_request("synth:0000000000",
                                        nranks=NR) is None
        with pytest.raises(mpi.CommError, match="not installed"):
            mpi.tune.resolve_request("synth:0000000000", nranks=NR,
                                     explicit=True)
