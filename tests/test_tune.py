"""mpi4torch_tpu.tune — size/topology-aware algorithms + autotuner
(ISSUE 3), plus the bandwidth tier (ISSUE 4).

Coverage per the acceptance criteria:

* value + gradient parity of every algorithm
  (``rhd``/``tree``/``hier``/``bidir``/``torus``) against ``ring``, on
  power-of-two and non-power-of-two worlds;
* bitwise parity: Mode A (SPMD schedule) vs Mode B (rendezvous fold of
  the matching association) per algorithm under ``deterministic_mode``,
  and all algorithms vs ring on exactly-representable data;
* HLO census proving each algorithm emits its distinct schedule in
  forward AND backward (ring: one all_reduce; rhd: 2·log2 N shrinking
  collective_permutes; tree: 2·log2 N full-width permutes; hier: one
  reduce_scatter + all_reduce + all_gather triple; bidir: two
  concurrent counter-rotating collective_permute chains over
  half-payloads with no dependency between them; torus: one grouped
  channel per (virtual or real) mesh axis), and the phase-pipelined
  deterministic ring fold dropping the trailing broadcast hops;
* a registry-sync guard: every registered ``AlgorithmSpec`` name must
  appear in the parity/grads and census matrices here, so a future
  algorithm registered without tests fails CI;
* selector determinism, three-tier auto selection (latency below the
  crossover, ring in the middle, multipath at/above the bandwidth
  crossover), the degrade/raise rule, and codec restrictions (q8 is
  ring-only);
* autotuner cache round-trip: persisted winners reload in a fresh
  table, corrupt/stale/wrong-version cache files fall back to defaults
  without crashing; concurrent saves union rather than lose entries;
  the ``python -m mpi4torch_tpu.tune`` inspection CLI;
* ``hier``/``torus`` on a 2D mesh: single-axis grouped forms and the
  two-axis ``comm_from_mesh(mesh, (outer, inner))`` communicator;
* fused per-bucket picks: small tail buckets take the latency
  algorithm below the measured crossover while body buckets keep the
  ring pair — or the multipath algorithm past the bandwidth crossover.
"""

import json
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import mpi4torch_tpu as mpi
from mpi4torch_tpu import tune
from jax import shard_map

NR = 8
CENSUS_NR = 4
ALGOS = ("ring", "rhd", "tree", "hier", "bidir", "torus")
# Algorithms with a dedicated forward+backward HLO census below.  The
# registry-sync guard asserts this set — and ALGOS — equals the
# registry, so registering an algorithm without census coverage fails
# here rather than shipping untested.
CENSUS_COVERED = frozenset(ALGOS)
# The codec-capable side of the registry (AlgorithmSpec.codec_capable):
# the ring-shaped schedules whose channels host the in-schedule
# quantized pipeline.  The guard asserts this literal equals the
# registry AND that every registered codec declares only names from it,
# so the (algorithm × codec) census matrix below — computed from the
# live registries — provably enumerates every combination a wire can
# carry.  Same structural pattern as SPLIT_PHASE_FORMS in
# test_nonblocking.py.
CODEC_CAPABLE = ("ring", "bidir", "torus")
COLLECTIVES = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all",
               "collective_permute")

comm = mpi.COMM_WORLD


def _codec_algorithm_pairs():
    """Every (codec-capable algorithm × codec declaring it) pair, from
    the LIVE registries — parametrizes the per-pair census test, so a
    newly registered codec or codec-capable algorithm gets census
    coverage automatically (and the guard below fails if the
    enumeration rules themselves drift)."""
    from mpi4torch_tpu.compress import available_codecs, get_codec

    pairs = []
    for algo in tune.available_algorithms():
        if not tune.get_algorithm(algo).codec_capable:
            continue
        for name in available_codecs():
            if algo in get_codec(name).algorithms:
                pairs.append((algo, name))
    return pairs


def test_registry_sync_guard():
    """Every registered AlgorithmSpec name must be exercised by the
    parity/grads matrix (ALGOS — parametrizes TestAlgorithmParity and
    TestBitwiseDeterministicParity) AND the HLO census matrix
    (CENSUS_COVERED); the codec-capable subset must match
    CODEC_CAPABLE, and every registered codec must declare only
    codec-capable algorithms — which makes the computed
    (algorithm × codec) matrix (_codec_algorithm_pairs, parametrizing
    TestCodecAlgorithmCensus) a complete enumeration.  A future
    algorithm or codec registered without census coverage fails CI
    right here.  The checker body lives in the shared registry-guard
    home (analyze.registry.tune_problems, messages unchanged); the
    coverage literals stay HERE, next to the matrices they pin."""
    from mpi4torch_tpu.analyze.registry import tune_problems

    assert tune_problems(ALGOS, CENSUS_COVERED, CODEC_CAPABLE) == []
    pairs = _codec_algorithm_pairs()
    assert pairs and len(pairs) == len(set(pairs))
    assert ("bidir", "q8") in pairs and ("torus", "q8_ef_hop") in pairs


@pytest.fixture(autouse=True)
def _isolated_tune_state(tmp_path, monkeypatch):
    """Every test gets its own cache file and pristine knobs — the
    autotuner's persistence must never leak between tests (or into the
    rest of the suite)."""
    monkeypatch.setenv("MPI4TORCH_TPU_TUNE_CACHE",
                       str(tmp_path / "tune_cache.json"))
    tune.clear()
    yield
    tune.clear()
    mpi.config.set_latency_crossover_bytes(None)
    mpi.config.set_bandwidth_crossover_bytes(None)
    mpi.config.set_phase_pipelined_ring(True)
    mpi.config.set_hier_group_size(None)
    mpi.config.set_default_algorithm(None)
    mpi.config.set_chain_unroll_max(mpi.config.DEFAULT_CHAIN_UNROLL_MAX)


def census(fn, *args, nr=CENSUS_NR, mesh_axes=None):
    """collective-op name -> count in the lowered StableHLO (and the
    text itself, for shape-level assertions)."""
    if mesh_axes is None:
        mesh = Mesh(np.asarray(jax.devices()[:nr]), ("w",))
        c = mpi.comm_from_mesh(mesh, "w")
    else:
        mesh, c = mesh_axes
    wrapped = shard_map(lambda *a: fn(c, *a), mesh=mesh, in_specs=P(),
                        out_specs=P(), check_vma=False)
    txt = jax.jit(wrapped).lower(*args).as_text()
    return {k: txt.count(f"stablehlo.{k}") for k in COLLECTIVES}, txt


def only(**expected):
    out = {k: 0 for k in COLLECTIVES}
    out.update(expected)
    return out


# ---------------------------------------------------------------------------
# Parity + gradients
# ---------------------------------------------------------------------------


class TestAlgorithmParity:
    @pytest.mark.parametrize("algo", ALGOS)
    def test_values_and_grads_match_ring(self, algo):
        rng = np.random.default_rng(3)
        data = jnp.asarray(rng.standard_normal((NR, 37)).astype(np.float32))

        def body(x, a):
            t = jax.lax.dynamic_index_in_dim(
                x, jnp.asarray(comm.rank + 0), 0, keepdims=False)
            y, g = jax.value_and_grad(lambda v: jnp.vdot(
                comm.Allreduce(v, mpi.MPI_SUM, algorithm=a), v))(t)
            return y, g

        want_y, want_g = mpi.run_spmd(lambda x: body(x, "ring"))(data)
        got_y, got_g = mpi.run_spmd(lambda x: body(x, algo))(data)
        np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(got_g), np.asarray(want_g),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("nr,algo", [(3, "tree"), (6, "tree"),
                                         (6, "hier"), (3, "bidir"),
                                         (6, "bidir"), (6, "torus")])
    def test_non_power_of_two_worlds(self, nr, algo):
        rng = np.random.default_rng(5)
        data = jnp.asarray(rng.standard_normal((nr, 19)).astype(np.float32))

        def body(x, a):
            t = jax.lax.dynamic_index_in_dim(
                x, jnp.asarray(comm.rank + 0), 0, keepdims=False)
            return comm.Allreduce(t, mpi.MPI_SUM, algorithm=a)

        want = np.asarray(mpi.run_spmd(lambda x: body(x, "ring"),
                                       nranks=nr)(data))
        got = np.asarray(mpi.run_spmd(lambda x: body(x, algo),
                                      nranks=nr)(data))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_max_reduction_on_explicit_algorithms(self):
        rng = np.random.default_rng(7)
        data = jnp.asarray(rng.standard_normal((NR, 23)).astype(np.float32))

        def body(x, a):
            t = jax.lax.dynamic_index_in_dim(
                x, jnp.asarray(comm.rank + 0), 0, keepdims=False)
            return comm.Allreduce(t, mpi.MPI_MAX, algorithm=a)

        want = np.asarray(mpi.run_spmd(lambda x: body(x, "ring"))(data))
        for algo in ("rhd", "tree", "bidir", "torus"):
            got = np.asarray(mpi.run_spmd(lambda x, a=algo: body(x, a))(data))
            np.testing.assert_array_equal(got, want, err_msg=algo)


class TestBitwiseDeterministicParity:
    @pytest.mark.parametrize("algo", ALGOS)
    def test_mode_a_vs_mode_b_bitwise(self, algo):
        # GENERAL float data: each algorithm's fixed association must
        # produce identical bits on the compiled schedule (Mode A) and
        # the rendezvous fold (Mode B) — the ISSUE 3 A/B contract.
        rng = np.random.default_rng(11)
        data = jnp.asarray(rng.standard_normal((NR, 33)).astype(np.float32))

        def det_body(x, a=algo):
            t = jax.lax.dynamic_index_in_dim(
                x, jnp.asarray(comm.rank + 0), 0, keepdims=False)
            return comm.Allreduce(t, mpi.MPI_SUM, algorithm=a)

        with mpi.config.deterministic_mode(True):
            a_out = np.asarray(mpi.run_spmd(det_body)(data))
        b_out = mpi.run_ranks(
            lambda: np.asarray(comm.Allreduce(
                data[comm.rank], mpi.MPI_SUM, algorithm=algo)), NR)
        for r in range(NR):
            np.testing.assert_array_equal(a_out[r], b_out[r],
                                          err_msg=f"{algo} rank {r}")

    @pytest.mark.parametrize("nr,root", [(3, 1), (6, 4), (8, 2)])
    def test_reduce_tree_nonzero_root_mode_a_vs_b_bitwise(self, nr, root):
        # The SPMD tree reduce relabels ranks relative to the ROOT
        # (rel = (idx - root) % n); the eager fold must rotate the
        # value list the same way or the associations — and the bits —
        # diverge for root != 0 (caught in review; regression).
        rng = np.random.default_rng(19)
        data = jnp.asarray(rng.standard_normal((nr, 27)).astype(np.float32))

        def det_body(x):
            t = jax.lax.dynamic_index_in_dim(
                x, jnp.asarray(comm.rank + 0), 0, keepdims=False)
            return comm.Reduce_(t, mpi.MPI_SUM, root=root,
                                algorithm="tree")

        with mpi.config.deterministic_mode(True):
            a_out = np.asarray(mpi.run_spmd(det_body, nranks=nr)(data))
        b_out = mpi.run_ranks(
            lambda: np.asarray(comm.Reduce_(
                data[comm.rank], mpi.MPI_SUM, root=root,
                algorithm="tree")), nr)
        for r in range(nr):
            np.testing.assert_array_equal(a_out[r], b_out[r],
                                          err_msg=f"rank {r}")

    def test_all_algorithms_bitwise_vs_ring_on_exact_data(self):
        # Small-integer floats sum exactly under ANY association, so
        # bitwise equality across algorithms is well-defined — the
        # acceptance criterion's parity-against-ring form.
        rng = np.random.default_rng(13)
        data = jnp.asarray(
            rng.integers(-8, 8, (NR, 29)).astype(np.float32))

        def det_body(x, a):
            t = jax.lax.dynamic_index_in_dim(
                x, jnp.asarray(comm.rank + 0), 0, keepdims=False)
            return comm.Allreduce(t, mpi.MPI_SUM, algorithm=a)

        with mpi.config.deterministic_mode(True):
            want = np.asarray(
                mpi.run_spmd(lambda x: det_body(x, "ring"))(data))
            for algo in ("rhd", "tree", "hier", "bidir", "torus"):
                got = np.asarray(
                    mpi.run_spmd(lambda x, a=algo: det_body(x, a))(data))
                np.testing.assert_array_equal(got, want, err_msg=algo)


# ---------------------------------------------------------------------------
# HLO census: each algorithm's distinct schedule, forward and backward
# ---------------------------------------------------------------------------


class TestAlgorithmCensus:
    X = jnp.ones((16,))   # f64 under the x64 harness

    def _fwd(self, algo):
        got, txt = census(
            lambda c, x: c.Allreduce(x, mpi.MPI_SUM, algorithm=algo),
            self.X)
        return got, txt

    def _fwd_bwd(self, algo):
        got, txt = census(
            lambda c, x: jax.value_and_grad(lambda v: jnp.vdot(
                c.Allreduce(v, mpi.MPI_SUM, algorithm=algo), v))(x),
            self.X)
        return got, txt

    def test_ring_is_one_all_reduce(self):
        got, _ = self._fwd("ring")
        assert got == only(all_reduce=1)

    def test_rhd_is_log_permutes_of_shrinking_width(self):
        logn = int(math.log2(CENSUS_NR))
        got, txt = self._fwd("rhd")
        assert got == only(collective_permute=2 * logn), got
        # The butterfly never moves the full payload: halving ships
        # 8- then 4-element halves (16 elems / 4 ranks), doubling the
        # reverse — no full-width (16-element) permute anywhere.  (The
        # operand type follows the attribute dict — match `: (tensor<…`,
        # not the source_target_pairs attribute's own tensor type.)
        widths = re.findall(
            r"collective_permute.*?:\s*\(tensor<(\d+)x", txt)
        assert widths and all(int(w) < 16 for w in widths), widths
        assert {int(w) for w in widths} == {8, 4}, widths

    def test_tree_is_log_permutes_full_width(self):
        logn = int(math.ceil(math.log2(CENSUS_NR)))
        got, txt = self._fwd("tree")
        assert got == only(collective_permute=2 * logn), got
        widths = re.findall(
            r"collective_permute.*?:\s*\(tensor<(\d+)x", txt)
        assert widths and all(int(w) == 16 for w in widths), widths

    def test_hier_is_rs_ar_ag_triple(self):
        got, _ = self._fwd("hier")
        assert got == only(reduce_scatter=1, all_reduce=1, all_gather=1)

    # The two counter-rotating ring directions of the bidir dual-ring,
    # as collective_permute source_target_pairs attribute payloads.
    _FWD_RING = "[[0, 1], [1, 2], [2, 3], [3, 0]]"
    _REV_RING = "[[0, 3], [1, 0], [2, 1], [3, 2]]"

    def _permute_pair_tables(self, txt):
        return re.findall(
            r"collective_permute.*?source_target_pairs = dense<(\[\[.*?\]\])>",
            txt)

    def test_bidir_is_two_counter_rotating_half_payload_chains(self):
        # The ISSUE 4 multipath criterion: two CONCURRENT
        # counter-rotating collective_permute chains over half-payloads
        # with no serialization barrier between them — each chain is an
        # explicit ring reduce-scatter + all-gather, 2(N-1) hops.
        got, txt = self._fwd("bidir")
        assert got == only(collective_permute=4 * (CENSUS_NR - 1)), got
        tables = self._permute_pair_tables(txt)
        # exactly half the permutes ride each direction
        assert tables.count(self._FWD_RING) == 2 * (CENSUS_NR - 1), tables
        assert tables.count(self._REV_RING) == 2 * (CENSUS_NR - 1), tables
        # every permute moves a SEGMENT of a half-payload (16 elems ->
        # 8-elem halves -> 2-elem ring segments), never the full tensor
        widths = re.findall(
            r"collective_permute.*?:\s*\(tensor<(\d+)x", txt)
        assert widths and all(
            int(w) == 16 // 2 // CENSUS_NR for w in widths), widths
        # no serialization barrier between the chains: neither chain's
        # permutes consume the other's values, so no optimization_barrier
        # op separates them in the lowered module
        assert "optimization_barrier" not in txt

    def test_bidir_backward_rides_swapped_channels(self):
        # The adjoint of a ring segment is a ring segment in the reverse
        # direction: backward = the same dual-ring machinery, so fwd+bwd
        # shows exactly twice the chains, still evenly split between the
        # two rotations (the swap flips which half rides which).
        got, txt = self._fwd_bwd("bidir")
        assert got == only(collective_permute=8 * (CENSUS_NR - 1)), got
        tables = self._permute_pair_tables(txt)
        assert tables.count(self._FWD_RING) == 4 * (CENSUS_NR - 1), tables
        assert tables.count(self._REV_RING) == 4 * (CENSUS_NR - 1), tables

    def test_torus_is_one_grouped_channel_per_axis(self):
        # Flat-axis torus: the hier factorization viewed as a virtual 2D
        # torus with the payload STRIPED across the two tiers — one
        # grouped reduce-scatter/all-reduce/all-gather channel per
        # (virtual) axis, concurrent because the halves share no values.
        got, txt = self._fwd("torus")
        assert got == only(reduce_scatter=2, all_reduce=2,
                           all_gather=2), got
        # the two channels' first-stage reduce_scatters ride DIFFERENT
        # axes of the factorization: consecutive inner groups for one,
        # strided outer groups for the other (4 ranks -> 2x2)
        groups = set(re.findall(
            r"reduce_scatter.*?replica_groups = dense<(\[\[.*?\]\])>",
            txt))
        assert groups == {"[[0, 1], [2, 3]]", "[[0, 2], [1, 3]]"}, groups

    def test_torus_backward_census_doubles(self):
        got, _ = self._fwd_bwd("torus")
        assert got == only(reduce_scatter=4, all_reduce=4, all_gather=4)

    def test_backward_census_matches_forward_per_algorithm(self):
        logn = int(math.log2(CENSUS_NR))
        got, _ = self._fwd_bwd("ring")
        assert got == only(all_reduce=2)
        got, _ = self._fwd_bwd("rhd")
        assert got == only(collective_permute=4 * logn), got
        got, _ = self._fwd_bwd("tree")
        assert got == only(collective_permute=4 * logn), got
        got, _ = self._fwd_bwd("hier")
        assert got == only(reduce_scatter=2, all_reduce=2, all_gather=2)

    def test_phase_pipelined_ring_fold_drops_broadcast_steps(self):
        # ISSUE 4: the deterministic chunked ring fold's all-gather head
        # overlaps the reduce-scatter tail — completed chunks relay
        # around the ring inside the SAME fused scan, so the trailing
        # full-payload tree-broadcast hops (ceil(log2 N) sequential
        # whole-tensor permutes AFTER the fold loop in the baseline)
        # disappear: fewer sequential permute steps than the two-phase
        # baseline, and every permute is chunk-sized and lives in the
        # loop.
        saved = (mpi.config.ordered_fold_gather_max_bytes(),
                 mpi.config.ordered_ring_chunk_bytes())
        mpi.config.set_ordered_fold_gather_max_bytes(0)  # force ring fold
        mpi.config.set_ordered_ring_chunk_bytes(64)      # 16 f64 -> 2 chunks
        try:
            with mpi.config.deterministic_mode(True):
                mpi.config.set_phase_pipelined_ring(False)
                base, btxt = census(
                    lambda c, v: c.Allreduce(v, mpi.MPI_SUM), self.X)
                mpi.config.set_phase_pipelined_ring(True)
                pipe, ptxt = census(
                    lambda c, v: c.Allreduce(v, mpi.MPI_SUM), self.X)
        finally:
            mpi.config.set_ordered_fold_gather_max_bytes(saved[0])
            mpi.config.set_ordered_ring_chunk_bytes(saved[1])
            mpi.config.set_phase_pipelined_ring(True)
        # baseline: 1 in-loop fold permute + ceil(log2 N) tree hops
        logn = int(math.ceil(math.log2(CENSUS_NR)))
        assert base == only(collective_permute=1 + logn), base
        # pipelined: fold + relay lanes, both inside the one scan — no
        # trailing broadcast permutes at all
        assert pipe == only(collective_permute=2), pipe
        assert pipe["collective_permute"] < base["collective_permute"]
        # the baseline's extra hops are FULL-payload (16 elems); the
        # pipelined program never permutes more than one chunk (8 elems)
        def widths(txt):
            return {int(w) for w in re.findall(
                r"collective_permute.*?:\s*\(tensor<(\d+)x", txt)}
        assert 16 in widths(btxt), widths(btxt)
        assert max(widths(ptxt)) <= 8, widths(ptxt)

    def test_phase_pipelined_ring_fold_bits_identical(self):
        # Pipelining must not touch the fold association: both forms are
        # bit-identical to each other and to the eager oracle.
        rng = np.random.default_rng(29)
        data = jnp.asarray(
            rng.standard_normal((NR, 3000)).astype(np.float32))

        def det_body(x):
            t = jax.lax.dynamic_index_in_dim(
                x, jnp.asarray(comm.rank + 0), 0, keepdims=False)
            return comm.Allreduce(t, mpi.MPI_SUM)

        saved = (mpi.config.ordered_fold_gather_max_bytes(),
                 mpi.config.ordered_ring_chunk_bytes())
        mpi.config.set_ordered_fold_gather_max_bytes(0)
        mpi.config.set_ordered_ring_chunk_bytes(1024)
        try:
            with mpi.config.deterministic_mode(True):
                mpi.config.set_phase_pipelined_ring(False)
                base = np.asarray(mpi.run_spmd(det_body)(data))
                mpi.config.set_phase_pipelined_ring(True)
                pipe = np.asarray(mpi.run_spmd(det_body)(data))
        finally:
            mpi.config.set_ordered_fold_gather_max_bytes(saved[0])
            mpi.config.set_ordered_ring_chunk_bytes(saved[1])
            mpi.config.set_phase_pipelined_ring(True)
        np.testing.assert_array_equal(base, pipe)
        oracle = mpi.run_ranks(
            lambda: np.asarray(comm.Allreduce(
                data[comm.rank], mpi.MPI_SUM)), NR)
        for r in range(NR):
            np.testing.assert_array_equal(pipe[r], oracle[r])

    def test_reduce_tree_is_log_permutes(self):
        got, _ = census(
            lambda c, x: c.Reduce_(x, mpi.MPI_SUM, root=0,
                                   algorithm="tree"), self.X)
        assert got == only(
            collective_permute=int(math.ceil(math.log2(CENSUS_NR))))

    def test_reduce_tree_fwd_bwd_adds_tree_bcast(self):
        logn = int(math.ceil(math.log2(CENSUS_NR)))
        got, _ = census(
            lambda c, x: jax.value_and_grad(lambda v: jnp.sum(
                c.Reduce_(v, mpi.MPI_SUM, root=0,
                          algorithm="tree")))(x), self.X)
        # adjoint of the tree reduce is the tree bcast: logn more hops
        assert got == only(collective_permute=2 * logn), got

    def test_bcast_algorithm_override(self):
        # Explicit "ring" pins the masked psum even at tree-regime size;
        # explicit "tree" pins the tree even above the threshold.
        got, _ = census(lambda c, x: c.Bcast_(x, root=1,
                                              algorithm="ring"), self.X)
        assert got == only(all_reduce=1)
        big = jnp.ones((mpi.config.bcast_tree_max_bytes() // 8 + 512,))
        got, _ = census(lambda c, x: c.Bcast_(x, root=1,
                                              algorithm="tree"), big)
        assert got == only(
            collective_permute=int(math.ceil(math.log2(CENSUS_NR))))


# ---------------------------------------------------------------------------
# Selector
# ---------------------------------------------------------------------------


class TestSelector:
    def test_auto_is_ring_without_evidence(self):
        for nbytes in (64, 1 << 20):
            assert tune.select_auto(nbytes=nbytes, dtype=jnp.float32,
                                    nranks=NR) == "ring"

    def test_selection_is_deterministic(self):
        mpi.config.set_latency_crossover_bytes(4096)
        picks = {tune.select_auto(nbytes=512, dtype=jnp.float32,
                                  nranks=NR) for _ in range(5)}
        assert len(picks) == 1

    def test_measured_crossover_drives_latency_pick(self):
        mpi.config.set_latency_crossover_bytes(4096)
        assert tune.select_auto(nbytes=512, dtype=jnp.float32,
                                nranks=NR) == "rhd"
        # non-power-of-two world: tree is the latency fallback
        assert tune.select_auto(nbytes=512, dtype=jnp.float32,
                                nranks=6) == "tree"
        assert tune.select_auto(nbytes=1 << 20, dtype=jnp.float32,
                                nranks=NR) == "ring"

    def test_cached_winner_wins(self):
        tune.record("allreduce", jnp.float32, 512, NR, "tree")
        assert tune.select_auto(nbytes=512, dtype=jnp.float32,
                                nranks=NR) == "tree"
        # a different size bucket is unaffected
        assert tune.select_auto(nbytes=1 << 22, dtype=jnp.float32,
                                nranks=NR) == "ring"

    def test_bandwidth_winner_not_applied_below_latency_crossover(self):
        """ISSUE 10 satellite: decode-sized messages (a few KiB) share
        power-of-two nbytes buckets with training tail buckets, so a
        bandwidth-tier winner cached under such a key must never be
        applied below the measured latency crossover — per-token serving
        traffic stays on the latency tier."""
        tune.record("allreduce", jnp.float32, 2048, NR, "bidir")
        # Without a measured crossover the cached winner is honored.
        assert tune.select_auto(nbytes=2048, dtype=jnp.float32,
                                nranks=NR) == "bidir"
        # With the crossover above it, the bandwidth winner is voided
        # and the latency tier decides.
        mpi.config.set_latency_crossover_bytes(4096)
        assert tune.select_auto(nbytes=2048, dtype=jnp.float32,
                                nranks=NR) == "rhd"
        # A latency-optimal cached winner below the crossover is still
        # honored as recorded (the guard voids bandwidth winners only)…
        tune.record("allreduce", jnp.float32, 2048, NR, "tree")
        assert tune.select_auto(nbytes=2048, dtype=jnp.float32,
                                nranks=NR) == "tree"
        # …and above the crossover a bandwidth winner applies normally.
        tune.record("allreduce", jnp.float32, 1 << 20, NR, "bidir")
        assert tune.select_auto(nbytes=1 << 20, dtype=jnp.float32,
                                nranks=NR) == "bidir"

    def test_tier_guard_exempts_codec_keyed_winners(self):
        """Compressed traffic never shares keys with decode payloads
        (decode is always exact), so the latency-tier guard must honor
        a codec-keyed bandwidth winner below the crossover — voiding it
        would strand the message on ring (the latency algorithms fail
        the codec's declared-algorithm gate)."""
        from mpi4torch_tpu.compress import get_codec

        q8 = get_codec("q8")
        tune.record("allreduce", jnp.float32, 2048, NR, "bidir",
                    codec=q8)
        mpi.config.set_latency_crossover_bytes(4096)
        assert tune.select_auto(nbytes=2048, dtype=jnp.float32,
                                nranks=NR, codec=q8) == "bidir"

    def test_bucket_nbytes_public_rule(self):
        assert tune.bucket_nbytes(1) == 1
        assert tune.bucket_nbytes(3000) == 4096
        assert tune.bucket_nbytes(4096) == 4096

    def test_deterministic_mode_pins_ring(self):
        mpi.config.set_latency_crossover_bytes(4096)
        assert tune.select_auto(nbytes=512, dtype=jnp.float32, nranks=NR,
                                deterministic=True) == "ring"

    def test_codec_restricts_candidates(self):
        from mpi4torch_tpu.compress import get_codec
        mpi.config.set_latency_crossover_bytes(4096)
        assert tune.select_auto(nbytes=512, dtype=jnp.float32, nranks=NR,
                                codec=get_codec("q8")) == "ring"

    def test_codec_applicable_algorithm_leg(self):
        from mpi4torch_tpu.compress import codec_applicable, get_codec
        q8 = get_codec("q8")
        assert codec_applicable(q8, jnp.float32)
        assert codec_applicable(q8, jnp.float32, algorithm="ring")
        assert not codec_applicable(q8, jnp.float32, algorithm="rhd")

    def test_explicit_rhd_non_power_of_two_raises(self):
        with pytest.raises(mpi.CommError, match="power-of-two"):
            mpi.run_spmd(lambda: comm.Allreduce(
                jnp.ones(4), mpi.MPI_SUM, algorithm="rhd"), nranks=6)()
        # same rule on the eager backend
        with pytest.raises(mpi.CommError, match="power-of-two"):
            mpi.run_ranks(lambda: comm.Allreduce(
                jnp.ones(4), mpi.MPI_SUM, algorithm="rhd"), 6)

    def test_scope_rhd_degrades_on_non_power_of_two(self):
        with mpi.config.algorithm_scope("rhd"):
            out = np.asarray(mpi.run_spmd(
                lambda: comm.Allreduce(jnp.ones(4), mpi.MPI_SUM),
                nranks=6)())
        np.testing.assert_allclose(out, 6.0)

    def test_allreduce_scope_leaves_bcast_size_dispatch_alone(self):
        # An allreduce-oriented scope ("rhd" serves allreduce only)
        # must VOID for Bcast_ — back to the tree/psum size dispatch —
        # not pin the masked-psum form (degrade is to auto, not to a
        # literal "ring").
        logn = int(math.ceil(math.log2(CENSUS_NR)))
        with mpi.config.algorithm_scope("rhd"):
            got, _ = census(lambda c, x: c.Bcast_(x, root=1),
                            jnp.ones((16,)))
        assert got == only(collective_permute=logn), got

    def test_bandwidth_crossover_drives_multipath_pick(self):
        # The third tier: latency algorithm below the latency crossover,
        # ring in the middle, the multipath dual-ring at/above the
        # bandwidth crossover.
        mpi.config.set_latency_crossover_bytes(4096)
        mpi.config.set_bandwidth_crossover_bytes(1 << 20)
        assert tune.select_auto(nbytes=512, dtype=jnp.float32,
                                nranks=NR) == "rhd"
        assert tune.select_auto(nbytes=64 * 1024, dtype=jnp.float32,
                                nranks=NR) == "ring"
        assert tune.select_auto(nbytes=4 << 20, dtype=jnp.float32,
                                nranks=NR) == "bidir"
        # any-world: bidir needs no factorization or power of two
        assert tune.select_auto(nbytes=4 << 20, dtype=jnp.float32,
                                nranks=5) == "bidir"

    def test_bandwidth_tier_respects_determinism_and_codecs(self):
        from mpi4torch_tpu.compress import get_codec
        mpi.config.set_bandwidth_crossover_bytes(1 << 20)
        # deterministic mode pins the bit-exact ring fold
        assert tune.select_auto(nbytes=4 << 20, dtype=jnp.float32,
                                nranks=NR, deterministic=True) == "ring"
        # the block-q8 family declares the bandwidth tier: past the
        # crossover, compressed traffic composes with the dual ring (the
        # in-schedule quantized pipeline on both rotations) — the two
        # biggest wire wins multiply instead of excluding each other
        assert tune.select_auto(nbytes=4 << 20, dtype=jnp.float32,
                                nranks=NR, codec=get_codec("q8")) == "bidir"
        # a ring-only codec (bf16: generic encoded-ring pipeline) still
        # keeps large compressed payloads on the ring
        assert tune.select_auto(nbytes=4 << 20, dtype=jnp.float32,
                                nranks=NR,
                                codec=get_codec("bf16")) == "ring"

    def test_cached_multipath_winner_wins(self):
        tune.record("allreduce", jnp.float32, 8 << 20, NR, "torus")
        assert tune.select_auto(nbytes=8 << 20, dtype=jnp.float32,
                                nranks=NR) == "torus"
        # a cached torus winner cannot serve a prime world: auto falls
        # back (never returns an algorithm the backend would reject)
        tune.record("allreduce", jnp.float32, 8 << 20, 5, "torus")
        assert tune.select_auto(nbytes=8 << 20, dtype=jnp.float32,
                                nranks=5) == "ring"

    def test_explicit_hier_on_prime_world_raises(self):
        with pytest.raises(mpi.CommError, match="factorization"):
            mpi.run_spmd(lambda: comm.Allreduce(
                jnp.ones(4), mpi.MPI_SUM, algorithm="hier"), nranks=5)()

    def test_explicit_torus_on_prime_world_raises_scope_degrades(self):
        with pytest.raises(mpi.CommError, match="factorization"):
            mpi.run_spmd(lambda: comm.Allreduce(
                jnp.ones(4), mpi.MPI_SUM, algorithm="torus"), nranks=5)()
        # same rule on the eager backend
        with pytest.raises(mpi.CommError, match="factorization"):
            mpi.run_ranks(lambda: comm.Allreduce(
                jnp.ones(4), mpi.MPI_SUM, algorithm="torus"), 5)
        with mpi.config.algorithm_scope("torus"):
            out = np.asarray(mpi.run_spmd(
                lambda: comm.Allreduce(jnp.ones(4), mpi.MPI_SUM),
                nranks=5)())
            np.testing.assert_allclose(out, 5.0)

    def test_explicit_bidir_works_on_any_world(self):
        for nr in (2, 5):
            out = np.asarray(mpi.run_spmd(
                lambda: comm.Allreduce(jnp.ones(7), mpi.MPI_SUM,
                                       algorithm="bidir"),
                nranks=nr)())
            np.testing.assert_allclose(out, float(nr))

    def test_bidir_scan_form_bitwise_matches_unrolled(self):
        # Past config.chain_unroll_max() ranks each chain phase rolls
        # into a lax.scan (O(1) program size on big pods); the wire
        # schedule — and therefore the bits — must be identical to the
        # unrolled census form.  Force the scan form on the 8-rank
        # world via the promoted config knob (ISSUE 5 satellite; the
        # autouse fixture restores the default).
        rng = np.random.default_rng(31)
        data = jnp.asarray(rng.standard_normal((NR, 37)).astype(np.float32))

        def body(x):
            t = jax.lax.dynamic_index_in_dim(
                x, jnp.asarray(comm.rank + 0), 0, keepdims=False)
            y, g = jax.value_and_grad(lambda v: jnp.vdot(
                comm.Allreduce(v, mpi.MPI_SUM, algorithm="bidir"), v))(t)
            return y, g

        uy, ug = mpi.run_spmd(body)(data)
        mpi.config.set_chain_unroll_max(2)
        sy, sg = mpi.run_spmd(body)(data)
        np.testing.assert_array_equal(np.asarray(uy), np.asarray(sy))
        np.testing.assert_array_equal(np.asarray(ug), np.asarray(sg))

    def test_chain_unroll_max_validated_and_fingerprinted(self):
        # The ISSUE 3 threshold-promotion contract: validated setter +
        # run_spmd jit-cache fingerprint coverage.
        before = mpi.config.thresholds_fingerprint()
        mpi.config.set_chain_unroll_max(7)
        assert mpi.config.chain_unroll_max() == 7
        assert mpi.config.thresholds_fingerprint() != before
        with pytest.raises(ValueError, match="chain_unroll_max"):
            mpi.config.set_chain_unroll_max(0)
        with pytest.raises(ValueError, match="chain_unroll_max"):
            mpi.config.set_chain_unroll_max("many")

    def test_unknown_algorithm_raises(self):
        with pytest.raises(ValueError, match="unknown collective"):
            comm.Allreduce(jnp.ones(4), mpi.MPI_SUM, algorithm="warp9")

    def test_explicit_codec_plus_algorithm_conflict_raises(self):
        with pytest.raises(ValueError, match="ring"):
            mpi.run_spmd(lambda: comm.Allreduce(
                jnp.ones(64, jnp.float32), mpi.MPI_SUM,
                compression="q8", algorithm="rhd"), nranks=NR)()

    def test_rhd_not_valid_for_bcast(self):
        with pytest.raises(mpi.CommError, match="serves"):
            mpi.run_spmd(lambda: comm.Bcast_(
                jnp.ones(4), 0, algorithm="rhd"), nranks=NR)()


# ---------------------------------------------------------------------------
# Cache round-trip
# ---------------------------------------------------------------------------


class TestCacheRoundTrip:
    KEY = dict(collective="allreduce", dtype="float32", nbytes=512,
               nranks=8)

    def test_record_persists_and_reloads(self):
        tune.record("allreduce", "float32", 512, 8, "rhd",
                    measurements={"ring": 1e-3, "rhd": 5e-4})
        path = tune.cache_path()
        with open(path) as f:
            data = json.load(f)
        from mpi4torch_tpu.tune.autotuner import CACHE_VERSION
        assert data["version"] == CACHE_VERSION
        assert any(v["algorithm"] == "rhd" for v in data["entries"].values())
        # fresh in-process table: the entry comes back from disk
        tune.clear()
        assert tune.lookup_algorithm(**self.KEY) == "rhd"
        assert tune.entry_from_disk(**self.KEY)

    def test_corrupt_cache_falls_back_without_crashing(self):
        with open(tune.cache_path(), "w") as f:
            f.write("{ not json ][")
        tune.clear()
        assert tune.lookup(**self.KEY) is None
        assert tune.select_auto(nbytes=512, dtype=jnp.float32,
                                nranks=8) == "ring"
        # and the file is recoverable by the next record
        tune.record("allreduce", "float32", 512, 8, "tree")
        tune.clear()
        assert tune.lookup_algorithm(**self.KEY) == "tree"

    def test_wrong_version_ignored(self):
        with open(tune.cache_path(), "w") as f:
            json.dump({"version": 999, "entries": {
                tune.make_key("allreduce", "float32", 512, 8):
                    {"algorithm": "rhd"}}}, f)
        tune.clear()
        assert tune.lookup(**self.KEY) is None

    def test_stale_algorithm_name_ignored(self):
        # A cache written by a future/older build naming an algorithm
        # this build does not register must not crash or mis-select.
        with open(tune.cache_path(), "w") as f:
            json.dump({"version": 1, "entries": {
                tune.make_key("allreduce", "float32", 512, 8):
                    {"algorithm": "warp9"}}}, f)
        tune.clear()
        assert tune.lookup(**self.KEY) is None
        assert tune.select_auto(nbytes=512, dtype=jnp.float32,
                                nranks=8) == "ring"

    def test_clear_remove_file_resets_to_defaults(self):
        tune.record("allreduce", "float32", 512, 8, "tree")
        tune.clear(remove_file=True)
        assert tune.lookup(**self.KEY) is None

    def test_generation_bumps_on_mutation(self):
        g0 = tune.generation()
        tune.record("allreduce", "float32", 512, 8, "tree")
        assert tune.generation() > g0

    def test_concurrent_saves_union_instead_of_losing_work(self):
        # Two processes tuning simultaneously: each write goes through a
        # UNIQUE tempfile + os.replace (readers never see a torn file)
        # and merges entries the other process persisted meanwhile —
        # last-writer-wins only per key, never whole-file.
        import os
        tune.record("allreduce", "float32", 512, 8, "rhd")
        # simulate the OTHER process persisting its own winner between
        # our record() calls: inject a foreign key directly on disk
        with open(tune.cache_path()) as f:
            data = json.load(f)
        foreign = tune.make_key("allreduce", "float32", 1 << 20, 16,
                                platform="cpu")
        data["entries"][foreign] = {"algorithm": "bidir"}
        with open(tune.cache_path(), "w") as f:
            json.dump(data, f)
        tune.record("allreduce", "float32", 2048, 8, "tree")
        with open(tune.cache_path()) as f:
            final = json.load(f)
        assert final["entries"][foreign]["algorithm"] == "bidir"
        algos = {e["algorithm"] for e in final["entries"].values()}
        assert algos == {"rhd", "tree", "bidir"}
        # no staging litter left behind in the cache directory
        cache_dir = os.path.dirname(tune.cache_path())
        assert not [p for p in os.listdir(cache_dir)
                    if p.endswith(".tmp")]

    def test_unwritable_cache_dir_degrades_in_process(self, monkeypatch,
                                                      tmp_path):
        # The save is best-effort: a cache path whose directory cannot
        # be created degrades to in-process-only tuning, never an error.
        blocker = tmp_path / "blocker"
        blocker.write_text("not a directory")
        monkeypatch.setenv("MPI4TORCH_TPU_TUNE_CACHE",
                           str(blocker / "tune_cache.json"))
        tune.clear()
        tune.record("allreduce", "float32", 512, 8, "tree")
        assert tune.lookup_algorithm("allreduce", "float32", 512,
                                     8) == "tree"


class TestCacheCli:
    """`python -m mpi4torch_tpu.tune --show/--clear` (ISSUE 4
    satellite): the winners table without reading raw JSON."""

    def _run(self, *argv):
        from mpi4torch_tpu.tune.__main__ import _main
        return _main(list(argv))

    def test_show_prints_winners_table(self, capsys):
        tune.record("allreduce", "float32", 512, 8, "rhd",
                    platform="cpu",
                    measurements={"ring": 1e-3, "rhd": 5e-4})
        tune.record("allreduce", "float32", 4 << 20, 8, "bidir",
                    platform="cpu")
        assert self._run("--show") == 0
        out = capsys.readouterr().out
        # one row per key: collective, dtype, size bucket, nranks,
        # platform -> algorithm
        # one row per key; flat (untied) entries show "-" in the tiers
        # column
        assert re.search(r"allreduce\s+float32\s+512\s+8\s+cpu\s+-\s+rhd",
                         out)
        assert re.search(
            r"allreduce\s+float32\s+4194304\s+8\s+cpu\s+-\s+bidir", out)
        assert "2 cached winner(s)" in out

    def test_show_empty_and_missing_cache(self, capsys):
        assert self._run() == 0
        assert "no cache" in capsys.readouterr().out

    def test_clear_removes_file(self, capsys):
        tune.record("allreduce", "float32", 512, 8, "tree")
        assert self._run("--clear") == 0
        tune.clear()
        assert tune.lookup("allreduce", "float32", 512, 8) is None
        assert self._run("--clear") == 0   # idempotent
        assert "no cache file" in capsys.readouterr().out

    def test_json_dump(self, capsys):
        tune.record("allreduce", "float32", 512, 8, "tree")
        assert self._run("--json") == 0
        data = json.loads(capsys.readouterr().out)
        assert any(e["algorithm"] == "tree"
                   for e in data["entries"].values())


class TestAutotunerMeasurement:
    def test_measure_then_serve_from_cache(self):
        sizes = (256, 2048)
        rep = tune.autotune_allreduce(sizes=sizes, nranks=4, iters=1)
        assert rep["tuned_from_cache"] is False
        assert set(rep["entries"]) == {"256", "2048"}
        for ent in rep["entries"].values():
            assert ent["winner"] in ALGOS
            assert set(ent["algorithms"]) >= {"ring", "tree"}
        # The persisted winners serve a second (fresh-table) run with
        # zero measurement — the tuned_from_cache evidence.
        tune.clear()
        rep2 = tune.ensure_tuned_allreduce(sizes=sizes, nranks=4, iters=1)
        assert rep2["tuned_from_cache"] is True
        assert rep2["from_disk"] is True   # table was cleared: real file
        assert {k: v["winner"] for k, v in rep2["entries"].items()} == \
            {k: v["winner"] for k, v in rep["entries"].items()}
        assert "crossover_bytes" in rep2


# ---------------------------------------------------------------------------
# hier on a 2D mesh
# ---------------------------------------------------------------------------


class TestHier2DMesh:
    def _mesh2d(self):
        return mpi.device_mesh({"g": 2, "l": 4})

    def test_single_axis_hier_inside_2d_mesh(self):
        # hier over one axis of a 2D mesh: the grouped schedule must
        # compose with an unrelated second mesh axis in scope.
        mesh = self._mesh2d()
        c = mpi.comm_from_mesh(mesh, "l")
        got, _ = census(
            lambda cc, x: cc.Allreduce(x, mpi.MPI_SUM, algorithm="hier"),
            jnp.arange(12.0), mesh_axes=(mesh, c))
        assert got == only(reduce_scatter=1, all_reduce=1, all_gather=1)
        f = jax.jit(shard_map(
            lambda x: c.Allreduce(x, mpi.MPI_SUM, algorithm="hier"),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
        x = jnp.arange(12.0)
        np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x) * 4)

    def test_two_axis_hier_comm_values_and_grads(self):
        mesh = self._mesh2d()
        hc = mpi.comm_from_mesh(mesh, ("g", "l"))
        assert hc.size == 8
        x = jnp.arange(13.0, dtype=jnp.float32)
        f = jax.jit(shard_map(lambda v: hc.Allreduce(v, mpi.MPI_SUM),
                              mesh=mesh, in_specs=P(), out_specs=P(),
                              check_vma=False))
        np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x) * 8)
        g = jax.jit(shard_map(
            lambda v: jax.grad(lambda y: jnp.vdot(
                hc.Allreduce(y, mpi.MPI_SUM), y))(v),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))(x)
        # adjoint of a sum-allreduce: allreduce of 2x, itself summed
        np.testing.assert_allclose(np.asarray(g), np.asarray(x) * 16)

    def test_two_axis_hier_census_is_rs_ar_ag(self):
        mesh = self._mesh2d()
        hc = mpi.comm_from_mesh(mesh, ("g", "l"))
        got, _ = census(lambda cc, x: cc.Allreduce(x, mpi.MPI_SUM),
                        jnp.arange(12.0), mesh_axes=(mesh, hc))
        assert got == only(reduce_scatter=1, all_reduce=1, all_gather=1)

    def test_two_axis_deterministic_matches_eager_grouped_bitwise(self):
        mesh = self._mesh2d()
        hc = mpi.comm_from_mesh(mesh, ("g", "l"))
        rng = np.random.default_rng(17)
        data = jnp.asarray(rng.standard_normal((8, 21)).astype(np.float32))

        def det_body(x):
            t = jax.lax.dynamic_index_in_dim(
                x, hc.rank, 0, keepdims=False)
            return hc.Allreduce(t, mpi.MPI_SUM)

        with mpi.config.deterministic_mode(True):
            f = jax.jit(shard_map(det_body, mesh=mesh, in_specs=P(),
                                  out_specs=P(("g", "l")),
                                  check_vma=False))
            a_out = np.asarray(f(data)).reshape(8, -1)
        # the 2-axis group is the inner axis extent (4 consecutive
        # ranks); the eager hier fold with the same group matches bitwise
        mpi.config.set_hier_group_size(4)
        try:
            b_out = mpi.run_ranks(
                lambda: np.asarray(comm.Allreduce(
                    data[comm.rank], mpi.MPI_SUM, algorithm="hier")), 8)
        finally:
            mpi.config.set_hier_group_size(None)
        for r in range(8):
            np.testing.assert_array_equal(a_out[0], b_out[r])

    def test_two_axis_torus_census_one_channel_per_axis(self):
        # The ISSUE 4 acceptance criterion: torus on a 2D mesh lowers to
        # one ring channel per axis — the halves' first-stage grouped
        # reduce_scatters ride the inner ("l") and outer ("g") mesh axes
        # respectively (distinct replica_groups), with no dependency
        # between the halves.
        mesh = self._mesh2d()
        hc = mpi.comm_from_mesh(mesh, ("g", "l"))
        got, txt = census(
            lambda cc, x: cc.Allreduce(x, mpi.MPI_SUM, algorithm="torus"),
            jnp.arange(12.0), mesh_axes=(mesh, hc))
        assert got == only(reduce_scatter=2, all_reduce=2, all_gather=2)
        groups = set(re.findall(
            r"reduce_scatter.*?replica_groups = dense<(\[\[.*?\]\])>",
            txt))
        assert groups == {"[[0, 1, 2, 3], [4, 5, 6, 7]]",
                          "[[0, 4], [1, 5], [2, 6], [3, 7]]"}, groups

    def test_two_axis_torus_values_and_grads(self):
        mesh = self._mesh2d()
        hc = mpi.comm_from_mesh(mesh, ("g", "l"))
        x = jnp.arange(13.0, dtype=jnp.float32)
        f = jax.jit(shard_map(
            lambda v: hc.Allreduce(v, mpi.MPI_SUM, algorithm="torus"),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
        np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x) * 8)
        g = jax.jit(shard_map(
            lambda v: jax.grad(lambda y: jnp.vdot(
                hc.Allreduce(y, mpi.MPI_SUM, algorithm="torus"), y))(v),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))(x)
        np.testing.assert_allclose(np.asarray(g), np.asarray(x) * 16)

    def test_two_axis_torus_deterministic_matches_eager_bitwise(self):
        # Mode A (2-axis torus schedule, deterministic grouped-halves
        # fold) vs Mode B (constants.reduce_torus with inner = the
        # inner-axis extent): the ISSUE 4 A/B contract on a 2D-mesh
        # world.
        mesh = self._mesh2d()
        hc = mpi.comm_from_mesh(mesh, ("g", "l"))
        rng = np.random.default_rng(23)
        data = jnp.asarray(rng.standard_normal((8, 21)).astype(np.float32))

        def det_body(x):
            t = jax.lax.dynamic_index_in_dim(
                x, hc.rank, 0, keepdims=False)
            return hc.Allreduce(t, mpi.MPI_SUM, algorithm="torus")

        with mpi.config.deterministic_mode(True):
            f = jax.jit(shard_map(det_body, mesh=mesh, in_specs=P(),
                                  out_specs=P(("g", "l")),
                                  check_vma=False))
            a_out = np.asarray(f(data)).reshape(8, -1)
        mpi.config.set_hier_group_size(4)
        try:
            b_out = mpi.run_ranks(
                lambda: np.asarray(comm.Allreduce(
                    data[comm.rank], mpi.MPI_SUM, algorithm="torus")), 8)
        finally:
            mpi.config.set_hier_group_size(None)
        for r in range(8):
            np.testing.assert_array_equal(a_out[0], b_out[r])

    def test_two_axis_auto_picks_torus_past_bandwidth_crossover(self):
        # The 2-axis backend grows the bandwidth tier too: auto = the
        # staged hier schedule below the measured crossover, the
        # multipath torus striping at/above it.
        mesh = self._mesh2d()
        hc = mpi.comm_from_mesh(mesh, ("g", "l"))
        mpi.config.set_bandwidth_crossover_bytes(1 << 10)
        big = jnp.ones((512,))    # 4 KiB f64 >= crossover
        got, _ = census(lambda cc, x: cc.Allreduce(x, mpi.MPI_SUM),
                        big, mesh_axes=(mesh, hc))
        assert got == only(reduce_scatter=2, all_reduce=2, all_gather=2)
        small = jnp.ones((16,))   # 128 B < crossover: staged hier
        got, _ = census(lambda cc, x: cc.Allreduce(x, mpi.MPI_SUM),
                        small, mesh_axes=(mesh, hc))
        assert got == only(reduce_scatter=1, all_reduce=1, all_gather=1)

    def test_two_axis_comm_rejects_other_ops_and_algorithms(self):
        mesh = self._mesh2d()
        hc = mpi.comm_from_mesh(mesh, ("g", "l"))
        with pytest.raises(mpi.CommError, match="Allreduce only"):
            jax.jit(shard_map(lambda x: hc.Bcast_(x, 0), mesh=mesh,
                              in_specs=P(), out_specs=P(),
                              check_vma=False)).lower(jnp.ones(4))
        with pytest.raises(mpi.CommError, match="single-axis"):
            jax.jit(shard_map(
                lambda x: hc.Allreduce(x, mpi.MPI_SUM, algorithm="rhd"),
                mesh=mesh, in_specs=P(), out_specs=P(),
                check_vma=False)).lower(jnp.ones(4))
        # bidir needs a single ring axis too: explicit raises, scope
        # yields to the native schedule
        with pytest.raises(mpi.CommError, match="single-axis"):
            jax.jit(shard_map(
                lambda x: hc.Allreduce(x, mpi.MPI_SUM,
                                       algorithm="bidir"),
                mesh=mesh, in_specs=P(), out_specs=P(),
                check_vma=False)).lower(jnp.ones(4))
        with mpi.config.algorithm_scope("bidir"):
            got, _ = census(lambda cc, x: cc.Allreduce(x, mpi.MPI_SUM),
                            jnp.ones(16), mesh_axes=(mesh, hc))
        assert got == only(reduce_scatter=1, all_reduce=1, all_gather=1)

    def test_invalid_config_group_raises(self):
        mpi.config.set_hier_group_size(3)   # does not divide 8
        try:
            with pytest.raises(mpi.CommError, match="hier_group_size"):
                mpi.run_spmd(lambda: comm.Allreduce(
                    jnp.ones(4), mpi.MPI_SUM, algorithm="hier"),
                    nranks=NR)()
        finally:
            mpi.config.set_hier_group_size(None)

    def test_scope_hier_with_invalid_config_group_degrades(self):
        # Same misconfiguration, but as a SCOPE default: degrade to
        # ring (the facade's degrade/raise rule reaches backend-side
        # validation too), on both backends.
        mpi.config.set_hier_group_size(3)   # does not divide 8
        try:
            with mpi.config.algorithm_scope("hier"):
                out = np.asarray(mpi.run_spmd(
                    lambda: comm.Allreduce(jnp.ones(4), mpi.MPI_SUM),
                    nranks=NR)())
                np.testing.assert_allclose(out, float(NR))
                res = mpi.run_ranks(lambda: np.asarray(
                    comm.Allreduce(jnp.ones(4), mpi.MPI_SUM)), NR)
                np.testing.assert_allclose(res[0], float(NR))
        finally:
            mpi.config.set_hier_group_size(None)

    def test_explicit_hier_on_degenerate_two_axis_mesh(self):
        # The flat-world registry gate (group factorization of the rank
        # PRODUCT) must not veto an explicit "hier" on a 2-axis comm —
        # the tiers are the mesh axes themselves, so even a product
        # with no nontrivial divisor lowers fine.
        mesh = mpi.device_mesh({"g": 2, "l": 1},
                               devices=jax.devices()[:2])
        hc = mpi.comm_from_mesh(mesh, ("g", "l"))
        x = jnp.arange(5.0)
        f = jax.jit(shard_map(
            lambda v: hc.Allreduce(v, mpi.MPI_SUM, algorithm="hier"),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
        np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x) * 2)

    def test_scope_algorithm_degrades_on_two_axis_comm(self):
        # A scope default the 2-axis backend cannot lower must yield to
        # its native hier schedule, not raise (only explicit rhd/tree
        # raise — covered above).
        mesh = self._mesh2d()
        hc = mpi.comm_from_mesh(mesh, ("g", "l"))
        x = jnp.arange(9.0)
        with mpi.config.algorithm_scope("tree"):
            f = jax.jit(shard_map(
                lambda v: hc.Allreduce(v, mpi.MPI_SUM), mesh=mesh,
                in_specs=P(), out_specs=P(), check_vma=False))
            np.testing.assert_allclose(np.asarray(f(x)),
                                       np.asarray(x) * 8)

    def test_scope_codec_degrades_on_two_axis_comm(self):
        # No compressed pipeline on the 2-axis backend: a scope codec
        # degrades to the exact wire; an explicit one raises.
        mesh = self._mesh2d()
        hc = mpi.comm_from_mesh(mesh, ("g", "l"))
        x = jnp.arange(9.0, dtype=jnp.float32)
        with mpi.config.compression_scope("q8"):
            f = jax.jit(shard_map(
                lambda v: hc.Allreduce(v, mpi.MPI_SUM), mesh=mesh,
                in_specs=P(), out_specs=P(), check_vma=False))
            np.testing.assert_allclose(np.asarray(f(x)),
                                       np.asarray(x) * 8)
        with pytest.raises(ValueError, match="compressed pipeline"):
            jax.jit(shard_map(
                lambda v: hc.Allreduce(v, mpi.MPI_SUM,
                                       compression="q8"),
                mesh=mesh, in_specs=P(), out_specs=P(),
                check_vma=False)).lower(x)

    def test_two_axis_comm_scope_and_fused_paths(self):
        # Scope defaults the 2-axis backend cannot lower must yield to
        # its native schedule through EVERY entry point — including the
        # fused tree, whose per-bucket facade calls forward resolved
        # names as explicit; and algorithm=False must force auto (hier)
        # even inside a scope.
        mesh = self._mesh2d()
        hc = mpi.comm_from_mesh(mesh, ("g", "l"))
        x = {"a": jnp.arange(7.0), "b": jnp.ones((5,))}
        with mpi.config.algorithm_scope("rhd"):
            f = jax.jit(shard_map(
                lambda t: hc.Allreduce_tree(t, mpi.MPI_SUM),
                mesh=mesh, in_specs=P(), out_specs=P(),
                check_vma=False))
            out = f(x)
            np.testing.assert_allclose(np.asarray(out["a"]),
                                       np.asarray(x["a"]) * 8)
        with mpi.config.algorithm_scope("ring"):
            got, _ = census(
                lambda cc, v: cc.Allreduce(v, mpi.MPI_SUM,
                                           algorithm=False),
                jnp.arange(12.0), mesh_axes=(mesh, hc))
        # False overrides the ring scope: auto = the native 2-level
        # schedule, not the flat psum
        assert got == only(reduce_scatter=1, all_reduce=1, all_gather=1)

    def test_backend_attribute_protocol_intact(self):
        # __getattr__ must stay protocol-correct: hasattr/getattr with
        # a default return normally for non-collective names; only the
        # known unsupported ops get the informative CommError.
        from mpi4torch_tpu.ops.spmd import HierMeshBackend
        hb = HierMeshBackend(("g", "l"), (2, 4))
        assert not hasattr(hb, "no_such_attribute")
        assert getattr(hb, "also_missing", None) is None
        with pytest.raises(mpi.CommError, match="Allreduce only"):
            hb.gather


# ---------------------------------------------------------------------------
# Fused per-bucket algorithm picks
# ---------------------------------------------------------------------------


class TestFusePerBucket:
    TREE = {"big": jnp.ones((3000,), jnp.float32),
            "small": jnp.ones((10,), jnp.float32)}

    def test_small_tail_bucket_takes_latency_algorithm(self):
        logn = int(math.log2(CENSUS_NR))
        mpi.config.set_latency_crossover_bytes(1024)
        got, _ = census(
            lambda c, t: c.Allreduce_tree(t, mpi.MPI_SUM,
                                          bucket_bytes=8192), self.TREE)
        # body bucket: one all-reduce (the ring, whole; PR 35); tail
        # bucket (40 B < crossover): the rhd butterfly
        assert got == only(all_reduce=1,
                           collective_permute=2 * logn), got

    def test_body_bucket_takes_bidir_past_bandwidth_crossover(self):
        # Three-tier fused picks (ISSUE 4): the body bucket (12000 B,
        # past the bandwidth crossover) rides the multipath dual-ring —
        # two counter-rotating chains — while the 40 B tail bucket keeps
        # the latency algorithm; no ring pair remains.
        logn = int(math.log2(CENSUS_NR))
        mpi.config.set_latency_crossover_bytes(1024)
        mpi.config.set_bandwidth_crossover_bytes(8192)
        got, _ = census(
            lambda c, t: c.Allreduce_tree(t, mpi.MPI_SUM,
                                          bucket_bytes=8192), self.TREE)
        assert got == only(
            collective_permute=4 * (CENSUS_NR - 1) + 2 * logn), got

    def test_without_crossover_every_bucket_is_one_ring_allreduce(self):
        # Since PR 35 a bucket's ring is one whole all-reduce, not a
        # reduce-scatter + all-gather pair.
        got, _ = census(
            lambda c, t: c.Allreduce_tree(t, mpi.MPI_SUM,
                                          bucket_bytes=8192), self.TREE)
        assert got == only(all_reduce=2), got

    def test_explicit_algorithm_pins_every_bucket(self):
        logn = int(math.ceil(math.log2(CENSUS_NR)))
        got, _ = census(
            lambda c, t: c.Allreduce_tree(t, mpi.MPI_SUM,
                                          bucket_bytes=8192,
                                          algorithm="tree"), self.TREE)
        assert got == only(collective_permute=2 * 2 * logn), got

    def test_compressed_buckets_stay_on_ring(self):
        mpi.config.set_latency_crossover_bytes(1024)
        _, txt = census(
            lambda c, t: c.Allreduce_tree(t, mpi.MPI_SUM,
                                          compression="q8",
                                          bucket_bytes=8192), self.TREE)
        # every bucket rides the quantized ring (int8 permutes); the
        # latency pick must not hijack a compressed bucket
        assert re.search(r"collective_permute.*xi8>", txt)

    def test_scope_hier_with_invalid_group_degrades_in_fused_path(self):
        # The fused path forwards per-bucket picks to comm.Allreduce as
        # explicit; backend-side applicability (config.hier_group_size
        # not dividing the comm) must still follow the scope-default
        # degrade rule — same observable as the bare facade call.
        mpi.config.set_hier_group_size(3)   # does not divide 8
        try:
            with mpi.config.algorithm_scope("hier"):
                out = mpi.run_spmd(lambda: comm.Allreduce_tree(
                    self.TREE, mpi.MPI_SUM, bucket_bytes=8192),
                    nranks=NR)()
            np.testing.assert_allclose(np.asarray(out["small"][0]),
                                       float(NR))
        finally:
            mpi.config.set_hier_group_size(None)

    def test_conflict_exception_type_matches_facade(self):
        # The same user error must raise the same exception type
        # through both entry points (one shared reconcile helper).
        with pytest.raises(ValueError, match="ring"):
            mpi.run_spmd(lambda: comm.Allreduce_tree(
                self.TREE, mpi.MPI_SUM, compression="q8",
                algorithm="rhd"), nranks=NR)()

    def test_int_buckets_keep_scope_algorithm_under_codec_scope(self):
        # A non-float bucket drops the scope codec (dtype degrade) and
        # must then honor the scope algorithm — matching what the
        # per-tensor facade does on the bare tensor (reconciliation is
        # per bucket, not tree-wide).
        logn = int(math.ceil(math.log2(CENSUS_NR)))
        itree = {"i": jnp.ones((64,), jnp.int32)}
        with mpi.config.compression_scope("q8"), \
                mpi.config.algorithm_scope("tree"):
            got, _ = census(
                lambda c, t: c.Allreduce_tree(t, mpi.MPI_SUM,
                                              bucket_bytes=8192), itree)
        assert got == only(collective_permute=2 * logn), got

    def test_fused_values_match_per_leaf(self):
        mpi.config.set_latency_crossover_bytes(1024)

        def body():
            return comm.Allreduce_tree(self.TREE, mpi.MPI_SUM,
                                       bucket_bytes=8192, mean=True)

        out = mpi.run_spmd(body, nranks=NR)()
        np.testing.assert_allclose(np.asarray(out["big"][0]), 1.0)
        np.testing.assert_allclose(np.asarray(out["small"][0]), 1.0)


# ---------------------------------------------------------------------------
# Config knobs
# ---------------------------------------------------------------------------


class TestConfigKnobs:
    def test_threshold_validation(self):
        with pytest.raises(ValueError):
            mpi.config.set_ordered_ring_chunk_bytes(0)
        with pytest.raises(ValueError):
            mpi.config.set_bcast_tree_max_bytes(-1)
        with pytest.raises(ValueError):
            mpi.config.set_latency_crossover_bytes("lots")
        with pytest.raises(ValueError):
            mpi.config.set_hier_group_size(1)
        with pytest.raises(ValueError):
            mpi.config.set_default_algorithm("warp9")

    def test_threshold_roundtrip_and_fingerprint(self):
        saved = mpi.config.bcast_tree_max_bytes()
        fp0 = mpi.config.thresholds_fingerprint()
        try:
            mpi.config.set_bcast_tree_max_bytes(12345)
            assert mpi.config.bcast_tree_max_bytes() == 12345
            assert mpi.config.thresholds_fingerprint() != fp0
        finally:
            mpi.config.set_bcast_tree_max_bytes(saved)
        assert mpi.config.thresholds_fingerprint() == fp0

    def test_algorithm_scope_nesting(self):
        assert mpi.config.default_algorithm() is None
        with mpi.config.algorithm_scope("tree"):
            assert mpi.config.default_algorithm() == "tree"
            with mpi.config.algorithm_scope(None):
                assert mpi.config.default_algorithm() is None
            assert mpi.config.default_algorithm() == "tree"
        assert mpi.config.default_algorithm() is None

    def test_autotuner_can_override_promoted_thresholds(self):
        # The promoted thresholds accept measured overrides (the
        # autotuner writes latency_crossover) — the setters are the
        # override surface for the other three.
        saved = (mpi.config.ordered_fold_gather_max_bytes(),
                 mpi.config.ordered_ring_chunk_bytes())
        try:
            mpi.config.set_ordered_fold_gather_max_bytes(1 << 16)
            mpi.config.set_ordered_ring_chunk_bytes(1 << 12)
            assert mpi.config.ordered_fold_gather_max_bytes() == 1 << 16
            assert mpi.config.ordered_ring_chunk_bytes() == 1 << 12
        finally:
            mpi.config.set_ordered_fold_gather_max_bytes(saved[0])
            mpi.config.set_ordered_ring_chunk_bytes(saved[1])


# ---------------------------------------------------------------------------
# (algorithm × codec) census: every pair the registries compose, guarded
# ---------------------------------------------------------------------------


class TestCodecAlgorithmCensus:
    """One forward HLO census per (codec-capable algorithm × codec)
    pair — parametrized from the LIVE registries
    (_codec_algorithm_pairs), so an unguarded combination cannot exist:
    registering one makes a census test appear, and the registry-sync
    guard pins the enumeration rules.  The expected collective counts
    are STRUCTURAL: per error-feedback round and per multipath channel,
    a quantized ring is (n-1) permute hops of the payload leaves plus
    one encoded all-gather of each leaf."""

    # big enough that both multipath halves are non-empty and span
    # multiple q8 blocks per chunk
    X = jnp.ones((4096,), jnp.float32)

    @pytest.mark.parametrize("algo,codec", _codec_algorithm_pairs())
    def test_pair_census(self, algo, codec):
        from mpi4torch_tpu.compress import get_codec

        cobj = get_codec(codec)
        leaves = len(jax.tree_util.tree_leaves(
            cobj.base().encode(jnp.ones(64, jnp.float32))[0]))
        channels = 2 if algo in ("bidir", "torus") else 1
        rounds = cobj.ef_rounds
        got, txt = census(
            lambda c, x: c.Allreduce(x, mpi.MPI_SUM, compression=codec,
                                     algorithm=algo), self.X)
        n = CENSUS_NR
        assert got["all_reduce"] == 0, (algo, codec, got)
        assert got["collective_permute"] == \
            rounds * channels * (n - 1) * leaves, (algo, codec, got)
        assert got["all_gather"] == rounds * channels * leaves, \
            (algo, codec, got)
        if cobj.base().hop_fused:
            # the quantized payload rides int8 end-to-end
            assert re.search(r"collective_permute.*xi8>", txt)
            assert re.search(r"all_gather.*xi8>", txt)

    @pytest.mark.parametrize("codec", ["q8", "q8_ef_hop"])
    def test_bidir_int8_permutes_on_both_rotations(self, codec):
        # The tentpole's census criterion: int8 collective_permutes on
        # BOTH source_target_pairs rotations of the dual ring.
        from mpi4torch_tpu.compress import int8_rotation_census

        _, txt = census(
            lambda c, x: c.Allreduce(x, mpi.MPI_SUM, compression=codec,
                                     algorithm="bidir"), self.X)
        norm, fwd, bwd = int8_rotation_census(txt, CENSUS_NR)
        assert fwd in norm and bwd in norm, (
            f"int8 permutes must ride both rotations; saw {sorted(norm)}")

    def test_bidir_fwd_bwd_census_doubles_with_swapped_rotations(self):
        # AD transparency on the multipath wire: the backward is the
        # same dual-ring schedule with channel directions swapped, so
        # the fwd+bwd program has exactly 2x the quantized collectives.
        got, txt = census(
            lambda c, x: jax.value_and_grad(lambda v: jnp.vdot(
                c.Allreduce(v, mpi.MPI_SUM, compression="q8",
                            algorithm="bidir"), v))(x), self.X)
        n = CENSUS_NR
        assert got["collective_permute"] == 2 * 2 * 2 * (n - 1)
        assert got["all_gather"] == 2 * 2 * 2
        assert got["all_reduce"] == 0

    def test_codec_keyed_cache_dimension(self):
        # The tune cache's codec dimension: compressed winners live
        # under their own keys and cannot hijack exact traffic.
        key_exact = tune.make_key("allreduce", jnp.float32, 1 << 20, NR,
                                  platform="cpu")
        key_q8 = tune.make_key("allreduce", jnp.float32, 1 << 20, NR,
                               platform="cpu", codec="q8")
        assert key_exact != key_q8 and key_q8.endswith("codec=q8")
        tune.record("allreduce", jnp.float32, 1 << 20, NR, "torus",
                    codec="q8")
        from mpi4torch_tpu.compress import get_codec
        assert tune.select_auto(nbytes=1 << 20, dtype=jnp.float32,
                                nranks=NR, codec=get_codec("q8")) == "torus"
        # exact traffic is untouched by the compressed winner
        assert tune.select_auto(nbytes=1 << 20, dtype=jnp.float32,
                                nranks=NR) == "ring"

    def test_autotune_sweep_codec_dimension(self):
        # The sweep's codec leg records winners under codec keys and
        # restricts candidates to what the codec declares.
        report = tune.autotune_allreduce(
            sizes=(1 << 12,), nranks=4, iters=1, persist=False,
            codecs=(None, "q8"))
        ent = report["entries"][str(1 << 12)]
        assert "winner" in ent                      # exact sweep intact
        q8_ent = ent["codecs"]["q8"]
        assert set(q8_ent["algorithms"]) <= set(CODEC_CAPABLE)
        assert "winner" in q8_ent
        assert tune.lookup_algorithm("allreduce", jnp.float32, 1 << 12, 4,
                                     codec="q8") == q8_ent["winner"]


class TestMeasurementRobustness:
    """ISSUE 7 satellite: per-size measurement is min-of-k, so a single
    preempted/slow sample cannot poison a persisted cache winner."""

    def test_time_step_is_outlier_immune(self):
        # 3 of the 5 timed samples are hit by a simulated preemption
        # pause — the OLD median-of-k would report >= the pause; the
        # min-of-k estimate must stay at the true (fast) step cost.
        import time as _time

        from mpi4torch_tpu.tune import autotuner as at

        calls = {"n": 0}

        def step(x):
            calls["n"] += 1
            # calls 1-2 are warmup; timed samples are calls 3..7 — hit
            # the 2nd, 3rd and 4th timed samples (median territory).
            if calls["n"] in (4, 5, 6):
                _time.sleep(0.12)
            return (x,)

        dt = at._time_step(step, jnp.ones((8,), jnp.float32), iters=5)
        assert dt < 0.06, (
            f"min-of-k must shrug off one-sided outliers, got {dt}")

    def test_outlier_cannot_flip_a_winner(self):
        # The decision-level regression: with the measurement rule
        # applied to two candidates' raw sample sets, a preemption hit
        # on the TRUE winner must not hand the cache key to the loser.
        # (Median-of-5 flips here: 3 of ring's 5 samples are hit.)
        from mpi4torch_tpu.tune import autotuner as at

        ring_samples = [0.001, 0.50, 0.48, 0.52, 0.001]   # true 1ms
        tree_samples = [0.002] * 5                        # true 2ms

        def measure(samples):
            # Drive _time_step's clock: each timed step() call advances
            # a fake perf_counter by its scripted duration (warmups: 0).
            import time as _time

            real = _time.perf_counter
            acc = {"t": 0.0}
            calls = {"n": 0}

            def step(x):
                calls["n"] += 1
                if calls["n"] > 2:   # calls 1-2 are warmup
                    acc["t"] += samples[calls["n"] - 3]
                return (x,)

            _time.perf_counter = lambda: acc["t"]
            try:
                return at._time_step(step, jnp.ones((4,), jnp.float32),
                                     iters=len(samples))
            finally:
                _time.perf_counter = real

        assert measure(ring_samples) < measure(tree_samples), (
            "the outlier-hit true winner must still measure fastest")

    def test_cache_version_keys_in_the_min_rule(self):
        # Winners measured under the old median rule must be discarded:
        # the measurement-rule change rides the cache version.
        from mpi4torch_tpu.tune import autotuner as at

        assert at.CACHE_VERSION >= 2
