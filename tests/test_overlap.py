"""mpi4torch_tpu.overlap — split-phase nonblocking collectives + the
overlap scheduler (ISSUE 5).

Coverage per the acceptance criteria:

* HLO census: a split-phase collective's *start* (its phase-1
  collective op) precedes compute interleaved between start and Wait,
  and its *done* (the phase-2 collective / completion barrier) follows
  it, in ONE jitted computation; for a 3-bucket fused tree under the
  scheduler, bucket ``i+1``'s start precedes bucket ``i``'s done (>= 2
  collectives in flight); the backward chain is REVERSED (the last
  adjoint collective is the all-gather adjoint of the FIRST start);
* bitwise parity between the split-phase and blocking forms on (1,),
  (3,), (8,) and (2,4)-mesh worlds, and Mode A vs Mode B under
  ``deterministic_mode``;
* gradients through start/wait pairs and through the scheduler;
* misuse: double-Wait raises (both backends, including through a
  ``JoinDummiesHandle`` copy), an un-waited handle at SPMD trace exit
  raises;
* scheduler prefetch depth (the window width is visible in the lowered
  program) and the ZeRO prefetch/reduce-scatter windows;
* the scope/explicit degrade-vs-raise matrix for overlap x codec;
* a registry-style sync guard in the test_tune mold: every split-phase
  form the facade exposes must be listed in
  ``overlap.SPLIT_PHASE_FORMS`` AND have census coverage here, so a
  future ``*_start`` shipped without tests fails CI.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import mpi4torch_tpu as mpi
from mpi4torch_tpu import overlap
from jax import shard_map

NR = 8
CENSUS_NR = 4
COLLECTIVES = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all",
               "collective_permute")

comm = mpi.COMM_WORLD

# The split-phase census matrix: every form in overlap.SPLIT_PHASE_FORMS
# must appear here with a dedicated start-precedes-compute /
# done-follows census test below (TestSplitPhaseCensus), mirroring
# test_tune's registry-sync guard.
SPLIT_CENSUS_COVERED = frozenset(
    {"Allreduce", "Reduce_scatter", "Allgather"})


@pytest.fixture(autouse=True)
def _isolated_overlap_state(tmp_path, monkeypatch):
    """Pristine knobs + private tune cache per test (the selector feeds
    the scheduler's per-bucket picks, so cross-test cache leakage would
    change which wire a bucket rides)."""
    monkeypatch.setenv("MPI4TORCH_TPU_TUNE_CACHE",
                       str(tmp_path / "tune_cache.json"))
    from mpi4torch_tpu import tune
    tune.clear()
    yield
    tune.clear()
    mpi.config.set_default_overlap(None)
    mpi.config.set_latency_crossover_bytes(None)
    mpi.config.set_bandwidth_crossover_bytes(None)


def test_split_phase_registry_sync_guard():
    """Every split-phase form the facade exposes (as ``<Form>_start``)
    must be registered in overlap.SPLIT_PHASE_FORMS and have census
    coverage in SPLIT_CENSUS_COVERED — adding a new *_start without
    extending both fails CI right here (the test_tune
    registry-sync-guard pattern; checker body shared via
    analyze.registry, messages unchanged — the coverage literal stays
    HERE, next to the census matrix it pins)."""
    from mpi4torch_tpu.analyze.registry import \
        overlap_split_phase_problems

    assert overlap_split_phase_problems(SPLIT_CENSUS_COVERED) == []


def _mesh_comm(nr=CENSUS_NR):
    mesh = Mesh(np.asarray(jax.devices()[:nr]), ("w",))
    return mesh, mpi.comm_from_mesh(mesh, "w")


def _lower_text(fn, *args, nr=CENSUS_NR):
    mesh, c = _mesh_comm(nr)
    wrapped = shard_map(lambda *a: fn(c, *a), mesh=mesh, in_specs=P(),
                       out_specs=P(), check_vma=False)
    return jax.jit(wrapped).lower(*args).as_text()


# ---------------------------------------------------------------------------
# HLO census: start precedes interleaved compute, done follows
# ---------------------------------------------------------------------------


class TestSplitPhaseCensus:
    def test_allreduce_start_straddles_compute(self):
        def body(c, x):
            h = c.Allreduce_start(x, mpi.MPI_SUM)
            mid = jnp.sin(x) * 2.0       # interleaved user compute
            return c.Wait(h) + mid

        txt = _lower_text(body, jnp.ones(64, jnp.float32))
        rs = txt.index("stablehlo.reduce_scatter")
        sin = txt.index("stablehlo.sine")
        ag = txt.index("stablehlo.all_gather")
        assert rs < sin < ag, (
            "split-phase Allreduce must put its reduce-scatter start "
            "before the interleaved compute and its all-gather done "
            "after it")

    def test_reduce_scatter_start_precedes_compute_done_follows(self):
        def body(c, x):
            h = c.Reduce_scatter_start(x.reshape(CENSUS_NR, -1),
                                       mpi.MPI_SUM, 0)
            mid = jnp.sin(x)
            return c.Wait(h).reshape(-1) + mid[:64 // CENSUS_NR]

        txt = _lower_text(body, jnp.ones(64, jnp.float32))
        rs = txt.index("stablehlo.reduce_scatter")
        sin = txt.index("stablehlo.sine")
        done = txt.rindex("stablehlo.optimization_barrier")
        assert rs < sin < done

    def test_allgather_start_precedes_compute_done_follows(self):
        def body(c, x):
            h = c.Allgather_start(x, 0)
            mid = jnp.sin(x)
            return c.Wait(h)[:16] + mid

        txt = _lower_text(body, jnp.ones(16, jnp.float32))
        ag = txt.index("stablehlo.all_gather")
        sin = txt.index("stablehlo.sine")
        done = txt.rindex("stablehlo.optimization_barrier")
        assert ag < sin < done

    def test_three_bucket_tree_keeps_window_in_flight(self):
        # The acceptance-criterion census: a 3-bucket fused tree with
        # split-phase enabled, ONE jitted computation — each bucket's
        # reduce-scatter start appears before the previous bucket's
        # all-gather done (>= 2 collectives in flight, vs the blocking
        # form's strict start_i..done_i..start_{i+1} nesting).
        tree = [jnp.ones(256, jnp.float32) * (i + 1) for i in range(3)]

        def body(c, t):
            return c.Allreduce_tree(t, mpi.MPI_SUM, bucket_bytes=1024,
                                    overlap=True)

        txt = _lower_text(body, tree)
        rs = [m.start() for m in re.finditer("stablehlo.reduce_scatter",
                                             txt)]
        ag = [m.start() for m in re.finditer("stablehlo.all_gather", txt)]
        assert len(rs) == 3 and len(ag) == 3
        # bucket order is trace order: rs[i]/ag[i] belong to bucket i.
        assert rs[0] < rs[1] < ag[0], \
            "bucket 1's start must precede bucket 0's done"
        assert rs[2] < ag[1], \
            "bucket 2's start must precede bucket 1's done"

    def test_scheduler_prefetch_depth_widens_window(self):
        # overlap=<int> sets the window depth: with depth 3 on a
        # 4-bucket tree, buckets 0..2 all start before bucket 0
        # completes; with the default depth 2, bucket 2's start comes
        # after bucket 0's done.
        tree = [jnp.ones(256, jnp.float32) * (i + 1) for i in range(4)]

        def body(depth):
            def f(c, t):
                return c.Allreduce_tree(t, mpi.MPI_SUM, bucket_bytes=1024,
                                        overlap=depth)
            return f

        txt2 = _lower_text(body(True), tree)
        txt3 = _lower_text(body(3), tree)
        for txt, depth in ((txt2, 2), (txt3, 3)):
            rs = [m.start() for m in
                  re.finditer("stablehlo.reduce_scatter", txt)]
            ag = [m.start() for m in re.finditer("stablehlo.all_gather",
                                                 txt)]
            assert len(rs) == 4 and len(ag) == 4
            in_flight_before_first_done = sum(1 for r in rs if r < ag[0])
            assert in_flight_before_first_done == depth, (
                f"window depth {depth}: expected {depth} starts before "
                f"the first done, saw {in_flight_before_first_done}")

    def test_backward_chain_is_reversed(self):
        # Two handles with DISTINCT payload sizes so forward and adjoint
        # collectives are identifiable by shape: forward order is
        # start_a, start_b, wait_a, wait_b; the transpose reverses the
        # wait chain, so the LAST collective in the lowered grad program
        # is the all-gather adjoint of start_a — the FIRST start.
        na, nb_ = 64, 32

        def body(c, x):
            a, b = x[:na], x[na:]
            ha = c.Allreduce_start(a, mpi.MPI_SUM)
            hb = c.Allreduce_start(b, mpi.MPI_SUM)
            ra = c.Wait(mpi.JoinDummiesHandle(ha, [hb.dummy]))
            rb = c.Wait(hb)
            return jnp.sum(ra) + jnp.sum(rb)

        def grad_body(c, x):
            return jax.grad(lambda v: body(c, v))(x)

        txt = _lower_text(grad_body, jnp.ones(na + nb_, jnp.float32))
        seg_a = na // CENSUS_NR
        ags = [m for m in re.finditer(
            r"stablehlo\.all_gather.*?tensor<1x(\d+)xf32>", txt)]
        assert ags, "no all_gather in the lowered grad program"
        # The final all_gather operates on bucket a's segment width —
        # start_a's adjoint runs LAST, i.e. the wait chain reversed.
        assert ags[-1].group(1) == str(seg_a), (
            f"expected the last adjoint all_gather on segment width "
            f"{seg_a} (the first start's), got {ags[-1].group(1)}")

    def test_zero_prefetch_forward_gathers_backward_scatters(self):
        # prefetch_allgather_tree: forward = one all_gather per shard
        # bucket (all issued ahead of their Waits); adjoint = the same
        # window of reduce-scatters in reverse.
        template = [jnp.ones(128, jnp.float32), jnp.ones(96, jnp.float32),
                    jnp.ones(64, jnp.float32)]

        def grad_body(c, shards):
            def loss(s):
                full = overlap.prefetch_allgather_tree(
                    c, s, template, bucket_bytes=256, depth=2)
                return sum(jnp.sum(f) for f in full)
            # value_and_grad keeps the forward gathers live (grad alone
            # would let XLA DCE them: the all_gather adjoint needs only
            # the cotangent).
            return jax.value_and_grad(loss)(shards)

        shards = [jnp.ones(128 // CENSUS_NR, jnp.float32),
                  jnp.ones(96 // CENSUS_NR, jnp.float32),
                  jnp.ones(64 // CENSUS_NR, jnp.float32)]
        txt = _lower_text(grad_body, shards)
        n_ag = txt.count("stablehlo.all_gather")
        n_rs = txt.count("stablehlo.reduce_scatter")
        assert n_ag >= 2 and n_rs == n_ag, (
            f"ZeRO prefetch adjoint must mirror gathers with scatters; "
            f"saw {n_ag} all_gather / {n_rs} reduce_scatter")


# ---------------------------------------------------------------------------
# Parity: split-phase vs blocking, Mode A vs Mode B
# ---------------------------------------------------------------------------


def _rank_slice(x):
    return jax.lax.dynamic_index_in_dim(
        x, jnp.asarray(comm.rank + 0), 0, keepdims=False)


class TestParity:
    @pytest.mark.parametrize("nr", [1, 3, 8])
    def test_bitwise_vs_blocking_deterministic(self, nr):
        rng = np.random.default_rng(17)
        data = jnp.asarray(rng.standard_normal((nr, 37)).astype(np.float32))

        def split(x):
            return comm.Wait(comm.Allreduce_start(_rank_slice(x),
                                                  mpi.MPI_SUM))

        def blocking(x):
            return comm.Allreduce(_rank_slice(x), mpi.MPI_SUM)

        with mpi.config.deterministic_mode(True):
            a = np.asarray(mpi.run_spmd(split, nranks=nr)(data))
            b = np.asarray(mpi.run_spmd(blocking, nranks=nr)(data))
        np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("nr", [1, 3, 8])
    def test_bitwise_vs_blocking_exact_data(self, nr):
        # Outside deterministic mode the ring pair and the native psum
        # may associate differently; on exactly-representable data every
        # association gives identical bits — the standard exact-data
        # bitwise probe (test_tune uses it for the algorithm matrix).
        data = jnp.asarray(
            np.arange(nr * 23, dtype=np.float32).reshape(nr, 23))

        def split(x):
            return comm.Wait(comm.Allreduce_start(_rank_slice(x),
                                                  mpi.MPI_SUM))

        def blocking(x):
            return comm.Allreduce(_rank_slice(x), mpi.MPI_SUM)

        a = np.asarray(mpi.run_spmd(split, nranks=nr)(data))
        b = np.asarray(mpi.run_spmd(blocking, nranks=nr)(data))
        np.testing.assert_array_equal(a, b)

    def test_bitwise_on_2d_mesh_world(self):
        # (2,4)-mesh: the 2-axis hier communicator serves split-phase
        # through the generic compute-at-start handles — bit-identical
        # to its blocking Allreduce by construction.
        mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                    ("dp", "tp"))
        c = mpi.comm_from_mesh(mesh, ("dp", "tp"))
        rng = np.random.default_rng(23)
        x = jnp.asarray(rng.standard_normal(33).astype(np.float32))

        def split(v):
            return c.Wait(c.Allreduce_start(v, mpi.MPI_SUM))

        def blocking(v):
            return c.Allreduce(v, mpi.MPI_SUM)

        run = lambda f: np.asarray(jax.jit(shard_map(
            f, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False))(x))
        np.testing.assert_array_equal(run(split), run(blocking))

    def test_mode_a_vs_mode_b_bitwise_deterministic(self):
        rng = np.random.default_rng(29)
        data = jnp.asarray(rng.standard_normal((NR, 31)).astype(np.float32))

        def split(x):
            return comm.Wait(comm.Allreduce_start(_rank_slice(x),
                                                  mpi.MPI_SUM))

        with mpi.config.deterministic_mode(True):
            a = np.asarray(mpi.run_spmd(split)(data))
        b = mpi.run_ranks(
            lambda: np.asarray(comm.Wait(comm.Allreduce_start(
                data[comm.rank], mpi.MPI_SUM))), NR)
        for r in range(NR):
            np.testing.assert_array_equal(a[r], b[r], err_msg=f"rank {r}")

    def test_eager_split_phase_bitwise_vs_blocking(self):
        rng = np.random.default_rng(31)
        data = jnp.asarray(rng.standard_normal((4, 21)).astype(np.float32))

        def body():
            split = comm.Wait(comm.Allreduce_start(data[comm.rank],
                                                   mpi.MPI_SUM))
            blocking = comm.Allreduce(data[comm.rank], mpi.MPI_SUM)
            return bool(np.array_equal(np.asarray(split),
                                       np.asarray(blocking)))

        assert all(mpi.run_ranks(body, 4))

    def test_scheduler_tree_bitwise_vs_blocking_fused(self):
        rng = np.random.default_rng(37)
        tree = {"a": jnp.asarray(rng.standard_normal(300).astype(np.float32)),
                "b": jnp.asarray(rng.standard_normal(45).astype(np.float32)),
                "c": jnp.asarray(rng.integers(0, 9, 30).astype(np.int32))}

        def run(ov):
            return mpi.run_spmd(lambda t: comm.Allreduce_tree(
                t, mpi.MPI_SUM, bucket_bytes=512, overlap=ov,
                mean=False))(tree)

        a, b = run(True), run(None)
        for k in tree:
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]), err_msg=k)

    def test_scheduler_tree_grads_match_blocking(self):
        rng = np.random.default_rng(41)
        tree = {"w": jnp.asarray(rng.standard_normal(130).astype(np.float32)),
                "v": jnp.asarray(rng.standard_normal(70).astype(np.float32))}

        def make(ov):
            def body(t):
                def loss(tr):
                    red = comm.Allreduce_tree(tr, mpi.MPI_SUM,
                                              bucket_bytes=256, overlap=ov,
                                              mean=True)
                    return sum(jnp.vdot(l, l)
                               for l in jax.tree.leaves(red))
                return jax.grad(loss)(t)
            return body

        a = mpi.run_spmd(make(2))(tree)
        b = mpi.run_spmd(make(None))(tree)
        for k in tree:
            np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                       rtol=1e-5, atol=1e-6, err_msg=k)

    def test_zero_step_overlap_bitwise(self):
        params = {"w": jnp.arange(600, dtype=jnp.float32).reshape(20, 30)
                  / 100, "b": jnp.ones(7, jnp.float32)}
        grads = jax.tree.map(lambda p: p * 0.5, params)

        class _Sgd:
            def init(self, p):
                return None

            def update(self, g, s, p):
                return jax.tree.map(lambda x: -0.1 * x, g), None

        from mpi4torch_tpu.parallel import zero as Z
        opt = _Sgd()

        def step(ov):
            def f():
                st = Z.zero_init(comm, opt, params)
                return Z.zero_step(comm, opt, params, grads, st,
                                   overlap=ov)[0]
            return mpi.run_spmd(f, nranks=NR)()

        a, b = step(True), step(None)
        for k in params:
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]), err_msg=k)

    def test_zero3_params_prefetch_bitwise_and_scope(self):
        from mpi4torch_tpu.parallel import zero as Z
        template = {"w": jnp.arange(96, dtype=jnp.float32),
                    "v": jnp.ones((5, 5), jnp.float32)}

        def gather(ov, scoped=False):
            def f():
                shards = Z.zero3_shard_params(comm, template)
                if scoped:
                    with mpi.config.overlap_scope(ov):
                        return Z.zero3_params(comm, shards, template)
                return Z.zero3_params(comm, shards, template, overlap=ov)
            return mpi.run_spmd(f, nranks=4)()

        blocking = gather(None)
        for variant in (gather(True), gather(3), gather(True, scoped=True)):
            for k in template:
                np.testing.assert_array_equal(np.asarray(variant[k]),
                                              np.asarray(blocking[k]),
                                              err_msg=k)


# ---------------------------------------------------------------------------
# WaitHandle API parity with the eager path
# ---------------------------------------------------------------------------


class TestHandleApi:
    def test_handle_is_waithandle_with_dummy(self):
        def body(x):
            h = comm.Allreduce_start(x, mpi.MPI_SUM)
            assert isinstance(h, mpi.WaitHandle)
            assert isinstance(h, mpi.SpmdWaitHandle)
            # .dummy joins like the eager handle's
            y = mpi.JoinDummies(x * 2, [h.dummy])
            return comm.Wait(h) + 0 * y

        out = np.asarray(mpi.run_spmd(body, nranks=4)(jnp.ones(8)))
        np.testing.assert_allclose(out[0], 4.0)

    def test_join_dummies_handle_preserves_kind(self):
        def body(x):
            h = comm.Allreduce_start(x, mpi.MPI_SUM)
            h2 = mpi.JoinDummiesHandle(h, [x * 3])
            assert isinstance(h2, mpi.SpmdWaitHandle)
            return comm.Wait(h2)

        out = np.asarray(mpi.run_spmd(body, nranks=4)(jnp.ones(8)))
        np.testing.assert_allclose(out[0], 4.0)


# ---------------------------------------------------------------------------
# Misuse guards
# ---------------------------------------------------------------------------


class TestMisuse:
    def test_double_wait_raises_spmd(self):
        def body(x):
            h = comm.Allreduce_start(x, mpi.MPI_SUM)
            comm.Wait(h)
            return comm.Wait(h)

        with pytest.raises(mpi.BifurcationError, match="exactly once"):
            mpi.run_spmd(body, nranks=4)(jnp.ones(4))

    def test_double_wait_through_joined_copy_raises_spmd(self):
        def body(x):
            h = comm.Allreduce_start(x, mpi.MPI_SUM)
            h2 = mpi.JoinDummiesHandle(h, [x])
            comm.Wait(h2)
            return comm.Wait(h)

        with pytest.raises(mpi.BifurcationError, match="exactly once"):
            mpi.run_spmd(body, nranks=4)(jnp.ones(4))

    def test_unwaited_handle_at_trace_exit_raises(self):
        def body(x):
            comm.Allreduce_start(x, mpi.MPI_SUM)
            return x

        with pytest.raises(mpi.DeadlockError, match="un-waited"):
            mpi.run_spmd(body, nranks=4)(jnp.ones(4))

    def test_unwaited_reports_the_form(self):
        def body(x):
            comm.Allgather_start(x, 0)
            return x

        with pytest.raises(mpi.DeadlockError, match="Allgather_start"):
            mpi.run_spmd(body, nranks=4)(jnp.ones(4))

    def test_double_wait_raises_eager(self):
        def body():
            h = comm.Allreduce_start(jnp.ones(3), mpi.MPI_SUM)
            comm.Wait(h)
            try:
                comm.Wait(h)
                return False
            except mpi.BifurcationError:
                return True

        assert all(mpi.run_ranks(body, 2))

    def test_double_wait_through_joined_copy_raises_eager(self):
        def body():
            h = comm.Allreduce_start(jnp.ones(3), mpi.MPI_SUM)
            h2 = mpi.JoinDummiesHandle(h, [jnp.ones(1)])
            comm.Wait(h2)
            try:
                comm.Wait(h)
                return False
            except mpi.BifurcationError:
                return True

        assert all(mpi.run_ranks(body, 2))


# ---------------------------------------------------------------------------
# Scope / explicit degrade-vs-raise matrix
# ---------------------------------------------------------------------------


class TestOverlapCompositionMatrix:
    def test_explicit_overlap_plus_explicit_codec_raises(self):
        tree = {"a": jnp.ones(256, jnp.float32)}
        with pytest.raises(mpi.CommError, match="split-phase"):
            mpi.run_spmd(lambda t: comm.Allreduce_tree(
                t, mpi.MPI_SUM, overlap=True, compression="q8"))(tree)

    def test_allreduce_start_explicit_codec_raises(self):
        with pytest.raises(ValueError, match="split-phase"):
            mpi.run_spmd(lambda x: comm.Wait(comm.Allreduce_start(
                x, mpi.MPI_SUM, compression="q8")), nranks=4)(
                    jnp.ones(64, jnp.float32))

    def test_allreduce_start_scope_codec_degrades_to_exact(self):
        data = jnp.asarray(
            np.arange(NR * 16, dtype=np.float32).reshape(NR, 16))

        def split(x):
            with mpi.config.compression_scope("q8"):
                return comm.Wait(comm.Allreduce_start(_rank_slice(x),
                                                      mpi.MPI_SUM))

        def exact(x):
            return comm.Wait(comm.Allreduce_start(_rank_slice(x),
                                                  mpi.MPI_SUM))

        a = np.asarray(mpi.run_spmd(split)(data))
        b = np.asarray(mpi.run_spmd(exact)(data))
        np.testing.assert_array_equal(a, b)

    def test_explicit_overlap_scope_codec_yields_to_exact_window(self):
        # Explicit overlap + scope codec: exactly one explicit half —
        # the scope codec yields, buckets ride the exact split wire.
        tree = {"a": jnp.asarray(np.arange(256, dtype=np.float32))}

        def body(t):
            with mpi.config.compression_scope("q8"):
                return comm.Allreduce_tree(t, mpi.MPI_SUM,
                                           bucket_bytes=512, overlap=True)

        def exact(t):
            return comm.Allreduce_tree(t, mpi.MPI_SUM, bucket_bytes=512,
                                       overlap=True)

        a = mpi.run_spmd(body)(tree)
        b = mpi.run_spmd(exact)(tree)
        np.testing.assert_array_equal(np.asarray(a["a"]),
                                      np.asarray(b["a"]))

    def test_scope_overlap_explicit_codec_keeps_codec_blocking(self):
        # Scope overlap + explicit codec: the codec is the explicit
        # half — honored; the scope overlap degrades per bucket to the
        # blocking codec pipeline.  Result matches the plain compressed
        # blocking tree exactly.
        rng = np.random.default_rng(43)
        tree = {"a": jnp.asarray(
            rng.standard_normal(256).astype(np.float32))}

        def scoped(t):
            with mpi.config.overlap_scope(True):
                return comm.Allreduce_tree(t, mpi.MPI_SUM,
                                           bucket_bytes=512,
                                           compression="q8")

        def blocking(t):
            return comm.Allreduce_tree(t, mpi.MPI_SUM, bucket_bytes=512,
                                       compression="q8")

        a = mpi.run_spmd(scoped)(tree)
        b = mpi.run_spmd(blocking)(tree)
        np.testing.assert_array_equal(np.asarray(a["a"]),
                                      np.asarray(b["a"]))

    def test_scope_overlap_mixed_dtypes_splits_exact_compresses_float(self):
        # Per-bucket composition under scope defaults: inside overlap +
        # compression scopes, the float bucket rides the blocking q8
        # pipeline while the int bucket rides the exact split wire.
        tree = {"f": jnp.asarray(np.arange(128, dtype=np.float32)),
                "i": jnp.asarray(np.arange(64, dtype=np.int32))}

        def scoped(t):
            with mpi.config.overlap_scope(True), \
                    mpi.config.compression_scope("q8"):
                return comm.Allreduce_tree(t, mpi.MPI_SUM,
                                           bucket_bytes=512)

        def blocking(t):
            with mpi.config.compression_scope("q8"):
                return comm.Allreduce_tree(t, mpi.MPI_SUM,
                                           bucket_bytes=512)

        a = mpi.run_spmd(scoped, nranks=4)(tree)
        b = mpi.run_spmd(blocking, nranks=4)(tree)
        for k in tree:
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]), err_msg=k)

    def test_eager_scope_overlap_nonsum_degrades(self):
        # A scope/process overlap default must not break a MAX tree on
        # the eager backend — it degrades to the blocking rendezvous
        # (the explicit overlap=True raise is regression-tested in
        # test_fuse).
        data = jnp.asarray(np.arange(8, dtype=np.float32))

        def body():
            with mpi.config.overlap_scope(True):
                out = comm.Allreduce_tree({"a": data * (comm.rank + 1)},
                                          mpi.MPI_MAX)
            return np.asarray(out["a"])

        outs = mpi.run_ranks(body, 4)
        np.testing.assert_array_equal(outs[0], np.asarray(data) * 4)

    def test_eager_pipeline_honors_window_depth(self, monkeypatch):
        # An integer overlap value must reach the eager Isend/Irecv
        # pipeline as its window depth (it was silently pinned to the
        # default of 2), and the result stays bitwise at any depth.
        from mpi4torch_tpu.fuse import collectives as fc

        seen = []
        orig = fc._pipeline_allreduce

        def spy(comm_, buckets, op, *, depth=2):
            seen.append(depth)
            return orig(comm_, buckets, op, depth=depth)

        monkeypatch.setattr(fc, "_pipeline_allreduce", spy)
        tree = [jnp.asarray(np.arange(512, dtype=np.float32))
                for _ in range(3)]

        def body(ov):
            def run():
                out = comm.Allreduce_tree(
                    [t * (comm.rank + 1) for t in tree], mpi.MPI_SUM,
                    bucket_bytes=1024, overlap=ov)
                return [np.asarray(t) for t in out]
            return mpi.run_ranks(run, 2)

        deep = body(4)
        assert seen and all(d == 4 for d in seen)
        seen.clear()
        shallow = body(1)
        assert seen and all(d == 1 for d in seen)
        for a, b in zip(deep[0], shallow[0]):
            np.testing.assert_array_equal(a, b)

    def test_eager_explicit_overlap_nonsum_still_raises(self):
        def body():
            try:
                comm.Allreduce_tree({"a": jnp.ones(4)}, mpi.MPI_MAX,
                                    overlap=True)
                return False
            except mpi.CommError:
                return True

        assert all(mpi.run_ranks(body, 2))

    def test_overlap_validation(self):
        with pytest.raises(ValueError, match="overlap"):
            mpi.config.set_default_overlap(0)
        with pytest.raises(ValueError, match="overlap"):
            mpi.config.set_default_overlap(-2)
        with pytest.raises(ValueError, match="overlap"):
            mpi.config.set_default_overlap("deep")
        with mpi.config.overlap_scope(4):
            assert mpi.config.default_overlap() == 4
        assert mpi.config.default_overlap() is None

    def test_run_spmd_jit_cache_keys_on_overlap_default(self):
        # Toggling the overlap default between calls must retrace: the
        # same run_spmd callable lowers the blocking form, then the
        # split-phase window.
        tree = [jnp.ones(256, jnp.float32) for _ in range(2)]

        def body(t):
            return comm.Allreduce_tree(t, mpi.MPI_SUM, bucket_bytes=1024)

        step = mpi.run_spmd(body, nranks=4)
        blocking = step(tree)
        mpi.config.set_default_overlap(True)
        try:
            overlapped = step(tree)
        finally:
            mpi.config.set_default_overlap(None)
        for a, b in zip(jax.tree.leaves(blocking),
                        jax.tree.leaves(overlapped)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# 1F1B double-buffered pipeline
# ---------------------------------------------------------------------------


class Test1F1BOverlap:
    def _run(self, overlap, n=4, n_mb=6, tag=0):
        from mpi4torch_tpu.parallel import pp

        def body():
            rank = comm.rank
            params = {"w": jnp.eye(4) * (0.5 + 0.1 * rank)}
            mbs = [jnp.ones((2, 4)) * (i + 1) for i in range(n_mb)]

            def apply_stage(p, x):
                return jnp.tanh(x @ p["w"])

            def loss_fn(y, i):
                return jnp.sum(y) / (i + 1)

            loss, grads = pp.pipeline_step_1f1b(
                comm, apply_stage, params, mbs, loss_fn,
                recv_like=jnp.zeros((2, 4)), tag=tag, overlap=overlap)
            return np.asarray(loss), np.asarray(grads["w"])

        return mpi.run_ranks(body, n)

    def test_overlap_bitwise_matches_blocking(self):
        blocking = self._run(None, tag=0)
        buffered = self._run(2, tag=10_000)
        for (l0, g0), (l1, g1) in zip(blocking, buffered):
            np.testing.assert_array_equal(l0, l1)
            np.testing.assert_array_equal(g0, g1)

    def test_deeper_window_identical(self):
        blocking = self._run(None, tag=0)
        deep = self._run(4, tag=20_000)
        for (l0, g0), (l1, g1) in zip(blocking, deep):
            np.testing.assert_array_equal(l0, l1)
            np.testing.assert_array_equal(g0, g1)


# ---------------------------------------------------------------------------
# Profiling span kinds
# ---------------------------------------------------------------------------


class TestProfilingSpans:
    def test_bucket_scope_phase_suffix(self):
        from mpi4torch_tpu.utils.profiling import bucket_scope
        with bucket_scope("Allreduce_tree", 0, 3, phase="start"):
            pass
        with bucket_scope("Allreduce_tree", 0, 3, phase="wait"):
            pass
        with pytest.raises(ValueError, match="start"):
            bucket_scope("Allreduce_tree", 0, 3, phase="middle")

    def test_split_phase_spans_reach_lowered_program(self):
        # The start/wait spans must be visible in the lowered program's
        # location metadata, so traces can attribute exposed vs hidden
        # communication per bucket.
        tree = [jnp.ones(256, jnp.float32) for _ in range(2)]


        def body(c, t):
            return c.Allreduce_tree(t, mpi.MPI_SUM, bucket_bytes=1024,
                                    overlap=True)

        mesh, c = _mesh_comm()
        wrapped = shard_map(lambda t: body(c, t), mesh=mesh, in_specs=P(),
                            out_specs=P(), check_vma=False)
        txt = jax.jit(wrapped).lower(tree).as_text(debug_info=True)
        assert "bucket0of2.start" in txt
        assert "bucket0of2.wait" in txt


# ---------------------------------------------------------------------------
# Scheduled-exposure census (overlap.census): the quantitative fold of
# the ordering censuses above — the exposed-comm fraction of a lowered
# program.
# ---------------------------------------------------------------------------


class TestScheduledExposure:
    def _tree_lowered(self, overlap_arg, nb=3):
        tree = [jnp.ones(1024, jnp.float32) for _ in range(nb)]
        mesh, c = _mesh_comm()
        wrapped = shard_map(
            lambda t: c.Allreduce_tree(t, mpi.MPI_SUM, bucket_bytes=4096,
                                       overlap=overlap_arg),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
        return jax.jit(wrapped).lower(tree)

    def test_blocking_program_is_fully_exposed(self):
        out = overlap.scheduled_exposure(self._tree_lowered(False))
        assert out["n_buckets"] == 3
        assert out["exposed_fraction"] == 1.0
        assert all(not b["split_phase"]
                   for b in out["buckets"].values())

    def test_windowed_program_is_strictly_lower(self):
        blocking = overlap.scheduled_exposure(self._tree_lowered(False))
        windowed = overlap.scheduled_exposure(self._tree_lowered(True))
        assert windowed["n_buckets"] == blocking["n_buckets"] == 3
        assert all(b["split_phase"]
                   for b in windowed["buckets"].values())
        # At most the window's trailing drain bucket is exposed (it can
        # census hidden too: the previous bucket's all-gather is wire in
        # flight inside its start->wait span).
        assert windowed["exposed_fraction"] < blocking["exposed_fraction"]
        assert windowed["n_exposed"] <= 1

    def test_census_accepts_debug_text(self):
        txt = self._tree_lowered(True).as_text(debug_info=True)
        from_text = overlap.scheduled_exposure(txt)
        from_lowered = overlap.scheduled_exposure(self._tree_lowered(True))
        assert from_text == from_lowered

    def test_census_without_buckets_is_none(self):
        mesh, c = _mesh_comm()
        wrapped = shard_map(
            lambda x: c.Allreduce(x, mpi.MPI_SUM),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
        out = overlap.scheduled_exposure(
            jax.jit(wrapped).lower(jnp.ones(64, jnp.float32)))
        assert out["n_buckets"] == 0
        assert out["exposed_fraction"] is None

    def test_zero_step_census_matches_bench_claim(self):
        # The overlap scheduler's acceptance bar, in miniature: the blocking
        # ZeRO step censuses fully exposed, the windowed split-phase
        # step strictly lower, on the same model.
        from mpi4torch_tpu.parallel import zero as Z

        params = {"w": jnp.ones((32, 24), jnp.float32),
                  "b": jnp.ones(41, jnp.float32)}
        grads = jax.tree.map(lambda p: p * 0.01, params)

        class _Sgd:
            def init(self, p):
                return None

            def update(self, g, s, p):
                return jax.tree.map(lambda x: -0.1 * x, g), None

        opt = _Sgd()

        def lower(ov):
            def f(g):
                with mpi.config.fusion_scope(1024):
                    st = Z.zero_init(comm, opt, params)
                    return Z.zero_step(comm, opt, params, g, st,
                                       overlap=ov)[0]
            return jax.jit(mpi.run_spmd(f)).lower(grads)

        blocking = overlap.scheduled_exposure(lower(False))
        windowed = overlap.scheduled_exposure(lower(True))
        assert blocking["n_buckets"] > 2
        assert blocking["exposed_fraction"] == 1.0
        assert windowed["exposed_fraction"] < 1.0
