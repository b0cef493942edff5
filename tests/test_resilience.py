"""Fault-tolerant collectives (mpi4torch_tpu.resilience, ISSUE 7).

Pins the tentpole contracts: deterministic fault injection at the Mode B
rendezvous/p2p chokepoints (composing with fused buckets, compressed
wires, and the overlap pipeline without per-subsystem hooks), failure
ATTRIBUTION (DeadlockError arrived/missing sets, RankFailedError naming
the dead rank, IntegrityError naming the lying rank), transient-fault
retry/backoff recovery, the zero-overhead-off integrity guards on both
backends (HLO-censused), preemption-safe checkpoint recovery, and the
registry-sync guard that makes a fault kind without matrix coverage a
CI failure.  The full fault matrix across the (3,)/(8,)/torus worlds
rides the `slow` lane (`make faults-smoke` runs it standalone); tier-1
keeps a fast representative subset.
"""

import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4torch_tpu as mpi
from mpi4torch_tpu import resilience as rz
from mpi4torch_tpu.resilience import guards as rguards
from mpi4torch_tpu.resilience import matrix as rmatrix
# The checker body lives in the shared registry-guard home since the
# analyze subsystem landed; resilience.__main__._check_registry_sync
# delegates there, so the smoke lane and this file still share ONE
# implementation.
from mpi4torch_tpu.analyze.registry import \
    resilience_problems as _check_registry_sync

comm = mpi.COMM_WORLD


@pytest.fixture(autouse=True)
def _restore_resilience_config():
    yield
    mpi.config.set_comm_retries(0)
    mpi.config.set_comm_backoff(0.05)
    mpi.config.set_comm_finite_guard("off")
    mpi.config.set_comm_wire_checksum(False)
    mpi.config.set_fault_plan(None)
    rguards.clear_violations()


def _allreduce(rank):
    return comm.Allreduce(jnp.arange(16.0) * (rank + 1), mpi.MPI_SUM)


# =========================================================================
# Registry-sync guard (the PR 4/6 pattern)
# =========================================================================

class TestRegistrySync:
    def test_registry_and_coverage_in_sync(self):
        # Every registered fault kind has a matrix row covering every
        # subsystem its sites reach (and is non-inert somewhere);
        # every covered kind is registered.  ONE checker shared with
        # the `make faults-smoke` lane.
        assert _check_registry_sync() == []

    def test_unregistered_coverage_or_uncovered_kind_fails(self):
        ghost = rz.FaultKind("ghost_fault", frozenset({"exchange"}),
                             transient=False, doc="test-only")
        rz.FAULT_KINDS[ghost.name] = ghost
        try:
            problems = _check_registry_sync()
            assert problems and "ghost_fault" in " ".join(problems)
        finally:
            del rz.FAULT_KINDS[ghost.name]

    def test_expected_error_table_typed(self):
        for kind, err in rmatrix.EXPECTED_ERROR.items():
            assert issubclass(err, mpi.CommError), (kind, err)


# =========================================================================
# Failure attribution
# =========================================================================

class TestAttribution:
    def test_deadlock_carries_arrived_and_missing(self):
        def late(rank):
            if rank == 2:
                time.sleep(0.9)
            return _allreduce(rank)

        with pytest.raises(mpi.DeadlockError) as ei:
            mpi.run_ranks(late, 3, timeout=0.25)
        assert ei.value.arrived == frozenset({0, 1})
        assert ei.value.missing == frozenset({2})

    def test_rank_death_typed_and_attributed(self):
        with rz.fault_scope([rz.FaultSpec("rank_death", rank=1,
                                          op="Allreduce")]):
            with pytest.raises(mpi.RankFailedError) as ei:
                mpi.run_ranks(_allreduce, 3, timeout=5.0)
        assert ei.value.ranks == frozenset({1})

    def test_p2p_recv_names_dead_peer(self):
        # A receiver blocked on a dead rank's message gets the typed,
        # attributed error, not a generic timeout.
        def fn(rank):
            if rank == 0:
                return comm.Recv(jnp.zeros(4), 1, 7)
            return comm.Send(jnp.ones(4), 0, 7)   # rank 1 dies here

        with rz.fault_scope([rz.FaultSpec("rank_death", rank=1,
                                          op="p2p")]):
            with pytest.raises(mpi.RankFailedError) as ei:
                mpi.run_ranks(fn, 2, timeout=5.0)
        assert 1 in ei.value.ranks

    def test_health_check_ok(self):
        reports = mpi.run_ranks(lambda r: comm.check_health(timeout=5.0), 3)
        for rep in reports:
            assert rep.ok and rep.arrived == frozenset({0, 1, 2})
            assert rep.missing == frozenset()

    def test_health_check_names_missing_rank(self):
        def fn(rank):
            if rank == 2:
                time.sleep(0.6)     # never probes within the bound
                return None
            return comm.check_health(timeout=0.2)

        reports = mpi.run_ranks(fn, 3)
        for rep in reports[:2]:
            assert not rep.ok
            assert rep.missing == frozenset({2})
            assert rep.arrived == frozenset({0, 1})

    def test_health_probe_recovers_after_failed_round(self):
        # A failed probe must NOT latch: once the slow rank is back,
        # the next collective probe reports healthy again (the
        # dedicated health barrier resets after a broken round drains).
        def fn(rank):
            if rank == 2:
                time.sleep(0.5)      # misses probe round 1 entirely
                return comm.check_health(timeout=2.0)
            first = comm.check_health(timeout=0.2)
            assert not first.ok and first.missing == frozenset({2})
            return comm.check_health(timeout=2.0)

        reports = mpi.run_ranks(fn, 3)
        for rep in reports:
            assert rep.ok, rep

    def test_health_probe_attributes_despite_world_failure(self):
        # A rank crashing while its peers are blocked in check_health:
        # the abort must still attribute — the waiting probers ARRIVED,
        # only the crashed rank is missing.
        reports = {}

        def fn(rank):
            if rank == 2:
                time.sleep(0.4)
                raise RuntimeError("boom")
            reports[rank] = comm.check_health(timeout=5.0)

        with pytest.raises(RuntimeError, match="boom"):
            mpi.run_ranks(fn, 3, timeout=5.0)
        for rank in (0, 1):
            rep = reports[rank]
            assert not rep.ok
            assert rep.arrived == frozenset({0, 1})
            assert rep.missing == frozenset({2})

    def test_health_probe_counts_hung_rank_as_missing_alongside_dead(self):
        # One rank dead AND one rank merely hung: the probe must not
        # fabricate the hung rank as arrived — `arrived` only contains
        # ranks that answered THIS probe.
        from mpi4torch_tpu.runtime import current_rank_context

        reports = {}

        def fn(rank):
            ctx = current_rank_context()
            if rank == 1:
                err = mpi.RankFailedError("rank 1 died", ranks=(1,))
                ctx.world.mark_dead(1, err)
                raise err
            if rank == 2:
                time.sleep(0.7)      # wedged: never probes
                return None
            time.sleep(0.1)          # let the death land first
            reports[rank] = comm.check_health(timeout=0.3)

        with pytest.raises(mpi.RankFailedError):
            mpi.run_ranks(fn, 3)
        rep = reports[0]
        assert not rep.ok
        assert rep.arrived == frozenset({0})
        assert rep.missing == frozenset({1, 2})

    def test_health_check_single_rank_world(self):
        rep = comm.check_health(timeout=1.0)
        assert rep.ok and rep.size == 1

    def test_check_health_raises_inside_spmd(self):
        def body(x):
            comm.check_health()
            return x

        with pytest.raises(mpi.CommError, match="host-level"):
            mpi.run_spmd(body, nranks=2)(jnp.ones(4))


# =========================================================================
# Retry / backoff recovery
# =========================================================================

class TestRetryRecovery:
    def test_slow_rank_recovers_within_retries(self):
        baseline = mpi.run_ranks(_allreduce, 3)
        mpi.config.set_comm_retries(5)
        mpi.config.set_comm_backoff(0.15)
        with rz.fault_scope([rz.FaultSpec("delay", rank=1, op="Allreduce",
                                          seconds=0.5)]) as plan:
            got = mpi.run_ranks(_allreduce, 3, timeout=0.25)
        assert plan.fired_kinds() == frozenset({"delay"})
        for b, g in zip(baseline, got):
            np.testing.assert_array_equal(np.asarray(b), np.asarray(g))

    def test_dropped_message_redelivered_on_retry(self):
        def fn(rank):
            if rank == 0:
                return comm.Recv(jnp.zeros(4), 1, 3)
            return comm.Send(jnp.ones(4) * 2, 0, 3)

        mpi.config.set_comm_retries(3)
        mpi.config.set_comm_backoff(0.1)
        with rz.fault_scope([rz.FaultSpec("drop_p2p", rank=1,
                                          op="p2p")]) as plan:
            out = mpi.run_ranks(fn, 2, timeout=0.25)
        assert plan.fired_kinds() == frozenset({"drop_p2p"})
        np.testing.assert_array_equal(np.asarray(out[0]), 2 * np.ones(4))

    def test_dropped_message_without_retries_deadlocks(self):
        def fn(rank):
            if rank == 0:
                return comm.Recv(jnp.zeros(4), 1, 3)
            return comm.Send(jnp.ones(4), 0, 3)

        with rz.fault_scope([rz.FaultSpec("drop_p2p", rank=1, op="p2p")]):
            with pytest.raises(mpi.DeadlockError, match="fault-injected"):
                mpi.run_ranks(fn, 2, timeout=0.25)

    def test_retry_knob_validation(self):
        with pytest.raises(ValueError):
            mpi.config.set_comm_retries(-1)
        with pytest.raises(ValueError):
            mpi.config.set_comm_backoff(-0.5)
        with pytest.raises(ValueError):
            mpi.config.set_comm_finite_guard("loud")


# =========================================================================
# Integrity guards
# =========================================================================

class TestFiniteGuard:
    def test_raise_names_offending_rank(self):
        mpi.config.set_comm_finite_guard("raise")
        with rz.fault_scope([rz.FaultSpec("corrupt_nan", rank=2,
                                          op="Allreduce")]):
            with pytest.raises(mpi.IntegrityError) as ei:
                mpi.run_ranks(_allreduce, 3, timeout=5.0)
        assert ei.value.ranks == frozenset({2})

    def test_warn_mode_warns_and_completes(self):
        # Size-1 world on the main thread: deterministic warning capture.
        mpi.config.set_comm_finite_guard("warn")
        with pytest.warns(rz.IntegrityWarning):
            out = comm.Allreduce(jnp.asarray([np.nan, 1.0]), mpi.MPI_SUM)
        assert np.isnan(np.asarray(out)[0])

    def test_off_mode_is_silent(self):
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            out = comm.Allreduce(jnp.asarray([np.nan, 1.0]), mpi.MPI_SUM)
        assert np.isnan(np.asarray(out)[0])
        assert rguards.last_violation() is None

    def test_guard_rides_the_trace_fingerprint(self):
        fp0 = mpi.config.thresholds_fingerprint()
        mpi.config.set_comm_finite_guard("warn")
        fp1 = mpi.config.thresholds_fingerprint()
        mpi.config.set_comm_finite_guard("off")
        assert fp0 != fp1

    def test_huge_finite_float64_is_not_a_false_positive(self):
        # numpy float64 payloads are checked WITHOUT jnp
        # canonicalization: with x64 disabled, jnp.asarray would
        # downcast 1e300 to f32 inf and accuse an innocent rank.
        assert rguards._all_finite({"x": np.asarray([1e300, -1e300])})
        assert not rguards._all_finite({"x": np.asarray([1e300, np.inf])})
        assert not rguards._all_finite(np.asarray([np.nan]))

    def test_bf16_payload_checked(self):
        mpi.config.set_comm_finite_guard("raise")
        with pytest.raises(mpi.IntegrityError):
            comm.Allreduce(jnp.asarray([np.nan], jnp.bfloat16), mpi.MPI_SUM)


class TestWireChecksum:
    def test_bitflip_on_q8_wire_detected_and_attributed(self):
        def ag(rank):
            x = jnp.linspace(-2.0, 2.0, 48, dtype=jnp.float32) * (rank + 1)
            return comm.Allgather(x, 0, compression="q8")

        mpi.config.set_comm_wire_checksum(True)
        with rz.fault_scope([rz.FaultSpec("bitflip", rank=1,
                                          op="Allgather.c")]):
            with pytest.raises(mpi.IntegrityError) as ei:
                mpi.run_ranks(ag, 3, timeout=5.0)
        assert ei.value.ranks == frozenset({1})

    def test_checksum_off_bitflip_is_silent_corruption(self):
        # The negative control: without the checksum leg the flipped
        # block folds in silently — the guard exists for a reason.
        def ag(rank):
            x = jnp.linspace(-2.0, 2.0, 48, dtype=jnp.float32) * (rank + 1)
            return comm.Allgather(x, 0, compression="q8")

        baseline = mpi.run_ranks(ag, 2)
        with rz.fault_scope([rz.FaultSpec("bitflip", rank=1,
                                          op="Allgather.c")]) as plan:
            got = mpi.run_ranks(ag, 2, timeout=5.0)
        assert plan.fired_kinds() == frozenset({"bitflip"})
        assert not np.array_equal(np.asarray(got[0]), np.asarray(baseline[0]))

    def test_checksum_on_clean_wire_is_bitwise_inert(self):
        def ag(rank):
            x = jnp.linspace(-2.0, 2.0, 48, dtype=jnp.float32) * (rank + 1)
            return comm.Allgather(x, 0, compression="q8")

        baseline = mpi.run_ranks(ag, 2)
        mpi.config.set_comm_wire_checksum(True)
        got = mpi.run_ranks(ag, 2)
        for b, g in zip(baseline, got):
            np.testing.assert_array_equal(np.asarray(b), np.asarray(g))

    def test_verify_wire_covers_meta_too(self):
        # The CRC must protect codec meta (shape/dtype/scales steer the
        # decode) alongside the payload blocks.
        payload = {"q": jnp.zeros((4,), jnp.int8)}
        meta = ("q8", (4,), "float32")
        crc = rguards.wire_checksum((meta, payload))
        assert rguards.verify_wire([(meta, payload, crc)], "op") \
            == [(meta, payload)]
        tampered = ("q8", (8,), "float32")
        with pytest.raises(mpi.IntegrityError):
            rguards.verify_wire([(tampered, payload, crc)], "op")

    def test_wire_checksum_roundtrip(self):
        payload = {"q": jnp.asarray([[1, -3], [7, 9]], jnp.int8),
                   "scale": jnp.asarray([0.5, 2.0], jnp.float32)}
        c = rguards.wire_checksum(payload)
        assert c == rguards.wire_checksum(payload)
        flipped = dict(payload, q=payload["q"].at[0, 0].set(2))
        assert c != rguards.wire_checksum(flipped)


# =========================================================================
# Mode A (SPMD) guard: HLO census + violation ledger
# =========================================================================

class TestModeAGuardCensus:
    def _lowered(self, compression=False):
        from jax.sharding import Mesh, PartitionSpec as P

        from jax import shard_map

        mesh = Mesh(np.asarray(jax.devices()), ("w",))
        cm = mpi.comm_from_mesh(mesh, "w")
        return jax.jit(shard_map(
            lambda a: cm.Allreduce(a, mpi.MPI_SUM,
                                   compression=compression),
            mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False)).lower(
                jnp.ones((256,), jnp.float32)).as_text()

    def test_off_path_bit_identical_to_guardless_build(self):
        # THE zero-overhead claim: guard off == the hook monkeypatched
        # out entirely, full StableHLO text equality (and no is_finite).
        text_off = self._lowered()
        hook = rguards.spmd_finite_value
        try:
            rguards.spmd_finite_value = lambda v, where: v
            text_bypassed = self._lowered()
        finally:
            rguards.spmd_finite_value = hook
        assert text_off == text_bypassed
        assert text_off.count("stablehlo.is_finite") == 0

    def test_checksum_knob_never_touches_mode_a(self):
        text_off = self._lowered()
        mpi.config.set_comm_wire_checksum(True)
        assert self._lowered() == text_off

    def test_guard_on_census_deltas(self):
        text_off = self._lowered()
        mpi.config.set_comm_finite_guard("warn")
        text_on = self._lowered()
        assert text_on.count("stablehlo.is_finite") \
            - text_off.count("stablehlo.is_finite") == 1
        assert text_on.count("stablehlo.custom_call") \
            - text_off.count("stablehlo.custom_call") == 1

    @pytest.mark.slow
    def test_guard_on_census_compressed(self):
        # The q8 leg of the census (an extra pair of lowerings) rides
        # the slow lane; the exact-path census above is the tier-1 pin.
        text_off = self._lowered("q8")
        mpi.config.set_comm_finite_guard("warn")
        text_on = self._lowered("q8")
        assert text_on.count("stablehlo.is_finite") \
            - text_off.count("stablehlo.is_finite") == 1

    def test_violation_ledger_records_nonfinite(self):
        from jax.sharding import Mesh, PartitionSpec as P

        from jax import shard_map

        mesh = Mesh(np.asarray(jax.devices()), ("w",))
        cm = mpi.comm_from_mesh(mesh, "w")
        fn = jax.jit(shard_map(
            lambda a: cm.Allreduce(a, mpi.MPI_SUM),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
        mpi.config.set_comm_finite_guard("warn")
        rguards.clear_violations()
        x = jnp.asarray([np.nan] + [1.0] * 255, jnp.float32)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            jax.block_until_ready(fn(x))
        rec = rguards.last_violation()
        assert rec is not None and rec["where"] == "Allreduce"

    def test_clean_input_leaves_ledger_empty(self):
        mpi.config.set_comm_finite_guard("warn")
        rguards.clear_violations()
        out = mpi.run_spmd(
            lambda x: comm.Allreduce(x, mpi.MPI_SUM), nranks=2)(
                jnp.ones((8,), jnp.float32))
        jax.block_until_ready(out)
        assert rguards.last_violation() is None


# =========================================================================
# Fault plan grammar
# =========================================================================

class TestFaultPlanGrammar:
    def test_unknown_kind_raises(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            rz.FaultSpec("meteor_strike")

    def test_index_and_count_window(self):
        plan = rz.FaultPlan([rz.FaultSpec("corrupt_nan", rank=0,
                                          op="Allreduce", index=1)])
        p0 = plan.on_exchange(None, 0, ("Allreduce", 1), jnp.ones(4))
        assert not np.isnan(np.asarray(p0)).any()      # call 0: skipped
        p1 = plan.on_exchange(None, 0, ("Allreduce", 2), jnp.ones(4))
        assert np.isnan(np.asarray(p1)).any()          # call 1: fires
        p2 = plan.on_exchange(None, 0, ("Allreduce", 3), jnp.ones(4))
        assert not np.isnan(np.asarray(p2)).any()      # count=1: done
        assert len(plan.fired) == 1

    def test_rank_and_op_filters(self):
        plan = rz.FaultPlan([rz.FaultSpec("corrupt_inf", rank=1,
                                          op="Allreduce")])
        p = plan.on_exchange(None, 0, ("Allreduce", 1), jnp.ones(4))
        assert np.isfinite(np.asarray(p)).all()        # wrong rank
        p = plan.on_exchange(None, 1, ("Bcast_", 0), jnp.ones(4))
        assert np.isfinite(np.asarray(p)).all()        # wrong op
        p = plan.on_exchange(None, 1, ("Allreduce.q8hop", 0), jnp.ones(4))
        assert np.isinf(np.asarray(p)).any()           # prefix matches

    def test_bitflip_targets_integer_wire_only(self):
        plan = rz.FaultPlan([rz.FaultSpec("bitflip", rank=0)])
        f = plan.on_exchange(None, 0, ("Allreduce", 0), jnp.ones(4))
        np.testing.assert_array_equal(np.asarray(f), np.ones(4))
        assert plan.fired == []                        # float: inert
        q = {"q": jnp.zeros((4,), jnp.int8), "s": jnp.ones(2)}
        flipped = plan.on_exchange(None, 0, ("Allreduce", 1), q)
        assert np.asarray(flipped["q"]).any()          # a bit moved
        np.testing.assert_array_equal(np.asarray(flipped["s"]), np.ones(2))
        assert len(plan.fired) == 1

    def test_bitflip_wraparound_does_not_cancel_itself(self):
        # nflips > payload bytes: revisited bytes must advance to the
        # next BIT, not re-flip bit 0 back to the original value.
        plan = rz.FaultPlan([rz.FaultSpec("bitflip", rank=0, nflips=8)])
        q = {"q": jnp.zeros((4,), jnp.int8)}     # 4 wire bytes, 8 flips
        flipped = plan.on_exchange(None, 0, ("Allreduce", 0), q)
        assert np.asarray(flipped["q"]).any(), (
            "wrapped flips cancelled the corruption while the ledger "
            "recorded it as fired")

    def test_fault_scope_restores_previous_plan(self):
        assert mpi.config.fault_plan() is None
        with rz.fault_scope([rz.FaultSpec("delay", seconds=0.0)]):
            assert mpi.config.fault_plan() is not None
            with rz.fault_scope([rz.FaultSpec("bitflip")]) as inner:
                assert mpi.config.fault_plan() is inner
            assert mpi.config.fault_plan() is not None
        assert mpi.config.fault_plan() is None

    def test_set_fault_plan_coerces_spec_lists(self):
        mpi.config.set_fault_plan([rz.FaultSpec("delay", seconds=0.0)])
        assert isinstance(mpi.config.fault_plan(), rz.FaultPlan)
        mpi.config.set_fault_plan(None)


# =========================================================================
# run_ranks timeout default (satellite bugfix)
# =========================================================================

class TestWorldTimeoutEnv:
    def test_run_ranks_honors_env_timeout(self, monkeypatch):
        # run_ranks used to hard-code timeout=60.0, silently bypassing
        # MPI4TORCH_TPU_WORLD_TIMEOUT; both paths must honor it now.
        from mpi4torch_tpu.runtime import World, current_rank_context

        monkeypatch.setenv("MPI4TORCH_TPU_WORLD_TIMEOUT", "123.5")
        out = mpi.run_ranks(
            lambda r: current_rank_context().world.timeout, 2)
        assert out == [123.5, 123.5]
        assert World(2).timeout == 123.5

    def test_run_ranks_explicit_timeout_still_wins(self, monkeypatch):
        from mpi4torch_tpu.runtime import current_rank_context

        monkeypatch.setenv("MPI4TORCH_TPU_WORLD_TIMEOUT", "123.5")
        out = mpi.run_ranks(
            lambda r: current_rank_context().world.timeout, 2,
            timeout=7.0)
        assert out == [7.0, 7.0]


# =========================================================================
# Fault matrix: fast representative subset (tier-1) + full sweep (slow)
# =========================================================================

# One representative cell per outcome class on the (3,) world — the
# fast lane's proof the matrix machinery is exercised end-to-end; the
# FULL matrix (every kind × subsystem × world) runs on the slow lane
# and in `make faults-smoke`, keeping tier-1 inside its 870s budget.
_FAST_CELLS = [
    ("rank_death", "fused"),        # raise, typed + attributed
    ("delay", "plain"),             # recover via retry/backoff
    ("drop_p2p", "overlap"),        # recover via redelivery
    ("corrupt_nan", "compressed"),  # raise via finite guard
    ("bitflip", "compressed"),      # raise via wire checksum
    ("bitflip", "fused"),           # inert off the encoded wire
]


class TestFaultMatrixFast:
    @pytest.mark.parametrize("kind,subsystem", _FAST_CELLS)
    def test_cell(self, kind, subsystem):
        rec = rmatrix.run_cell(kind, subsystem, nranks=3)
        assert rec["status"] == "ok", rec


@pytest.mark.slow
class TestFaultMatrixFull:
    @pytest.mark.parametrize("nranks,algorithm", rmatrix.WORLDS)
    def test_world(self, nranks, algorithm):
        failures = []
        for kind, subsystem in rmatrix.coverage_cells():
            if subsystem == "checkpoint":
                continue
            if algorithm is not None and subsystem not in (
                    "plain", "compressed"):
                continue
            rec = rmatrix.run_cell(kind, subsystem, nranks=nranks,
                                   algorithm=algorithm)
            if rec["status"] != "ok":
                failures.append(rec)
        assert not failures, failures

    def test_checkpoint_cell(self, tmp_path):
        pytest.importorskip("orbax.checkpoint")
        rec = rmatrix.run_checkpoint_cell(str(tmp_path / "run"))
        assert rec["status"] == "ok", rec
