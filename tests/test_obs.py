"""mpi4torch_tpu.obs — unified runtime observability (ISSUE 12).

Covers the five layers: chokepoint comm tracing (typed CommEvents at
World.exchange + the p2p mailboxes, zero per-subsystem hooks), the
process-wide metrics registry (retry events / integrity violations /
serve counters under one namespace, Prometheus export, the shared
percentile rule), the failure flight recorder (rank-attributed
postmortems — tested through the fault matrix's rank_death cell,
alongside the existing attribution cells), Chrome-trace export, and
the static-vs-runtime reconciliation (measured Mode B wire == analyze
predictions EXACTLY).  The off-path contract — obs disabled lowers
bit-identical to an obs-less build — is censused here; `make
obs-smoke` runs the full lane.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4torch_tpu as mpi
from mpi4torch_tpu import COMM_WORLD as comm, analyze, config, obs
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _lower(fn, *args):
    mesh = Mesh(np.asarray(jax.devices()), ("w",))
    cm = mpi.comm_from_mesh(mesh, "w")
    return jax.jit(shard_map(lambda *a: fn(cm, *a), mesh=mesh,
                             in_specs=P(), out_specs=P(),
                             check_vma=False)).lower(*args)


class TestCommTracing:
    def test_off_by_default(self):
        assert config.comm_tracer() is None
        # The untraced path still works (and records nothing anywhere).
        out = mpi.run_ranks(
            lambda r: comm.Allreduce(jnp.ones(4, jnp.float32) * (r + 1),
                                     mpi.MPI_SUM), 2)
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      np.full(4, 3.0))

    def test_exchange_events_censused(self):
        with obs.trace() as t:
            mpi.run_ranks(
                lambda r: comm.Allreduce(
                    jnp.arange(256, dtype=jnp.float32) * (r + 1),
                    mpi.MPI_SUM, algorithm="ring"), 3)
        assert config.comm_tracer() is None   # restored on exit
        evs = t.events_for(rank=0, channel="exchange")
        assert len(evs) == 1
        ev = evs[0]
        assert ev.op == "Allreduce"
        assert ev.family == "all_reduce"
        assert ev.payload_bytes == 256 * 4
        assert ev.algorithm == "ring"
        assert ev.world_size == 3
        assert ev.status == "ok"
        assert ev.duration_s >= 0
        # every rank recorded its own copy of the logical collective
        assert {e.rank for e in t.events_for(channel="exchange")} \
            == {0, 1, 2}

    def test_values_unchanged_under_tracing(self):
        def body(rank):
            x = jnp.full(5, float(rank) + 1.0)
            y = comm.Allreduce(x, mpi.MPI_SUM)
            g = jax.grad(
                lambda v: jnp.sum(comm.Allreduce(v, mpi.MPI_SUM)))(x)
            return np.asarray(y), np.asarray(g)

        plain = mpi.run_ranks(body, 3)
        with obs.trace():
            traced = mpi.run_ranks(body, 3)
        for (y0, g0), (y1, g1) in zip(plain, traced):
            np.testing.assert_array_equal(y0, y1)
            np.testing.assert_array_equal(g0, g1)

    def test_bucket_labels_on_fused_buckets(self):
        def body(rank):
            tree = {"a": jnp.arange(96, dtype=jnp.float32) * (r0 + 1)
                    for r0 in [rank]}
            return comm.Allreduce_tree(tree, mpi.MPI_SUM,
                                       bucket_bytes=128)
        with obs.trace() as t:
            mpi.run_ranks(body, 2)
        labels = {e.bucket for e in t.events_for(rank=0)
                  if e.bucket is not None}
        assert labels, "fused buckets recorded no bucket labels"
        assert all("Allreduce_tree.bucket" in b for b in labels)

    def test_p2p_events(self):
        def body(rank):
            h = comm.Isend(jnp.ones(8), (rank + 1) % 2, 3)
            buf = mpi.JoinDummies(jnp.zeros(8), [h.dummy])
            y = comm.Recv(buf, (rank - 1) % 2, 3)
            ret = comm.Wait(mpi.JoinDummiesHandle(h, [y]))
            return mpi.JoinDummies(y, [ret])
        with obs.trace() as t:
            mpi.run_ranks(body, 2)
        sends = t.events_for(channel="p2p_send")
        recvs = t.events_for(channel="p2p_recv")
        assert len(sends) == 2 and len(recvs) == 2
        # x64 harness: default dtype is f64 -> 8 bytes/elem
        itemsize = jnp.ones(1).dtype.itemsize
        assert all(e.payload_bytes == 8 * itemsize for e in sends)
        assert all(e.payload_bytes == 8 * itemsize for e in recvs)
        assert sends[0].peer is not None and sends[0].tag == 3

    def test_ring_buffer_bounded(self):
        with obs.trace(ring=4) as t:
            def body(rank):
                x = jnp.ones(2, jnp.float32)
                for _ in range(9):
                    x = comm.Allreduce(x, mpi.MPI_SUM)
                return x
            mpi.run_ranks(body, 2)
        tails = t.tails()
        assert all(len(v) == 4 for v in tails.values())
        # newest-last ordering
        for tail in tails.values():
            assert tail[-1].seq == max(e.seq for e in tail)


class TestModeAEvents:
    def test_spmd_hook_off_is_bit_identical(self):
        mesh = Mesh(np.asarray(jax.devices()), ("w",))
        cm = mpi.comm_from_mesh(mesh, "w")
        x = jnp.ones(64, jnp.float32)

        def lowered():
            return jax.jit(shard_map(
                lambda a: cm.Allreduce(a, mpi.MPI_SUM), mesh=mesh,
                in_specs=P(), out_specs=P(),
                check_vma=False)).lower(x).as_text()

        base = lowered()
        hook = obs.tracing.spmd_collective_event
        try:
            obs.tracing.spmd_collective_event = lambda v, where: v
            assert lowered() == base
        finally:
            obs.tracing.spmd_collective_event = hook
        # A Mode B-only tracer must not move the lowering either.
        with obs.trace():
            assert lowered() == base
        # A mode_a tracer prices exactly one host callback.
        with obs.trace(mode_a=True):
            on = lowered()
        assert on.count("stablehlo.custom_call") \
            - base.count("stablehlo.custom_call") == 1

    def test_mode_a_flag_rides_fingerprint(self):
        base = config.thresholds_fingerprint()
        assert base[-1] is False
        with obs.trace(mode_a=True):
            assert config.thresholds_fingerprint()[-1] is True
        with obs.trace():   # Mode B-only: no retrace forced
            assert config.thresholds_fingerprint() == base

    def test_mode_a_events_recorded(self):
        with obs.trace(mode_a=True) as t:
            step = mpi.run_spmd(
                lambda v: comm.Allreduce(v, mpi.MPI_SUM), nranks=4)
            jax.block_until_ready(step(jnp.ones(32, jnp.float32)))
        evs = t.events_for(channel="spmd")
        assert evs and evs[0].op == "Allreduce"
        assert evs[0].payload_bytes == 32 * 4


class TestMetricsRegistry:
    def test_counter_gauge_histogram_snapshot(self):
        reg = obs.MetricsRegistry()
        reg.inc("widgets_total", 2, help="widgets")
        reg.inc("widgets_total")
        reg.set_gauge("depth", 7)
        for v in (0.5e-4, 2e-3, 5.0):
            reg.observe("latency_seconds", v)
        snap = reg.snapshot()
        assert snap["counters"]["widgets_total"] == 3
        assert snap["gauges"]["depth"] == 7
        h = snap["histograms"]["latency_seconds"]
        assert h["count"] == 3 and h["sum"] == pytest.approx(5.00205)
        reg.reset()
        assert reg.snapshot()["counters"] == {}

    def test_prometheus_text(self):
        reg = obs.MetricsRegistry()
        reg.inc("events_total", 5, help="events seen")
        reg.observe("dur_seconds", 0.02)
        text = reg.prometheus_text()
        assert "# TYPE mpi4torch_events_total counter" in text
        assert "mpi4torch_events_total 5" in text
        assert 'mpi4torch_dur_seconds_bucket{le="+Inf"} 1' in text
        assert "mpi4torch_dur_seconds_count 1" in text

    def test_collectors_polled_at_snapshot(self):
        reg = obs.MetricsRegistry()
        state = {"n": 1}
        reg.register_collector("thing", lambda: dict(state))
        assert reg.snapshot()["collected"]["thing"] == {"n": 1}
        state["n"] = 9
        assert reg.snapshot()["collected"]["thing"] == {"n": 9}

    def test_broken_collector_isolated(self):
        reg = obs.MetricsRegistry()
        reg.register_collector("bad", lambda: 1 / 0)
        got = reg.snapshot()["collected"]["bad"]
        assert "error" in got and "ZeroDivisionError" in got["error"]

    def test_default_registry_has_serve_collector(self):
        snap = obs.snapshot()
        assert "serve" in snap["collected"]
        assert "n_engines" in snap["collected"]["serve"]

    def test_every_serve_counter_mirrors_as_metric(self):
        # ISSUE 17 satellite: the paging counters (prefix_hits,
        # cow_copies, blocks_in_use, ...) must reach the exposition
        # like every other ServeStats counter — registry-sync, not a
        # hand-picked subset, so a new counter cannot ship unmirrored.
        from mpi4torch_tpu import serve
        from mpi4torch_tpu.utils.profiling import (ServeStats,
                                                   _register_serve_stats)

        serve.reset_stats()
        s = _register_serve_stats(ServeStats())
        for name in ServeStats._COUNTERS:
            s.count(name, 0)
        try:
            text = obs.prometheus_text()
            for name in ServeStats._COUNTERS:
                assert f"mpi4torch_serve_{name} " in text, name
            for paging in ("prefix_hits", "cow_copies", "preempted",
                           "blocks_in_use", "blocks_free",
                           "blocks_cached"):
                assert paging in ServeStats._COUNTERS
        finally:
            serve.reset_stats()

    def test_percentile_matches_bench_rule(self):
        vals = [5.0, 1.0, 3.0, 2.0, 4.0]
        # the nearest-rank floor rule: sorted[min(int(q*n), n-1)]
        s = sorted(vals)
        for q in (0.0, 0.5, 0.9, 0.99, 1.0):
            assert obs.percentile(vals, q) \
                == s[min(int(q * len(s)), len(s) - 1)]
        assert obs.percentile([], 0.5) is None


class TestAdHocSurfacesUnified:
    """The satellite contract: retry_events and last_violation() keep
    their historical access paths AND appear as obs metrics."""

    def test_retry_events_mirrored(self):
        from mpi4torch_tpu.resilience import fault_scope

        obs.reset_metrics()
        spec = mpi.FaultSpec("drop_p2p", rank=0, op="p2p", index=0)
        seen = {}
        config.set_comm_retries(4)
        config.set_comm_backoff(0.05)
        try:
            with obs.trace() as t:
                def body(rank):
                    from mpi4torch_tpu.runtime import \
                        current_rank_context
                    ctx = current_rank_context()
                    if rank == 0:
                        ctx.world.p2p_send(0, 1, 9, jnp.ones(4))
                    else:
                        got = ctx.world.p2p_recv(0, 1, 9)
                        seen["retry_events"] = ctx.world.retry_events
                        return got
                with fault_scope([spec]):
                    mpi.run_ranks(body, 2, timeout=0.3)
        finally:
            config.set_comm_retries(0)
            config.set_comm_backoff(0.05)
        assert seen["retry_events"] >= 1          # old surface intact
        counters = obs.snapshot()["counters"]
        assert counters.get("comm_retry_events_total", 0) >= 1
        # ... and the recovering receive's event carries its retries.
        recvs = t.events_for(channel="p2p_recv")
        assert any(e.retries >= 1 for e in recvs)

    def test_violation_ledger_mirrored(self):
        import warnings

        from mpi4torch_tpu.resilience import guards

        obs.reset_metrics()
        guards.clear_violations()
        config.set_comm_finite_guard("warn")
        try:
            def body(rank):
                x = jnp.full(4, float("nan") if rank == 1 else 1.0)
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")
                    return comm.Allreduce(x, mpi.MPI_SUM)
            mpi.run_ranks(body, 2)
        finally:
            config.set_comm_finite_guard("off")
        viol = guards.last_violation()            # old surface intact
        assert viol is not None and viol["ranks"] == [1]
        counters = obs.snapshot()["counters"]
        assert counters.get("integrity_violations_total", 0) >= 1
        guards.clear_violations()

    def test_tune_cache_counters(self):
        from mpi4torch_tpu import tune

        obs.reset_metrics()
        tune.autotuner.lookup("allreduce", jnp.float32, 123456789, 3,
                              platform="nosuch")
        counters = obs.snapshot()["counters"]
        assert counters.get("tune_cache_misses_total", 0) >= 1


class TestServeStatsRehome:
    """One weakref registry implementation: ServeStats registration
    rides obs.metrics.sources(); serve.stats()/reset_stats() keep
    their semantics; snapshot gains p50/p99 via the shared rule."""

    def test_registry_is_the_obs_one(self):
        from mpi4torch_tpu import serve
        from mpi4torch_tpu.utils.profiling import (ServeStats,
                                                   _register_serve_stats)

        serve.reset_stats()
        s = _register_serve_stats(ServeStats())
        from mpi4torch_tpu.obs.metrics import sources
        assert s in sources().live("serve")
        s.count("steps", 3)
        assert serve.stats()["steps"] == 3
        serve.reset_stats()
        assert sources().live("serve") == []
        assert serve.stats()["steps"] == 0
        assert s.counters["steps"] == 0    # reset IN PLACE, as before

    def test_snapshot_p50_p99(self):
        from mpi4torch_tpu.utils.profiling import ServeStats

        s = ServeStats()
        for i, rid in enumerate(("a", "b", "c")):
            s.mark(rid, "submitted")
            s.spans[rid]["first_token"] = \
                s.spans[rid]["submitted"] + 0.1 * (i + 1)
            s.spans[rid]["finished"] = \
                s.spans[rid]["submitted"] + 0.2 * (i + 1)
        snap = s.snapshot()
        ttft = [0.1, 0.2, 0.3]
        assert snap["ttft_s"]["p50"] == pytest.approx(
            obs.percentile(ttft, 0.50))
        assert snap["ttft_s"]["p99"] == pytest.approx(
            obs.percentile(ttft, 0.99))
        assert snap["e2e_s"]["p50"] == pytest.approx(0.4)
        assert {"mean", "max", "p50", "p99"} <= set(snap["e2e_s"])


class TestFlightRecorder:
    """The postmortem cell, alongside the fault matrix's existing
    rank_death attribution cells (resilience.matrix)."""

    def test_rank_death_postmortem_in_matrix_cell(self):
        from mpi4torch_tpu.resilience import matrix

        with obs.trace(ring=8) as t:
            rec = matrix.run_cell("rank_death", "plain", nranks=3)
        assert rec["status"] == "ok", rec     # the existing cell holds
        pm = t.last_postmortem()
        assert pm is not None
        assert pm["error"] == "RankFailedError"
        assert pm["failed_ranks"] == [1]      # the matrix's target rank
        # survivor tails consistent: everyone's last event is the torn
        # collective the dead rank also recorded last.
        from mpi4torch_tpu.obs.flight import last_event_signature
        dead_sig = last_event_signature(pm, 1)
        assert dead_sig is not None
        for r in range(3):
            assert last_event_signature(pm, r) == dead_sig

    def test_postmortem_format_and_dump(self, tmp_path):
        spec = mpi.FaultSpec("rank_death", rank=1, op="Allreduce",
                             index=1)
        from mpi4torch_tpu.resilience import fault_scope

        with obs.trace(ring=8) as t:
            with fault_scope([spec]):
                with pytest.raises(mpi.RankFailedError):
                    def body(rank):
                        x = jnp.ones(8, jnp.float32)
                        for _ in range(3):
                            x = comm.Allreduce(x, mpi.MPI_SUM)
                        return x
                    mpi.run_ranks(body, 3, timeout=2.0)
        pm = t.last_postmortem()
        text = obs.format_postmortem(pm)
        assert "FLIGHT RECORDER POSTMORTEM" in text
        assert "rank(s): [1]" in text
        assert "** FAILED/MISSING **" in text
        paths = obs.dump_postmortem(pm, str(tmp_path))
        import json
        with open(paths["json"], encoding="utf-8") as f:
            loaded = json.load(f)
        assert loaded["failed_ranks"] == [1]
        assert "tails" in loaded and loaded["tails"]

    def test_integrity_error_postmortem(self):
        """Failures raised OUTSIDE the chokepoints (the guards verify
        the decoded list after the rendezvous) still get a postmortem
        via the run_ranks reaper hook."""
        spec = mpi.FaultSpec("corrupt_nan", rank=1, op="Allreduce")
        from mpi4torch_tpu.resilience import fault_scope

        config.set_comm_finite_guard("raise")
        try:
            with obs.trace() as t:
                with fault_scope([spec]):
                    with pytest.raises(mpi.IntegrityError):
                        mpi.run_ranks(
                            lambda r: comm.Allreduce(
                                jnp.ones(8, jnp.float32), mpi.MPI_SUM),
                            2, timeout=2.0)
        finally:
            config.set_comm_finite_guard("off")
        pm = t.last_postmortem()
        assert pm is not None and pm["error"] == "IntegrityError"
        assert pm["failed_ranks"] == [1]


class TestChromeTraceExport:
    def test_export_structure(self, tmp_path):
        with obs.trace() as t:
            mpi.run_ranks(
                lambda r: comm.Allreduce(jnp.ones(16, jnp.float32),
                                         mpi.MPI_SUM), 2)
        doc = obs.chrome_trace(t.events)
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len(xs) == 2
        assert {e["tid"] for e in xs} == {0, 1}
        assert all(e["args"]["payload_bytes"] == 64 for e in xs)
        assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in xs)
        path = obs.write_chrome_trace(str(tmp_path / "t.json"),
                                      t.events)
        import json
        with open(path, encoding="utf-8") as f:
            assert json.load(f)["traceEvents"]


class TestReconcile:
    """The static-vs-runtime contract on tier-1-sized workloads (the
    full four-schedule matrix incl. q8 + serve decode runs in `make
    obs-smoke`)."""

    def test_ring_allreduce_exact(self):
        x = jnp.arange(512, dtype=jnp.float32)
        with obs.trace() as t:
            mpi.run_ranks(
                lambda r: comm.Allreduce(x * (r + 1), mpi.MPI_SUM,
                                         algorithm="ring"), 8)
        low = _lower(lambda cm, a: cm.Allreduce(a, mpi.MPI_SUM,
                                                algorithm="ring"), x)
        rep = obs.reconcile(t.events, low, dropped=t.dropped)
        assert rep["ok"], rep
        assert rep["measured"]["wire_bytes"] \
            == rep["predicted"]["wire_bytes"] == 2 * 7 * 512 * 4 // 8
        assert rep["measured"]["counts"] == {"all_reduce": 1}

    def test_reshard_migration_exact(self):
        from mpi4torch_tpu import reshard as rs

        fl = rs.layout((8,), 0, None)
        tl = rs.layout((2, 4), 0, 1)
        G = (64, 32)
        shard = fl.shard_shape(G)
        with obs.trace() as t:
            def body(rank):
                x = jnp.arange(int(np.prod(shard)), dtype=jnp.float32
                               ).reshape(shard) * (rank + 1)
                return comm.Reshard(x, fl, tl)
            mpi.run_ranks(body, 8)
        low = _lower(lambda cm, a: cm.Reshard(a, fl, tl),
                     jnp.zeros(shard, jnp.float32))
        rep = obs.reconcile(t.events, low, dropped=t.dropped)
        assert rep["ok"], rep

    def test_bookkeeping_excluded_and_determinism_checked(self):
        # Barrier + fold-share rounds are bookkeeping, not wire.
        with obs.trace() as t:
            def body(rank):
                from mpi4torch_tpu.runtime import current_rank_context
                ctx = current_rank_context()
                ctx.world.barrier(ctx.rank)
                return comm.Allreduce(jnp.ones(4, jnp.float32),
                                      mpi.MPI_SUM)
            mpi.run_ranks(body, 2)
        mt = obs.measured_wire_table(t.events)
        assert mt["excluded"]["bookkeeping"] == 1
        assert mt["logical_events"] == 1
        assert mt["per_rank_consistent"]

    def test_mismatch_detected(self):
        # A prediction for a DIFFERENT payload must not reconcile.
        x = jnp.arange(512, dtype=jnp.float32)
        with obs.trace() as t:
            mpi.run_ranks(
                lambda r: comm.Allreduce(x, mpi.MPI_SUM,
                                         algorithm="ring"), 4)
        low = _lower(
            lambda cm, a: cm.Allreduce(a, mpi.MPI_SUM,
                                       algorithm="ring"),
            jnp.arange(1024, dtype=jnp.float32))
        rep = obs.reconcile(t.events, low, dropped=t.dropped)
        assert not rep["ok"]
        assert not rep["matches"]["wire_bytes"]

    def test_dropped_events_fail_the_contract(self):
        x = jnp.ones(64, jnp.float32)
        with obs.trace() as t:
            mpi.run_ranks(
                lambda r: comm.Allreduce(x, mpi.MPI_SUM,
                                         algorithm="ring"), 8)
        low = _lower(lambda cm, a: cm.Allreduce(a, mpi.MPI_SUM,
                                                algorithm="ring"), x)
        good = obs.reconcile(t.events, low, dropped=0)
        bad = obs.reconcile(t.events, low, dropped=3)
        assert good["ok"] and not bad["ok"]
        # Passing the tracer itself reads .dropped automatically — the
        # canonical form cannot under-report a truncated census.
        assert obs.reconcile(t, low)["ok"]
        t.dropped = 5
        assert not obs.reconcile(t, low)["ok"]

    def test_spmd_events_counted_in_exclusions(self):
        # Mode A step events are not rendezvous wire, but they must
        # appear in the exclusion report, never vanish silently.
        with obs.trace(mode_a=True) as t:
            step = mpi.run_spmd(
                lambda v: comm.Allreduce(v, mpi.MPI_SUM), nranks=4)
            jax.block_until_ready(step(jnp.ones(16, jnp.float32)))
        mt = obs.measured_wire_table(t.events)
        assert mt["excluded"]["spmd"] == len(
            t.events_for(channel="spmd")) > 0

    def test_compressed_allgather_unmodeled_not_crashed(self):
        # The rendezvous-codec Allgather's encoded wire has no
        # event-reproducible Mode A census: it must land in the
        # unmodeled exclusion report, never raise out of the table.
        with obs.trace() as t:
            mpi.run_ranks(
                lambda r: comm.Allgather(
                    jnp.linspace(-1, 1, 64,
                                 dtype=jnp.float32) * (r + 1),
                    0, compression="q8"), 2)
        mt = obs.measured_wire_table(t.events)
        assert mt["excluded"]["unmodeled"].get("Allgather.c", 0) == 1
        assert mt["logical_events"] == 0

    def test_wire_contribution_shared_formula(self):
        # The ONE formula: analyze's static pass and the runtime
        # conversion agree by construction.
        assert analyze.wire_contribution("collective_permute", 100) \
            == 100
        assert analyze.wire_contribution("all_gather", 100, 4) == 300
        assert analyze.wire_contribution("all_reduce", 100, 4) \
            == pytest.approx(150.0)
        assert analyze.wire_contribution("reduce_scatter", 100, 4) \
            == pytest.approx(75.0)
        with pytest.raises(ValueError):
            analyze.wire_contribution("all_reduce", 100, None)
        with pytest.raises(ValueError):
            analyze.wire_contribution("nosuch", 100, 4)
