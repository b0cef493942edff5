"""Online self-tuning controller (ISSUE 19, mpi4torch_tpu.ctl).

The control loop in layers: the EWMA goodput estimator over synthetic
CommEvent streams (tier attribution == the census rule, cursor, codec
invariance), the two-watermark drift monitor (the no-flap hysteresis
property), the decision ledger, the config knobs
(validation/snapshot/fingerprint), the registry-sync guard, and the
REAL closed loop — a brownout driven through an epoch-fenced consensus
to the q8 winner and back, bitwise against the explicit-q8 oracle and
the pre-episode exact result, on the (8,) and (2,2,2) stacks over the
thread AND process transports.  ``make ctl-smoke`` runs the standalone
lane over the same surface.
"""

import json

import numpy as np
import pytest

import mpi4torch_tpu as mpi
from mpi4torch_tpu import config, ctl, obs, tune
from mpi4torch_tpu.analyze.registry import ctl_problems
from mpi4torch_tpu.compress import get_codec
from mpi4torch_tpu.ctl.__main__ import (closed_loop_episode,
                                        synthetic_event,
                                        synthetic_round)
from mpi4torch_tpu.ctl.controller import SelfTuningController
from mpi4torch_tpu.elastic.membership import StaleEpochError

NR = 8
TIERS = (2, 2, 2)


@pytest.fixture(autouse=True)
def _isolated_state(tmp_path, monkeypatch):
    monkeypatch.setenv("MPI4TORCH_TPU_TUNE_CACHE",
                       str(tmp_path / "tune_cache.json"))
    from mpi4torch_tpu.csched import synth as S
    snap = config.snapshot_process_state()
    tune.clear()
    S.clear_installed()
    yield
    config.apply_process_state(snap)
    config.set_fault_plan(None)
    tune.clear()
    S.clear_installed()


# ---------------------------------------------------------------------------
# Estimator
# ---------------------------------------------------------------------------

class TestEstimator:
    def test_ewma_halflife_math(self):
        e = ctl.Ewma(1.0)                      # alpha = 1/2
        assert e.update(4.0) == 4.0            # first sample adopted
        assert e.update(2.0) == pytest.approx(3.0)
        e4 = ctl.Ewma(4.0)
        e4.update(1.0)
        for _ in range(4):                     # one half-life of samples
            e4.update(0.0)
        assert e4.value == pytest.approx(0.5)

    def test_tier_attribution_is_the_census_rule(self):
        est = ctl.BandwidthEstimator(TIERS, halflife=1.0)
        # group of 2 -> innermost tier, 4 -> middle, whole-world (and
        # None) -> top: csched.tier_of_group on the measured stream.
        est.ingest([synthetic_event(0, 0, 1e6, group_size=2),
                    synthetic_event(1, 0, 2e6, group_size=4),
                    synthetic_event(2, 0, 3e6, group_size=None),
                    synthetic_event(3, 0, 3e6, group_size=8)])
        assert est.tier_estimates() == pytest.approx((1e6, 2e6, 3e6))
        assert est.tier_samples() == (1, 1, 2)

    def test_cursor_never_double_counts(self):
        est = ctl.BandwidthEstimator(TIERS, halflife=1.0)
        events = synthetic_round(0, 1e6)
        assert est.ingest(events) == NR
        assert est.ingest(events) == 0         # same seqs: no-op
        assert est.ingest(events + [synthetic_event(NR, 0, 5e5)]) == 1
        assert est.tier_estimates()[-1] == pytest.approx(7.5e5)

    def test_filters(self):
        est = ctl.BandwidthEstimator(TIERS, halflife=1.0)
        n = est.ingest([
            synthetic_event(0, 0, 9e9, bookkeeping=True),
            synthetic_event(1, 0, 9e9, status="Timeout"),
            synthetic_event(2, 0, 9e9, channel="p2p_send"),
            synthetic_event(3, 0, 9e9, nbytes=0),
        ])
        assert n == 0
        assert est.tier_estimates() == (None, None, None)

    def test_goodput_is_codec_invariant(self):
        # A q8 event's encoded bytes scale back to LOGICAL bytes by
        # the codec's own wire accounting, so the estimate reads the
        # same bandwidth whether the wire is exact or compressed.
        wire = get_codec("q8").wire_bytes((4096,), "float32")
        factor = (4096 * 4) / wire
        ev = synthetic_event(0, 0, 1e6, nbytes=wire, codec="q8")
        assert ctl.goodput_bytes(ev) == pytest.approx(wire * factor)
        exact = synthetic_event(1, 0, 1e6)
        assert ctl.goodput_bytes(exact) == exact.payload_bytes
        # Unregistered codec: degrade to encoded bytes, never raise.
        odd = synthetic_event(2, 0, 1e6, codec="no-such-codec")
        assert ctl.goodput_bytes(odd) == odd.payload_bytes

    def test_per_link_estimates(self):
        est = ctl.BandwidthEstimator(TIERS, halflife=1.0)
        est.ingest(synthetic_round(0, 1e6))
        links = est.link_estimates()
        assert sorted(links) == list(range(NR))
        assert all(v == pytest.approx(1e6) for v in links.values())


# ---------------------------------------------------------------------------
# Drift monitor
# ---------------------------------------------------------------------------

class TestDriftMonitor:
    def _calibrated(self, low=0.5, high=0.8, patience=2):
        est = ctl.BandwidthEstimator(TIERS, halflife=1.0)
        mon = ctl.DriftMonitor(len(TIERS), low=low, high=high,
                               patience=patience)
        est.ingest(synthetic_round(0, 1e6))
        mon.calibrate(est)
        return est, mon

    def test_no_flap_inside_the_band(self):
        est, mon = self._calibrated()
        seq = NR
        for i in range(16):   # oscillate INSIDE the hysteresis band
            est.ingest(synthetic_round(seq, 0.55e6 if i % 2
                                       else 0.75e6))
            seq += NR
            rep = mon.check(est)
            assert rep.changed == {}
        assert mon.states == ("ok", "ok", "ok")

    def test_patience_gates_both_directions(self):
        est, mon = self._calibrated()
        est.ingest(synthetic_round(NR, 0.1e6))
        assert mon.check(est).changed == {}       # 1st sag: patience
        est.ingest(synthetic_round(2 * NR, 0.1e6))
        rep = mon.check(est)
        assert rep.changed == {2: "degraded"}     # 2nd consecutive
        assert rep.degraded == (2,) and not rep.ok
        est.ingest(synthetic_round(3 * NR, 1e6))
        assert mon.check(est).changed == {}       # 1st recovery
        est.ingest(synthetic_round(4 * NR, 1e6))
        assert mon.check(est).changed == {2: "ok"}

    def test_single_excursion_resets(self):
        est, mon = self._calibrated()
        est.ingest(synthetic_round(NR, 0.1e6))
        mon.check(est)
        est.ingest(synthetic_round(2 * NR, 1e6))   # back in band
        mon.check(est)
        est.ingest(synthetic_round(3 * NR, 0.1e6))
        assert mon.check(est).changed == {}        # counter was reset

    def test_uncalibrated_tier_self_calibrates(self):
        est = ctl.BandwidthEstimator(TIERS, halflife=1.0)
        mon = ctl.DriftMonitor(len(TIERS))
        mon.calibrate(est)                         # all-None baseline
        est.ingest([synthetic_event(0, 0, 1e6, group_size=2)])
        rep = mon.check(est)
        assert rep.ratios[0] == pytest.approx(1.0)  # first value IS
        assert mon.baseline[0] == pytest.approx(1e6)  # the baseline

    def test_as_reconcile_shape(self):
        est, mon = self._calibrated()
        rep = mon.check(est)
        doc = rep.as_reconcile()
        assert doc["ok"] and set(doc["matches"]) == {"tier0", "tier1",
                                                     "tier2"}
        assert doc["measured"] == list(rep.estimates)

    def test_live_bandwidths_mixes_sag_into_declared(self):
        est, mon = self._calibrated(patience=1)
        est.ingest(synthetic_round(NR, 0.5e6))
        rep = mon.check(est)
        live = ctl.live_bandwidths(rep, (4.0, 2.0, 1.0))
        assert live[:2] == (4.0, 2.0)              # unsampled: declared
        assert live[2] == pytest.approx(0.5, abs=0.01)  # sagged: scaled
        uniform = ctl.live_bandwidths(rep, None)
        assert uniform[2] == pytest.approx(0.5, abs=0.01)

    def test_threshold_validation(self):
        with pytest.raises(ValueError):
            ctl.DriftMonitor(3, low=0.8, high=0.5)
        with pytest.raises(ValueError):
            ctl.DriftMonitor(3, patience=0)
        with pytest.raises(ValueError):
            ctl.DriftMonitor(0)


# ---------------------------------------------------------------------------
# Config knobs
# ---------------------------------------------------------------------------

class TestConfigKnobs:
    def test_defaults_off(self):
        assert config.ctl_enabled() is False

    def test_validated_setters(self):
        with pytest.raises(ValueError):
            config.set_ctl_halflife(0.0)
        with pytest.raises(ValueError):
            config.set_ctl_drift_thresholds(0.8, 0.5)
        with pytest.raises(ValueError):
            config.set_ctl_drift_thresholds(0.0, 0.5)
        with pytest.raises(ValueError):
            config.set_ctl_drift_patience(0)
        with pytest.raises(ValueError):
            config.set_ctl_min_switch_epochs(-1)
        with pytest.raises(ValueError):
            config.set_ctl_codec_crossover(0.0)
        with pytest.raises(ValueError):
            config.set_ctl_codec_crossover(1.5)

    def test_snapshot_round_trips_ctl_knobs(self):
        config.set_ctl_enabled(True)
        config.set_ctl_halflife(2.5)
        config.set_ctl_drift_thresholds(0.2, 0.6)
        config.set_ctl_drift_patience(3)
        config.set_ctl_min_switch_epochs(4)
        config.set_ctl_codec_crossover(0.1)
        snap = config.snapshot_process_state()
        for k in ("ctl_enabled", "ctl_halflife", "ctl_drift_thresholds",
                  "ctl_drift_patience", "ctl_min_switch_epochs",
                  "ctl_codec_crossover"):
            assert k in snap
        config.set_ctl_enabled(False)
        config.set_ctl_halflife(4.0)
        config.set_ctl_drift_thresholds(0.5, 0.8)
        config.apply_process_state(snap)
        assert config.ctl_enabled() is True
        assert config.ctl_halflife() == 2.5
        assert config.ctl_drift_thresholds() == (0.2, 0.6)
        assert config.ctl_drift_patience() == 3
        assert config.ctl_min_switch_epochs() == 4
        assert config.ctl_codec_crossover() == 0.1

    def test_fingerprint_covers_ctl_knobs(self):
        fp = config.thresholds_fingerprint()
        config.set_ctl_halflife(9.0)
        fp2 = config.thresholds_fingerprint()
        assert fp != fp2
        config.set_ctl_drift_thresholds(0.11, 0.91)
        assert config.thresholds_fingerprint() != fp2
        # The mode_a tracer flag stays the LAST element (tests/test_obs
        # reads fingerprint[-1]) — ctl entries must sit before it.
        assert config.thresholds_fingerprint()[-1] is False


# ---------------------------------------------------------------------------
# Ledger + registry guard
# ---------------------------------------------------------------------------

class TestLedger:
    def test_record_validates_and_counts(self):
        led = ctl.DecisionLedger()
        d = led.record(3, "crossover", tier=2, ratio=0.1,
                       old={"winner": "a", "weighted_cost": 4.0},
                       new={"winner": "b", "codec": "synth_q8",
                            "weighted_cost": 1.0})
        assert d.epoch == 3 and d.trigger == "crossover"
        assert len(led) == 1 and led.triggers() == ["crossover"]
        with pytest.raises(ValueError):
            led.record(4, "vibes")

    def test_json_and_table(self, tmp_path):
        led = ctl.DecisionLedger()
        led.record(1, "drift", tier=0, ratio=0.42,
                   old={"winner": "synth:aa", "weighted_cost": 8.0},
                   new={"winner": "synth:bb", "codec": "synth",
                        "weighted_cost": 2.0})
        led.record(2, "recovery", new={"restored": ["compression"]})
        doc = json.loads(led.to_json())
        assert [d["trigger"] for d in doc["decisions"]] == \
            ["drift", "recovery"]
        path = led.dump(str(tmp_path / "ledger.json"))
        with open(path, "r", encoding="utf-8") as f:
            assert json.load(f) == doc
        table = led.format_table()
        assert "synth:bb[synth]" in table and "8->2" in table
        assert "restored:compression" in table

    def test_registry_guard_clean(self):
        assert ctl_problems() == []

    def test_registry_guard_fires_on_drift(self, monkeypatch):
        import mpi4torch_tpu.ctl.__main__ as ctl_main
        monkeypatch.setattr(ctl_main, "LEDGER_COVERED", ("drift",))
        probs = ctl_problems()
        assert probs and "coverage literal" in probs[0]

    def test_policy_map_delegates_to_registered_triggers(self):
        from mpi4torch_tpu.resilience.degrade import DEGRADE_POLICIES
        assert set(ctl.POLICY_TRIGGER) == set(DEGRADE_POLICIES)
        assert set(ctl.POLICY_TRIGGER.values()) <= set(ctl.TRIGGER_KINDS)


# ---------------------------------------------------------------------------
# Controller (synthetic streams)
# ---------------------------------------------------------------------------

class TestController:
    def _controller(self, **kw):
        config.set_ctl_enabled(True)
        config.set_ctl_halflife(1.0)
        config.set_ctl_drift_patience(2)
        return SelfTuningController(n_ranks=NR, tiers=TIERS,
                                    nbytes=1 << 14, persist=False, **kw)

    def test_tier_stack_must_factor_the_world(self):
        with pytest.raises(ctl.CtlError):
            SelfTuningController(n_ranks=NR, tiers=(2, 2))

    def test_disabled_poll_is_inert(self):
        c = SelfTuningController(n_ranks=NR, tiers=TIERS)
        before = config.snapshot_process_state()
        assert c.poll() is None
        assert c.poll(synthetic_round(0, 1.0)) is None
        assert config.snapshot_process_state() == before
        assert len(c.ledger) == 0
        assert c.estimator.tier_samples() == (0, 0, 0)

    def test_drift_rerank_installs_exact_winner(self):
        c = self._controller()
        try:
            c.observe(synthetic_round(0, 1e6))
            c.calibrate()
            assert c.poll(synthetic_round(NR, 0.4e6)) is None
            d = c.poll(synthetic_round(2 * NR, 0.4e6))
            assert d is not None and d.trigger == "drift"
            assert d.tier == 2 and d.ratio == pytest.approx(0.4,
                                                            abs=0.01)
            assert d.new["codec"] == "synth"
            assert d.new["weighted_cost"] <= d.old["weighted_cost"]
            assert config.tier_bandwidths() is not None
            ent = tune.lookup("allreduce", "float32", 1 << 14, NR,
                              codec="synth", tiers=TIERS)
            assert ent is not None
            assert ent["algorithm"] == d.new["installed"]
            assert ent["ctl"] == {"provenance": "online-switched",
                                  "epoch": d.epoch, "trigger": "drift"}
        finally:
            c.reset()

    def test_crossover_escalates_codec(self):
        c = self._controller()
        try:
            c.observe(synthetic_round(0, 1e6))
            c.calibrate()
            c.poll(synthetic_round(NR, 1e3))
            d = c.poll(synthetic_round(2 * NR, 1e3))
            assert d is not None and d.trigger == "crossover"
            codec = config.default_compression()
            assert getattr(codec, "name", codec) == "q8"
            assert d.new["codec"] == "synth_q8"
            assert d.new["weighted_cost"] < d.old["weighted_cost"]
            assert d.new["tier_wire"][-1] < d.old["tier_wire"][-1]
        finally:
            c.reset()

    def test_min_epoch_hysteresis_suppresses_then_retries(self):
        c = self._controller()
        config.set_ctl_min_switch_epochs(5)
        try:
            c.observe(synthetic_round(0, 1e6))
            c.calibrate()
            c.poll(synthetic_round(NR, 1e3))
            d = c.poll(synthetic_round(2 * NR, 1e3))
            assert d is not None                    # first switch free
            # Recovered measurements, but the min-epochs hysteresis
            # suppresses the de-escalation switch...
            c.poll(synthetic_round(3 * NR, 1e6))
            d2 = c.poll(synthetic_round(4 * NR, 1e6))
            assert d2 is None and c._escalated
            # ...and the condition is STATE-based, so a later poll
            # (with the hysteresis relaxed) retries and ratifies.
            config.set_ctl_min_switch_epochs(1)
            d3 = c.poll(synthetic_round(5 * NR, 1e6))
            assert d3 is not None and d3.trigger == "recovery"
            assert config.default_compression() is None
        finally:
            c.reset()

    def test_fault_fast_path_shares_ledger_and_epoch(self):
        c = self._controller()
        try:
            tr = c.apply("codec_escalate")
            assert c.ledger.triggers() == ["fault"]
            d = list(c.ledger)[-1]
            assert d.policy == "codec_escalate"
            assert d.epoch == tr.epoch == c.runtime.epoch
        finally:
            c.reset()
        assert config.default_compression() is None


# ---------------------------------------------------------------------------
# The closed loop (real traffic, real fault, both transports)
# ---------------------------------------------------------------------------

class TestClosedLoop:
    @pytest.mark.parametrize("tiers,backend", [
        ((2, 2, 2), "thread"),
        ((8,), "thread"),
        ((2, 2, 2), "process"),
        pytest.param((8,), "process", marks=pytest.mark.slow),
    ])
    def test_brownout_escalate_recover_round_trip(self, tiers, backend):
        ev = closed_loop_episode(n=NR, tiers=tiers, backend=backend)
        esc, rec = ev["escalation"], ev["recovery"]
        assert ev["healthy_poll"] is None
        assert ev["patience_poll"] is None
        assert esc is not None and esc.trigger == "crossover"
        assert ev["compression_during"] == "q8"
        # Escalated phase rides the SAME wire as the explicit-q8
        # oracle — bitwise.
        for got, want in zip(ev["escalated"], ev["oracle_q8"]):
            assert np.array_equal(got, want)
        # A phase prepared against the pre-switch view is FENCED.
        assert ev["stale_fenced"] is True
        # Recovery restores the EXACT pre-episode configuration and
        # result.
        assert rec is not None and rec.trigger == "recovery"
        assert rec.epoch > esc.epoch
        assert ev["compression_after"] is None
        assert ev["bandwidths_after"] is None
        for got, want in zip(ev["recovered"], ev["exact_before"]):
            assert np.array_equal(got, want)
        assert ev["ledger"].triggers() == ["crossover", "recovery"]
        if len(tiers) > 1:
            # A real stack re-ranks to a DISTINCT lossy winner with
            # the weighted-cost improvement pinned; the installed
            # entry carries its online provenance for tune --show.
            assert esc.new["weighted_cost"] < esc.old["weighted_cost"]
            assert esc.new["tier_wire"][-1] < esc.old["tier_wire"][-1]
            ent = ev["tune_entry"]
            assert ent is not None
            assert ent["ctl"]["provenance"] == "online-switched"
            assert ent["ctl"]["epoch"] == esc.epoch
        if ev["fired_exact"] and ev["fired_q8"]:
            # The throttle reads wire bytes, so the codec flip shrinks
            # the browned sleep by the compression factor.
            assert max(f["bytes"] for f in ev["fired_q8"]) \
                < max(f["bytes"] for f in ev["fired_exact"])

    def test_stale_fence_names_epochs(self):
        c = SelfTuningController(n_ranks=4, tiers=(4,))
        stale = c.runtime.view
        c.runtime.consensus()
        with pytest.raises(StaleEpochError) as ei:
            c.runtime.run_phase(lambda pos, rid: None, view=stale)
        assert ei.value.have == stale.epoch
        assert ei.value.want == c.runtime.epoch


# ---------------------------------------------------------------------------
# Off-path discipline + surfaces
# ---------------------------------------------------------------------------

class TestOffPath:
    def test_lowering_bit_identical_and_eager_unchanged(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map

        mesh = Mesh(np.asarray(jax.devices()), ("w",))
        cm = mpi.comm_from_mesh(mesh, "w")
        x = jnp.arange(128, dtype=jnp.float32)

        def lowered():
            return jax.jit(shard_map(
                lambda a: cm.Allreduce(a, mpi.MPI_SUM),
                mesh=mesh, in_specs=P(), out_specs=P(),
                check_vma=False)).lower(x).as_text()

        def eager():
            return [np.asarray(o) for o in mpi.run_ranks(
                lambda r: mpi.COMM_WORLD.Allreduce(
                    jnp.arange(64, dtype=jnp.float32) * (r + 1),
                    mpi.MPI_SUM), 4)]

        text0, res0 = lowered(), eager()
        c = SelfTuningController(n_ranks=NR, tiers=TIERS)
        attached = c.poll(), c.poll(synthetic_round(0, 1.0))
        assert attached == (None, None)
        assert lowered() == text0
        for got, want in zip(eager(), res0):
            assert np.array_equal(got, want)

    def test_engine_consults_controller_between_steps(self):
        import jax
        import jax.numpy as jnp
        from mpi4torch_tpu.models import transformer as T
        from mpi4torch_tpu.serve import Engine, ServeConfig

        cfg = T.TransformerConfig(vocab=37, d_model=16, n_heads=4,
                                  n_layers=2, d_ff=32, max_seq=24)
        params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                    dtype=jnp.float64)
        eng = Engine(cfg, params, ServeConfig(slots=2))

        class _Probe:
            polls = 0

            def poll(self):
                _Probe.polls += 1

        eng.attach_controller(_Probe())
        eng.submit(np.array([1, 2, 3]), max_new=2)
        eng.step()
        eng.step()
        assert _Probe.polls == 2
        eng.attach_controller(None)
        eng.step()
        assert _Probe.polls == 2


class TestTuneShowProvenance:
    def test_rows_render_online_switched(self):
        from mpi4torch_tpu.tune.__main__ import _rows

        data = {"entries": {
            "allreduce|float32|16384|8|cpu|codec=synth_q8|tiers=2x2x2":
                {"algorithm": "synth:abcdef",
                 "program": {"phases": [{"steps": [{}, {}]}]},
                 "ctl": {"provenance": "online-switched", "epoch": 3,
                         "trigger": "crossover"}},
            "allreduce|float32|16384|8|cpu":
                {"algorithm": "ring", "measurements": {"ring": 1.0}},
        }}
        rows = _rows(data)
        sources = {r[6]: r[7] for r in rows}
        assert sources["synth:abcdef"] == \
            "online-switched(crossover@epoch 3, 2 steps)"
        assert sources["ring"] == "measured"

    def test_record_carries_ctl_stamp(self):
        tune.record("allreduce", "float32", 4096, 8, "ring",
                    persist=False,
                    ctl={"provenance": "online-switched", "epoch": 7,
                         "trigger": "drift"})
        ent = tune.lookup("allreduce", "float32", 4096, 8)
        assert ent["ctl"]["epoch"] == 7
