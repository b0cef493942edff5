"""``python -m mpi4torch_tpu.ctl --smoke`` — the ctl-smoke lane
(``make ctl-smoke``).

What it proves, exiting non-zero on ANY divergence:

* **registry sync** — the ledger's trigger vocabulary, this lane's
  coverage literal (:data:`LEDGER_COVERED`) and the degrade-policy
  delegation map move together (``analyze.registry.ctl_problems``);
* **estimator units** — per-tier attribution of a synthetic CommEvent
  stream matches the census rule (``csched.tier_of_group``) and the
  EWMA math is exact;
* **no-flap hysteresis** — ratios oscillating inside the watermark
  band never flip a tier's drift state;
* **deterministic brownout cell** — an injected ``brownout`` (the
  PR 15 kind) on the outer tier drives the controller through
  consensus to the q8/synth_q8 winner (bitwise vs the explicit-q8
  oracle), a stale view is FENCED (``StaleEpochError``), the decision
  ledger names the trigger with the weighted-cost improvement pinned,
  and clearing the fault de-escalates back to the exact pre-episode
  configuration (bitwise vs the pre-episode result);
* **fault fast path** — ``apply("codec_escalate")`` (the PR 15
  DEGRADE_POLICIES surface) runs through the same ratified switch and
  lands in the same ledger with trigger ``fault``;
* **off path** — with ``config.ctl_enabled()`` False (the default),
  ``poll`` returns None, the config snapshot is untouched and the
  Mode A lowering text is bit-identical;
* **coverage** — the union of triggers the cells actually recorded
  equals :data:`LEDGER_COVERED` (no vacuous coverage literal).
"""

from __future__ import annotations

import sys

#: The trigger kinds the cells below (and tests/test_ctl.py) actually
#: drive through the ledger.  analyze.registry.ctl_problems() compares
#: this against ledger.TRIGGER_KINDS — add a trigger, add a cell.
LEDGER_COVERED = ("drift", "crossover", "recovery", "fault")


def _fail(failures: list, msg: str) -> None:
    failures.append(msg)
    print(f"FAIL: {msg}")


def _ok(msg: str) -> None:
    print(f"ok  : {msg}")


# ---------------------------------------------------------------------------
# Synthetic event stream helpers (shared with tests/test_ctl.py)
# ---------------------------------------------------------------------------

def synthetic_event(seq: int, rank: int, bw: float, *,
                    nbytes: int = 4096, group_size=None,
                    world_size: int = 8, **kw):
    """A measurable exchange CommEvent whose (bytes, duration) encode
    the given bandwidth exactly — the estimator unit-test currency."""
    from ..obs.events import CommEvent

    fields = dict(seq=seq, rank=rank, world=0, world_size=world_size,
                  channel="exchange", op="Allreduce",
                  payload_bytes=nbytes, duration_s=nbytes / bw,
                  family="all_reduce", group_size=group_size)
    fields.update(kw)
    return CommEvent(**fields)


def synthetic_round(seq0: int, bw: float, *, nranks: int = 8,
                    nbytes: int = 4096, group_size=None):
    """One whole-world round: ``nranks`` events at bandwidth ``bw``."""
    return [synthetic_event(seq0 + r, r, bw, nbytes=nbytes,
                            group_size=group_size,
                            world_size=nranks)
            for r in range(nranks)]


# ---------------------------------------------------------------------------
# The closed-loop brownout episode (shared with tests/test_ctl.py)
# ---------------------------------------------------------------------------

def closed_loop_episode(*, n: int = 8, tiers=(2, 2, 2),
                        backend: str = "thread",
                        payload: int = 1024,
                        per_byte_s: float = 5e-5,
                        timeout: float = 60.0) -> dict:
    """Run the full measure→escalate→recover episode with REAL Mode B
    traffic and a REAL brownout fault, and return the evidence:

    ``exact_before`` / ``escalated`` / ``recovered`` per-rank results,
    ``oracle_q8`` (the explicit ``compression="q8"`` run the escalated
    phase must match bitwise), the escalation and recovery
    :class:`~mpi4torch_tpu.ctl.ledger.Decision` records, the fired
    brownout evidence split by phase, the stale-fence outcome, and the
    final config deltas.  The caller asserts; this driver only
    collects — so the smoke lane and the tests read ONE flow.
    """
    import numpy as np

    import mpi4torch_tpu as mpi
    from .. import config as _cfg, obs
    from ..elastic.membership import StaleEpochError
    from ..resilience.faults import FaultSpec, fault_scope
    from .controller import SelfTuningController

    comm = mpi.COMM_WORLD
    ev: dict = {"backend": backend, "tiers": tuple(tiers), "n": n}

    def body(rank, compression=None):
        # ONE call site for every phase (the chaos-cell discipline):
        # compression=None reads the PROCESS-wide default the
        # controller's escalation flips, so the exact and escalated
        # phases run literally the same code.  Allgather: its eager q8
        # wire carries ENCODED payloads, so the codec flip provably
        # shrinks the bytes the brownout throttles.
        import jax.numpy as jnp

        x = jnp.linspace(-2.0, 2.0, payload,
                         dtype=jnp.float32) * (rank + 1)
        return comm.Allgather(x, 0, compression=compression)

    def run(compression=None):
        outs = mpi.run_ranks(
            lambda r: body(r, compression=compression), n,
            backend=backend, timeout=timeout)
        return [np.asarray(o) for o in outs]

    snap = _cfg.snapshot_process_state()
    # Knobs FIRST: the controller's estimator/monitor adopt the
    # halflife, patience and watermarks at construction.  The
    # watermarks bracket the episode's real dynamics: the brownout
    # sags goodput ~10x+ below the low watermark, while the healthy
    # q8 wire sits at roughly half the exact baseline on the eager CPU
    # path (per-hop quantize overhead dominates at smoke payloads) —
    # so recovery must trip on "well above the sag", not "back at
    # exactly the exact-wire baseline".
    _cfg.set_ctl_enabled(True)
    _cfg.set_ctl_halflife(1.0)
    _cfg.set_ctl_drift_thresholds(0.15, 0.3)
    _cfg.set_ctl_drift_patience(2)
    _cfg.set_ctl_min_switch_epochs(1)
    ctl = SelfTuningController(n_ranks=n, tiers=tiers,
                               nbytes=payload * 4, persist=False)
    try:
        # The oracle is pinned BEFORE the episode: the escalated phase
        # must equal an explicitly-q8 run bitwise (same code path the
        # flipped process-wide default selects).
        ev["oracle_q8"] = run(compression="q8")
        with obs.trace() as tracer:
            ev["exact_before"] = run()
            run()
            ctl.observe()
            ctl.calibrate()
            ev["healthy_poll"] = ctl.poll()     # must be None
            view_before = ctl.runtime.view
            spec = FaultSpec("brownout", op="Allgather",
                             per_byte_s=per_byte_s, count=10 ** 6)
            with fault_scope([spec]) as plan:
                run()
                ev["patience_poll"] = ctl.poll()  # 1st sag: patience
                run()
                ev["escalation"] = ctl.poll()     # 2nd sag: switch
                n_exact_fired = len(plan.fired)
                ev["escalated"] = run()           # rides the q8 wire
                ev["fired_exact"] = [f.info for f in
                                     plan.fired[:n_exact_fired]
                                     if f.info]
                ev["fired_q8"] = [f.info for f in
                                  plan.fired[n_exact_fired:]
                                  if f.info]
            # A phase prepared against the pre-switch view is FENCED.
            try:
                ctl.runtime.run_phase(lambda pos, rid: None,
                                      view=view_before)
                ev["stale_fenced"] = False
            except StaleEpochError as e:
                ev["stale_fenced"] = (e.have == view_before.epoch
                                      and e.want == ctl.runtime.epoch)
            ev["compression_during"] = getattr(
                _cfg.default_compression(), "name",
                _cfg.default_compression())
            ev["bandwidths_during"] = _cfg.tier_bandwidths()
            # Fault cleared: healthy rounds walk the monitor back
            # above the high watermark.  Wall-time noise on the tiny
            # smoke payloads can reset the patience counter, so poll
            # until the recovery ratifies (bounded — the PASS criteria
            # are that it DOES ratify and restores bitwise).
            ev["recovery"] = None
            for _ in range(8):
                run()
                d = ctl.poll()
                if d is not None:
                    ev["recovery"] = d
                    break
            ev["recovered"] = run()
        ev["compression_after"] = _cfg.default_compression()
        ev["bandwidths_after"] = _cfg.tier_bandwidths()
        ev["ledger"] = ctl.ledger
        ev["epochs"] = [d.epoch for d in ctl.ledger]
        ev["tune_entry"] = _installed_entry(ctl)
    finally:
        _cfg.apply_process_state(snap)
        ctl.reset()
    return ev


def _installed_entry(ctl):
    """The tune-cache entry the escalation installed (None when the
    search found no distinct lossy winner — the flat-stack case)."""
    from ..tune.autotuner import lookup

    for slot in ("synth_q8", "synth"):
        ent = lookup("allreduce", ctl.dtype, ctl.nbytes,
                     ctl.runtime.view.size, codec=slot,
                     tiers=ctl.tiers)
        if ent is not None and ent.get("ctl"):
            return dict(ent, slot=slot)
    return None


# ---------------------------------------------------------------------------
# Cells
# ---------------------------------------------------------------------------

def _cell_guard(failures) -> None:
    from ..analyze.registry import ctl_problems

    probs = ctl_problems()
    for p in probs:
        _fail(failures, f"[registry] {p}")
    if not probs:
        _ok("registry: trigger kinds == ledger coverage == "
            "degrade-policy delegation map")


def _cell_estimator(failures) -> None:
    from .estimate import BandwidthEstimator

    est = BandwidthEstimator((2, 2, 2), halflife=1.0)
    events = []
    # Whole-world traffic charges the top tier; group-of-2 the inner
    # tier; group-of-4 the middle — the census attribution rule.
    events += synthetic_round(0, 1e6)
    events += [synthetic_event(8, 0, 2e6, group_size=2),
               synthetic_event(9, 0, 4e6, group_size=4)]
    n = est.ingest(events)
    tiers = est.tier_estimates()
    okays = (n == 10
             and abs(tiers[2] - 1e6) < 1e-6
             and abs(tiers[0] - 2e6) < 1e-6
             and abs(tiers[1] - 4e6) < 1e-6)
    if not okays:
        _fail(failures, f"estimator attribution/EWMA off: ingested "
                        f"{n}, tiers={tiers}")
        return
    # Cursor: re-ingesting the same events adds nothing; bookkeeping
    # and failed events are never samples.
    n2 = est.ingest(events)
    n3 = est.ingest([synthetic_event(10, 0, 9e9, bookkeeping=True,
                                     family=None),
                     synthetic_event(11, 0, 9e9, status="Timeout")])
    if n2 or n3:
        _fail(failures, f"estimator counted stale/bookkeeping/failed "
                        f"events ({n2}, {n3})")
        return
    # EWMA halflife: one more top-tier sample at half the bandwidth
    # with halflife=1 (alpha=1/2) lands exactly between.
    est.ingest([synthetic_event(12, 0, 5e5)])
    if abs(est.tier_estimates()[2] - 7.5e5) > 1e-6:
        _fail(failures, f"EWMA halflife math off: "
                        f"{est.tier_estimates()[2]}")
        return
    _ok("estimator: census-rule tier attribution, cursor, filters and "
        "EWMA halflife exact on a synthetic stream")


def _cell_no_flap(failures) -> None:
    from .drift import DriftMonitor
    from .estimate import BandwidthEstimator

    est = BandwidthEstimator((2, 2, 2), halflife=1.0)
    mon = DriftMonitor(3, low=0.5, high=0.8, patience=2)
    est.ingest(synthetic_round(0, 1e6))
    mon.calibrate(est)
    seq = 8
    # Oscillate INSIDE the hysteresis band (0.5..0.8 of baseline) for
    # many checks: no state may ever change.
    flips = []
    for i in range(12):
        bw = 0.55e6 if i % 2 else 0.75e6
        est.ingest(synthetic_round(seq, bw))
        seq += 8
        rep = mon.check(est)
        flips += list(rep.changed.items())
    if flips or mon.states != ("ok", "ok", "ok"):
        _fail(failures, f"hysteresis flapped inside the band: "
                        f"{flips}, states={mon.states}")
        return
    # And a single sub-low excursion (patience 2) must not degrade.
    est.ingest(synthetic_round(seq, 0.2e6))
    rep = mon.check(est)
    est.ingest(synthetic_round(seq + 8, 1e6))
    est.ingest(synthetic_round(seq + 16, 1e6))
    rep2 = mon.check(est)
    if rep.changed or rep2.changed or not rep2.ok:
        _fail(failures, "a single sub-watermark excursion flipped the "
                        f"state ({rep.changed}, {rep2.changed})")
        return
    _ok("hysteresis: 12 in-band oscillations + a single excursion, "
        "zero state changes (the no-flap property)")


def _cell_drift_rerank(failures) -> None:
    """Mild sag (below low, above the codec crossover) re-ranks the
    EXACT winner under the live bandwidth vector — trigger ``drift``,
    no codec flip."""
    from .. import config as _cfg, tune
    from .controller import SelfTuningController

    snap = _cfg.snapshot_process_state()
    _cfg.set_ctl_enabled(True)
    _cfg.set_ctl_halflife(1.0)
    _cfg.set_ctl_drift_patience(2)
    ctl = SelfTuningController(n_ranks=8, tiers=(2, 2, 2),
                               nbytes=1 << 14, persist=False)
    try:
        ctl.observe(synthetic_round(0, 1e6))
        ctl.calibrate()
        d1 = ctl.poll(synthetic_round(8, 0.4e6))
        d2 = ctl.poll(synthetic_round(16, 0.4e6))
    finally:
        _cfg.apply_process_state(snap)
        ctl.reset()
    if d1 is not None:
        _fail(failures, "drift switch fired before patience ran out")
        return
    if d2 is None or d2.trigger != "drift":
        _fail(failures, f"expected a drift decision, got {d2!r}")
        return
    live = d2.new.get("weighted_cost")
    prior = d2.old.get("weighted_cost")
    if not (live is not None and prior is not None
            and live <= prior):
        _fail(failures, f"re-ranked winner does not improve the live "
                        f"weighted cost ({prior} -> {live})")
        return
    ent = tune.lookup_algorithm("allreduce", "float32", 1 << 14, 8,
                                codec="synth", tiers=(2, 2, 2))
    if d2.new.get("installed") is None or ent != d2.new["installed"]:
        _fail(failures, f"drift switch install not in the tune cache "
                        f"(decision {d2.new.get('installed')!r}, "
                        f"cache {ent!r})")
        return
    _ok(f"drift re-rank: tier {d2.tier} at {d2.ratio:.2f} -> exact "
        f"winner {d2.new['winner']} installed at epoch {d2.epoch}, "
        f"live cost {prior:.4g}->{live:.4g}, codec untouched")


def _cell_closed_loop(failures) -> None:
    import numpy as np

    ev = closed_loop_episode(n=8, tiers=(2, 2, 2), backend="thread")
    esc, rec = ev["escalation"], ev["recovery"]
    if ev["healthy_poll"] is not None or ev["patience_poll"] is not None:
        _fail(failures, "controller switched without drift evidence "
                        "(healthy or within-patience poll acted)")
        return
    if esc is None or esc.trigger != "crossover":
        _fail(failures, f"expected a crossover escalation, got {esc!r}")
        return
    if ev["compression_during"] != "q8":
        _fail(failures, "escalation did not flip the process-wide "
                        f"codec (got {ev['compression_during']!r})")
        return
    if not (esc.new.get("weighted_cost") < esc.old.get("weighted_cost")):
        _fail(failures, "weighted-cost improvement not pinned: "
                        f"{esc.old.get('weighted_cost')} -> "
                        f"{esc.new.get('weighted_cost')}")
        return
    wire_old = esc.old.get("tier_wire", ())
    wire_new = esc.new.get("tier_wire", ())
    if not (wire_old and wire_new and wire_new[-1] < wire_old[-1]):
        _fail(failures, f"outer-tier wire did not shrink: {wire_old} "
                        f"-> {wire_new}")
        return
    for got, want in zip(ev["escalated"], ev["oracle_q8"]):
        if not np.array_equal(got, want):
            _fail(failures, "escalated phase diverges from the "
                            "explicit-q8 oracle (bitwise)")
            return
    if ev["fired_exact"] and ev["fired_q8"]:
        b_exact = max(f["bytes"] for f in ev["fired_exact"])
        b_q8 = max(f["bytes"] for f in ev["fired_q8"])
        if not b_q8 < b_exact:
            _fail(failures, f"q8 wire did not shrink the throttled "
                            f"bytes ({b_exact} -> {b_q8})")
            return
    else:
        _fail(failures, "vacuous cell: brownout did not fire in both "
                        "phases")
        return
    if ev["stale_fenced"] is not True:
        _fail(failures, "stale pre-switch view was NOT fenced")
        return
    if rec is None or rec.trigger != "recovery":
        _fail(failures, f"expected a recovery decision, got {rec!r}")
        return
    if ev["compression_after"] is not None \
            or ev["bandwidths_after"] is not None:
        _fail(failures, "recovery did not restore the pre-episode "
                        "knobs")
        return
    for got, want in zip(ev["recovered"], ev["exact_before"]):
        if not np.array_equal(got, want):
            _fail(failures, "recovered phase diverges from the "
                            "pre-episode exact result (bitwise)")
            return
    if not (rec.epoch > esc.epoch):
        _fail(failures, f"epochs not monotone: {ev['epochs']}")
        return
    ent = ev["tune_entry"]
    if ent is None or ent.get("ctl", {}).get("provenance") \
            != "online-switched":
        _fail(failures, "installed winner carries no online-switched "
                        "provenance for tune --show")
        return
    _ok(f"closed loop: brownout -> crossover@epoch {esc.epoch} "
        f"(cost {esc.old['weighted_cost']:.4g}->"
        f"{esc.new['weighted_cost']:.4g}, outer wire "
        f"{wire_old[-1]}->{wire_new[-1]}, throttled bytes "
        f"{b_exact}->{b_q8}), bitwise vs q8 oracle, stale view "
        f"fenced, recovery@epoch {rec.epoch} bitwise vs pre-episode")


def _cell_fault_fast_path(failures) -> None:
    from .. import config as _cfg
    from .controller import SelfTuningController

    snap = _cfg.snapshot_process_state()
    ctl = SelfTuningController(n_ranks=4, tiers=(4,), persist=False)
    try:
        tr = ctl.apply("codec_escalate")
        codec = getattr(_cfg.default_compression(), "name",
                        _cfg.default_compression())
        decs = list(ctl.ledger)
    finally:
        ctl.reset()
        _cfg.apply_process_state(snap)
    if codec != "q8":
        _fail(failures, f"fault fast path did not escalate the codec "
                        f"(got {codec!r})")
        return
    if not (decs and decs[-1].trigger == "fault"
            and decs[-1].policy == "codec_escalate"
            and decs[-1].epoch == tr.epoch):
        _fail(failures, f"fault transition not ledgered: {decs!r}")
        return
    if _cfg.default_compression() is not None:
        _fail(failures, "reset() did not restore the codec")
        return
    _ok(f"fault fast path: apply('codec_escalate') ran the SAME "
        f"ratified switch (epoch {tr.epoch}) and ledgered trigger "
        "'fault'; reset restored")


def _cell_off_path(failures) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import jax.numpy as jnp

    import mpi4torch_tpu as mpi
    from .. import config as _cfg
    from jax import shard_map
    from .controller import SelfTuningController

    mesh = Mesh(np.asarray(jax.devices()), ("w",))
    cm = mpi.comm_from_mesh(mesh, "w")
    x = jnp.arange(256, dtype=jnp.float32)

    def lowered():
        return jax.jit(shard_map(
            lambda a: cm.Allreduce(a, mpi.MPI_SUM),
            mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False)).lower(x).as_text()

    before_text = lowered()
    before_snap = _cfg.snapshot_process_state()
    ctl = SelfTuningController(n_ranks=8, tiers=(2, 2, 2))
    polls = [ctl.poll(), ctl.poll(synthetic_round(0, 1.0))]
    after_text = lowered()
    after_snap = _cfg.snapshot_process_state()
    if polls != [None, None]:
        _fail(failures, f"disabled controller acted: {polls}")
        return
    if after_snap != before_snap:
        _fail(failures, "disabled controller mutated config: "
              f"{ {k: (before_snap[k], after_snap[k]) for k in before_snap if before_snap[k] != after_snap[k]} }")
        return
    if after_text != before_text:
        _fail(failures, "controller-off lowering is NOT bit-identical")
        return
    if len(ctl.ledger) != 0:
        _fail(failures, "disabled controller wrote ledger decisions")
        return
    _ok("off path: ctl_enabled=False -> poll is a no-op, config "
        "snapshot untouched, Mode A lowering text bit-identical "
        f"({len(before_text)} chars)")


def _cell_ledger(failures) -> None:
    import json
    import os
    import tempfile

    from .ledger import DecisionLedger

    led = DecisionLedger()
    led.record(3, "crossover", tier=2, ratio=0.01,
               estimates=(None, 2e6, 1e3),
               old={"winner": "synth:aa", "codec": "synth",
                    "weighted_cost": 9.0, "tier_wire": (0, 0, 4096)},
               new={"winner": "synth:bb", "codec": "synth_q8",
                    "weighted_cost": 2.5, "tier_wire": (0, 0, 1024)})
    led.record(4, "recovery", new={"restored": ["compression"]})
    doc = json.loads(led.to_json())
    table = led.format_table()
    with tempfile.TemporaryDirectory() as td:
        path = led.dump(os.path.join(td, "ledger.json"))
        with open(path, "r", encoding="utf-8") as f:
            dumped = json.load(f)
    okays = (len(doc["decisions"]) == 2
             and doc == dumped
             and doc["decisions"][0]["trigger"] == "crossover"
             and doc["decisions"][0]["epoch"] == 3
             and "crossover" in table and "recovery" in table
             and "9->2.5" in table
             and "synth:bb[synth_q8]" in table)
    if not okays:
        _fail(failures, f"ledger dump/table round-trip broke:\n{table}")
        return
    try:
        led.record(5, "vibes")
    except ValueError:
        _ok("ledger: JSON == dumped file == table rows; unknown "
            "trigger kinds refused")
    else:
        _fail(failures, "ledger accepted an unregistered trigger kind")


def _smoke() -> int:
    import jax

    from .ledger import TRIGGER_KINDS

    ndev = len(jax.devices())
    print(f"ctl-smoke: {ndev} device(s), platform "
          f"{jax.devices()[0].platform}")

    failures: list = []
    _cell_guard(failures)
    _cell_estimator(failures)
    _cell_no_flap(failures)
    _cell_drift_rerank(failures)
    _cell_closed_loop(failures)
    _cell_fault_fast_path(failures)
    _cell_off_path(failures)
    _cell_ledger(failures)

    # The coverage literal is not allowed to be vacuous: the cells
    # above must have recorded every registered trigger kind.
    from ..obs import metrics as _metrics

    snap = _metrics.snapshot()
    seen = {t for t in TRIGGER_KINDS
            if snap.get("counters", {}).get(
                f'ctl_switches_total{{trigger="{t}"}}', 0) > 0}
    if seen != set(LEDGER_COVERED):
        _fail(failures, f"trigger coverage is vacuous: cells recorded "
                        f"{sorted(seen)}, literal says "
                        f"{sorted(LEDGER_COVERED)}")
    else:
        _ok(f"coverage: every trigger kind fired a ledgered switch "
            f"{sorted(seen)}")

    if failures:
        print(f"\nctl-smoke: {len(failures)} failure(s)")
        return 1
    print("\nctl-smoke: all cells passed")
    return 0


def main(argv) -> int:
    if "--smoke" in argv:
        return _smoke()
    print(__doc__)
    return 2


if __name__ == "__main__":
    from mpi4torch_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    sys.exit(main(sys.argv[1:]))
