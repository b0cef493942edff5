"""Static-vs-runtime reconciliation: the traced Mode B wire against the
``analyze`` predictions of the matching Mode A lowering.

The repo's perf-evidence currency is deterministic estimators read off
the lowering (wire bytes, op counts, scheduled exposure — ROADMAP).
This module closes the loop at runtime: :func:`reconcile` joins what
the Mode B chokepoints *measured* against what
:func:`mpi4torch_tpu.analyze.wire_bytes_per_device` *predicts* for the
equivalent Mode A program, and the match is EXACT, not statistical —
Mode B payload bytes are censused at the rendezvous, never sampled.

The join speaks the analyzer's language.  Every modeled Mode B logical
collective is converted to the per-device wire bytes and StableHLO
collective-kind counts its Mode A execution would census:

* uncompressed ring-path collectives use THE shared accounting formula
  (:func:`mpi4torch_tpu.analyze.wire_contribution` — one definition for
  the static pass and the runtime conversion) with a 1:1 logical→HLO
  count (an Allreduce is one ``all_reduce``, a reshard permute step one
  ``collective_permute``, ...);
* compressed or non-ring allreduce events carry their codec/algorithm
  labels in the rendezvous signature, and their conversion **lowers the
  equivalent single collective** (same shape/dtype/codec/algorithm/
  world) and censuses it with the same ``analyze`` pass — so the
  in-schedule q8 pipeline's int8+scale permute schedule is priced
  exactly, not modeled approximately.

``reconcile(events, lowered)`` then asserts two exact equalities:
total per-device wire bytes, and the per-kind collective counts.  A
passing report proves the runtime executed exactly the collectives the
static analysis predicts — no extra rendezvous, none missing, none
resized, the codec really on the wire.  It is a CI-checkable contract
(``make obs-smoke``), not a dashboard.

Caveats the report is explicit about: fold-once shares and barriers are
*bookkeeping* (thread-rendezvous artifacts with no Mode A wire op) and
are excluded but counted; root/varying-shape collectives (``Bcast_``,
``Gather``, ...) and raw p2p traffic are listed as *unmodeled* rather
than silently mispriced; exact byte equality needs payloads divisible
by the replica-group size (the fractional accountings round once on
each side).  ``scheduled_exposure`` of the lowering rides along in the
prediction section — exposure is a static schedule property with no
Mode B analogue (the rendezvous is blocking by construction).
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

__all__ = ["measured_wire_table", "reconcile", "equivalent_wire",
           "equivalent_tier_wire"]


# Cache of equivalent single-collective censuses, keyed by the logical
# signature (head, shape, dtype, codec, algorithm, world size).
_equiv_cache: Dict[tuple, Tuple[int, Dict[str, int]]] = {}

# Cache of equivalent lowerings' StableHLO text under the same keying —
# the tier breakdown (:func:`equivalent_tier_wire`) re-censuses the SAME
# text per tier stack instead of re-lowering.
_equiv_text_cache: Dict[tuple, str] = {}
_equiv_tier_cache: Dict[tuple, List[int]] = {}


# The heads the equivalent-lowering census can reproduce (their
# signatures carry the full shape/dtype and the facade call is a plain
# Allreduce); anything else that cannot take the formula path is
# classified unmodeled upstream (events._UNMODELED_HEADS), never
# crashed on.
_EQUIV_HEADS = ("Allreduce", "Allreduce.q8hop", "Allreduce.c")


def _needs_equivalent_lowering(ev) -> bool:
    if ev.op not in _EQUIV_HEADS:
        return False
    return (ev.codec is not None
            or ev.algorithm not in (None, "auto", "ring"))


def _equiv_key(ev) -> tuple:
    from .. import config as _config

    # The equivalent lowering depends on the same trace-time knobs the
    # jit cache keys on (quant hop impl, ring chunk bytes, hier group,
    # tier stack, ...) — fold the fingerprint in so a config change
    # never serves a stale census.
    return (ev.op, tuple(ev.shape or ()), ev.dtype, ev.codec,
            ev.algorithm, ev.world_size,
            _config.thresholds_fingerprint())


def _equivalent_text(ev, key: tuple) -> str:
    """StableHLO text of the Mode A lowering equivalent to one Mode B
    collective event (same facade call — shape, dtype, codec, algorithm
    — over an ``ev.world_size``-device mesh); cached per logical
    signature so the total census and every tier breakdown re-census
    ONE lowering.  Needs >= ``world_size`` local (virtual) devices."""
    got = _equiv_text_cache.get(key)
    if got is not None:
        return got
    if ev.shape is None or ev.dtype is None:
        raise ValueError(
            f"event {ev.op} carries no shape/dtype signature — cannot "
            "lower its equivalent collective")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import mpi4torch_tpu as mpi
    from jax import shard_map

    n = ev.world_size
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"equivalent lowering of a {n}-rank collective needs {n} "
            f"local devices; have {len(devs)} (run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    mesh = Mesh(np.asarray(devs[:n]), ("obs_w",))
    cm = mpi.comm_from_mesh(mesh, "obs_w")
    codec = ev.codec if ev.codec is not None else False
    algo = None if ev.algorithm in (None, "auto") else ev.algorithm
    x = jnp.zeros(tuple(ev.shape), jnp.dtype(ev.dtype))

    def prog(v):
        return cm.Allreduce(v, mpi.MPI_SUM, compression=codec,
                            algorithm=algo)

    lowered = jax.jit(shard_map(prog, mesh=mesh, in_specs=P(),
                                out_specs=P(), check_vma=False)).lower(x)
    text = lowered.as_text()
    _equiv_text_cache[key] = text
    return text


def equivalent_wire(ev) -> Tuple[int, Dict[str, int]]:
    """Per-device wire bytes and collective-kind counts of the Mode A
    lowering equivalent to one Mode B collective event, censused with
    :func:`analyze.wire_bytes_per_device`.  Cached per logical
    signature; needs >= ``world_size`` local (virtual) devices."""
    from .. import analyze

    key = _equiv_key(ev)
    got = _equiv_cache.get(key)
    if got is not None:
        return got
    got = analyze.wire_bytes_per_device(_equivalent_text(ev, key))
    _equiv_cache[key] = got
    return got


def equivalent_tier_wire(ev, tiers) -> List[int]:
    """Per-tier wire bytes of the equivalent Mode A lowering of one
    Mode B collective event — :func:`analyze.tier_wire_table` over the
    SAME cached lowering text :func:`equivalent_wire` censuses, so the
    tier breakdown can only split the total, never disagree with it.
    This is how grouped/compressed schedules (hier, tier-stack folds,
    q8 pipelines) get their per-tier traffic priced EXACTLY: from the
    replica groups of the actual lowering, not a formula."""
    from .. import analyze

    tiers = tuple(int(g) for g in tiers)
    key = _equiv_key(ev) + (tiers,)
    got = _equiv_tier_cache.get(key)
    if got is not None:
        return got
    got = analyze.tier_wire_table(_equivalent_text(ev, key[:-1]), tiers)
    _equiv_tier_cache[key] = got
    return got


def _split_phase_start(ev) -> bool:
    """True when the event ran inside a split-phase ``.start`` bucket
    scope (the eager ``Allreduce_start`` runs its blocking rendezvous
    within the start span, carried into the event's bucket label by the
    tracer's label stack)."""
    if not ev.bucket:
        return False
    from ..analyze.parse import bucket_of

    b = bucket_of(ev.bucket)
    return b is not None and b[3] == "start"


def _formula_row(ev) -> Tuple[float, Dict[str, int]]:
    from ..analyze import wire_contribution

    s = ev.group_size if ev.group_size else ev.world_size
    if ev.family == "all_reduce" and _split_phase_start(ev):
        # A split-phase allreduce lowers in Mode A as the explicit
        # reduce_scatter + all_gather PAIR (start issues the RS, Wait
        # completes the AG) — same total wire, two ops in the census.
        return (wire_contribution("reduce_scatter", ev.payload_bytes, s)
                + wire_contribution("all_gather", ev.payload_bytes / s,
                                    s),
                {"reduce_scatter": 1, "all_gather": 1})
    return (wire_contribution(ev.family, ev.payload_bytes, s),
            {ev.family: 1})


def _formula_tier(ev, tiers: tuple) -> int:
    """Tier of a formula-priced event: formula rows are plain ring-path
    collectives whose replica group is a contiguous run of ranks, so a
    group size matching the product of the first j tier factors spans
    exactly tiers 0..j-1 (top differing digit j-1); anything else —
    including the whole world — crosses the top tier.  Grouped schedules
    whose groups are NOT contiguous runs (hier's strided inter-group
    stage, tier-stack folds) never take this path: their algorithm label
    routes them through the equivalent lowering, where the tier comes
    from the actual replica groups."""
    s = ev.group_size if ev.group_size else ev.world_size
    p = 1
    for j, g in enumerate(tiers):
        p *= g
        if s == p:
            return j
    return len(tiers) - 1


def measured_wire_table(events: Iterable, rank: Optional[int] = None,
                        tiers=None) -> dict:
    """Convert a Mode B event stream into the analyzer's census
    vocabulary: per-device wire bytes + per-kind collective counts.

    Uses ONE rank's events (``rank=None`` = the lowest rank present —
    wire accountings are per device) after checking every rank recorded
    the SAME logical collective sequence (op, family, bytes, group) —
    the determinism property that makes the census a contract.  Returns
    ``{"wire_bytes", "counts", "logical_events", "by_op",
    "per_rank_consistent", "excluded"}``; with a tier stack ``tiers``
    the report additionally carries ``"tier_wire"`` — the per-tier
    split of ``wire_bytes`` (equivalent-lowering rows read their tiers
    from the actual replica groups via :func:`equivalent_tier_wire`,
    formula rows from the contiguous-run rule), summing to the total
    exactly."""
    events = list(events)
    evs = [e for e in events if e.channel == "exchange"]
    ranks = sorted({e.rank for e in evs})
    n_spmd = sum(1 for e in events if e.channel == "spmd")

    def logical(seq):
        """Side-effect-free filter: the modeled, completed logical
        collectives of one rank's event sequence."""
        return [e for e in seq
                if e.status == "ok" and not e.bookkeeping
                and e.family is not None and not e.unmodeled]

    per_rank = {r: logical([e for e in evs if e.rank == r])
                for r in ranks}
    use = (rank if rank is not None else ranks[0]) if ranks else None
    rows = per_rank.get(use, [])

    # Exclusion accounting for the selected rank only (symmetric when
    # the consistency check below holds), except p2p and Mode A spmd
    # step events, which are reported trace-wide (p2p is inherently
    # asymmetric; spmd events have no rank) — EVERY dropped event
    # class is counted, never silently filtered.
    excluded = {"bookkeeping": 0, "errors": 0, "unmodeled": {},
                "p2p": sum(1 for e in events
                           if e.channel in ("p2p_send", "p2p_recv")),
                "spmd": n_spmd}
    for e in evs:
        if e.rank != use:
            continue
        if e.status != "ok":
            excluded["errors"] += 1
        elif e.unmodeled:
            excluded["unmodeled"][e.op] = \
                excluded["unmodeled"].get(e.op, 0) + 1
        elif e.bookkeeping or e.family is None:
            excluded["bookkeeping"] += 1

    def fingerprint(seq):
        return [(e.op, e.family, e.payload_bytes, e.group_size,
                 e.algorithm, e.codec, e.bucket) for e in seq]

    consistent = len({tuple(fingerprint(v)) for v in per_rank.values()}
                     ) <= 1

    tiers = tuple(int(g) for g in tiers) if tiers is not None else None
    tier_wire = [0.0] * len(tiers) if tiers is not None else None
    wire = 0.0
    counts: Dict[str, int] = {}
    by_op: Dict[str, dict] = {}
    for e in rows:
        if _needs_equivalent_lowering(e):
            b, c = equivalent_wire(e)
            if tiers is not None:
                for level, tw in enumerate(equivalent_tier_wire(e, tiers)):
                    tier_wire[level] += tw
        else:
            b, c = _formula_row(e)
            if tiers is not None:
                tier_wire[_formula_tier(e, tiers)] += b
        wire += b
        for k, v in c.items():
            counts[k] = counts.get(k, 0) + v
        slot = by_op.setdefault(e.op, {"events": 0, "wire_bytes": 0.0,
                                       "payload_bytes": 0})
        slot["events"] += 1
        slot["wire_bytes"] += b
        slot["payload_bytes"] += e.payload_bytes
    for slot in by_op.values():
        slot["wire_bytes"] = int(round(slot["wire_bytes"]))
    out = {
        "rank": use,
        "wire_bytes": int(round(wire)),
        "counts": counts,
        "logical_events": len(rows),
        "by_op": by_op,
        "per_rank_consistent": consistent,
        "ranks": ranks,
        "excluded": excluded,
    }
    if tiers is not None:
        out["tiers"] = list(tiers)
        out["tier_wire"] = [int(round(w)) for w in tier_wire]
    return out


def reconcile(events_or_tracer, lowered_or_text,
              rank: Optional[int] = None,
              dropped: Optional[int] = None, tiers=None) -> dict:
    """Join a traced Mode B event stream against the ``analyze``
    predictions of the matching Mode A lowering.

    ``events_or_tracer`` is the :class:`~.trace.CommTracer` itself
    (preferred — its ``dropped`` count is read automatically, so a
    truncated trace can never reconcile by omission) or a plain event
    list (then pass ``dropped=tracer.dropped`` yourself; it defaults
    to 0 only for event lists that never lived in a bounded tracer).

    Returns a report whose ``ok`` is True iff (1) every rank recorded
    the same logical collective sequence, (2) the measured per-device
    wire bytes equal :func:`analyze.wire_bytes_per_device` of the
    lowering EXACTLY, (3) the measured per-kind collective counts equal
    the parse's counts exactly, and (4) the tracer dropped nothing
    (a truncated census is not a census).  With a tier stack ``tiers``
    (innermost first) the join additionally prices per-tier traffic —
    measured (:func:`measured_wire_table` with ``tiers=``) against
    predicted (:func:`analyze.tier_wire_table` of the lowering) — and
    ``matches["tier_wire"]`` demands the split match EXACTLY too: the
    runtime put its bytes on the tiers the static census says, not just
    the right total.  See the module docstring for what is excluded and
    why."""
    from .. import analyze

    events = events_or_tracer
    if hasattr(events, "events") and hasattr(events, "dropped"):
        if dropped is None:
            dropped = events.dropped
        events = events.events
    if dropped is None:
        dropped = 0
    measured = measured_wire_table(events, rank=rank, tiers=tiers)
    pred_bytes, pred_counts = analyze.wire_bytes_per_device(
        lowered_or_text)
    try:
        exposure = analyze.scheduled_exposure(lowered_or_text)
    except Exception:  # noqa: BLE001 — exposure is advisory here
        exposure = None
    matches = {
        "wire_bytes": measured["wire_bytes"] == pred_bytes,
        "counts": measured["counts"] == pred_counts,
    }
    predicted = {
        "wire_bytes": pred_bytes,
        "counts": pred_counts,
        "scheduled_exposure": (exposure or {}).get(
            "exposed_fraction") if exposure else None,
    }
    if tiers is not None:
        predicted["tier_wire"] = analyze.tier_wire_table(
            lowered_or_text, tiers)
        matches["tier_wire"] = (measured["tier_wire"]
                                == predicted["tier_wire"])
    report = {
        "measured": measured,
        "predicted": predicted,
        "matches": matches,
        "dropped_events": int(dropped),
        "ok": bool(all(matches.values())
                   and measured["per_rank_consistent"]
                   and not dropped),
    }
    return report
