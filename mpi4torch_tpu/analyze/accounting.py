"""Unified deterministic accounting passes over the shared parse.

The repo's perf-evidence currency is deterministic estimators read off
the lowering (ROADMAP: HLO op counts, wire bytes, scheduled exposure,
peak liveness — the regression currency while wall-clock evidence is
CPU-smoke only).  This module re-expresses all three text-census
accountings as passes over :func:`mpi4torch_tpu.analyze.parse_program`;
the historical entry points (``reshard.peak_live_bytes``,
``overlap.scheduled_exposure``) delegate here, and the counts recorded
before the move are regression-pinned bit-identical in
tests/test_analyze.py (q8-bidir 7280 B, the
(8,)->(2,4) reshard migration 98304 B vs the 917504 B gather, the serve
decode step's per-token wire bytes and exposure fractions).

* :func:`wire_bytes_per_device` — per-device bytes-on-wire under the
  standard ring accountings: a ``collective_permute`` ships its operand
  once; an ``all_gather`` over groups of size s ships the local shard
  (s-1) times; an ``all_reduce`` 2(s-1)/s of the payload; a
  ``reduce_scatter`` (s-1)/s; an ``all_to_all`` keeps 1/s local and
  ships the rest.
* :func:`peak_live_bytes` — last-use SSA liveness scan, censused per
  ``func.func`` (SSA names are function scopes; the maximum wins).
  An *estimator* — XLA buffer assignment can alias and fuse — but exact
  about what a planner controls: a program that materializes an
  ``N x shard`` gather carries that tensor through its liveness range
  no matter how it is scheduled.
* :func:`scheduled_exposure` — the split-phase window census: a bucket
  whose ``.start``/``.wait`` span has another collective's wire op in
  flight inside it is *hidden*; an empty window (or a blocking,
  zero-width one) is *exposed*.  Blocking programs census 1.0 by
  construction, windowed split-phase programs strictly lower.  Exact
  about the program, conservative about the runtime: it never claims
  wall-clock hiding, only that the schedule keeps >= 2 transfers in
  flight.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from .parse import (WIRE_OPS, ParsedProgram, dtype_bytes,
                    parse_program, tensor_bytes)

__all__ = [
    "wire_contribution",
    "wire_bytes_per_device",
    "tier_wire_table",
    "weighted_wire_cost",
    "peak_live_bytes",
    "scheduled_exposure",
]


def _parsed(lowered_or_text) -> ParsedProgram:
    if isinstance(lowered_or_text, ParsedProgram):
        return lowered_or_text
    return parse_program(lowered_or_text)


# ------------------------------------------------------------- wire bytes

def _payload_bytes(op) -> int:
    """Operand bytes with the historical strictness: the wire table is
    a verdict surface, so an UNKNOWN payload element type is an error,
    not a silent zero — while a legitimately empty payload (a
    zero-sized dim) prices at 0, as it always did."""
    desc = op.operand_types[0] if op.operand_types else ""
    n = tensor_bytes(desc)
    if n == 0 and dtype_bytes(op.dtype or "") is None:
        raise ValueError(f"unknown element type in tensor<{desc}>")
    return n


def wire_contribution(kind: str, payload_bytes: float,
                      group_size: int = None) -> float:
    """Per-device bytes-on-wire of ONE collective under the standard
    ring accountings (module docstring): THE shared formula — the
    static pass below applies it to parsed StableHLO ops, and the
    runtime reconciler (:func:`mpi4torch_tpu.obs.reconcile`) applies it
    to censused Mode B chokepoint payloads, so the two sides can only
    agree or disagree about the *traffic*, never about the pricing
    rule."""
    if kind == "collective_permute":
        return float(payload_bytes)
    s = group_size
    if s is None or s < 1:
        raise ValueError(
            f"{kind} needs a replica-group size to price; got {s!r}")
    if kind == "all_gather":
        return (s - 1) * float(payload_bytes)
    if kind == "all_reduce":
        return 2 * (s - 1) / s * float(payload_bytes)
    if kind in ("reduce_scatter", "all_to_all"):
        return (s - 1) / s * float(payload_bytes)
    raise ValueError(f"unknown wire collective kind {kind!r}")


def wire_bytes_per_device(lowered_or_text) -> Tuple[int, Dict[str, int]]:
    """Deterministic per-device bytes-on-wire of a lowered program
    (see module docstring for the per-kind accountings).  Returns
    ``(total_bytes, per-op-kind counts)``, a pass over the shared
    parse."""
    parsed = _parsed(lowered_or_text)
    wire = 0.0
    counts: Dict[str, int] = {}
    for op in parsed.collectives:
        if op.kind != "collective_permute" and op.group_size is None:
            continue  # no replica_groups: not a priceable transfer
        counts[op.kind] = counts.get(op.kind, 0) + 1
        wire += wire_contribution(op.kind, _payload_bytes(op),
                                  op.group_size)
    return int(round(wire)), counts


def _op_tier(op, tiers) -> int:
    """Tier of ONE parsed collective under the mixed-radix attribution
    rule (single source: :func:`mpi4torch_tpu.csched.census.tier_of_group`
    — the highest tier whose digit differs among any group's members).
    A ``collective_permute`` is attributed by its ``source_target_pairs``
    (each pair is a 2-member group); an op with no replica groups spans
    the whole axis and prices at the top tier."""
    from ..csched.census import tier_of_group

    top = len(tiers) - 1
    if op.kind == "collective_permute":
        pairs = op.source_target_pairs
        if not pairs:
            return top
        return max(tier_of_group(pair, tiers) for pair in pairs)
    if not op.replica_groups:
        return top
    return max(tier_of_group(g, tiers) for g in op.replica_groups)


def tier_wire_table(lowered_or_text, tiers) -> List[int]:
    """Per-tier split of :func:`wire_bytes_per_device` under a flat-world
    tier stack ``tiers`` (innermost first — the
    ``config.tier_stack()`` / ``tune.resolve_tier_stack`` grammar).

    Each parsed collective's whole wire contribution lands on the tier
    of its WIDEST replica-group span (an ``all_gather`` over an
    innermost-tier group is intra-pod traffic no matter how many such
    groups tile the axis; a group mixing outer-tier digits crosses the
    outer wire).  The returned ints sum to the
    :func:`wire_bytes_per_device` total, so this is a *breakdown*, not
    a second accounting — the same invariant
    :func:`mpi4torch_tpu.csched.census.program_tier_census` keeps on the
    IR side, which lets the ``--tiers`` lane assert the lowered text's
    table equals the program census exactly."""
    tiers = tuple(int(g) for g in tiers)
    if not tiers:
        raise ValueError("tier_wire_table needs a non-empty tier stack")
    parsed = _parsed(lowered_or_text)
    per = [0.0] * len(tiers)
    for op in parsed.collectives:
        if op.kind != "collective_permute" and op.group_size is None:
            continue
        per[_op_tier(op, tiers)] += wire_contribution(
            op.kind, _payload_bytes(op), op.group_size)
    return [int(round(w)) for w in per]


def weighted_wire_cost(lowered_or_text, tier_bandwidths,
                       tiers=None) -> float:
    """The bandwidth-weighted wire census of a lowered program:
    ``sum(tier_wire[l] / tier_bandwidths[l])`` — relative seconds-on-wire
    under the configured per-tier bandwidths, the ranking functional of
    tier-dimension synthesis (:func:`mpi4torch_tpu.csched.synthesize_tiers`)
    read off the ACTUAL lowering rather than the IR census.  ``tiers``
    defaults to ``config.tier_stack()`` (which must then be set)."""
    from ..csched.census import weighted_cost

    if tiers is None:
        from .. import config as _config

        tiers = _config.tier_stack()
        if tiers is None:
            raise ValueError(
                "weighted_wire_cost needs a tier stack: pass tiers= or "
                "set config.set_tier_stack(...)")
    return weighted_cost(tier_wire_table(lowered_or_text, tiers),
                         tier_bandwidths)


# ----------------------------------------------------------- peak liveness

import re as _re

_DEF_RE = _re.compile(r"^\s*(%[\w.#-]+)(?::\d+)?\s*=")
_ARG_RE = _re.compile(r"(%arg\d+):\s*tensor<([^>]*)>")
_VAL_RE = _re.compile(r"%[\w.#-]+")
_TENSOR_RE = _re.compile(r"tensor<([^>]*)>")


def _result_bytes(line: str) -> int:
    """Byte size of a definition line's result(s): the tensor types
    after ``->`` when the op spells a function type, else the trailing
    type annotation."""
    if "->" in line:
        tail = line.rsplit("->", 1)[1]
    elif ":" in line:
        tail = line.rsplit(":", 1)[1]
    else:
        return 0
    return sum(tensor_bytes(m.group(1))
               for m in _TENSOR_RE.finditer(tail))


def _peak_one(lines) -> int:
    size: Dict[str, int] = {}
    born: Dict[str, int] = {}
    last: Dict[str, int] = {}
    for i, ln in enumerate(lines):
        for m in _ARG_RE.finditer(ln):
            name, desc = m.group(1), m.group(2)
            if name not in size:
                size[name] = tensor_bytes(desc)
                born[name] = i
                last[name] = i
        d = _DEF_RE.match(ln)
        defined = d.group(1) if d else None
        if defined is not None and defined not in size:
            size[defined] = _result_bytes(ln)
            born[defined] = i
        for m in _VAL_RE.finditer(ln):
            name = m.group(0)
            if name in size:
                last[name] = max(last.get(name, i), i)

    events: Dict[int, Tuple[int, int]] = {}
    for name, b in size.items():
        s, e = events.get(born[name], (0, 0))
        events[born[name]] = (s + b, e)
        s, e = events.get(last[name], (0, 0))
        events[last[name]] = (s, e + b)
    live = peak = 0
    for i in sorted(events):
        add, drop = events[i]
        live += add
        peak = max(peak, live)
        live -= drop
    return peak


def peak_live_bytes(lowered_or_text) -> int:
    """Max over program points of the summed byte sizes of live SSA
    values (values live from definition to last textual use, function
    arguments included), censused per ``func.func`` chunk with the
    maximum winning — the ``reshard.peak_live_bytes`` contract on the
    shared parse."""
    parsed = _parsed(lowered_or_text)
    return max([0] + [_peak_one(chunk)
                      for chunk in parsed.function_chunks])


# ------------------------------------------------------ scheduled exposure

def scheduled_exposure(lowered_or_text) -> Dict:
    """Census a lowering for scheduled communication exposure.

    Returns ``{"n_buckets", "n_exposed", "exposed_fraction",
    "buckets"}`` where ``buckets`` maps ``"<Op>.bucket<i>of<n>"`` to
    ``{"split_phase": bool, "exposed": bool}``.  ``exposed_fraction``
    is ``None`` when the program contains no bucket collectives (e.g. a
    single-device world whose collectives lowered away).  The
    ``overlap.scheduled_exposure`` contract, now a pass over the shared
    parse's event stream."""
    parsed = _parsed(lowered_or_text)

    # One bucket_of() evaluation per event (the property regex-searches
    # the scope path on every access).
    by_bucket: Dict[tuple, Dict[str, List[int]]] = {}
    wire: List[tuple] = []
    for ev in parsed.events:
        b = ev.bucket
        if b is not None:
            slot = by_bucket.setdefault(b[:3], {"start": [], "wait": [],
                                                "plain": []})
            slot[b[3] or "plain"].append(ev.line)
        if ev.kind in WIRE_OPS:
            wire.append((ev.line, b[:3] if b is not None else None))

    buckets = {}
    n_exposed = 0
    for key in sorted(by_bucket):
        slot = by_bucket[key]
        split = bool(slot["start"] and slot["wait"])
        if split:
            lo, hi = max(slot["start"]), min(slot["wait"])
            hidden = any(lo < idx < hi and wkey != key
                         for idx, wkey in wire)
            exposed = not hidden
        else:
            # Blocking bucket (or a start that was never waited —
            # defensively exposed): zero-width completion window.
            exposed = True
        n_exposed += exposed
        op, i, n = key
        buckets[f"{op}.bucket{i}of{n}"] = {"split_phase": split,
                                           "exposed": exposed}

    nb = len(buckets)
    return {
        "n_buckets": nb,
        "n_exposed": n_exposed,
        "exposed_fraction": (round(n_exposed / nb, 4) if nb else None),
        "buckets": buckets,
    }
