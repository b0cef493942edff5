"""The first chip's idle time in the traced phase, shared out whole
among the program's spans, the step's own unspanned time and the time
between two ``Engine.step()`` calls, with the spans laid on the
device's clock.

The program's spans are on ``time.perf_counter_ns()``.
``idle_under_span.align`` lays the traced phase's step records on the
trace's *host* line (through the ``bench.engine_step.*`` wrappers).
The device's events are on the device's clock, which runs ahead of the
host line's by a skew ``s`` that nothing records.  The step's own
causality brackets it: a decode-only step that follows a decode-only
step owns one burst of device events (the one that overlaps its
``decode.fetch.tokens`` span most);

- none of the burst's events starts before the step's
  ``decode.dispatch.call`` began, so ``s <= min(burst start - call
  start)``;
- the burst's last event ends before ``decode.fetch.tokens`` returned,
  so ``s >= max(burst end - tokens end)``.

The skew is the bracket's middle and its half width the error of every
edge below.  The bracket is as wide as the shortest way from the host
to the device and back: 1.1-1.35 ms on a v5e's host (0.15-0.3 ms from
the call's start to the first event, 1.1 ms from the last event to the
tokens on the host), around a skew that differs from one capture to the
next (-0.4 to -1.8 ms read).  Nothing is read where the bracket is
empty or wider than ``MAX_BRACKET_NS`` (an edge then errs by more than
a third of the 3 ms gaps to be shared out), where the steps cannot be
laid on the host line, or where the step log has no ``dispatch.call`` /
``fetch.tokens`` spans (every commit before they were added).

With the spans shifted onto the device's clock, each piece of idle
time goes to the innermost span that covers it (``mpi4torch.serve.
step`` itself where no child does), to ``BETWEEN`` where it lies
between one step's end and the next step's start of the same engine
(the caller's), or to ``OUTSIDE``.  A metric names the owners it adds
up: ``under`` (an owner counts if it is one of these names or a span
below one of them) less ``except`` (the same rule).

``read`` (in the metric's file): ``share`` (% of the idle time),
``skew_us``, ``bracket_us`` (the half width) or ``result_latency_ms``
(median over the paired steps of ``fetch.tokens`` end - the burst's
end, on the one clock: what dispatching step n + 1 before fetching
step n would hide).  The first read of a run prints the whole account
on standard error, in ms a paired decode step.
"""

import json
import statistics
import sys

from benchmarks import trace_reduce as tr
from benchmarks.readers import idle_under_span

STEP = "mpi4torch.serve.step"
CALL = STEP + ".decode.dispatch.call"
TOKENS = STEP + ".decode.fetch.tokens"
BETWEEN, OUTSIDE = "_between_steps_", "_outside_"
MAX_BRACKET_NS = 2_000_000
# Device events closer than this are one burst: inside a program the
# gaps are microseconds, between two decode steps milliseconds.
BURST_GAP_NS = 200_000


def bursts(busy: list) -> list:
    """Disjoint sorted busy intervals -> the bursts they form."""
    out = []
    for a, b in busy:
        if out and a - out[-1][1] < BURST_GAP_NS:
            out[-1][1] = b
        else:
            out.append([a, b])
    return out


def span_of(rec: dict, name: str):
    """The one span of that name in a step record, or None."""
    found = [(t0, t1) for n, t0, t1, _ in rec["spans"] if n == name]
    return found[0] if len(found) == 1 else None


def decode_only(rec: dict) -> bool:
    return rec["prefill_tokens"] == 0 and rec["admitted"] == 0 \
        and rec["active"] > 0


def pairs(steps: list, offset: int, busy: list) -> list:
    """``(call start, tokens end, burst start, burst end, end of the
    burst before)`` on the trace's two clocks for every decode-only
    step that follows a decode-only step of the same engine and has a
    burst under its ``fetch.tokens`` span, with one before it."""
    groups = bursts(busy)
    out, j = [], 0
    for prev, rec in zip(steps, steps[1:]):
        if not (decode_only(prev) and decode_only(rec)
                and prev["engine"] == rec["engine"]):
            continue
        call, tokens = span_of(rec, CALL), span_of(rec, TOKENS)
        if call is None or tokens is None:
            continue
        lo, hi = tokens[0] + offset, tokens[1] + offset
        while j < len(groups) and groups[j][1] <= lo - MAX_BRACKET_NS:
            j += 1
        best, cover = 0, 0
        for k in range(j, len(groups)):
            if groups[k][0] >= hi + MAX_BRACKET_NS:
                break
            c = tr.length(tr.clip([groups[k]], lo, hi))
            if c > cover:
                best, cover = k, c
        if cover and best:
            out.append((call[0] + offset, hi, *groups[best],
                        groups[best - 1][1]))
    return out


def bracket(paired: list):
    """``(lower, upper)`` bounds in ns of the device clock's lead over
    the host line's, or None where nothing was paired."""
    if not paired:
        return None
    return (max(b1 - tokens1 for _, tokens1, _, b1, _ in paired),
            min(b0 - call0 for call0, _, b0, _, _ in paired))


def own_intervals(rec: dict) -> list:
    """``(name, disjoint intervals)`` per span of a step record: the
    span's interval less the spans inside it (children close, and are
    recorded, before their parents)."""
    out = []
    spans = rec["spans"]
    for i, (name, t0, t1, _) in enumerate(spans):
        inside = tr.union([(a, b) for _, a, b, _ in spans[:i]
                           if t0 <= a and b <= t1])
        out.append((name, tr.subtract([[t0, t1]], inside)))
    return out


def owners_of(steps: list, shift: int, lo: int, hi: int) -> dict:
    """Owner -> intervals on the device's clock that cover ``[lo, hi]``
    once: every span's own time, the gaps between steps, the rest."""
    out: dict = {}
    last = {}
    covered = []
    for rec in steps:
        for name, own in own_intervals(rec):
            out.setdefault(name, []).extend(
                (a + shift, b + shift) for a, b in own)
        covered.append((rec["t0_ns"] + shift, rec["t1_ns"] + shift))
        prev = last.get(rec["engine"])
        if prev is not None and rec["t0_ns"] > prev:
            gap = (prev + shift, rec["t0_ns"] + shift)
            out.setdefault(BETWEEN, []).append(gap)
            covered.append(gap)
        last[rec["engine"]] = rec["t1_ns"]
    out[OUTSIDE] = tr.subtract([[lo, hi]], tr.union(covered))
    return out


def account(record):
    """The whole account of a run, computed once and kept in the
    record; None where there is nothing to read."""
    if "idle_owners" not in record.extras:
        record.extras["idle_owners"] = _account(record)
        if record.extras["idle_owners"] is not None:
            print("idle_owners", json.dumps(report(
                record.extras["idle_owners"])), file=sys.stderr, flush=True)
    return record.extras["idle_owners"]


def _account(record):
    aligned = idle_under_span.align(record)
    if aligned is None or not record.trace.devices:
        return None
    steps, offset = aligned
    lo, hi = tr.window(record.trace)
    busy = tr.busy(record.trace.devices[min(record.trace.devices)])
    idle = tr.subtract([[lo, hi]], tr.clip(busy, lo, hi))
    paired = pairs(steps, offset, busy)
    bounds = bracket(paired)
    if not idle or bounds is None:
        return None
    lower, upper = bounds
    if upper < lower or upper - lower > MAX_BRACKET_NS:
        return None
    skew = (lower + upper) // 2
    # The chip's wait before each paired step's burst: a decode step's
    # own idle time, with no admission in it.
    waits = tr.union([(before, b0) for _, _, b0, _, before in paired])
    owned = {owner: tr.union(ivals) for owner, ivals
             in owners_of(steps, offset + skew, lo, hi).items()}

    def under(gaps, ivals):
        return tr.length(gaps) - tr.length(tr.subtract(gaps, ivals))

    return {"skew_ns": skew, "half_width_ns": (upper - lower) / 2,
            "total_idle_ns": tr.length(idle),
            "idle_ns": {o: under(idle, iv) for o, iv in owned.items()},
            "paired": len(paired), "wait_ns": tr.length(waits),
            "wait_ns_by": {o: under(waits, iv) for o, iv in owned.items()},
            "result_latency_ns": statistics.median(
                tokens1 + skew - b1 for _, tokens1, _, b1, _ in paired),
            "launch_latency_ns": statistics.median(
                b0 - skew - call0 for call0, _, b0, _, _ in paired)}


def report(acc: dict) -> dict:
    """The account as it is printed: the clocks in us, every owner's
    share of the idle time in %, and the chip's wait before a paired
    decode step's burst in ms, whole and by owner."""
    n = acc["paired"]
    return {"skew_us": acc["skew_ns"] / 1e3,
            "bracket_us": acc["half_width_ns"] / 1e3,
            "paired_steps": n,
            "result_latency_ms": acc["result_latency_ns"] / 1e6,
            "launch_latency_ms": acc["launch_latency_ns"] / 1e6,
            "idle_ms": acc["total_idle_ns"] / 1e6,
            "share": {k: round(100.0 * v / acc["total_idle_ns"], 3)
                      for k, v in sorted(acc["idle_ns"].items()) if v},
            "wait_ms_a_paired_step": acc["wait_ns"] / 1e6 / n,
            "wait_ms_by": {k: round(v / 1e6 / n, 4)
                           for k, v in sorted(acc["wait_ns_by"].items())
                           if v}}


def below(owner: str, names: list) -> bool:
    return any(owner == n or owner.startswith(n + ".") for n in names)


def read(record, args):
    acc = account(record)
    if acc is None:
        return None
    what = args["read"]
    if what == "skew_us":
        return acc["skew_ns"] / 1e3
    if what == "bracket_us":
        return acc["half_width_ns"] / 1e3
    if what == "result_latency_ms":
        return acc["result_latency_ns"] / 1e6
    if what != "share":
        raise ValueError(f"idle_owners cannot read {what!r}")
    mine = sum(ns for owner, ns in acc["idle_ns"].items()
               if below(owner, args["under"])
               and not below(owner, args.get("except", [])))
    return 100.0 * mine / acc["total_idle_ns"]
