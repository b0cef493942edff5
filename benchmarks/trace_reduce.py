"""From a profiler trace (``.xplane.pb``) to numbers.

One reduction for every PR: device busy time as the union of the
intervals in which an operation runs, time by operation, kernel time by
name, collective time and the part of it no other operation hides, and
the idle gaps laid against the benchmark's own host spans (``bench.*``,
written with ``jax.profiler.TraceAnnotation``, so they share the
trace's clock to within about a millisecond).

What a v5e trace holds (looked at by hand, PR 24): one plane per chip,
``/device:TPU:<i>``, whose line ``XLA Ops`` has one event per executed
HLO instruction (the name is the instruction's text, ``%fusion.3 = ...``)
and whose line ``Async XLA Ops`` has one span from each ``*-start`` to
its ``*-done``; the plane ``/host:CPU`` has one line per thread, and
TraceAnnotations are on the line ``python``.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SYNC_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
SPAN_PREFIX = "bench."
# An instruction is a collective by its opcode, not by its name: the
# name follows the JAX primitive (``%psum.3 = ... all-reduce(...)``).
COLLECTIVE = re.compile(
    r" (all-reduce|reduce-scatter|all-gather|collective-permute|all-to-all)"
    r"(-start|-done)?\(")
_SUFFIX = re.compile(r"(\.\d+)+$")


def op_name(event_name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """``fusion.3`` -> ``fusion``: instructions of one kind add up."""
    return _SUFFIX.sub("", name)


def is_collective(event_name: str) -> bool:
    """Whether the instruction's text (the event's name) is a collective
    or the start or done of one."""
    return bool(COLLECTIVE.search(event_name))


@dataclass
class DeviceTrace:
    sync: list = field(default_factory=list)    # (name, start_ns, end_ns)
    spans: list = field(default_factory=list)   # async start..done
    collectives: set = field(default_factory=set)   # names of collectives


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)  # ordinal -> DeviceTrace
    host: list = field(default_factory=list)     # bench.* (name, start, end)


def load(path: str) -> Trace:
    import jax

    trace = Trace()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = trace.devices.setdefault(int(m.group(1)), DeviceTrace())
            for line in plane.lines:
                into = {SYNC_LINE: dev.sync, ASYNC_LINE: dev.spans}.get(
                    line.name)
                if into is None:
                    continue
                for e in line.events:
                    name = op_name(e.name)
                    into.append((name, e.start_ns,
                                 e.start_ns + e.duration_ns))
                    if is_collective(e.name):
                        dev.collectives.add(name)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        trace.host.append((e.name, e.start_ns,
                                           e.start_ns + e.duration_ns))
    trace.host.sort(key=lambda s: s[1])
    return trace


def union(intervals) -> list:
    """Disjoint sorted intervals covering the same points."""
    out = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def length(disjoint) -> float:
    return float(sum(b - a for a, b in disjoint))


def subtract(a, b) -> list:
    """Points of disjoint sorted ``a`` that no interval of disjoint
    sorted ``b`` covers."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, cur = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append([cur, hi])
    return out


def clip(disjoint, lo, hi) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in disjoint
            if min(b, hi) > max(a, lo)]


def window(trace: Trace) -> tuple:
    """The traced window on the trace's clock: from the first to the last
    host span, or the device events' extent where there is none."""
    if trace.host:
        return (min(s[1] for s in trace.host), max(s[2] for s in trace.host))
    ev = [e for d in trace.devices.values() for e in d.sync + d.spans]
    return (min(e[1] for e in ev), max(e[2] for e in ev))


def busy(dev: DeviceTrace) -> list:
    """Where an instruction runs on the chip's core.  An asynchronous
    copy or collective in flight while no instruction runs is not busy
    time; the ``*-done`` that waits for it is."""
    return union([(a, b) for _, a, b in dev.sync])


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    lo, hi = window(trace)
    per = [length(clip(busy(d), lo, hi)) for d in trace.devices.values()]
    return sum(per) / len(per) / 1e9 if per else 0.0


def window_seconds(trace: Trace) -> float:
    lo, hi = window(trace)
    return (hi - lo) / 1e9


def seconds_by_kind(trace: Trace) -> dict:
    """Seconds the chips' cores spent by kind of instruction, averaged
    over the chips (the ``XLA Ops`` line: an asynchronous pair shows as
    its ``*-done``'s wait, not as its span, which overlaps compute)."""
    n = max(1, len(trace.devices))
    out: dict = {}
    for dev in trace.devices.values():
        for name, a, b in dev.sync:
            k = op_kind(name)
            out[k] = out.get(k, 0.0) + (b - a) / 1e9 / n
    return out


def kernel_events(trace: Trace, needle: str) -> list:
    """Durations (s) of the ``XLA Ops`` events whose instruction name
    holds ``needle``, on the first chip (every chip runs the same
    program)."""
    if not trace.devices:
        return []
    dev = trace.devices[min(trace.devices)]
    return [(b - a) / 1e9 for name, a, b in dev.sync if needle in name]


def collective_seconds(trace: Trace) -> tuple:
    """(total, exposed) seconds in collectives, averaged over the chips.
    Total is the union of the collectives' intervals (asynchronous spans
    and synchronous instructions); exposed is the part of it during
    which no other instruction runs on that chip — a ``*-done`` that
    waits is a collective instruction, so waiting counts as exposed."""
    tot = exp = 0.0
    for dev in trace.devices.values():
        coll = union([(a, b) for n_, a, b in dev.sync + dev.spans
                      if n_ in dev.collectives])
        other = union([(a, b) for n_, a, b in dev.sync
                       if n_ not in dev.collectives])
        tot += length(coll)
        exp += length(subtract(coll, other))
    n = max(1, len(trace.devices))
    return tot / n / 1e9, exp / n / 1e9


def idle_by_span(trace: Trace) -> dict:
    """Idle seconds of the first chip, by the host span that covers most
    of each gap (``_no_span_`` where none does)."""
    if not trace.devices:
        return {}
    lo, hi = window(trace)
    gaps = subtract([[lo, hi]], clip(busy(trace.devices[min(trace.devices)]),
                                     lo, hi))
    out: dict = {}
    j = 0
    for a, b in gaps:
        while j < len(trace.host) and trace.host[j][2] <= a:
            j += 1
        best, best_cover = "_no_span_", 0
        k = j
        while k < len(trace.host) and trace.host[k][1] < b:
            name, s, e = trace.host[k]
            cover = min(e, b) - max(s, a)
            if cover > best_cover:
                best, best_cover = name, cover
            k += 1
        out[best] = out.get(best, 0.0) + (b - a) / 1e9
    return out


def breakdown(trace: Trace, top: int = 10) -> dict:
    def first(d):
        return [[k, v] for k, v in sorted(d.items(),
                                          key=lambda kv: -kv[1])[:top]]
    return {"device_ops": first(seconds_by_kind(trace)),
            "idle_gaps": first(idle_by_span(trace))}
