"""Share (%) of the first chip's busy time in the traced phase that ran
under one scope of the program (``scope``: a key of the family's
``SCOPES``), forward, recomputed and backward instructions together.

The run hands over ``extras["op_scopes"]``, the scope of each instruction
of the compiled step; an event of the trace carries its instruction's
name.  Each event counts with its own time only: where the trace nests
events (a ``while`` and the instructions of its body), the children's
time is taken off the parent's, so that the scopes' times and the time
under no scope add up to the busy time.  The shares of every scope and
of none are printed to standard error, and the scope's largest parts by
what their instructions compute.
"""

import sys


def self_times(events) -> list:
    """``[(name, own ns)]`` of properly nested ``(name, start, end)``
    events: an event's own time is its duration less its direct
    children's."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [e[2] - e[1] for e in events]
    stack = []
    for i in order:
        _, start, end = events[i]
        while stack and events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(end, events[stack[-1]][2]) - start
        stack.append(i)
    return [(events[i][0], max(0, own[i])) for i in range(len(events))]


def by_scope(trace, scopes: dict) -> tuple:
    """Own nanoseconds of the first chip's events by scope key (``None``:
    under no scope), and by (scope key, what the instruction computes)."""
    total, parts = {}, {}
    for name, ns in self_times(trace.devices[min(trace.devices)].sync):
        key, label = scopes.get(name, (None, name.split(".")[0]))
        total[key] = total.get(key, 0) + ns
        parts[key, label] = parts.get((key, label), 0) + ns
    return total, parts


def report(times: dict, parts: dict, top: int = 12) -> str:
    total = sum(times.values())
    share = lambda ns: f"{100.0 * ns / total:.2f}%"
    by_time = sorted(times.items(), key=lambda kv: -kv[1])
    lines = ["scope shares of busy time: " + ", ".join(
        f"{k or 'no scope'} {share(v)}" for k, v in by_time)]
    for key, _ in by_time:
        largest = sorted(((v, label) for (k, label), v in parts.items()
                          if k == key), reverse=True)[:top]
        lines.append(f"  {key or 'no scope'}: " + ", ".join(
            f"{label or '-'} {share(v)}" for v, label in largest))
    return "\n".join(lines)


def read(record, args):
    scopes = record.extras.get("op_scopes")
    if record.trace is None or not record.trace.devices or not scopes:
        return None
    if "scope_times" not in record.extras:      # once a record
        record.extras["scope_times"] = by_scope(record.trace, scopes)
        print(report(*record.extras["scope_times"]), file=sys.stderr)
    times, _ = record.extras["scope_times"]
    total = sum(times.values())
    if not total or args["scope"] not in times:
        return None
    return 100.0 * times[args["scope"]] / total
