"""Share (%) of its roofline that one of a family's kernels reached in
the traced phase: the least time the chip could take for its calls over
the time their events took.

The run hands over ``extras["kernel_calls"][<kernel>]``: ``events``,
what the names of the kernel's events in the trace hold, ``calls``, the
(FLOP, bytes) its family counts for each call the traced steps made
(from those steps' own counters where the work depends on the data), and
``beside``, what the names of the events hold that prepare its calls
(their time is the kernel's too, so that no part of the work is left
out; they are no calls).  One call is one event; where the trace holds
another number of them the count is of something else and nothing is
reported.  Printed to standard error: the events, their time, the least
time and what binds it.
"""

import sys


def read(record, args):
    from benchmarks import flops, trace_reduce

    kernel = (record.extras.get("kernel_calls") or {}).get(args["kernel"])
    if record.trace is None or not kernel or not record.ctx.peaks:
        return None
    times = trace_reduce.kernel_events(record.trace, kernel["events"])
    if not times or len(times) != len(kernel["calls"]):
        print(f"roofline {args['kernel']}: {len(times)} events named "
              f"{kernel['events']!r} for {len(kernel['calls'])} calls "
              f"counted: not reported", file=sys.stderr)
        return None
    least, binds = 0.0, set()
    for flop, nbytes in kernel["calls"]:
        seconds, bound = flops.least_seconds(flop, nbytes, record.ctx.peaks)
        least += seconds
        binds.add(bound)
    beside = trace_reduce.kernel_events(record.trace, kernel["beside"]) \
        if kernel.get("beside") else []
    total = sum(times) + sum(beside)
    print(f"roofline {args['kernel']}: {len(times)} events, {total:.6f} s "
          f"of kernel time ({sum(beside):.6f} s of it in {len(beside)} "
          f"events beside), {least:.6f} s at the least, bound by "
          f"{sorted(binds)}; {sum(c[0] for c in kernel['calls']):.4g} FLOP "
          f"needed", file=sys.stderr)
    return 100.0 * least / total
