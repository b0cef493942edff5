"""A flash kernel's share of its roofline, from the traced phase.

``kernels`` are keys of ``program.kernel_names()``; ``cost`` is the
function of ``benchmarks/flops.py`` that gives (FLOP, bytes) of one call.
The run says which calls the traced phase made: ``uniform`` (every call
has one shape; recomputed calls are calls) or ``in_order`` (one shape per
admission, each followed by one call per layer).  The share is the least
time the chip could take for those calls over the time their events
took.  Which bound binds is printed to standard error.
"""

import sys


def read(record, args):
    from benchmarks import flops, program, trace_reduce

    calls = record.extras.get("flash_calls")
    if record.trace is None or not calls or not record.ctx.peaks:
        return None
    names = program.kernel_names()
    times = [trace_reduce.kernel_events(record.trace, names[k])
             for k in args["kernels"]]
    if not all(times):
        return None
    cost = getattr(flops, args["cost"])
    n_events = len(times[0])
    if "uniform" in calls:
        shapes = [calls["uniform"]] * n_events
    else:
        layers = record.ctx.cfg["num_hidden_layers"]
        shapes = [s for s in calls["in_order"] for _ in range(layers)]
        if len(shapes) != n_events:
            return None
    if any(len(t) != n_events for t in times):
        return None
    least = 0.0
    binds = set()
    for s in shapes:
        seconds, bound = flops.least_seconds(*cost(s), record.ctx.peaks)
        least += seconds
        binds.add(bound)
    total = sum(sum(t) for t in times)
    print(f"roofline {args['kernels']}: {n_events} calls, bound by "
          f"{sorted(binds)}, {total:.6f} s of kernel time", file=sys.stderr)
    return 100.0 * least / total
