"""Share (%) of its roofline that a part of the program reached in the
traced phase which the compiler writes itself, as fusions with no name
of their own: the least time the chip could take for its calls over the
time of every event that ran under one named scope of the program.

The run hands over ``extras["kernel_calls"][<kernel>]``: ``scope``, what
the ``op_name`` of the part's instructions holds (looked up in
``extras["op_scopes"]``, the scope and ``op_name`` tail of each
instruction of the compiled programs), and ``calls``, the (FLOP, bytes)
its family counts for each call the traced steps made.  An event counts
with its own time (``scope_time_share.self_times``: a ``while`` and the
instructions of its body are not counted twice).  How many events a call
makes is the compiler's affair, so no count is held against another;
without the programs' texts, without an event under the scope or without
a counted call nothing is reported.
"""

import sys


def read(record, args):
    from benchmarks import flops
    from benchmarks.readers.scope_time_share import self_times

    kernel = (record.extras.get("kernel_calls") or {}).get(args["kernel"])
    scopes = record.extras.get("op_scopes")
    if record.trace is None or not record.trace.devices or not scopes \
            or not record.ctx.peaks or not kernel \
            or not kernel.get("scope") or not kernel["calls"]:
        return None
    dev = record.trace.devices[min(record.trace.devices)]
    times = [ns / 1e9 for name, ns in self_times(dev.sync)
             if kernel["scope"] in scopes.get(name, (None, ""))[1]]
    if not times or not sum(times):
        return None
    least, binds = 0.0, set()
    for flop, nbytes in kernel["calls"]:
        seconds, bound = flops.least_seconds(flop, nbytes, record.ctx.peaks)
        least += seconds
        binds.add(bound)
    print(f"roofline {args['kernel']}: {len(times)} events under "
          f"{kernel['scope']!r}, {sum(times):.6f} s, for "
          f"{len(kernel['calls'])} calls counted; {least:.6f} s at the "
          f"least, bound by {sorted(binds)}; "
          f"{sum(c[0] for c in kernel['calls']):.4g} FLOP needed",
          file=sys.stderr)
    return 100.0 * least / sum(times)
