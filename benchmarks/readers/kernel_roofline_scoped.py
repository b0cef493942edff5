"""``kernel_roofline`` for a kernel part of whose work the compiler
writes itself: the share (%) of its roofline that the kernel reached in
the traced phase, its own events' time and the time of every event that
ran under one named scope of the program together.

The run hands over ``extras["kernel_calls"][<kernel>]`` as
``readers/kernel_roofline.py`` has it, with ``beside_scope`` in the
place of ``beside``: what the ``op_name`` of the instructions holds that
prepare the kernel's calls (a gather the compiler names ``fusion.<n>``
has no name of its own to be found by), looked up in
``extras["op_scopes"]``, the scope and ``op_name`` tail of each
instruction of the compiled programs.  Without those (a trace whose
events do not line up with the programs' texts) part of the work could
not be timed, and nothing is reported.  The kernel's own share (the
least time over its own events' time, one event a counted call) is
``kernel_roofline``'s; the scope's events then join the time.
"""

import sys


def read(record, args):
    from benchmarks import trace_reduce
    from benchmarks.readers import kernel_roofline

    kernel = (record.extras.get("kernel_calls") or {}).get(args["kernel"])
    scopes = record.extras.get("op_scopes")
    if not kernel or not scopes or not kernel.get("beside_scope"):
        return None
    alone = kernel_roofline.read(record, args)
    if alone is None:
        return None
    own = sum(trace_reduce.kernel_events(record.trace, kernel["events"]))
    dev = record.trace.devices[min(record.trace.devices)]
    beside = [(b - a) / 1e9 for name, a, b in dev.sync
              if kernel["beside_scope"] in scopes.get(name, (None, ""))[1]]
    print(f"roofline {args['kernel']}: with {sum(beside):.6f} s in "
          f"{len(beside)} events under {kernel['beside_scope']!r} beside "
          f"the kernel's own {own:.6f} s", file=sys.stderr)
    return alone * own / (own + sum(beside))
