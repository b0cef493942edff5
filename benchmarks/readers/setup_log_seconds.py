"""Seconds of set-up under the compile-log records the metric's file
names (``kinds``, ``cache``, ``under``: ``program_setup.chosen``): the
union of their intervals, so that a program traced inside another's
trace is not counted twice.  0 where the log holds none of them;
nothing to read where the program keeps no compile log (every commit
before the one that added it) or a ring is full."""


def read(record, args):
    from benchmarks import program_setup, trace_reduce

    cut = program_setup.cut(record)
    if cut is None:
        return None
    chosen = program_setup.chosen(cut[args.get("where", "setup")], args)
    return trace_reduce.length(trace_reduce.union(
        [(r["t0_ns"], r["t1_ns"]) for r in chosen])) / 1e9
