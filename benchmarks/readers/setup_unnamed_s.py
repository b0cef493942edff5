"""``setup_s`` less what the program's logs name of it: the union of
every compile-log record that ended in set-up and of the set-up spans
named ``spans``, clipped to set-up.  What is left is under no record of
the program's: imports, device start, the caller's weights and check
steps, the fill's device time, a traced run's traced phase.  Nothing to
read where the program keeps no compile log or a ring is full."""


def read(record, args):
    from benchmarks import program_setup, trace_reduce

    cut = program_setup.cut(record)
    if cut is None:
        return None
    named = trace_reduce.union(
        [(r["t0_ns"], r["t1_ns"]) for r in cut["setup"]]
        + [(t0, t1) for name, t0, t1, *_ in cut["spans"]
           if name in args["spans"]])
    named = trace_reduce.clip(named, cut["t_start_ns"], cut["t_open_ns"])
    return record.scalars["setup_s"] - trace_reduce.length(named) / 1e9
