"""The sum of the step records' count ``count`` over the steps of the
measured window (what those steps added to the program's counter of
that name).  Nothing to read where any step of the window lacks the
count (every commit before the one that added it) or where the log
cannot vouch for the window."""


def read(record, args):
    from benchmarks import program_spans

    steps = program_spans.steps_of(record)
    if not steps or any(args["count"] not in r for r in steps):
        return None
    return sum(r[args["count"]] for r in steps)
