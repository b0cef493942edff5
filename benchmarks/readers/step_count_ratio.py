"""``scale`` x the sum of the step records' count ``num`` over the sum
of their count ``den``, over the steps of the measured window (the
counts are what each step added to the program's counters of those
names).  Nothing to read where the denominator is zero."""


def read(record, args):
    from benchmarks import program_spans

    steps = program_spans.steps_of(record)
    if not steps:
        return None
    den = sum(r[args["den"]] for r in steps)
    if not den:
        return None
    return float(args["scale"]) * sum(r[args["num"]] for r in steps) / den
